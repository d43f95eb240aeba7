"""Moonlight-16B-A3B (moonshotai, ``model_type: deepseek_v3``) in plain
``jax.numpy``: the causal-LM forward pass, its training loss and, by
``jax.grad``, its gradients.  Float32 throughout under
``jax.default_matmul_precision('highest')``, dense [T, T] masks, a
Python loop over the heads and one over the experts, no kernel, no
sort, no cache, nothing imported from ``paddle_tpu.ops`` or
``paddle_tpu.parallel``.

Written from the published ``config.json`` and the equations of HF
``modeling_deepseek_v3`` as the issue states them; for every layer,
16 heads, no bias anywhere, RMSNorm eps 1e-5:

    u = rms_norm(x, g_in)
    q = u Wq                      [T, 16, 192] = [q_nope 128 | q_rope 64]
    [c | k_r] = u Wkva            512 + 64 wide (q_lora_rank null: the
                                  queries have no latent)
    c~ = rms_norm(c, g_latent)    a gain of its own, the same eps
    [k_nope | v] = c~ Wkvb        [T, 16, 128 + 128]
    rotary, theta 50000, no scaling, on q_rope and on k_r, which is
      ONE 64-wide key for all 16 heads; the input's pairs are
      (2i, 2i + 1) (``rope_interleave``): HF brings them to
      [evens | odds] and applies rotate-half, and leaves them so
    k_h = [k_nope_h | k_r];  scores q_h . k_h / sqrt(192); key j
      visible to query i iff j <= i; softmax in f32; o_h = p_h v_h,
      128 wide
    y = x + concat_h(o_h) Wo      Wo [16 * 128, 2048]
    w = rms_norm(y, g_post)
    layer 0 (dense):  y + down(silu(gate w) * up w), width 11264
    layers >= 1:      s = sigmoid(w Wg) over all 64, in f32; the CHOICE
                      is the 6 largest of s + b (``noaux_tc``; one
                      group, so the group step is the identity); the
                      GATES are s_e / (sum over the 6 of s + 1e-20) *
                      2.446: the bias b picks and never weighs;
                      y + shared(w) + sum over the chosen experts HELD
                      HERE of gate_e expert_e(w); an expert is
                      down(silu(gate w) * up w) of width 1408, the
                      shared one of width 2 * 1408
    logits = rms_norm(x, g_final) W_head   (head not tied, over the
             held rows of the vocabulary)

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end).

THE BIAS.  ``b`` (64 floats a sparse layer) is no parameter: it gets
no gradient (``loss_and_grads`` differentiates ``params`` only) and
after each train step moves by ``bias_update``: b += gamma *
sign(mean load - load), from that step's loads.

THE SHARE.  ``held = (first, count)`` gives this copy the routed
experts first .. first + count - 1 (``gate`` / ``up`` / ``down`` are
[count, ...]); the router and its bias stay 64 wide and the top-6 are
taken over all experts; what the absent experts would have added is
left out, and that partial sum goes on to the next layer.
``held=None``: all experts.  The vocabulary slice is simply a smaller
vocabulary.

What ``config.json`` does not settle, as this file and
``paddle_tpu/models/moonlight.py`` read it (``assumed`` in the
benchmark's configuration file gives the reasons):

- gamma 0.001 (the DeepSeek-V3 report's bias update speed; no key);
- the bias's values: the published buffer starts at zero and a
  checkpoint's is trained; callers hand in what they hold;
- no sequence-wise auxiliary loss (``seq_aux`` true, no coefficient);
- ``rope_interleave`` true (the ``deepseek_v3`` default; no key);
- the latent norm's eps is ``rms_norm_eps``;
- the two shared experts are one gated MLP of width 2 x 1408, not
  scaled and with no gate of their own (HF ``DeepseekV3MoE``);
- no softmax-scale correction (no rotary scaling, so ``mscale`` is 1).

``params`` is the flat list of arrays in the order
``paddle_tpu.models.moonlight.build_pretrain`` creates its TRAINABLE
parameters: embedding; per layer g_in, Wq, Wkva, g_latent, Wkvb, Wo,
g_post, then for a dense layer gate, up, down and for a sparse one Wg,
gate [count, D, H], up [count, D, H], down [count, H, D], shared gate,
shared up, shared down; g_final; W_head.  ``biases``: one [64] array a
sparse layer, in layer order.
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rope_interleaved(x, positions, theta):
    """x [B, T, H, R], positions [B, T]: the input's pairs (2i, 2i+1)
    turned by pos * theta^(-2i/R); the output in [evens | odds] order,
    as HF ``apply_rotary_pos_emb_interleave`` leaves it."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (np.float32(theta) ** (
        np.arange(half, dtype=np.float32) / np.float32(half)))
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
        x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], -1)


def attention(u, positions, wq, wkva, g_latent, wkvb, wo, sizes,
              remat=False):
    b, t, _ = u.shape
    heads, nope, rope, dv, rank = (sizes[n] for n in (
        'heads', 'qk_nope', 'qk_rope', 'v_dim', 'kv_rank'))
    q = (u @ wq).reshape(b, t, heads, nope + rope)
    kva = u @ wkva
    latent = rms_norm(kva[..., :rank], g_latent, sizes['rms_eps'])
    kv = (latent @ wkvb).reshape(b, t, heads, nope + dv)
    q_rope = rope_interleaved(q[..., nope:], positions,
                              sizes['rope_theta'])
    k_rope = rope_interleaved(kva[..., rank:][:, :, None, :], positions,
                              sizes['rope_theta'])[:, :, 0]   # [B, T, R]
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = (nope + rope) ** -0.5

    def one_head(qn, qr, kn, kr, v):
        """qn, kn [B, T, nope], qr, kr [B, T, rope], v [B, T, dv]."""
        scores = (jnp.einsum('bqd,bkd->bqk', qn, kn) +
                  jnp.einsum('bqd,bkd->bqk', qr, kr)) * scale
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum('bqk,bkd->bqd', probs, v)

    if remat:
        one_head = jax.checkpoint(one_head)
    context = jnp.stack(
        [one_head(q[:, :, h, :nope], q_rope[:, :, h], kv[:, :, h, :nope],
                  k_rope, kv[:, :, h, nope:]) for h in range(heads)], 2)
    return context.reshape(b, t, heads * dv) @ wo


def gated_mlp(w, gate, up, down):
    return (jax.nn.silu(w @ gate) * (w @ up)) @ down


def route(w, wg, bias, top_k, scale, chosen=None):
    """-> (chosen [S, k], gates [S, k], load [E]): the choice by
    s + b, the gates from s alone.  A ``chosen`` handed in replaces
    the choice (a program's own, where the two are to be compared
    apart from the tokens whose 6th and 7th biased scores nearly tie);
    the gates are still this function's."""
    scores = jax.nn.sigmoid(w @ wg)
    if chosen is None:
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    gates = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scale
    load = jnp.sum(jax.nn.one_hot(chosen, wg.shape[-1]), (0, 1))
    return chosen, gates, load


def routed_share(w, wg, bias, gate, up, down, top_k, scale, held,
                 chosen=None):
    """w [S, D] -> (the held experts' part of the routed sum [S, D],
    load [E]): a Python loop over the held experts, each on every
    token, times the token's gate for it or 0."""
    first = 0 if held is None else held[0]
    chosen, gates, load = route(w, wg, bias, top_k, scale, chosen)
    out = jnp.zeros_like(w)
    for e in range(gate.shape[0]):
        share = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        out = out + share[:, None] * gated_mlp(w, gate[e], up[e],
                                               down[e])
    return out, load


def bias_update(bias, load, gamma):
    """b + gamma * sign(mean load - load)."""
    load = jnp.asarray(load, jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(load) - load)


def forward(params, biases, ids, positions, *, sizes, dtype=jnp.float32,
            remat=False, chosen=None):
    """-> (logits [B, T, V], [expert loads [E] per sparse layer]).
    ``sizes``: layers, dense_layers, heads, qk_nope, qk_rope, v_dim,
    kv_rank, top_k, routed_scale, experts_held, rms_eps, rope_theta
    (``sizes_of`` takes them from a ``MoonlightConfig``).  ``dtype``
    other than float32 computes EVERYTHING in it, the router too: the
    deliberately cruder model a tolerance has to tell from this one.
    ``remat`` keeps no [T, T] scores for a gradient and computes them
    again (the same numbers; what lets ``jax.grad`` of this fit one
    chip at the published widths).  ``chosen``: one [S, k] array of
    expert ids a sparse layer, to route by instead of this model's
    own choice (``route``)."""
    params = iter([jnp.asarray(p, dtype) for p in params])
    biases = iter([jnp.asarray(b, dtype) for b in biases])
    chosen = iter(chosen if chosen is not None
                  else [None] * (sizes['layers'] - sizes['dense_layers']))

    def take(n):
        return [next(params) for _ in range(n)]

    eps = sizes['rms_eps']
    loads = []
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[ids]
        b, t, width = x.shape
        for i in range(sizes['layers']):
            g_in, wq, wkva, g_latent, wkvb, wo, g_post = take(7)
            u = rms_norm(x, g_in, eps)
            x = x + attention(u, positions, wq, wkva, g_latent, wkvb,
                              wo, sizes, remat)
            w = rms_norm(x, g_post, eps)
            if i < sizes['dense_layers']:
                x = x + gated_mlp(w, *take(3))
                continue
            wg, gate, up, down, s_gate, s_up, s_down = take(7)
            routed, load = routed_share(
                w.reshape(b * t, width), wg, next(biases), gate, up,
                down, sizes['top_k'], sizes['routed_scale'],
                sizes['experts_held'], next(chosen))
            x = x + gated_mlp(w, s_gate, s_up, s_down) + \
                routed.reshape(b, t, width)
            loads.append(load)
        g_final, head = take(2)
        logits = rms_norm(x, g_final, eps) @ head
    assert next(params, None) is None and next(biases, None) is None
    return logits, loads


def loss(params, biases, ids, positions, labels, *, sizes,
         dtype=jnp.float32, remat=False, chosen=None):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, _ = forward(params, biases, ids, positions, sizes=sizes,
                        dtype=dtype, remat=remat, chosen=chosen)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_and_grads(params, biases, ids, positions, labels, *, sizes,
                   remat=False, chosen=None):
    """(loss, [d loss / d param] in ``params`` order); the biases are
    held fixed."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, biases, ids, positions,
                                    labels, sizes=sizes, remat=remat,
                                    chosen=chosen)


def sizes_of(cfg):
    """The ``sizes`` dict of a
    ``paddle_tpu.models.moonlight.MoonlightConfig`` (plain attribute
    reads: this module imports nothing of the zoo)."""
    return dict(layers=cfg.layers, dense_layers=cfg.dense_layers,
                heads=cfg.heads, qk_nope=cfg.qk_nope,
                qk_rope=cfg.qk_rope, v_dim=cfg.v_dim,
                kv_rank=cfg.kv_rank, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta)
