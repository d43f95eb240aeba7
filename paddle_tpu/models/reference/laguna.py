"""Laguna-S-2.1 (poolside, ``model_type: laguna``) in plain
``jax.numpy``: the causal-LM forward pass, its training loss and, by
``jax.grad``, its gradients.  Float32 throughout under
``jax.default_matmul_precision('highest')``, dense [T, T] masks, a
Python loop over the K/V heads and one over the experts, no kernel, no sort, no cache, nothing
imported from ``paddle_tpu.ops`` or ``paddle_tpu.parallel``.

Written from the published ``config.json`` (no modelling code is at
hand here); for layer ``l`` of kind ``layer_types[l]`` with ``H_l`` =
``num_attention_heads_per_layer[l]`` query heads, 8 K/V heads, d = 128,
no bias anywhere, RMSNorm eps 1e-6:

    u = rms_norm(x, g_in)
    q = u Wq  [T, H_l, d];  k = u Wk, v = u Wv  [T, 8, d]
    rotary, rotate-half pairing (feature i with i + R/2):
      sliding layers: theta 10000 over all R = 128 features;
      full layers:    over the first R = 64 only, the other 64 pass
                      through; inverse frequencies by YaRN (dim 64,
                      base 500000, factor 128, original length 8192,
                      beta_fast 32, beta_slow 1, as HF
                      ``_compute_yarn_parameters``); cos and sin times
                      attention_factor 1.4852030263919618
    query head h attends K/V head h // (H_l / 8); scores q k^T /
    sqrt(d); key j visible to query i iff j <= i, and on sliding
    layers also i - j < 512; softmax in f32; no dropout
    g = sigmoid(u Wg)  [T, H_l]: one gate a head and token
    y = x + concat_h(g_h o_h) Wo
    w = rms_norm(y, g_post)
    layer 0 (dense):   y + down(silu(gate w) * up w), width 12288
    layers >= 1:       p = softmax(w Wr) over all 256, in f32; the 10
                       largest; gates p_e / (sum of the 10) * 2.5,
                       applied to the experts' outputs;
                       y + shared(w) + sum over the chosen experts HELD
                       HERE of gate_e expert_e(w); experts and the
                       shared expert are down(silu(gate w) * up w) of
                       width 1024
    logits = rms_norm(x, g_final) W_head   (head not tied, over the
             held rows of the vocabulary)

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end).  No
auxiliary loss: the config carries no coefficient for one.

THE SHARE.  ``held = (first, count)`` gives this copy the routed
experts first .. first + count - 1 (``gate`` / ``up`` / ``down`` are
[count, ...]); the router stays 256 wide and the top-10 are taken over
all experts; what the absent experts would have added is left out, and
that partial sum goes on to the next layer.  ``held=None``: all
experts.  The vocabulary slice is simply a smaller vocabulary.

What ``config.json`` does not settle, as this file and
``paddle_tpu/models/laguna.py`` read it (``assumed`` in the benchmark's
configuration file gives the reasons):

- the gate is a sigmoid of a linear map of the NORMED BLOCK INPUT
  (the head-wise output gate of arXiv:2505.06708), multiplied onto
  each head's context before Wo;
- router scores are a softmax (no ``scoring_func``, no correction
  bias key); ``moe_router_logit_softcapping`` 0 is off;
- the shared expert has no gate of its own and is not scaled;
- no QK-norm (no key for one);
- each expert's ``intermediate`` width is ``moe_intermediate_size``.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.laguna.build_pretrain`` creates its parameters:
embedding; per layer g_in, Wq, Wk, Wv, Wg, Wo, g_post, then for a
dense layer gate, up, down and for a sparse one Wr, gate [count, D, H],
up [count, D, H], down [count, H, D], shared gate, shared up, shared
down; g_final; W_head.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = 'full_attention', 'sliding_attention'
PER_LAYER = {'dense': 10, 'sparse': 14}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters``'s inverse frequencies, [dim/2]."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   np.float32(high - low), 0, 1).astype(np.float32)
    extrapolation = 1 - ramp
    return (1.0 / (np.float32(factor) * pos_freqs) * (1 - extrapolation)
            + 1.0 / pos_freqs * extrapolation).astype(np.float32)


def rope(x, positions, inv_freq, factor=1.0):
    """x [B, T, H, d], positions [B, T]: rotate-half pairing over the
    first 2 * len(inv_freq) features, the rest pass through."""
    half = inv_freq.shape[0]
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1) * factor
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1) * factor
    turned, rest = x[..., :2 * half], x[..., 2 * half:]
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]],
                              -1)
    turned = turned * cos.astype(x.dtype) + rotated * sin.astype(x.dtype)
    return jnp.concatenate([turned, rest], -1)


def attention(u, positions, wq, wk, wv, wg, wo, kind, sizes,
              remat=False):
    b, t, _ = u.shape
    d, kv = sizes['head_dim'], sizes['kv_heads']
    heads = wq.shape[1] // d
    q = (u @ wq).reshape(b, t, heads, d)
    k = (u @ wk).reshape(b, t, kv, d)
    v = (u @ wv).reshape(b, t, kv, d)
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    visible = j <= i
    if kind == FULL:
        y = sizes['yarn']
        rotary = int(d * y['partial_rotary_factor'])
        table = yarn_inv_freq(
            rotary, y['rope_theta'], y['factor'],
            y['original_max_position_embeddings'], y['beta_fast'],
            y['beta_slow'])
        factor = y['attention_factor']
    else:
        table = sizes['sliding_theta'] ** (
            -np.arange(d // 2, dtype=np.float32) / np.float32(d // 2))
        factor = 1.0
        visible = visible & (i - j < sizes['window'])
    q, k = rope(q, positions, table, factor), \
        rope(k, positions, table, factor)
    group = heads // kv

    def one_group(qg, kg, vg):
        """The ``group`` query heads that read one K/V head: qg
        [B, T, group, d], kg and vg [B, T, d]."""
        scores = jnp.einsum('bqgd,bkd->bgqk', qg, kg) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum('bgqk,bkd->bqgd', probs, vg)

    if remat:
        one_group = jax.checkpoint(one_group)
    # query head h reads K/V head h // group: one K/V head at a time
    context = jnp.concatenate(
        [one_group(q[:, :, n * group:(n + 1) * group], k[:, :, n],
                   v[:, :, n]) for n in range(kv)], 2)
    gate = jax.nn.sigmoid(u @ wg)                       # [B, T, H]
    return (context * gate[..., None]).reshape(b, t, heads * d) @ wo


def gated_mlp(w, gate, up, down):
    return (jax.nn.silu(w @ gate) * (w @ up)) @ down


def routed_share(w, wr, gate, up, down, top_k, scale, held):
    """w [S, D] -> (the held experts' part of the routed sum [S, D],
    load [E]): a Python loop over the held experts, each on every
    token, times the token's gate for it or 0."""
    first = 0 if held is None else held[0]
    probs = jax.nn.softmax(w @ wr, -1)
    weight, chosen = jax.lax.top_k(probs, top_k)
    weight = weight / jnp.sum(weight, -1, keepdims=True) * scale
    out = jnp.zeros_like(w)
    for e in range(gate.shape[0]):
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        out = out + share[:, None] * gated_mlp(w, gate[e], up[e],
                                               down[e])
    load = jnp.sum(jax.nn.one_hot(chosen, wr.shape[-1]), (0, 1))
    return out, load


def forward(params, ids, positions, *, sizes, dtype=jnp.float32,
            remat=False):
    """-> (logits [B, T, V], [expert loads [E] per sparse layer]).
    ``sizes``: layer_types, mlp_types, head_dim, kv_heads, window,
    top_k, routed_scale, experts_held, rms_eps, sliding_theta, yarn
    (``sizes_of`` takes them from a ``LagunaConfig``).  ``dtype`` other
    than float32 computes EVERYTHING in it: the deliberately cruder
    model a tolerance has to tell from this one.  ``remat`` keeps no
    [T, T] scores for a gradient and computes them again (the same
    numbers; what lets ``jax.grad`` of this fit one chip at the
    published widths)."""
    params = iter([jnp.asarray(p, dtype) for p in params])

    def take(n):
        return [next(params) for _ in range(n)]

    eps = sizes['rms_eps']
    loads = []
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[ids]
        b, t, width = x.shape
        for kind, mlp in zip(sizes['layer_types'], sizes['mlp_types']):
            g_in, wq, wk, wv, wg, wo, g_post = take(7)
            u = rms_norm(x, g_in, eps)
            x = x + attention(u, positions, wq, wk, wv, wg, wo, kind,
                              sizes, remat)
            w = rms_norm(x, g_post, eps)
            if mlp == 'dense':
                x = x + gated_mlp(w, *take(3))
                continue
            wr, gate, up, down, s_gate, s_up, s_down = take(7)
            routed, load = routed_share(
                w.reshape(b * t, width), wr, gate, up, down,
                sizes['top_k'], sizes['routed_scale'],
                sizes['experts_held'])
            x = x + gated_mlp(w, s_gate, s_up, s_down) + \
                routed.reshape(b, t, width)
            loads.append(load)
        g_final, head = take(2)
        logits = rms_norm(x, g_final, eps) @ head
    assert next(params, None) is None
    return logits, loads


def loss(params, ids, positions, labels, *, sizes, dtype=jnp.float32,
         remat=False):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, _ = forward(params, ids, positions, sizes=sizes, dtype=dtype,
                        remat=remat)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_and_grads(params, ids, positions, labels, *, sizes):
    """(loss, [d loss / d param] in ``params`` order)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, ids, positions, labels,
                                    sizes=sizes)


def sizes_of(cfg):
    """The ``sizes`` dict of a ``paddle_tpu.models.laguna.LagunaConfig``
    (plain attribute reads: this module imports nothing of the zoo)."""
    return dict(layer_types=list(cfg.layer_types),
                mlp_types=list(cfg.mlp_types), head_dim=cfg.head_dim,
                kv_heads=cfg.kv_heads, window=cfg.window,
                top_k=cfg.top_k, routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps,
                sliding_theta=cfg.sliding_theta, yarn=dict(cfg.yarn))
