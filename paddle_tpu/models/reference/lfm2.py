"""LFM2-8B-A1B (``model_type: lfm2_moe``) in plain ``jax.numpy``,
float32, ``default_matmul_precision('highest')``: forward, loss and,
through ``jax.grad``, gradients.  No kernel, no sort, no fused op: the
convolution an explicit sum over taps of shifted arrays, dense [T, T]
masks, Python loops over heads and experts.  It imports nothing of the
zoo or of fluid.

One layer of the model, on the block input ``x`` [B, T, 2048]:

    u = rms_norm(x, g_op);  h = x + Op(u)
    w = rms_norm(h, g_ffn); y = h + FF(w)          (eps 1e-5, a gain each)

``Op`` of a ``conv`` layer (18 of 24):

    [B | C | X] = u W_in            W_in [2048, 6144], thirds in that order
    z = B * X
    c_t = sum_{j=0..2} w_j * z_(t-2+j)    w [2048, 3], one filter a channel;
                                    z before the sequence's start is 0, so
                                    w_2 weighs the token itself; never
                                    across the sequences of a batch
    Op = (C * c) W_out              W_out [2048, 2048]; no bias anywhere

``Op`` of a ``full_attention`` layer (6 of 24: 2, 6, 10, 14, 18, 21):

    q = u Wq  [32 heads, 64];  k = u Wk, v = u Wv  [8 heads, 64]
    q = rms_norm(q, g_q), k = rms_norm(k, g_k)   over the 64 of EACH head,
                                    one 64-wide gain for q and one for k
    THEN rotary: rotate-half over the whole 64, theta 1e6, no scaling
    query head i attends K/V head i // 4; scores over sqrt(64), causal,
    softmax in float32;  Op = context Wo,  Wo [2048, 2048]

``FF`` of layers 0 and 1: ``W2(silu(W1 w) * W3 w)``, width 7168.
``FF`` of layers 2 to 23, the router in float32:

    s = sigmoid(w Wg)                       over 32 experts
    chosen = top-4 of (s + b)               b: 32 floats a layer, a
                                            buffer, no parameter
    g_i = s_i / (sum over the chosen of s_j + 1e-6)  x 1
    FF = sum over the chosen i of g_i E_i(w),  E_i a SiLU-gated MLP of 1792

    logits = rms_norm(x, g_final) E^T       the head IS the embedding E

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end).

THE BIAS takes no gradient and after each train step moves by
``bias_update``: b += gamma * sign(mean load - load).

THE SHARE.  ``held = (first, count)`` gives this copy the routed
experts first .. first + count - 1 (``gate`` / ``up`` / ``down`` are
[count, ...]); the router and its bias stay 32 wide and the top-4 are
taken over all experts; what the absent experts would have added is
left out, and that partial sum goes on to the next layer.  The
vocabulary slice is simply a smaller vocabulary.  The layers run are
``first_layer .. first_layer + layers - 1`` of the model, each with the
operator and the FF its own index gives it.

Readings of ``config.json`` (the catalog's row) and of HF
``transformers``' ``lfm2_moe`` code as written down without a network;
``assumed`` in the benchmark's configuration file gives the reasons:

- the thirds of ``W_in`` are B, C, X in that order (``in_proj(x)
  .chunk(3)`` -> ``B, C, x``) and the taps as ``Conv1d(groups=hidden,
  kernel_size=conv_L_cache, padding=conv_L_cache - 1)`` cut to the
  first T outputs lays them: the LAST tap on the token itself;
- QK-norm before rotary (``q_layernorm`` / ``k_layernorm`` over
  ``head_dim``, then ``apply_rotary_pos_emb``);
- the 1e-6 of the gates' renormalisation;
- embedding and head TIED (the LFM2 family's convention; no key);
- a last RMSNorm before the head (``embedding_norm``);
- how the bias moves (Moonlight's rule; gamma is the caller's: the
  zoo's default is DeepSeek-V3's 0.001, the benchmark's cell sets its
  own and says why) and its values (the published buffer is trained;
  callers hand in what they hold); no auxiliary loss;
- ``routed_scaling_factor`` 1 multiplies nothing.

Departures from the published model: none in the equations; the SHARE
above (experts held, vocabulary rows, layers run) is the caller's.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.lfm2.build_pretrain`` creates its TRAINABLE
parameters: embedding; per layer g_op, then W_in, filter [C, L], W_out
(conv) or Wq, Wk, Wv, g_q, g_k, Wo (attention), g_ffn, then gate, up,
down (dense) or Wg, gate [count, D, H], up, down [count, H, D]
(sparse); g_final.  ``biases``: one [32] array a sparse layer.
"""

import jax
import jax.numpy as jnp
import numpy as np

CONV = 'conv'


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x [B, T, H, d], positions [B, T]: rotate-half over the whole
    head, feature i paired with i + d/2, both turned by pos *
    theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (np.float32(theta) ** (
        np.arange(half, dtype=np.float32) / np.float32(half)))
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
        x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(z, w):
    """z [B, T, C], w [C, L] -> c_t = sum_j w[:, j] * z_(t-(L-1)+j),
    z zero before the start: L shifted copies, each behind its zeros."""
    taps = w.shape[1]
    t = z.shape[1]
    out = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j             # how far this tap looks back
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], 1)
        out = out + shifted * w[:, j]
    return out


def conv_operator(u, w_in, w, w_out):
    width = u.shape[-1]
    bcx = u @ w_in
    gate_in, gate_out, x = (bcx[..., :width], bcx[..., width:2 * width],
                            bcx[..., 2 * width:])
    return (gate_out * short_conv(gate_in * x, w)) @ w_out


def attention_operator(u, positions, wq, wk, wv, g_q, g_k, wo, sizes,
                       remat=False):
    b, t, _ = u.shape
    heads, kv_heads = sizes['heads'], sizes['kv_heads']
    d = wq.shape[1] // heads
    eps, theta = sizes['rms_eps'], sizes['rope_theta']
    q = rms_norm((u @ wq).reshape(b, t, heads, d), g_q, eps)
    k = rms_norm((u @ wk).reshape(b, t, kv_heads, d), g_k, eps)
    v = (u @ wv).reshape(b, t, kv_heads, d)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_head(qh, kh, vh):
        scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(visible, scores, -jnp.inf).astype(jnp.float32),
            -1).astype(qh.dtype)
        return jnp.einsum('bqk,bkd->bqd', probs, vh)

    if remat:
        one_head = jax.checkpoint(one_head)
    group = heads // kv_heads
    context = jnp.stack(
        [one_head(q[:, :, h], k[:, :, h // group], v[:, :, h // group])
         for h in range(heads)], 2)
    return context.reshape(b, t, heads * d) @ wo


def gated_mlp(w, gate, up, down):
    return (jax.nn.silu(w @ gate) * (w @ up)) @ down


def route(w, wg, bias, top_k, scale, renorm_eps, chosen=None):
    """-> (chosen [S, k], gates [S, k], load [E]): the choice by
    s + b, the gates from s alone.  A ``chosen`` handed in replaces
    the choice (a program's own, where the two are to be compared
    apart from the tokens whose 4th and 5th biased scores nearly
    tie); the gates are still this function's."""
    scores = jax.nn.sigmoid(w @ wg)
    if chosen is None:
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    gates = picked / (jnp.sum(picked, -1, keepdims=True) +
                      renorm_eps) * scale
    load = jnp.sum(jax.nn.one_hot(chosen, wg.shape[-1]), (0, 1))
    return chosen, gates, load


def routed_share(w, wg, bias, gate, up, down, sizes, chosen=None):
    """w [S, D] -> (the held experts' part of the routed sum [S, D],
    load [E]): a Python loop over the held experts, each on every
    token, times the token's gate for it or 0."""
    held = sizes['experts_held']
    first = 0 if held is None else held[0]
    chosen, gates, load = route(w, wg, bias, sizes['top_k'],
                                sizes['routed_scale'],
                                sizes['renorm_eps'], chosen)
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
            remat=False, chosen=None, head=None):
    """-> (logits [B, T, V], [expert loads [E] per sparse layer]).
    ``sizes``: layers, first_layer, layer_types (the MODEL's), dense_layers,
    heads, kv_heads, top_k, routed_scale, renorm_eps, experts_held,
    rms_eps, rope_theta (``sizes_of`` takes them from an
    ``Lfm2Config``).  ``dtype`` other than float32 computes EVERYTHING
    in it, the router too: the deliberately cruder model a tolerance
    has to tell from this one.  ``remat`` keeps no [T, T] scores for a
    gradient and computes them again.  ``chosen``: one [S, k] array of
    expert ids a sparse layer, to route by instead of this model's own
    choice (``route``).  ``head`` [V, D]: a head of its own in place
    of the embedding, the reading of the model that does NOT tie them
    (for the tests that show a tolerance tells the two apart)."""
    params = iter([jnp.asarray(p, dtype) for p in params])
    biases = iter([jnp.asarray(b, dtype) for b in biases])
    indices = range(sizes['first_layer'],
                    sizes['first_layer'] + sizes['layers'])
    n_sparse = sum(i >= sizes['dense_layers'] for i in indices)
    chosen = iter(chosen if chosen is not None else [None] * n_sparse)

    def take(n):
        return [next(params) for _ in range(n)]

    eps = sizes['rms_eps']
    loads = []
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[ids]
        b, t, width = x.shape
        for i in indices:
            (g_op,) = take(1)
            u = rms_norm(x, g_op, eps)
            if sizes['layer_types'][i] == CONV:
                x = x + conv_operator(u, *take(3))
            else:
                x = x + attention_operator(u, positions, *take(6),
                                           sizes, remat)
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn, eps)
            if i < sizes['dense_layers']:
                x = x + gated_mlp(w, *take(3))
                continue
            wg, gate, up, down = take(4)
            routed, load = routed_share(
                w.reshape(b * t, width), wg, next(biases), gate, up,
                down, sizes, next(chosen))
            x = x + routed.reshape(b, t, width)
            loads.append(load)
        (g_final,) = take(1)
        logits = rms_norm(x, g_final, eps) @ (      # tied
            embedding if head is None else jnp.asarray(head, dtype)).T
    assert next(params, None) is None and next(biases, None) is None
    return logits, loads


def loss(params, biases, ids, positions, labels, *, sizes,
         dtype=jnp.float32, remat=False, chosen=None, head=None):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, _ = forward(params, biases, ids, positions, sizes=sizes,
                        dtype=dtype, remat=remat, chosen=chosen,
                        head=head)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_and_grads(params, biases, ids, positions, labels, *, sizes,
                   remat=False, chosen=None):
    """(loss, [d loss / d param] in ``params`` order); the biases are
    held fixed.  The embedding's gradient holds both of its uses."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, biases, ids, positions,
                                    labels, sizes=sizes, remat=remat,
                                    chosen=chosen)


def sizes_of(cfg):
    """The ``sizes`` dict of a ``paddle_tpu.models.lfm2.Lfm2Config``
    (plain attribute reads: this module imports nothing of the zoo)."""
    return dict(layers=cfg.layers, first_layer=cfg.first_layer,
                layer_types=list(cfg.layer_types),
                dense_layers=cfg.dense_layers, heads=cfg.heads,
                kv_heads=cfg.kv_heads, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                renorm_eps=cfg.renorm_eps,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta)
