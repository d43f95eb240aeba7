"""Solar-Open2-250B (upstage, ``model_type: solar_open2``) in plain
``jax.numpy``, float32, ``default_matmul_precision('highest')``:
forward, loss and, through ``jax.grad``, gradients.  No kernel, no
chunk, no sort: the linear-attention state is carried TOKEN BY TOKEN by
a ``lax.scan``, the softmax layers use dense [T, T] masks one head at a
time, the experts are a Python loop.  It imports nothing of the zoo or
of fluid.

One layer, on the block input ``x`` [B, T, 4096] (RMSNorm eps 1e-5, a
gain each, no bias anywhere):

    u = rms_norm(x, g_op);  h = x + Op(u)
    w = rms_norm(h, g_ffn); y = h + shared(w) + routed(w)

``Op`` of the layers in ``gqa_layers`` (0, 4, 8, ... 44): softmax
attention WITHOUT any position encoding (``use_rope: false``):

    q = u Wq [64 heads, 128];  k = u Wk, v = u Wv [8 heads, 128]
    query head i attends K/V head i // 8; scores over sqrt(128), causal,
    softmax in float32
    Op = (ctx * sigmoid(u Wgate)) Wo      Wgate [4096, 64 x 128]
                                          (``use_gqa_gate``), elementwise

``Op`` of the other 36 layers: the gated delta rule with a per-channel
decay (``linear_attn_config``: 64 heads of 128, ``short_conv_kernel_size``
4), per head h with d = 128:

    q~, k~, v~ = u Wq, u Wk, u Wv         each [T, 64 x 128]
    q, k, v = silu(conv4(.))              a causal depthwise filter of 4
                                          taps a channel, the LAST tap on
                                          the token itself, zero before
                                          the sequence's start
    q_h = q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2;  k_h = k_h / sqrt(|k_h|^2 + 1e-6)
    a_t = -exp(A_log_h) * softplus((u Wf_down) Wf_up + dt_bias)    in R^128:
          the LOG of the decay of each key channel (Wf_down [4096, 128],
          Wf_up [128, 64 x 128]: ``kda_use_full_proj: false``)
    beta_t = 2 sigmoid(u Wb)_h            (``kda_allow_neg_eigval``: the 2)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t                       S [128, 128], zero at the start
    o'_h = rms_norm(o_h, g_o) * sigmoid(((u Wg_down) Wg_up)_h)
          one 128-wide gain for all heads
    Op = concat_h(o'_h) Wo

``FF`` of EVERY layer (``first_k_dense_replace`` 0), the router in
float32:

    s = sigmoid(w Wr)                     over 320 experts
    chosen = top-8 of (s + b)             b: 320 floats a layer, a buffer
    g_i = s_i / (sum over the chosen of s_j + 1e-20)  x 1
    routed = sum over the chosen i of g_i E_i(w)
    E_i and the one shared expert: down(silu(gate w) * up w), width 1280

    logits = rms_norm(x, g_final) W_head  (head not tied)

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end).

THE BIAS takes no gradient and after each train step moves by
``bias_update``: b += gamma * sign(mean load - load).

THE SHARE.  The head counts are read off the weights' shapes (a
projection's width over ``head_dim``): handed the columns of 8 of the
64 query heads with their ONE K/V head, or of 8 of the 64 KDA heads
(with those heads' filters, ``A_log``, ``dt_bias``, ``Wb`` columns and
``Wo`` rows), this computes that share's part of the operator's
result; the low-rank down-projections, the norms' gains and the router
are every share's alike, and the parts of all shares add up to the
whole operator (``tests/test_solar_open2.py``).  ``held = (first,
count)`` gives this copy the routed experts first .. first + count - 1;
the router and its bias stay 320 wide and pick top-8 of all; what the
absent experts would have added is left out, and that partial result
goes on to the next layer.  The vocabulary slice is simply a smaller
vocabulary.  The layers run are ``first_layer .. first_layer + layers
- 1`` of the model, each with the operator its own index gives it.

What ``config.json`` (the catalog's row) does not settle, as this file
and ``paddle_tpu/models/solar_open2.py`` read it (``assumed`` in the
benchmark's configuration file gives the reasons): the delta-rule
layer's details above that the ``kda_*`` keys only name (SiLU after the
filters, L2-normalised q and k with 1e-6 under the root, the low-rank
decay and output gates with a bottleneck of one head's width, the
per-head RMSNorm with a sigmoid gate); the FORM of ``use_gqa_gate``;
the router's score function and choice bias; a last norm.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.solar_open2.build_pretrain`` creates its TRAINABLE
parameters: embedding; per layer g_op, then Wq, Wk, Wv, Wgate, Wo (a
``gqa_layers`` layer) or Wq, filter_q [C, 4], Wk, filter_k, Wv,
filter_v, Wf_down, Wf_up, A_log [H], dt_bias [H x 128], Wb, g_o [128],
Wg_down, Wg_up, Wo; g_ffn, Wr, gate [count, D, W], up, down [count, W,
D], shared gate, shared up, shared down; g_final; W_head.  ``biases``:
one [320] array a layer.
"""

import jax
import jax.numpy as jnp

GQA, KDA = 'gqa', 'kda'
QK_NORM_EPS = 1e-6
# tokens a checkpointed block of the recurrence under ``remat``
REMAT_BLOCK = 64


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


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


def l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) +
                        QK_NORM_EPS)


def kda_recurrence(q, k, v, a, beta, block=None):
    """q, k, a [B, T, H, dk], v [B, T, H, dv], beta [B, T, H] -> o [B,
    T, H, dv]: the state stepped one token at a time.  ``block`` (a
    divisor of T) changes nothing of the arithmetic: the tokens are
    stepped in blocks under ``jax.checkpoint``, so that a gradient
    keeps the state at each block's start and steps the block again,
    instead of keeping T states of [dk, dv] a head."""
    b, t, h, dk = k.shape

    def step(state, x):
        q_t, k_t, v_t, a_t, beta_t = x
        state = jnp.exp(a_t)[..., None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.einsum('bhkv,bhk->bhv', state, k_t))
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.einsum('bhkv,bhk->bhv', state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta))
    zero = jnp.zeros((b, h, dk, v.shape[-1]), v.dtype)
    if block is None:
        _, out = jax.lax.scan(step, zero, xs)
    else:
        xs = tuple(x.reshape((t // block, block) + x.shape[1:])
                   for x in xs)
        _, out = jax.lax.scan(
            jax.checkpoint(lambda state, x: jax.lax.scan(step, state, x)),
            zero, xs)
        out = out.reshape((t,) + out.shape[2:])
    return jnp.moveaxis(out, 0, 1)


def kda_inputs(u, wq, fq, wk, fk, wv, fv, wf_down, wf_up, a_log, dt_bias,
               wb, sizes):
    """Steps 1 to 4: -> (q, k, v, a, beta) as ``kda_recurrence`` takes
    them."""
    b, t, _ = u.shape
    d = sizes['kda_head_dim']
    h = wq.shape[1] // d

    def branch(w, f):
        return jax.nn.silu(short_conv(u @ w, f)).reshape(b, t, h, d)

    q = l2_normalize(branch(wq, fq)) * d ** -0.5
    k = l2_normalize(branch(wk, fk))
    v = branch(wv, fv)
    rate = jax.nn.softplus((u @ wf_down) @ wf_up + dt_bias)
    a = -jnp.exp(a_log)[:, None] * rate.reshape(b, t, h, d)
    beta = jax.nn.sigmoid(u @ wb)
    if sizes['neg_eigval']:
        beta = 2.0 * beta
    return q, k, v, a, beta


def kda_operator(u, wq, fq, wk, fk, wv, fv, wf_down, wf_up, a_log,
                 dt_bias, wb, g_o, wg_down, wg_up, wo, sizes, block=None):
    b, t, _ = u.shape
    q, k, v, a, beta = kda_inputs(u, wq, fq, wk, fk, wv, fv, wf_down,
                                  wf_up, a_log, dt_bias, wb, sizes)
    o = rms_norm(kda_recurrence(q, k, v, a, beta, block), g_o,
                 sizes['rms_eps'])
    gate = jax.nn.sigmoid((u @ wg_down) @ wg_up).reshape(o.shape)
    return (o * gate).reshape(b, t, -1) @ wo


def gqa_operator(u, wq, wk, wv, wgate, wo, sizes, remat=False):
    b, t, _ = u.shape
    d = sizes['head_dim']
    heads, kv_heads = wq.shape[1] // d, wk.shape[1] // d
    q = (u @ wq).reshape(b, t, heads, d)
    k = (u @ wk).reshape(b, t, kv_heads, d)
    v = (u @ wv).reshape(b, t, kv_heads, d)
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
         for h in range(heads)], 2).reshape(b, t, heads * d)
    return (context * jax.nn.sigmoid(u @ wgate)) @ wo


def gated_mlp(w, gate, up, down):
    return (jax.nn.silu(w @ gate) * (w @ up)) @ down


def route(w, wr, bias, top_k, scale, chosen=None):
    """-> (chosen [S, k], gates [S, k], load [E]): the choice by
    s + b, the gates from s alone.  A ``chosen`` handed in replaces the
    choice (a program's own, where the two are to be compared apart
    from the tokens whose 8th and 9th biased scores nearly tie)."""
    scores = jax.nn.sigmoid(w @ wr)
    if chosen is None:
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    gates = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scale
    load = jnp.sum(jax.nn.one_hot(chosen, wr.shape[-1]), (0, 1))
    return chosen, gates, load


def routed_share(w, wr, bias, gate, up, down, sizes, chosen=None):
    """w [S, D] -> (the held experts' part of the routed sum [S, D],
    load [E]): a Python loop over the held experts, each on every
    token, times the token's gate for it or 0."""
    held = sizes['experts_held']
    first = 0 if held is None else held[0]
    chosen, gates, load = route(w, wr, bias, sizes['top_k'],
                                sizes['routed_scale'], chosen)
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


def forward(params, biases, ids, *, sizes, dtype=jnp.float32,
            remat=False, chosen=None):
    """-> (logits [B, T, V], [expert loads [E] per layer]).  ``sizes``:
    layers, first_layer, gqa_layers (the MODEL's), head_dim,
    kda_head_dim, neg_eigval, top_k, routed_scale, experts_held, rms_eps
    (``sizes_of`` takes them from a ``SolarOpen2Config``).  ``dtype``
    other than float32 computes EVERYTHING in it, the decays, the state
    and the router too: the deliberately cruder model a tolerance has
    to tell from this one.  ``remat`` keeps no [T, T] scores for a
    gradient and steps the recurrence in checkpointed blocks.  ``chosen``: one [S, k] array of expert ids a layer, to
    route by instead of this model's own choice (``route``)."""
    params = iter([jnp.asarray(p, dtype) for p in params])
    biases = iter([jnp.asarray(b, dtype) for b in biases])
    indices = range(sizes['first_layer'],
                    sizes['first_layer'] + sizes['layers'])
    chosen = iter(chosen if chosen is not None else [None] * len(indices))

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
            if i in sizes['gqa_layers']:
                x = x + gqa_operator(u, *take(5), sizes, remat)
            else:
                x = x + kda_operator(
                    u, *take(15), sizes,
                    REMAT_BLOCK if remat and t % REMAT_BLOCK == 0 else None)
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn, eps)
            wr, gate, up, down = take(4)
            routed, load = routed_share(
                w.reshape(b * t, width), wr, next(biases), gate, up,
                down, sizes, next(chosen))
            x = x + gated_mlp(w, *take(3)) + routed.reshape(b, t, width)
            loads.append(load)
        g_final, head = take(2)
        logits = rms_norm(x, g_final, eps) @ head
    assert next(params, None) is None and next(biases, None) is None
    return logits, loads


def loss(params, biases, ids, labels, *, sizes, dtype=jnp.float32,
         remat=False, chosen=None):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, _ = forward(params, biases, ids, sizes=sizes, dtype=dtype,
                        remat=remat, chosen=chosen)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_and_grads(params, biases, ids, labels, *, sizes, remat=False,
                   chosen=None):
    """(loss, [d loss / d param] in ``params`` order); the biases are
    held fixed."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, biases, ids, labels,
                                    sizes=sizes, remat=remat,
                                    chosen=chosen)


def sizes_of(cfg):
    """The ``sizes`` dict of a ``paddle_tpu.models.solar_open2.
    SolarOpen2Config`` (plain attribute reads: this module imports
    nothing of the zoo)."""
    return dict(layers=cfg.layers, first_layer=cfg.first_layer,
                gqa_layers=tuple(cfg.gqa_layers), head_dim=cfg.head_dim,
                kda_head_dim=cfg.kda_head_dim,
                neg_eigval=cfg.neg_eigval, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps)
