"""Nemotron-H's forward pass and loss in plain jax.numpy: float32 at
highest matmul precision, the Mamba-2 recurrence stepped TOKEN BY TOKEN
by a ``lax.scan`` over the equations below (no chunk, no matrix form),
the filter a sum over taps of shifted arrays, one dense [T, T] causal
mask a head (``lax.map``), a Python loop over the held experts; no
kernel, no sort.  What ``models/nemotron_h.py`` and the benchmark family
(``benchmark/families/nemotron_h.py``, which keeps its own copy) are
held to.

The model, as ``config.json`` (``nemotron_h``) gives it.  Stream x [B,
T, 2688]; layer i of kind ``hybrid_override_pattern[i]``:

    x = x + mixer_i(rms_norm(x) * g_i)              (layer_norm_epsilon 1e-5)

then a last RMSNorm with a gain and an untied head.  No position
encoding anywhere.  ``residual_in_fp32`` false.

``M``, Mamba-2 (64 heads of 64: inner 4096; 8 groups, 128 states,
4 taps with a bias, no projection bias):

    [z | xBC | dt] = u W_in                     4096 | 6144 | 64
    xBC = silu(filter(xBC) + bias)              tap j looks 3 - j back
    [x | B | C] = xBC                           4096 | 8 x 128 | 8 x 128
    delta_t,h = softplus(dt_t,h + dt_bias_h)    no clamp: time_step_limit (0, inf)
    a_t,h = -exp(A_log_h) * delta_t,h           one scalar a head and token
    S_t,h = exp(a_t,h) S_(t-1),h + delta_t,h x_t,h B_t,g(h)^T   S [64, 128], S_(-1) = 0
    y_t,h = S_t,h C_t,g(h) + D_h x_t,h          g(h) = h // 8
    out = rms_norm_grouped(y * silu(z)) W_out   groups of 512, one gain a channel

``E``, routed feed-forward (128 experts of width 1856, top-6, one
shared expert of width 3712, ``relu2``, ``norm_topk_prob``,
``routed_scaling_factor`` 2.5, ``n_group`` = ``topk_group`` = 1):

    s = sigmoid(u W_r)                          float32, all 128
    chosen = top-6 of s + b                     b: a bias for the CHOICE only
    gate_e = 2.5 * s_e / (sum of the chosen s + 1e-20)
    out = sum over the chosen e HELD HERE of gate_e * relu(u U_e)^2 D_e
          + relu(u U_s)^2 D_s

``*``, attention (32 query heads of 128 over 2 K/V heads, no bias):

    q = u Wq, k = u Wk, v = u Wv;  softmax(q k^T / sqrt(128)) v, causal,
    16 query heads a K/V head;  ctx Wo.  NOTHING is rotated.

ASSUMED, because ``config.json`` does not settle it, none changing a
published shape:

- no position encoding: the ``nemotron_h`` attention reads neither
  ``rope_theta`` nor ``partial_rotary_factor`` (Nemotron-H's report:
  "no position embeddings"), as remembered, no network here; the
  Mamba-2 layers carry order;
- the choice bias b (the router's ``e_score_correction_bias``, no
  config key), non-trainable, and that the train program moves it by
  DeepSeek-V3's rule ``b += gamma * sign(mean load - load)``;
- the gated norm's order, ``norm(y * silu(z))`` (Mamba-2's
  ``norm_before_gate`` false), statistics over each group of 512;
- a last RMSNorm with a gain before the untied head;
- no auxiliary balance loss in the training loss;
- the startup values, which the config's keys imply where they can:
  ``A_log = log(1 .. 64)``, ``D = 1``, ``dt_bias`` the inverse softplus
  of steps log-uniform in [``time_step_min``, ``time_step_max``] and at
  least ``time_step_floor``; ``rescale_prenorm_residual``: the Mamba-2
  mixers' W_out (the modelling code's ``out_proj.weight``, which only
  that mixer has, as remembered) starts sqrt(52) smaller; every other
  matrix Normal(0, 0.02), the filter PyTorch's Conv1d default, gains
  1; the table's rows Normal(0, 1) (PaLM's and T5's unit-variance
  embedding; the first norm rescales it).  The startup values stand in
  for a TRAINED model's, whose streams differ from token to token: with
  0.02 in the table a token's row (rms 0.02) is lost under the first
  Mamba-2 mixer's output (rms 0.18), whose mean over tokens is not zero
  (a SiLU's output has a mean, and so have the filter's bias and the
  gate), every later router sees that one direction in every row, and a
  chip that holds 8 of 128 experts sees several times its share of the
  rows or a fraction, by seed (PERF.md section 6, PR 65; PR 63's (7),
  (8) for SDAR's attention).

``params`` are the program's parameters in creation order
(``models.nemotron_h.parameter_specs``): embedding; per layer the norm's
gain, then ``M``: W_in, filter [6144, 4], filter bias, dt_bias [64],
A_log [64], D [64], the gated norm's gain [8, 512], W_out; ``E``:
router, up [E_held, D, W], down, choice bias [128], shared up, shared
down; ``*``: Wq, Wk, Wv, Wo; the last norm's gain; the head.
"""

import jax
import jax.numpy as jnp

PER_KIND = {'M': 8, 'E': 6, '*': 4}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def causal_filter(z, w, bias):
    """z [B, T, C], w [C, taps], bias [C]: tap j looks taps - 1 - j
    back, nothing before a sequence's start."""
    taps, t = w.shape[1], z.shape[1]
    out = jnp.zeros_like(z) + bias
    for j in range(taps):
        back = taps - 1 - j
        out = out + w[:, j] * jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], 1)
    return out


def recurrence(x, delta, a, bm, cm, dskip, block=None):
    """The ``S_t`` / ``y_t`` lines above, a token at a time: x [B, T,
    H, P], delta [B, T, H], a [H] (= -exp(A_log)), bm, cm [B, T, G, N],
    dskip [H] -> y [B, T, H, P].  ``block``: the tokens in blocks of
    that many (T a whole number of them), each computed again for its
    gradient, so that a gradient keeps T / block states and not T."""
    b, t, h, p = x.shape
    per_group = h // bm.shape[2]

    def token(state, item):
        x_t, delta_t, b_t, c_t = item
        b_t, c_t = (jnp.repeat(v, per_group, axis=1) for v in (b_t, c_t))
        state = jnp.exp(delta_t * a)[..., None, None] * state + \
            (delta_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum('bhpn,bhn->bhp', state, c_t) + \
            dskip[:, None] * x_t

    def tokens(state, items):
        return jax.lax.scan(token, state, items)

    items = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, bm, cm))
    start = jnp.zeros((b, h, p, bm.shape[-1]), x.dtype)
    if block:
        items = tuple(v.reshape((t // block, block) + v.shape[1:])
                      for v in items)
        _, y = jax.lax.scan(jax.checkpoint(tokens), start, items)
        y = y.reshape((t,) + y.shape[2:])
    else:
        _, y = tokens(start, items)
    return jnp.moveaxis(y, 0, 1)


def mamba2(u, w_in, conv_w, conv_b, dt_bias, a_log, dskip, norm_g, w_out,
           eps, block=None):
    b, t, _ = u.shape
    heads, groups, inner = dt_bias.shape[0], norm_g.shape[0], norm_g.size
    states = (conv_w.shape[0] - inner) // (2 * groups)
    z, xbc, dt = jnp.split(u @ w_in, [inner, inner + conv_w.shape[0]], -1)
    xbc = jax.nn.silu(causal_filter(xbc, conv_w, conv_b))
    x, bm, cm = jnp.split(xbc, [inner, inner + groups * states], -1)
    delta = jax.nn.softplus(dt + dt_bias)
    y = recurrence(
        x.reshape(b, t, heads, inner // heads), delta, -jnp.exp(a_log),
        bm.reshape(b, t, groups, states), cm.reshape(b, t, groups, states),
        dskip, block)
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(
        b, t, groups, inner // groups)
    return rms_norm(gated, norm_g, eps).reshape(b, t, inner) @ w_out


def attention(u, wq, wk, wv, wo, head_dim, remat=False):
    """``remat``: a head's [T, T] scores are computed again for its
    gradient, not kept."""
    b, t, _ = u.shape
    q, k, v = ((u @ w).reshape(b, t, -1, head_dim) for w in (wq, wk, wv))
    per_kv = q.shape[2] // k.shape[2]
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_head(args):
        qh, kh, vh = args                       # [B, T, d] each
        scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(
            visible, scores, -jnp.inf).astype(jnp.float32), -1)
        return jnp.einsum('bqk,bkd->bqd', probs.astype(qh.dtype), vh)

    context = jax.lax.map(jax.checkpoint(one_head) if remat else one_head, (
        jnp.moveaxis(q, 2, 0),
        jnp.repeat(jnp.moveaxis(k, 2, 0), per_kv, axis=0),
        jnp.repeat(jnp.moveaxis(v, 2, 0), per_kv, axis=0)))
    return jnp.moveaxis(context, 0, 2).reshape(b, t, -1) @ wo


def relu2_mlp(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def routed(u, router, up, down, bias, top_k, first, scale,
           renormalize=True):
    """u [S, D] -> the part of the routed sum that the experts first ..
    first + E_held - 1 give, the router over ALL experts."""
    scores = jax.nn.sigmoid((u @ router).astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weight = jnp.take_along_axis(scores, chosen, -1)
    if renormalize:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * scale
    out = jnp.zeros_like(u)
    for e in range(up.shape[0]):                # the experts held
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0), -1)
        out = out + share[:, None].astype(u.dtype) * \
            relu2_mlp(u, up[e], down[e])
    return out


def forward(params, ids, *, pattern, head_dim, top_k, first=0,
            routed_scale=2.5, eps=1e-5, renormalize=True,
            dtype=jnp.float32, remat=False, block=None):
    """-> logits [B, T, V].  ``pattern``: the letters of the layers
    run.  ``dtype`` other than float32 computes EVERYTHING in it: the
    deliberately cruder model a tolerance has to tell from this one.
    ``remat`` / ``block``: ``attention``'s and ``recurrence``'s, for a
    gradient at the published widths."""
    params = [jnp.asarray(p, dtype) for p in params]
    assert len(params) == 3 + sum(1 + PER_KIND[c] for c in pattern), \
        len(params)
    rest = iter(params[1:])

    def take(n):
        return [next(rest) for _ in range(n)]

    with jax.default_matmul_precision('highest'):
        x = params[0][ids]
        b, t, width = x.shape
        for kind in pattern:
            (gain,) = take(1)
            u = rms_norm(x, gain, eps)
            if kind == 'M':
                x = x + mamba2(u, *take(8), eps, block)
            elif kind == '*':
                x = x + attention(u, *take(4), head_dim, remat)
            else:
                router, up, down, bias, shared_up, shared_down = take(6)
                flat = u.reshape(b * t, width)
                x = x + relu2_mlp(u, shared_up, shared_down) + routed(
                    flat, router, up, down, bias, top_k, first,
                    routed_scale, renormalize).reshape(b, t, width)
        gain, head = take(2)
        return rms_norm(x, gain, eps) @ head


def next_token_loss(logits, labels):
    """The mean cross-entropy over the positions that carry a label
    (>= 0)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss(params, feed, **sizes):
    return next_token_loss(forward(params, feed['ids'], **sizes),
                           feed['labels'])


def loss_and_grads(params, feed, **sizes):
    """(loss, [d loss / d param] in ``params`` order; the choice
    biases' are zero: they enter the choice only)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, feed, **sizes)
