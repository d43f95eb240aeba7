"""Phi-4-mini-flash-reasoning (Microsoft, ``model_type: phi4flash``; the
SambaY decoder-hybrid-decoder) in plain ``jax.numpy``: the causal-LM
forward pass, the training loss and, by ``jax.grad``, its gradients.
Float32 throughout under ``jax.default_matmul_precision('highest')``,
a ``lax.scan`` over SINGLE TOKENS for the recurrence (no chunks), dense
[T, T] masked softmaxes, the four products of differential attention as
published, no kernel, no cache, nothing imported from ``paddle_tpu.ops``.

Written from memory of the model's public code (``modeling_phi4flash.py``
beside the config), the SambaY report (arXiv:2507.06607), Mamba
(arXiv:2312.00752) and Differential Transformer (arXiv:2410.05258):
there is no network here.  ``params`` is a dict by the program's
parameter names (``paddle_tpu/models/phi4flash.py``
``parameter_specs``); ``sizes`` holds the configuration file's keys.

With L = ``num_hidden_layers`` (a multiple of 4), layer i is

    i even, i <= L/2        Mamba; layer L/2 hands on m as the MEMORY
    i odd,  i <  L/2        differential attention, ``sliding_window``
    i = L/2 + 1             differential attention, full; its K, V SHARED
    i even, i >= L/2 + 2    gated memory unit over m
    i odd,  i >= L/2 + 3    differential cross-attention (Wq, Wo only)

BLOCK (pre-norm, LayerNorm WITH bias, eps ``layer_norm_eps``, no
position encoding: the config has no rotary key):

    x = x + Mix_i(LN1(x))
    x = x + W2 (up * silu(gate)),   [gate | up] = W1 LN2(x)

embedding, the blocks, a last LayerNorm, logits = h E^T (tied),
next-token cross-entropy over the held rows.

MAMBA (d_inner = 2 hidden, N = 16, 4 taps, dt_rank = ceil(hidden / 16)):

    [x | z] = W_in u;  x = silu(conv4(x) + b_conv)   (causal depthwise,
                                     the LAST tap on the token itself)
    [dt | B | C] = W_x x;  delta = softplus(W_dt dt + b_dt);  A = -exp(A_log)
    h_t = exp(delta_t A) * h_(t-1) + (delta_t x_t) B_t^T     h: [d_inner, N]
    m_t = h_t C_t + D * x_t;   out = W_out (m * silu(z))

GMU: out = W_out' (m * silu(W_in' u)), m the memory of layer L/2.

DIFFERENTIAL ATTENTION (heads of d = 64; H query, Hkv K/V heads): q as
[T, H/2, 2, d], k and v as [T, Hkv/2, 2, d]; q1, q2 = q[..., 0, :],
q[..., 1, :], likewise k1, k2, v1, v2; P(a, b) the masked softmax of
a b^T / sqrt(d), query pair j reading K/V pair j // (H / Hkv):

    attn1 = [P(q1, k1) v1 | P(q1, k1) v2]
    attn2 = [P(q2, k2) v1 | P(q2, k2) v2]
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
    o = rms_norm_2d(attn1 - lam attn2) * g * (1 - lam0);  out = Wo o + bo

What ``config.json`` does not settle, as this file and the program read
it (``assumed`` in the benchmark's configuration file gives the
reasons): the Mamba sizes above (the public code's defaults); Wqkv / Wq
and Wo carry a bias (the public code builds them with ``bias=True``;
``mlp_bias`` and ``lm_head_bias`` false as published); the MLP's first
half is the gate; the sub-norm's eps 1e-5; lam0's i is the layer's index
in the model AS RUN.

Departures from the published code, none in the mathematics: the
recurrence is a scan over tokens where the public code calls a fused
kernel; the four attention products are dense; dropout rates are 0 as
published.
"""

import math

import jax
import jax.numpy as jnp

MAMBA, WINDOW, FULL, GMU, CROSS = \
    'mamba', 'sliding_attention', 'full_attention', 'gmu', 'cross_attention'
EMBEDDING = 'phi4flash.embed_tokens'


def layer_kinds(layers):
    assert layers % 4 == 0, layers
    half = layers // 2
    kinds = []
    for i in range(layers):
        if i % 2 == 0:
            kinds.append(MAMBA if i <= half else GMU)
        elif i < half:
            kinds.append(WINDOW)
        else:
            kinds.append(FULL if i == half + 1 else CROSS)
    return kinds


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def causal_filter(z, w, b):
    """z [B, T, C], w [C, L], b [C]: out[t] = sum_j w[:, j] z[t - (L - 1)
    + j] + b, z zero before the sequence."""
    taps, t = w.shape[1], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps)) + b


def selective_scan(x, delta, a, bm, cm, dskip, state_dtype=None,
                   remat=False):
    """The recurrence a token at a time: x, delta [B, T, D], a [D, N],
    bm, cm [B, T, N], dskip [D] -> m [B, T, D].  ``state_dtype`` keeps
    the state in another type between tokens (the control that a
    bfloat16 state has to fail); ``remat`` has the gradient keep the
    state before each token alone and compute the token's decay again
    (one [T, D, N] array a layer where three do not fit at 8192
    tokens); the same numbers."""
    def step(h, token):
        x_t, delta_t, b_t, c_t = token
        h = jnp.exp(delta_t[:, :, None] * a) * h.astype(x.dtype) + \
            (delta_t * x_t)[:, :, None] * b_t[:, None, :]
        m = jnp.einsum('bdn,bn->bd', h, c_t) + dskip * x_t
        return h.astype(state_dtype or x.dtype), m

    zero = jnp.zeros((x.shape[0],) + a.shape, state_dtype or x.dtype)
    tokens = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, bm, cm))
    if remat:
        step = jax.checkpoint(step)
    return jnp.moveaxis(jax.lax.scan(step, zero, tokens)[1], 0, 1)


def mamba(u, p, sizes, without=(), state_dtype=None, remat=False):
    """-> (the operator's output, m before the gate)."""
    n, rank = sizes['mamba_d_state'], sizes['mamba_dt_rank']
    x, z = jnp.split(u @ p['w_in'], 2, -1)
    x = jax.nn.silu(causal_filter(x, p['conv_w'], p['conv_b']))
    dt, bm, cm = jnp.split(x @ p['w_x'], [rank, rank + n], -1)
    delta = jax.nn.softplus(dt @ p['w_dt'] + p['b_dt'])
    skip = p['d'] * (0.0 if 'skip' in without else 1.0)
    m = selective_scan(x, delta, -jnp.exp(p['a_log']), bm, cm, skip,
                       state_dtype, remat)
    return (m * jax.nn.silu(z)) @ p['w_out'], m


def gmu(u, memory, p):
    return (memory * jax.nn.silu(u @ p['w_in'])) @ p['w_out']


def attend(q, k, values, window, block=None):
    """q, k [B, T, H, d] (the K/V heads already repeated), ``values`` a
    list of [B, T, H, d] -> [P v for v in values] with P the masked
    softmax of q k^T / sqrt(d) [B, H, T, T]: causal, and with a window
    query t sees keys t - window + 1 .. t.  ``block``: that many
    queries at a time (``lax.map`` over recomputed blocks), or a long
    sequence's [T, T] probabilities do not fit; the same numbers."""
    b, t, h, d = q.shape
    kpos = jnp.arange(t)

    def rows(qb, qpos):
        scores = jnp.einsum('bqhd,bkhd->bhqk', qb, k) / math.sqrt(d)
        ahead = qpos[:, None] - kpos[None, :]
        keep = ahead >= 0
        if window:
            keep &= ahead < window
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return [jnp.einsum('bhqk,bkhd->bqhd', probs, v) for v in values]

    if not block or block >= t:
        return rows(q, kpos)
    assert t % block == 0, (t, block)
    out = jax.lax.map(
        jax.checkpoint(lambda args: rows(*args)),
        (jnp.moveaxis(q.reshape(b, t // block, block, h, d), 1, 0),
         kpos.reshape(t // block, block)))
    return [jnp.moveaxis(o, 0, 1).reshape(b, t, h, d) for o in out]


def differential_attention(q, k, v, p, i, window, sizes, without=(),
                           block=None):
    """q [B, T, H d], k, v [B, T, Hkv d] as projected -> [B, T, H d]
    before Wo: the four products."""
    h, kv, d = sizes['num_attention_heads'], \
        sizes['num_key_value_heads'], sizes['head_dim']
    b, t = q.shape[:2]
    q = q.reshape(b, t, h // 2, 2, d)
    k = jnp.repeat(k.reshape(b, t, kv // 2, 2, d), h // kv, axis=2)
    v = jnp.repeat(v.reshape(b, t, kv // 2, 2, d), h // kv, axis=2)
    v1, v2 = v[:, :, :, 0], v[:, :, :, 1]
    attn1 = jnp.concatenate(
        attend(q[:, :, :, 0], k[:, :, :, 0], [v1, v2], window, block), -1)
    attn2 = jnp.concatenate(
        attend(q[:, :, :, 1], k[:, :, :, 1], [v1, v2], window, block), -1)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
    lam = lam0
    if 'lambda' not in without:
        lam = jnp.exp(jnp.sum(p['lq1'] * p['lk1'])) - \
            jnp.exp(jnp.sum(p['lq2'] * p['lk2'])) + lam0
    o = rms_norm(attn1 - lam * attn2, p['subln_g'],
                 sizes['subln_eps']) * (1.0 - lam0)
    return o.reshape(b, t, h * d)


def attention(u, p, i, kind, shared, sizes, without=(), block=None):
    """-> (the operator's output, the layer's (k, v) as projected)."""
    h, kv, d = sizes['num_attention_heads'], \
        sizes['num_key_value_heads'], sizes['head_dim']
    if kind == CROSS:
        q = u @ p['wq'] + p['bq']
        k, v = shared
    else:
        q, k, v = jnp.split(u @ p['wqkv'] + p['bqkv'],
                            [h * d, (h + kv) * d], -1)
    window = sizes['sliding_window'] if kind == WINDOW else 0
    if 'window_511' in without and window:
        window -= 1
    o = differential_attention(q, k, v, p, i, window, sizes, without,
                               block)
    return o @ p['wo'] + p['bo'], (k, v)


def forward(params, ids, *, sizes, dtype=jnp.float32, without=(),
            state_dtype=None, remat=False, block=None):
    """ids [B, T] -> logits [B, T, V] float32.  ``dtype`` other than
    float32 computes everything but the logits in it (the control a
    lower precision has to fail); ``without`` leaves a part out
    (``skip``: D * x; ``lambda``: lam left at lam0; ``window_511``: a
    key fewer; ``memory_gradient`` / ``shared_gradient``: no gradient
    from the gated memory units into the memory, or from the cross
    layers into the shared K and V); ``remat`` recomputes each layer for
    the gradient and ``block`` computes the attention that many queries
    at a time, or a long sequence's [T, T] probabilities do not fit."""
    kinds = layer_kinds(sizes['num_hidden_layers'])
    eps = sizes['layer_norm_eps']

    def layer_params(i, kind):
        prefix = 'phi4flash.%d.' % i
        return {name[len(prefix):].replace(kind + '.', ''):
                jnp.asarray(value, dtype)
                for name, value in params.items()
                if name.startswith(prefix)}

    def layer(x, memory, shared, p, i, kind):
        u = layer_norm(x, p['ln1.g'], p['ln1.b'], eps)
        if kind == MAMBA:
            op, m = mamba(u, p, sizes, without, state_dtype, remat)
            if i == len(kinds) // 2:
                memory = m
        elif kind == GMU:
            if 'memory_gradient' in without:
                memory = jax.lax.stop_gradient(memory)
            op = gmu(u, memory, p)
        else:
            if kind == CROSS and 'shared_gradient' in without:
                shared = jax.lax.stop_gradient(shared)
            op, own = attention(u, p, i, kind, shared, sizes, without,
                                block)
            if kind == FULL:
                shared = own
        x = x + op
        gate, up = jnp.split(
            layer_norm(x, p['ln2.g'], p['ln2.b'], eps) @ p['mlp.w1'], 2, -1)
        return x + (up * jax.nn.silu(gate)) @ p['mlp.w2'], memory, shared

    with jax.default_matmul_precision('highest'):
        table = jnp.asarray(params[EMBEDDING], dtype)
        x, memory, shared = table[ids], None, None
        for i, kind in enumerate(kinds):
            run = layer
            if remat:
                run = jax.checkpoint(layer, static_argnums=(4, 5))
            x, memory, shared = run(x, memory, shared,
                                    layer_params(i, kind), i, kind)
        h = layer_norm(x, jnp.asarray(params['phi4flash.ln_f.g'], dtype),
                       jnp.asarray(params['phi4flash.ln_f.b'], dtype), eps)
        return (h @ table.T).astype(jnp.float32)


def cross_entropy(logits, labels):
    """Mean over the positions that carry a label (>= 0)."""
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss(params, ids, labels, **kw):
    return cross_entropy(forward(params, ids, **kw), labels)


def loss_and_grads(params, ids, labels, **kw):
    return jax.value_and_grad(lambda p: loss(p, ids, labels, **kw))(
        {k: jnp.asarray(v) for k, v in params.items()})


def sizes_of(cfg):
    """A ``Phi4FlashConfig`` as the dict of sizes this file reads."""
    return {'num_hidden_layers': cfg.layers, 'hidden_size': cfg.hidden,
            'num_attention_heads': cfg.heads,
            'num_key_value_heads': cfg.kv_heads, 'head_dim': cfg.head_dim,
            'intermediate_size': cfg.intermediate,
            'sliding_window': cfg.window, 'vocab_size': cfg.vocab_size,
            'layer_norm_eps': cfg.ln_eps, 'subln_eps': cfg.subln_eps,
            'mamba_d_state': cfg.d_state, 'mamba_d_conv': cfg.d_conv,
            'mamba_d_inner': cfg.d_inner, 'mamba_dt_rank': cfg.dt_rank}
