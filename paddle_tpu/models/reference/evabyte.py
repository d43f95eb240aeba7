"""EvaByte (EvaByte/EvaByte ``config.json``, ``model_type: evabyte``,
``attention_class: eva``) in plain ``jax.numpy``: the forward pass,
its multi-byte-prediction loss and, by ``jax.grad``, its gradients.
Float32 throughout under ``jax.default_matmul_precision('highest')``
(on a TPU a float32 matmul is otherwise bfloat16 passes), no kernel,
no folding of windows into the batch, no log-sum-exp merge, nothing
imported from ``paddle_tpu.ops``.

One layer on the block input x [T, D] (``fp32_skip_add``: x, h, y and
both adds are float32 whatever the branches compute in; here all is):

    h = x + A(N1(x));   y = h + M(N2(h))
    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + g)   norm_add_unit_offset
    M(u) = (silu(u Wg) * (u Wu)) Wd
    A(u): q = u Wq, k = u Wk, v = u Wv, each [T, H, d]; q, k rotated,
      rotate-half over the whole head, angle t * theta^(-2i/d).  Per
      head, s = d^-1/2, windows w(t) = t // W, chunks of C positions:
        a_j  = softmax over the C positions j of chunk c of (k_j . phi)
        k~_c = sum_j a_j k_j + mu;    v~_c = sum_j a_j v_j
        L(t) = {j : w(j) = w(t), j <= t}          exact, inside t's window
        R(t) = {c : c < (W / C) w(t)}             chunks of EARLIER windows
        o_t  = softmax over L(t) + R(t) of (s q_t . [k_j | k~_c])
               times [v_j | v~_c]                 ONE softmax over both
      A = concat_heads(o) Wo
    z = N_last(y);  logits_i = z W_i, i < P;  head i at position t
    predicts byte t + 1 + i;  loss = mean over i of the mean over the
    positions that have that label of CE(logits_i[t], byte[t + 1 + i])

EVA is Zheng et al. (ICLR 2023), "Efficient Attention via Control
Variates", with the sampled random feature replaced by the learned
``adaptive_phi`` and the learned ``adaptive_mu_k`` added to the pooled
key, as EvaByte ships it.  Written from the catalog's row and that
description, no network here; what the row does not fix, the same in
the program and here (``benchmark/configs/evabyte-6.5b.json``
``assumed`` gives each reason):

- k . phi is NOT scaled by s;
- mu is added AFTER the pooling (to the pooled key, not to each key);
- the summaries pool ROTATED keys;
- the eight heads are eight [D, V] matrices with equal weights in the
  loss.

Departures from a straightforward transcription, each on purpose:

- attention is computed in blocks of queries (``lax.map``), each
  against the whole [T + T / C] mask: one block's scores alive, not
  all T of them, so that it fits at 4096 and at 32768; the numbers
  are the one softmax's;
- ``remat`` recomputes a block's scores in the backward pass.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.evabyte.build_pretrain`` creates its parameters:
embedding; per layer g1, Wq, Wk, Wv, phi [H, d], mu [H, d], Wo, g2,
Wg, Wu, Wd; g_last; W_0 .. W_{P-1}.
"""

import jax
import jax.numpy as jnp
import numpy as np

PER_LAYER = 11


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1.0 + g)


def rotate(x, positions, theta):
    """[B, T, H, d]: rotate-half, feature i with i + d/2, turned by
    pos * theta^(-2i / d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (np.float32(theta) ** (
        np.arange(d // 2, dtype=np.float32) / np.float32(d // 2)))
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle).astype(x.dtype), \
        jnp.sin(angle).astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chunk_summaries(k, v, phi, mu, chunk):
    """k [B, T, H, d], v [B, T, H, dv], phi, mu [H, d] -> (k~ [B, T /
    chunk, H, d], v~ [B, T / chunk, H, dv])."""
    b, t, h, d = k.shape
    kc = k.reshape(b, t // chunk, chunk, h, d)
    vc = v.reshape(b, t // chunk, chunk, h, v.shape[-1])
    a = jax.nn.softmax(jnp.einsum('bnchd,hd->bnch', kc, phi).astype(
        jnp.float32), axis=2).astype(k.dtype)
    return (jnp.einsum('bnch,bnchd->bnhd', a, kc) + mu,
            jnp.einsum('bnch,bnchd->bnhd', a, vc))


def eva_attention(q, k, v, phi, mu, window, chunk, block=512,
                  remat=False):
    """q, k (rotated), v [B, T, H, d] -> o [B, T, H, d]: one softmax
    over the exact keys of the query's window and the summaries of
    every earlier window's chunks, a block of queries at a time."""
    b, t, h, d = q.shape
    ks, vs = chunk_summaries(k, v, phi, mu, chunk)
    keys = jnp.concatenate([k, ks], 1)          # [B, T + T / C, H, d]
    values = jnp.concatenate([v, vs], 1)
    kpos, cpos = jnp.arange(t), jnp.arange(t // chunk)
    block = min(block, t)

    def one_block(args):
        qb, qpos = args                         # [B, n, H, d], [n]
        scores = jnp.einsum('bqhd,bkhd->bhqk', qb, keys) * d ** -0.5
        mine = qpos[:, None] // window
        local = (kpos[None, :] // window == mine) & \
            (kpos[None, :] <= qpos[:, None])
        remote = cpos[None, :] < (window // chunk) * mine
        probs = jax.nn.softmax(jnp.where(
            jnp.concatenate([local, remote], 1), scores,
            -jnp.inf).astype(jnp.float32), -1).astype(qb.dtype)
        return jnp.einsum('bhqk,bkhd->bqhd', probs, values)

    if remat:
        one_block = jax.checkpoint(one_block)
    out = jax.lax.map(one_block, (
        jnp.moveaxis(q.reshape(b, t // block, block, h, d), 1, 0),
        jnp.arange(t).reshape(t // block, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def forward(params, ids, pos_ids, cfg, dtype=jnp.float32, block=512,
            remat=False):
    """-> logits [B, T, P, V] (float32).  ``cfg``: an
    ``EvaByteConfig``.  ``dtype`` other than float32 computes
    everything but the logits in it."""
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    heads, d = cfg.heads, cfg.head_dim
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[ids]
        b, t, _ = x.shape
        for _ in range(cfg.layers):
            g1, wq, wk, wv, phi, mu, wo, g2, wg, wu, wd = take(PER_LAYER)
            u = rms_norm(x, g1, cfg.rms_eps)
            q = rotate((u @ wq).reshape(b, t, heads, d), pos_ids,
                       cfg.rope_theta)
            k = rotate((u @ wk).reshape(b, t, heads, d), pos_ids,
                       cfg.rope_theta)
            v = (u @ wv).reshape(b, t, heads, d)
            o = eva_attention(q, k, v, phi, mu, cfg.window, cfg.chunk,
                              block, remat)
            x = x + o.reshape(b, t, heads * d) @ wo
            u = rms_norm(x, g2, cfg.rms_eps)
            x = x + (jax.nn.silu(u @ wg) * (u @ wu)) @ wd
        (g_last,) = take(1)
        z = rms_norm(x, g_last, cfg.rms_eps)
        return jnp.stack([(z @ w).astype(jnp.float32)
                          for w in take(cfg.pred_heads)], 2)


def loss_from_logits(logits, labels):
    """logits [B, T, P, V], labels [B, T, P] (-1: no label) -> the
    mean over the heads of each head's mean cross-entropy over its
    labelled positions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    per_head = -jnp.sum(jnp.where(valid, picked, 0.0), (0, 1)) / \
        jnp.sum(valid, (0, 1))
    return jnp.mean(per_head)


def loss(params, feed, cfg, dtype=jnp.float32, block=512, remat=False):
    return loss_from_logits(
        forward(params, feed['ids'], feed['pos_ids'], cfg, dtype, block,
                remat), feed['labels'])
