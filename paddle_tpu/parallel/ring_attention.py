"""Ring attention: context parallelism over a mesh axis.

NEW capability vs the reference (SURVEY.md §5: sequence scaling there is
LoD batching only).  The sequence dim is sharded over the 'sp' axis; K/V
blocks rotate around the ICI ring via ppermute while each device
accumulates its Q-block's attention with a numerically-stable online
softmax (flash-attention style streaming).  Communication overlaps with
the next block's compute (XLA schedules the ppermute DMA concurrently).

Differentiable: jax.vjp through ppermute reverses the ring, so the same
code serves training.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as _shard_map


def _block_attend(q, k, v, m, l, acc, q_off, k_off, scale, causal,
                  dropout_rate=0.0, dropout_seed=None,
                  dropout_g_offset=0):
    """One K/V block of online-softmax attention.
    q [B,Tq,H,D], k/v [B,Tk,H,D]; m,l [B,H,Tq]; acc [B,Tq,H,D].
    Dropout (post-softmax, reference semantics) draws the SAME counter
    hash as the flash kernels at GLOBAL (q_off/k_off-shifted)
    positions, so ring-sharded and dense runs are bit-identical
    stochastic functions of the seed; the normalizer l accumulates the
    undropped probs, so the lse merge stays exact."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    tq, tk = q.shape[1], k.shape[1]
    if causal:
        qpos = q_off + jnp.arange(tq)
        kpos = k_off + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    if dropout_rate:
        from ..ops.pallas.flash_attention import dropout_keep_dense
        b, h = q.shape[0], q.shape[2]
        keep = dropout_keep_dense(dropout_seed, b, h, tq, tk, q_off,
                                  k_off, dropout_g_offset,
                                  dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    pv = jnp.einsum('bhqk,bkhd->bqhd', p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return m_new, l_new, acc_new


def ring_attention_inner(q, k, v, axis_name, causal=False,
                         dropout_rate=0.0, dropout_seed=None,
                         dropout_g_offset=0):
    """Call INSIDE shard_map with q,k,v sequence-sharded [B,T_loc,H,D]."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    perm = [(j, (j + 1) % n) for j in range(n)]

    m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, tq, h, d), jnp.float32)

    def body(i, carry):
        m, l, acc, kk, vv = carry
        kv_idx = (idx - i) % n
        m, l, acc = _block_attend(q, kk, vv, m, l, acc,
                                  idx * tq, kv_idx * tq, scale, causal,
                                  dropout_rate, dropout_seed,
                                  dropout_g_offset)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return m, l, acc, kk, vv

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body,
                                        (m0, l0, acc0, k, v))
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis='sp', causal=False):
    """q,k,v: GLOBAL [B,T,H,D] arrays; returns [B,T,H,D].  Shards T over
    `axis` and runs the ring."""
    spec = P(None, axis, None, None)
    f = _shard_map(
        functools.partial(ring_attention_inner, axis_name=axis,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def ring_flash_attention_inner(q, k, v, axis_name, causal=False,
                               dropout_rate=0.0, dropout_seed=None,
                               dropout_g_offset=0):
    """Ring attention with the Pallas FLASH kernel as the per-block
    engine: each hop runs blockwise flash attention over the resident
    K/V shard (no [T_loc, T_loc] scores in HBM — the long-context
    configuration this exists for), and partial results merge in
    log-sum-exp space:

        L' = logaddexp(L, lse_blk)
        o' = o * exp(L - L') + o_blk * exp(lse_blk - L')

    Differentiable end-to-end: the flash kernel exposes lse as a real
    output (ops/pallas/flash_attention.py, ``with_lse``) whose cotangent
    folds into dS inside the backward kernels, and jax.vjp reverses the
    ppermute ring.  Call INSIDE shard_map with q,k,v sequence-sharded
    [B, T_loc, H, D]."""
    from ..ops.pallas.flash_attention import flash_attention
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    l0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)

    def _drop_kw(k_off):
        if not dropout_rate:
            return {}
        return {'dropout_rate': dropout_rate,
                'dropout_seed': dropout_seed,
                'dropout_offsets': (idx * tq, k_off),
                'dropout_g_offset': dropout_g_offset}

    # a ring's blocks are flash's at any length: no floor
    def full_block(kk, vv, k_off):
        return flash_attention(q, kk, vv, causal=False, min_seq=0,
                               with_lse=True, **_drop_kw(k_off))

    def diag_block(kk, vv, k_off):
        return flash_attention(q, kk, vv, causal=True, min_seq=0,
                               with_lse=True, **_drop_kw(k_off))

    def skip_block(kk, vv, k_off):
        return (jnp.zeros((b, tq, h, d), q.dtype),
                jnp.full((b, h, tq), -jnp.inf, jnp.float32))

    def body(i, carry):
        o, lse, kk, vv = carry
        kv_idx = (idx - i) % n
        if causal:
            # kv block ahead of the diagonal contributes nothing;
            # on the diagonal the block is internally causal
            case = jnp.where(kv_idx > idx, 2,
                             jnp.where(kv_idx == idx, 1, 0))
            o_blk, lse_blk = jax.lax.switch(
                case, [full_block, diag_block, skip_block], kk, vv,
                kv_idx * tq)
        else:
            o_blk, lse_blk = full_block(kk, vv, kv_idx * tq)
        o_blk = o_blk.astype(jnp.float32)
        lse_new = jnp.logaddexp(lse, lse_blk)
        # guard rows no block has touched yet (-inf - -inf = nan)
        w_old = jnp.where(jnp.isfinite(lse),
                          jnp.exp(lse - lse_new), 0.0)
        w_blk = jnp.where(jnp.isfinite(lse_blk),
                          jnp.exp(lse_blk - lse_new), 0.0)
        # [B,H,T] weights -> [B,T,H,1] to scale outputs
        wo = jnp.transpose(w_old, (0, 2, 1))[..., None]
        wb = jnp.transpose(w_blk, (0, 2, 1))[..., None]
        o = o * wo + o_blk * wb
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return o, lse_new, kk, vv

    o, lse, _, _ = jax.lax.fori_loop(0, n, body, (o0, l0, k, v))
    return o.astype(q.dtype)


def ring_flash_attention(q, k, v, mesh, axis='sp', causal=False):
    """Global-array wrapper for ring_flash_attention_inner."""
    spec = P(None, axis, None, None)
    f = _shard_map(
        functools.partial(ring_flash_attention_inner, axis_name=axis,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def reference_attention(q, k, v, causal=False):
    """Dense reference for testing: [B,T,H,D]."""
    d = q.shape[-1]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / (d ** 0.5)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)
