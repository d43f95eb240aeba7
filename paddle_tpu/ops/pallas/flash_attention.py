"""Flash attention forward AND backward as Pallas TPU kernels.

Replaces the reference's fused attention chain
(operators/fused/multihead_matmul_op.cu: QK^T -> softmax -> PV as cuBLAS
+ custom softmax kernels) with online-softmax kernels: Q blocks ride the
MXU against K/V blocks streamed through VMEM; no [T, T] score matrix
ever materializes in HBM.

Backward is the standard two-pass flash scheme wired through custom_vjp:
the forward additionally emits the per-row log-sum-exp (lse); backward
precomputes delta = rowsum(dO * O), then one kernel recomputes p blocks
to accumulate dQ (grid over Q blocks) and a second accumulates dK/dV
(+ the key-bias gradient) with a grid over K blocks.

An optional additive key bias [B, T] (padding masks, per-key biases)
is applied to the scores inside the kernels — the BERT input-mask path
(models/bert.py) — and receives a real gradient so learned biases work.

Both public entries go through common.dispatch(): compiled on a TPU,
the dense XLA chain anywhere else, and the Pallas interpreter only
under FLAGS_pallas_force (tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Round-3 sweep on the v5-lite chip (tools/bench_flash.py): large
# blocks dominate for d=64 — underfilled MXU passes cost more than the
# extra VMEM residency.  512/1024 is the best compiling config at seq
# 2048 (39.1 ms vs 69.1 ms at 256/256 and 77.7 ms naive XLA) and
# clamps to 512/512 at seq 512 (5.6 ms vs 7.2 ms naive); 2048-wide
# blocks exceed VMEM — _block_sizes clamps them (see VMEM model there).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024

# Measured flash-vs-naive crossover (fwd+bwd; pre-round reading, not
# measured on current code): below this sequence length XLA's fused dense chain fits
# VMEM outright and beats the kernel, so flash_attention() auto-selects
# the dense path — the public entry never ships the regression pocket.
FLASH_MIN_SEQ = 512

# platform probe / VMEM model / block clamp live in common.py now
# (shared by the whole kernel library); the module-level aliases keep
# the original private names importable.
from . import common as _common  # noqa: E402

VMEM_BUDGET_BYTES = _common.VMEM_BUDGET_BYTES
_vmem_estimate = _common.vmem_estimate
_block_sizes = _common.block_sizes

_common.register_kernel(
    'flash_attention',
    dense_fallback='ops.pallas.flash_attention._dense_path',
    has_vjp=True,
    doc='streamed softmax(QK)V; dispatches dense below min_seq',
    op_types=('matmul', 'scale', 'softmax', 'dropout'))


def _dropout_keep(seed, g, qpos, kpos, keep_threshold):
    """Deterministic per-(head, q, k) keep mask from a counter hash
    (murmur3-finalizer mix): the same element draws the same bit in the
    forward kernel, both backward kernels, the dense path, and any
    replay (per-op grad or whole-program vjp) — the (op_seed, step)
    keying discipline the dropout op uses, in-kernel.  Integer ops
    only, so Mosaic and interpret mode agree bit-for-bit."""
    h = (qpos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ \
        (kpos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)) ^ \
        (jnp.asarray(g, jnp.uint32) * jnp.uint32(0xC2B2AE3D)) ^ seed
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> jnp.uint32(16))
    return (h >> jnp.uint32(8)) < jnp.uint32(keep_threshold)


def _keep_threshold(rate):
    """24-bit integer threshold for keep-probability (1 - rate)."""
    return int(round((1.0 - float(rate)) * (1 << 24)))


def _seed_off(seed_ref, idx):
    """Offset slot of the packed (1,4) seed operand
    ([seed, q_off, k_off, g_off]) — ring attention shards T (and dp
    meshes shard B), so local block positions and the per-instance
    head index must shift to GLOBAL ones for the dropout hash."""
    return jnp.asarray(seed_ref[0, idx], jnp.int32)


def dropout_keep_dense(seed, b, h, tq, tk, q_off=0, k_off=0, g_off=0,
                       rate=0.0):
    """[b, h, tq, tk] keep mask at GLOBAL positions — the dense-form
    twin of the in-kernel draw, shared by the XLA dense dispatch arm
    and the einsum ring (_block_attend) so every path stays
    bit-identical to the Pallas kernels."""
    g = (jax.lax.broadcasted_iota(jnp.int32, (b, h, tq, tk), 0) * h +
         jax.lax.broadcasted_iota(jnp.int32, (b, h, tq, tk), 1) +
         jnp.asarray(g_off, jnp.int32))
    qpos = jnp.asarray(q_off, jnp.int32) +         jax.lax.broadcasted_iota(jnp.int32, (b, h, tq, tk), 2)
    kpos = jnp.asarray(k_off, jnp.int32) +         jax.lax.broadcasted_iota(jnp.int32, (b, h, tq, tk), 3)
    return _dropout_keep(jnp.asarray(seed, jnp.uint32), g, qpos, kpos,
                         _keep_threshold(rate))


def _pack_seed(seed, offsets=None, g_off=0):
    """[seed, q_off, k_off, g_off] uint32 (1,4) operand for the
    kernels.  g_off shifts the per-instance head index to its GLOBAL
    value when the batch dim is itself sharded (dp x sp meshes): the
    kernels see local batch indices, and without the shift two dp
    shards would draw identical masks for different samples."""
    qo, ko = offsets if offsets is not None else (0, 0)
    return jnp.stack([jnp.asarray(seed, jnp.uint32),
                      jnp.asarray(qo, jnp.uint32),
                      jnp.asarray(ko, jnp.uint32),
                      jnp.asarray(g_off, jnp.uint32)]).reshape(1, 4)


def _precision(dtype):
    """float32 operands multiply at full precision, as every matmul op
    of an f32 program does (ops/math_ops.py); bfloat16 ones (AMP) in
    the MXU's one native pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot(a, b, contract):
    """a . b over ``contract`` (one dim of each), accumulated in f32."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=_precision(a.dtype),
        preferred_element_type=jnp.float32)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                      block_k, has_bias, rate):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    o_ref, lse_ref = rest
    # q_ref: [1, bq, d]; k/v_ref: [1, T, d]; bias_ref: [1, 1, T];
    # o_ref: [1, bq, d]; lse_ref: [1, 1, bq]  (the singleton middle dim
    # satisfies the TPU block-shape rule for 1-D-per-row operands)
    # dots consume the native (usually bf16) dtype and accumulate in
    # f32 (_dot): the MXU runs bf16 at 2x f32 throughput and VMEM
    # traffic halves — the pre-cast-to-f32 variant measured ~25%
    # slower at seq 512; f32 operands multiply at full precision
    q = q_ref[0]
    bq, d = q.shape
    t = k_ref.shape[1]
    q_off = pl.program_id(1) * bq
    g_id = pl.program_id(0)

    nk = t // block_k

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = _dot(q, k, (1, 1))
        s = s * scale
        if has_bias:
            bias = bias_ref[0, 0, pl.dslice(i * block_k,
                                            block_k)].astype(jnp.float32)
            s = s + bias[None, :]
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq,
                                                                block_k),
                                                    0)
            kpos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        # dropout applies AFTER softmax (reference: dropout around the
        # probs, python/paddle/fluid/layers/nn.py): the normalizer l
        # accumulates the UNDROPPED p, only the V-weighting is masked
        l_new = l * corr + jnp.sum(p, axis=1)
        if rate:
            qpos_d = q_off + _seed_off(seed_ref, 1) + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos_d = i * block_k + _seed_off(seed_ref, 2) + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            keep = _dropout_keep(seed_ref[0, 0],
                                 g_id + _seed_off(seed_ref, 3),
                                 qpos_d, kpos_d, _keep_threshold(rate))
            p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        acc_new = acc * corr[:, None] + _dot(p.astype(v.dtype), v,
                                             (1, 0))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # skip fully-masked K blocks beyond the diagonal
        last = (q_off + bq + block_k - 1) // block_k
        nk_eff = jnp.minimum(nk, last)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-20)
    out = acc / l_safe[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    lse_ref[0, 0] = (m_safe + jnp.log(l_safe)).astype(jnp.float32)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                         block_k, has_bias, has_glse, rate):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    do_ref, lse_ref, delta_ref = rest[0], rest[1], rest[2]
    glse_ref = rest[3] if has_glse else None
    dq_ref = rest[-1]
    """Grid (BH, T/bq): recompute p row-blocks from q and lse, then
    dq = sum_k (p * (dO V^T - delta)) K * scale."""
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)
    # lse cotangent (ring-merge path): dS_ij += p_ij * g_lse_i, so it
    # rides the same (dp - delta) rail; absent for plain attention
    glse = glse_ref[0, 0].astype(jnp.float32) if has_glse else None
    bq, d = q.shape
    t = k_ref.shape[1]
    q_off = pl.program_id(1) * bq
    g_id = pl.program_id(0)
    nk = t // block_k

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = _dot(q, k, (1, 1))
        s = s * scale
        if has_bias:
            bias = bias_ref[0, 0, pl.dslice(i * block_k,
                                            block_k)].astype(jnp.float32)
            s = s + bias[None, :]
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            kpos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse[:, None]), 0.0)
        dp = _dot(do, v, (1, 1))
        if rate:
            # softmax vjp with post-softmax dropout u: dS = p*(u*dp -
            # delta); delta = rowsum(dO*O) already sees the dropout
            # because O was computed WITH it
            qpos_d = q_off + _seed_off(seed_ref, 1) + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos_d = i * block_k + _seed_off(seed_ref, 2) + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            keep = _dropout_keep(seed_ref[0, 0],
                                 g_id + _seed_off(seed_ref, 3),
                                 qpos_d, kpos_d, _keep_threshold(rate))
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        dd = dp - delta[:, None]
        if has_glse:
            dd = dd + glse[:, None]
        ds = p * dd * scale
        return dq + _dot(ds.astype(k.dtype), k, (1, 0))

    if causal:
        last = (q_off + bq + block_k - 1) // block_k
        nk_eff = jnp.minimum(nk, last)
    else:
        nk_eff = nk
    dq = jax.lax.fori_loop(0, nk_eff, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                          block_q, has_bias, has_glse, rate):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    do_ref, lse_ref, delta_ref = rest[0], rest[1], rest[2]
    glse_ref = rest[3] if has_glse else None
    dk_ref, dv_ref = rest[-3:-1] if has_bias else rest[-2:]
    dbias_ref = rest[-1] if has_bias else None
    """Grid (BH, T/bk): for one K/V block, stream Q row-blocks:
    dv = sum_q p^T dO;  ds_raw = p * (dO V^T - delta);
    dk = sum_q ds_raw^T Q * scale;  dbias = sum_q ds_raw (per key)."""
    k = k_ref[0]
    v = v_ref[0]
    bias = bias_ref[0, 0].astype(jnp.float32) if has_bias else None
    bk, d = k.shape
    t = q_ref.shape[1]
    k_off = pl.program_id(1) * bk
    g_id = pl.program_id(0)
    nq = t // block_q

    def body(j, carry):
        dk, dv, dbias = carry
        q = q_ref[0, pl.dslice(j * block_q, block_q), :]
        do = do_ref[0, pl.dslice(j * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32)
        delta = delta_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32)
        glse = glse_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32) if has_glse else None
        s = _dot(q, k, (1, 1))
        s = s * scale
        if has_bias:
            s = s + bias[None, :]
        if causal:
            qpos = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            kpos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse[:, None]), 0.0)
        if rate:
            qpos_d = j * block_q + _seed_off(seed_ref, 1) + \
                jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            kpos_d = k_off + _seed_off(seed_ref, 2) + \
                jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            keep = _dropout_keep(seed_ref[0, 0],
                                 g_id + _seed_off(seed_ref, 3),
                                 qpos_d, kpos_d, _keep_threshold(rate))
            pu = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        else:
            keep, pu = None, p
        dv = dv + _dot(pu.astype(do.dtype), do, (0, 0))
        dp = _dot(do, v, (1, 1))
        if rate:
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        dd = dp - delta[:, None]
        if has_glse:
            dd = dd + glse[:, None]
        ds_raw = p * dd
        dk = dk + _dot(ds_raw.astype(q.dtype), q, (0, 0)) * scale
        if has_bias:
            dbias = dbias + jnp.sum(ds_raw, axis=0)
        return dk, dv, dbias

    if causal:
        # q blocks strictly above the diagonal contribute nothing
        j0 = k_off // block_q
    else:
        j0 = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    db0 = jnp.zeros((bk,), jnp.float32)
    dk, dv, dbias = jax.lax.fori_loop(j0, nq, body, (dk0, dv0, db0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    if has_bias:
        dbias_ref[0, 0] = dbias.astype(dbias_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                            block_q, block_k, has_bias, has_glse,
                            rate):
    """Single-pass backward: grid (BH,) only.  The two-pass scheme
    (dq grid over Q blocks, dk/dv grid over K blocks) recomputes the
    score block s AND the prob-cotangent dp = dO V^T in BOTH kernels —
    9 MXU dots per (q,k) tile-pair step instead of 7.  When the whole
    per-head working set fits VMEM (q/k/v/do rows + an f32 dq
    accumulator — true for the long-context shapes this kernel
    exists for), one kernel can walk k-blocks x q-blocks computing s
    and dp ONCE and accumulating all three gradients: dk/dv stream out
    per k-block, dq rides a VMEM carry.  Measured motivation: the
    round-5 traced per-op table put the flash kernels at 41% of the
    BERT-s2048 step with 2/9 of their dot FLOPs being these
    recomputes."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    do_ref, lse_ref, delta_ref = rest[0], rest[1], rest[2]
    glse_ref = rest[3] if has_glse else None
    acc_ref = rest[-1]          # f32 VMEM scratch for the dq carry
    if has_bias:
        dq_ref, dk_ref, dv_ref, dbias_ref = rest[-5], rest[-4], \
            rest[-3], rest[-2]
    else:
        dq_ref, dk_ref, dv_ref = rest[-4], rest[-3], rest[-2]
        dbias_ref = None
    t, d = q_ref.shape[1], q_ref.shape[2]
    g_id = pl.program_id(0)
    nq, nk = t // block_q, t // block_k

    def k_step(i, _):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        bias = bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32) if has_bias else None

        def q_step(j, carry):
            dk, dv, dbias = carry
            q = q_ref[0, pl.dslice(j * block_q, block_q), :]
            do = do_ref[0, pl.dslice(j * block_q, block_q), :]
            lse = lse_ref[0, 0, pl.dslice(j * block_q,
                                          block_q)].astype(jnp.float32)
            delta = delta_ref[0, 0, pl.dslice(j * block_q,
                                              block_q)].astype(
                jnp.float32)
            s = _dot(q, k, (1, 1))
            s = s * scale
            if has_bias:
                s = s + bias[None, :]
            if causal:
                qpos = j * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = i * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(qpos >= kpos, s, -jnp.inf)
            p = jnp.where(jnp.isfinite(s),
                          jnp.exp(s - lse[:, None]), 0.0)
            dp = _dot(do, v, (1, 1))
            if rate:
                qpos_d = j * block_q + _seed_off(seed_ref, 1) + \
                    jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                kpos_d = i * block_k + _seed_off(seed_ref, 2) + \
                    jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1)
                keep = _dropout_keep(
                    seed_ref[0, 0], g_id + _seed_off(seed_ref, 3),
                    qpos_d, kpos_d, _keep_threshold(rate))
                pu = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
                dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
            else:
                pu = p
            dv = dv + _dot(pu.astype(do.dtype), do, (0, 0))
            dd = dp - delta[:, None]
            if has_glse:
                glse = glse_ref[0, 0, pl.dslice(j * block_q,
                                                block_q)].astype(
                    jnp.float32)
                dd = dd + glse[:, None]
            ds_raw = p * dd
            dk = dk + _dot(ds_raw.astype(q.dtype), q, (0, 0)) * scale
            if has_bias:
                dbias = dbias + jnp.sum(ds_raw, axis=0)
            dq_blk = _dot(ds_raw.astype(k.dtype), k, (1, 0)) * scale
            # dq accumulates across k-blocks in the f32 VMEM scratch
            # (read-modify-write through the ref: Mosaic supports
            # dynamic slicing on refs, not on carried values)
            cur = acc_ref[pl.dslice(j * block_q, block_q), :]
            acc_ref[pl.dslice(j * block_q, block_q), :] = cur + dq_blk
            return dk, dv, dbias

        if causal:
            j0 = (i * block_k) // block_q
        else:
            j0 = 0
        dk0 = jnp.zeros((block_k, d), jnp.float32)
        dv0 = jnp.zeros((block_k, d), jnp.float32)
        db0 = jnp.zeros((block_k,), jnp.float32)
        dk, dv, dbias = jax.lax.fori_loop(
            j0, nq, q_step, (dk0, dv0, db0))
        dk_ref[0, pl.dslice(i * block_k, block_k), :] = \
            dk.astype(dk_ref.dtype)
        dv_ref[0, pl.dslice(i * block_k, block_k), :] = \
            dv.astype(dv_ref.dtype)
        if has_bias:
            dbias_ref[0, 0, pl.dslice(i * block_k, block_k)] = \
                dbias.astype(dbias_ref.dtype)
        return 0

    acc_ref[...] = jnp.zeros((t, d), jnp.float32)
    jax.lax.fori_loop(0, nk, k_step, 0)
    dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, bias, seed2, do, lse3, delta3, glse3, h,
                     causal, block_q, block_k, interpret, rate):
    """pallas_call plumbing for the one-pass backward (grid (BH,))."""
    bh, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    has_bias = bias is not None
    has_glse = glse3 is not None
    kernel = functools.partial(
        _flash_bwd_fused_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, has_bias=has_bias,
        has_glse=has_glse, rate=rate)
    row = pl.BlockSpec((1, t, d), lambda i: (i, 0, 0))
    vec = pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0))
    in_specs = [row, row, row]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, t),
                                     lambda i: (i // h, 0, 0)))
        operands.append(bias[:, None, :])
    if rate:
        in_specs.append(pl.BlockSpec((1, 4), lambda i: (0, 0)))
        operands.append(seed2)
    in_specs += [row, vec, vec]
    operands += [do, lse3, delta3]
    if has_glse:
        in_specs.append(vec)
        operands.append(glse3)
    out_specs = [row, row, row]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if has_bias:
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
    from jax.experimental.pallas import tpu as pltpu
    res = pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
        interpret=interpret,
    )(*operands)
    if has_bias:
        dq, dk, dv, dbias_bh = res
        b = bh // h
        dbias = dbias_bh[:, 0, :].reshape(b, h, t).sum(axis=1)
    else:
        dq, dk, dv = res
        dbias = None
    return dq, dk, dv, dbias


# The fused one-pass backward engages when the per-head VMEM residency
# fits; False forces the two-pass scheme (sweeps / A-B measurement).
FUSED_BWD = True
# Fused-backward tile shape (chip-swept round 5: 512/512 best at d=64
# within the VMEM budget; larger k-tiles push the f32 score blocks
# over it and fall back to two-pass).
FUSED_BLOCK_Q = 512
FUSED_BLOCK_K = 512


def _fused_bwd_vmem(t, d, block_q, block_k, itemsize):
    """Resident bytes for the fused backward: q/k/v/do full rows, the
    f32 dq accumulator + dk/dv/score f32 blocks (x2 slack for compiler
    temporaries)."""
    rows = 4 * t * d * itemsize
    dq_acc = t * d * 4
    blocks = 2 * block_k * d * 4 + 3 * block_q * block_k * 4
    return rows + dq_acc + 2 * blocks + (1 << 19)


def _flash_fwd(q, k, v, bias, seed, h, causal, block_q, block_k,
               interpret, rate=0.0):
    """q,k,v: [BH, T, D], bias: [B, T] or None, seed: packed (1,4)
    uint32 [seed, q_off, k_off, g_off] (_pack_seed, required when
    rate>0) -> (o [BH,T,D], lse [BH,T])."""
    bh, t, d = q.shape
    block_q, block_k = _block_sizes(t, block_q, block_k, d,
                                    q.dtype.itemsize)
    scale = 1.0 / (d ** 0.5)
    has_bias = bias is not None
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               causal=causal, block_k=block_k,
                               has_bias=has_bias, rate=rate)
    grid = (bh, t // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
    ]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, t),
                                     lambda i, j: (i // h, 0, 0)))
        operands.append(bias[:, None, :])
    if rate:
        in_specs.append(pl.BlockSpec((1, 4), lambda i, j: (0, 0)))
        operands.append(jnp.asarray(seed, jnp.uint32).reshape(1, 4))
    o, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return o, lse3[:, 0, :]


def _flash_bwd(q, k, v, bias, seed, o, lse, do, g_lse, h, causal,
               block_q, block_k, interpret, rate=0.0):
    bh, t, d = q.shape
    block_q, block_k = _block_sizes(t, block_q, block_k, d,
                                    q.dtype.itemsize)
    scale = 1.0 / (d ** 0.5)
    # delta = rowsum(dO * O): one fused elementwise+reduce in XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    has_bias = bias is not None
    has_glse = g_lse is not None
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    glse3 = g_lse.astype(jnp.float32)[:, None, :] if has_glse else None
    seed2 = jnp.asarray(seed, jnp.uint32).reshape(1, 4) if rate else None
    seed_spec = pl.BlockSpec((1, 4), lambda i, j: (0, 0))

    fq, fk = min(block_q, FUSED_BLOCK_Q), min(block_k, FUSED_BLOCK_K)
    while t % fq:
        fq //= 2
    while t % fk:
        fk //= 2
    if FUSED_BWD and _fused_bwd_vmem(t, d, fq, fk, q.dtype.itemsize) \
            <= VMEM_BUDGET_BYTES:
        return _flash_bwd_fused(q, k, v, bias, seed2, do, lse3, delta3,
                                glse3, h, causal, fq, fk, interpret,
                                rate)

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_k=block_k,
                                  has_bias=has_bias, has_glse=has_glse,
                                  rate=rate)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
    ]
    dq_operands = [q, k, v]
    if has_bias:
        dq_specs.append(pl.BlockSpec((1, 1, t),
                                     lambda i, j: (i // h, 0, 0)))
        dq_operands.append(bias[:, None, :])
    if rate:
        dq_specs.append(seed_spec)
        dq_operands.append(seed2)
    dq_specs += [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
    ]
    dq_operands += [do, lse3, delta3]
    if has_glse:
        dq_specs.append(pl.BlockSpec((1, 1, block_q),
                                     lambda i, j: (i, 0, j)))
        dq_operands.append(glse3)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, t // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*dq_operands)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   has_bias=has_bias,
                                   has_glse=has_glse, rate=rate)
    dkv_specs = [
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
    ]
    dkv_operands = [q, k, v]
    if has_bias:
        dkv_specs.append(pl.BlockSpec((1, 1, block_k),
                                      lambda i, j: (i // h, 0, j)))
        dkv_operands.append(bias[:, None, :])
    if rate:
        dkv_specs.append(seed_spec)
        dkv_operands.append(seed2)
    dkv_specs += [
        pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
    ]
    dkv_operands += [do, lse3, delta3]
    if has_glse:
        dkv_specs.append(pl.BlockSpec((1, 1, t),
                                      lambda i, j: (i, 0, 0)))
        dkv_operands.append(glse3)
    out_specs = [
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, 1, block_k),
                                      lambda i, j: (i, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
    res = pl.pallas_call(
        dkv_kernel,
        grid=(bh, t // block_k),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*dkv_operands)
    if has_bias:
        dk, dv, dbias_bh = res
        # bias is per (batch, key): sum head lanes
        b = bh // h
        dbias = dbias_bh.reshape(b, h, t).sum(axis=1)
    else:
        dk, dv = res
        dbias = None
    return dq, dk, dv, dbias


def _dense_reference(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum('btd,bsd->bts', q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bts,bsd->btd', p, v.astype(jnp.float32)).astype(
        q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, bias, seed, h, causal, rate, interpret):
    """(o, lse): lse is a first-class differentiable output so ring
    attention can merge per-block flash results (parallel/
    ring_attention.py ring_flash_attention).  ``interpret`` is the one
    common.dispatch() decision the public entry made; forward and
    backward kernels all read it."""
    return _flash_fwd(q, k, v, bias, seed, h, causal, DEFAULT_BLOCK_Q,
                      DEFAULT_BLOCK_K, interpret, rate)


def _flash_lse_fwd_rule(q, k, v, bias, seed, h, causal, rate,
                        interpret):
    o, lse = _flash_fwd(q, k, v, bias, seed, h, causal,
                        DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret,
                        rate)
    return (o, lse), (q, k, v, bias, seed, o, lse)


def _flash_lse_bwd_rule(h, causal, rate, interpret, res, gs):
    q, k, v, bias, seed, o, lse = res
    g, g_lse = gs
    dq, dk, dv, dbias = _flash_bwd(q, k, v, bias, seed, o, lse, g,
                                   g_lse, h, causal, DEFAULT_BLOCK_Q,
                                   DEFAULT_BLOCK_K, interpret, rate)
    return dq, dk, dv, (None if bias is None
                        else dbias.astype(bias.dtype)), None


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias, seed, h, causal, rate, interpret):
    # o-only primitive with its OWN vjp so the common (non-ring) path
    # never ships a zeros g_lse operand into the backward kernels
    o, _ = _flash_fwd(q, k, v, bias, seed, h, causal, DEFAULT_BLOCK_Q,
                      DEFAULT_BLOCK_K, interpret, rate)
    return o


def _flash_fwd_rule(q, k, v, bias, seed, h, causal, rate, interpret):
    o, lse = _flash_fwd(q, k, v, bias, seed, h, causal,
                        DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret,
                        rate)
    return o, (q, k, v, bias, seed, o, lse)


def _flash_bwd_rule(h, causal, rate, interpret, res, g):
    q, k, v, bias, seed, o, lse = res
    dq, dk, dv, dbias = _flash_bwd(q, k, v, bias, seed, o, lse, g,
                                   None, h, causal, DEFAULT_BLOCK_Q,
                                   DEFAULT_BLOCK_K, interpret, rate)
    return dq, dk, dv, (None if bias is None
                        else dbias.astype(bias.dtype)), None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _dense_path(q, k, v, causal, key_bias, dropout_rate=0.0,
                dropout_seed=None, dropout_offsets=None,
                dropout_g_offset=0, with_lse=False):
    """Fused-by-XLA dense chain on [B, T, H, D] (bf16 dots, f32
    softmax) — the measured winner below FLASH_MIN_SEQ, where the
    whole chain fits VMEM outright.  Differentiable via XLA autodiff.
    Dropout draws the SAME counter-hash mask as the Pallas kernels, so
    the two dispatch arms are bit-identical stochastic functions of
    (seed, element position).  ``with_lse`` also returns the per-row
    log-sum-exp [B, H, T] of the undropped scores (the
    flash_attention_with_lse contract)."""
    b, t, h, d = q.shape
    s = jnp.einsum('bthd,bshd->bhts', q, k,
                   precision=_precision(q.dtype),
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    if key_bias is not None:
        s = s + key_bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate:
        # SAME hash as the kernels (per-element head-index array here,
        # the grid program_id there)
        qo, ko = dropout_offsets if dropout_offsets is not None \
            else (0, 0)
        keep = dropout_keep_dense(dropout_seed, b, h, t, t, qo, ko,
                                  dropout_g_offset, dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    p = p.astype(q.dtype)
    o = jnp.einsum('bhts,bshd->bthd', p, v,
                   precision=_precision(q.dtype))
    if with_lse:
        return o, jax.nn.logsumexp(s, axis=-1)
    return o


def flash_attention(q, k, v, causal=False, key_bias=None,
                    min_seq=None, dropout_rate=0.0, dropout_seed=None,
                    dropout_offsets=None, dropout_g_offset=0,
                    auto_partitioned=False):
    """q,k,v: [B, T, H, D]; key_bias: optional [B, T] additive score
    bias (e.g. padding mask as 0 / -10000) -> [B, T, H, D].

    dropout_rate > 0 applies dropout to the attention probabilities
    INSIDE the kernels (reference default: dropout around softmax,
    operators/dropout_op.cu used by layers/nn.py) — the [T, T] probs
    still never materialize.  The mask is a counter hash of
    (dropout_seed, head, q, k): forward, both backward kernels, and
    any replay regenerate it bit-for-bit, so per-op grad replay and
    whole-program vjp see the same network.  dropout_seed must be a
    uint32 scalar (fold the op seed with the step).

    Auto-dispatch through common.dispatch(): sequences shorter than
    `min_seq` (default FLASH_MIN_SEQ, the measured crossover) run the
    dense XLA chain, and so does every call off a TPU unless
    FLAGS_pallas_force asks for the interpreter (tests), and every
    call an op lowering marks ``auto_partitioned`` (the GSPMD runner's
    trace; see common.dispatch()).  Pass min_seq=0 to drop the floor
    (benchmark sweeps)."""
    b, t, h, d = q.shape
    if min_seq is None:
        min_seq = FLASH_MIN_SEQ
    rate = float(dropout_rate or 0.0)
    if rate and dropout_seed is None:
        raise ValueError('dropout_rate > 0 needs a dropout_seed')
    fused, interpret = _common.dispatch(
        'flash_attention', True, checks=(('below_floor', t >= min_seq),),
        auto_partitioned=auto_partitioned)
    if not fused:
        return _dense_path(q, k, v, causal, key_bias, rate,
                           dropout_seed, dropout_offsets,
                           dropout_g_offset)

    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)

    if key_bias is not None:
        key_bias = key_bias.astype(jnp.float32)
    seed = _pack_seed(dropout_seed, dropout_offsets,
                      dropout_g_offset) if rate else None
    out = _flash(to_bh(q), to_bh(k), to_bh(v), key_bias, seed, h,
                 causal, rate, interpret)
    return jnp.transpose(out.reshape(b, h, t, d), (0, 2, 1, 3))


def flash_attention_with_lse(q, k, v, causal=False, key_bias=None,
                             dropout_rate=0.0, dropout_seed=None,
                             dropout_offsets=None, dropout_g_offset=0):
    """Like flash_attention but also returns the per-row log-sum-exp
    [B, H, T] — the merge state for blockwise/ring composition.  Both
    outputs are differentiable (the lse cotangent folds into dS inside
    the backward kernels).  lse is computed from the UNDROPPED probs
    (dropout scales only the V-weighting), so ring merges stay exact
    under dropout."""
    b, t, h, d = q.shape
    rate = float(dropout_rate or 0.0)
    if rate and dropout_seed is None:
        raise ValueError('dropout_rate > 0 needs a dropout_seed')
    fused, interpret = _common.dispatch('flash_attention', True)
    if not fused:
        return _dense_path(q, k, v, causal, key_bias, rate,
                           dropout_seed, dropout_offsets,
                           dropout_g_offset, with_lse=True)

    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)

    if key_bias is not None:
        key_bias = key_bias.astype(jnp.float32)
    seed = _pack_seed(dropout_seed, dropout_offsets,
                      dropout_g_offset) if rate else None
    o, lse = _flash_lse(to_bh(q), to_bh(k), to_bh(v), key_bias, seed,
                        h, causal, rate, interpret)
    o = jnp.transpose(o.reshape(b, h, t, d), (0, 2, 1, 3))
    return o, lse.reshape(b, h, t)
