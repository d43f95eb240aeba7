"""Flash attention forward AND backward as Pallas TPU kernels.

Replaces the reference's fused attention chain
(operators/fused/multihead_matmul_op.cu: QK^T -> softmax -> PV as cuBLAS
+ custom softmax kernels) with online-softmax kernels: Q blocks ride the
MXU against K/V blocks streamed through VMEM; no [T, T] score matrix
ever materializes in HBM.

Backward is wired through custom_vjp: the forward additionally emits
the per-row log-sum-exp (lse); backward precomputes delta =
rowsum(dO * O), then either ONE kernel per head walks k-blocks x
q-blocks and accumulates dQ, dK, dV (+ the key-bias gradient) while
the head's rows fit the VMEM the call can ask Mosaic for
(_flash_bwd_fused_kernel; common.one_pass_backward_vmem counts them:
every bfloat16 shape a cell runs, up to 8192 positions at 192 over
128), or the standard two-pass scheme: one kernel recomputes p blocks
to accumulate dQ (grid over Q blocks) and a second accumulates dK/dV
with a grid over K blocks (float32 rows of an 8k sequence, and
FUSED_BWD = False).

The four kernel bodies share the per-tile chain (_score_tile: scores,
scale, bias, causal mask, dropout multiplier) and the tile loop
(_loop, one or two tiles a trip: _second_tile); what differs
between callers — bias, rate, causal, whether 1/sqrt(d) is a power of
two, operand width — is static at trace time.

q and k share one width d (the contraction of q k^T, and the softmax
scale 1/sqrt(d)); v, and with it o and do, may have another, dv (a
latent-attention head: 192-wide keys over 128-wide values).  Every
product then runs at its own width (q k^T, dq, dk over d; p v, dv,
dO v^T over dv) and nothing is padded to the wider one.

An optional additive key bias [B, T] (padding masks, per-key biases)
is applied to the scores inside the kernels — the BERT input-mask path
(models/bert.py) — and receives a real gradient so learned biases work.

The masks, all static at trace time and all applied per tile by
_score_tile, with the tiles wholly outside them never visited
(_key_blocks, _query_blocks):

- none: every query sees every key;
- ``causal``: query i sees the keys j <= i;
- ``causal`` with ``window``: the band 0 <= i - j < window;
- ``coarse`` = (window, chunk): the keys are SUMMARIES, Tk >= T /
  chunk of them, one a chunk of ``chunk`` positions, and query i sees
  those of every window of ``window`` positions before its own
  (_coarse_visible).  The one mask under which queries and keys
  differ in length: the resident rows are then of two lengths (K and
  V in a forward or dq call, Q and dO in a dkv call, both in the
  fused backward), and every estimate of common.py takes the length
  that is resident;
- ``relation`` = (block, inclusive), a RELATION BETWEEN BLOCKS of
  ``block`` positions (block diffusion's two copies of a sequence):
  query i sees key j where j // block <= i // block (``inclusive``:
  block-causal, the clean copy over itself) or j // block <
  i // block (strictly: the corrupted copy over the clean one, whose
  first block sees nothing: out 0, lse -inf, as under the coarse
  mask).

Two layouts, and the shape picks (_heads_a_step): the forward and the
one-pass backward address the op's own [B, T, H*64] operands, the pair
of heads 2p, 2p+1 a grid step (128 lanes dense, nothing transposed in
or out, the residuals the op's inputs and output), where the heads are
64 wide, not grouped, even in number and under no band or coarse mask
(BERT's calls); every other call hands the same bodies one head a step
of [B*H, T, D] copies.  Counters ``pallas/flash_attention/layout_paired``
/ ``layout_transposed``, one a lowering.

A call of a handful of keys (SMALL_KEYS or fewer, no mask, bias or
dropout: block diffusion's own blocks folded into the batch) is
neither these kernels' nor the dense chain's shape: small_keys.py's
two calls take it, by the shape alone.

flash_attention() is the one public entry; ``with_lse`` makes the
rows' log-sum-exp a second, differentiable output (its cotangent
folds into dS inside the backward kernels), by which partial results
over disjoint key sets merge: ring attention's blocks
(parallel/ring_attention.py), EVA's exact and summarised keys
(layers.eva_attention).  mesh_flash_attention() wraps it for the
GSPMD runner.  Both go through common.dispatch(): compiled on a TPU,
the dense XLA chain anywhere else, and the Pallas interpreter only
under FLAGS_pallas_force (tests).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Block sizes: 512/1024 is the largest pair that compiles at seq 2048
# (2048-wide blocks exceed VMEM: _block_sizes clamps them, see the
# VMEM model in common.py) and clamps to 512/512 at seq 512; smaller
# blocks underfill the MXU at d=64 and lost every sweep (rounds 3, 5).
# What the calls cost on a v5e, [B*H, T, 64] bf16 with a key bias and
# rate 0.1, is in PERF.md section 6 (PR 29) with the ablation.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024

# Below this sequence length flash_attention() runs the dense XLA
# chain (a pre-round reading, not re-measured on this code: ROADMAP
# S3 / S4 ask for the crossover at s128-s256 again).
FLASH_MIN_SEQ = 512

# A call whose WHOLE key length is this or less, with no mask, key bias
# or dropout, goes to small_keys.py's two calls (block diffusion's own
# blocks: 4 queries x 4 keys, the blocks folded into the batch).  Their
# cost does not move with the length (a grid step is 128 key rows
# whatever their blocks; forward + backward 0.46 ms at 4096 rows of 32
# heads over 4 of 128, at 4 keys and at 16) while the dense chain's
# falls as the blocks grow, 1.68 ms at 4 keys and 0.99 at 16 (my chip
# run, PR 64; PERF.md section 6); 16 covers block diffusion's published
# block lengths.  Not measured beyond.
SMALL_KEYS = 16

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


# the draw itself (row term, column term, finalizer, threshold) lives
# in ops/keep_hash.py, shared with the dropout op; the kernels below
# trace the same functions under their original names
from ..keep_hash import (_keep_rows, _keep_cols, _dropout_keep,  # noqa: E402,F401
                         _keep_threshold)


def _seed_off(seed_ref, idx):
    """Offset slot of the packed (1,4) seed operand
    ([seed, q_off, k_off, g_off]) — ring attention shards T (and dp
    meshes shard B), so local block positions and the per-instance
    head index must shift to GLOBAL ones for the dropout hash."""
    return jnp.asarray(seed_ref[0, idx], jnp.int32)


def _draw_rows(seed_ref, g, q0, n):
    """[n, 1] row term of the draw for local rows q0 .. q0+n-1 of the
    grid's head ``g`` (the seed operand shifts both to global); None
    without a seed operand (rate 0)."""
    if seed_ref is None:
        return None
    qpos = q0 + _seed_off(seed_ref, 1) + \
        jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    return _keep_rows(seed_ref[0, 0], g + _seed_off(seed_ref, 3), qpos)


def _draw_cols(seed_ref, k0, n):
    """[1, n] column term of the draw for local columns k0 .. k0+n-1."""
    if seed_ref is None:
        return None
    return _keep_cols(k0 + _seed_off(seed_ref, 2) +
                      jax.lax.broadcasted_iota(jnp.int32, (1, n), 1))


def dropout_keep_dense(seed, b, h, tq, tk, q_off=0, k_off=0, g_off=0,
                       rate=0.0):
    """[b, h, tq, tk] keep mask at GLOBAL positions — the dense-form
    twin of the in-kernel draw, shared by the XLA dense dispatch arm
    and the einsum ring (_block_attend) so every path stays
    bit-identical to the Pallas kernels."""
    g = (jax.lax.broadcasted_iota(jnp.int32, (b, h, 1, 1), 0) * h +
         jax.lax.broadcasted_iota(jnp.int32, (b, h, 1, 1), 1) +
         jnp.asarray(g_off, jnp.int32))
    qpos = jnp.asarray(q_off, jnp.int32) + \
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, tq, 1), 2)
    kpos = jnp.asarray(k_off, jnp.int32) + \
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, tk), 3)
    return _dropout_keep(_keep_rows(seed, g, qpos), _keep_cols(kpos),
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


def _scale_is_exact(scale):
    """True where multiplying by ``scale`` only moves the exponent
    (1/sqrt(d) for d = 16, 64, 256): then (x * scale) . y and
    (x . y) * scale are the same bits, in bf16 and in f32, and the
    kernels scale the [block, d] operand their loop holds fixed
    instead of every [block_q, block_k] score tile."""
    return math.frexp(scale)[0] == 0.5


def _score_tile(q, k, bias, rows, cols, *, scale, causal, q0, k0,
                rate, window=0, coarse=None, relation=None):
    """What the four kernel bodies share for one [bq, bk] tile:
    s = q k^T (* scale, unless an operand already carries it:
    scale=None) (+ bias[None, :]) (-inf above the diagonal and, with
    a ``window``, ``window`` or more keys below it), and the
    a ``coarse`` (window, keys a window) mask instead: -inf from key
    (q // window) * keys on, _coarse_visible; or the block
    ``relation``: _block_visible), and the
    dropout multiplier u = 1/(1-rate) where the element is kept, 0
    where it is dropped (None at rate 0), drawn from the tile's
    [bq, 1] ``rows`` and [1, bk] ``cols`` terms.  Callers turn s into
    probabilities with ONE exp(s - stat[:, None]), stat finite (the
    running max or the saved lse): a masked s = -inf gives exactly 0
    there, so no isfinite guard follows."""
    s = _dot(q, k, (1, 1))
    if scale is not None:
        s = s * scale
    if bias is not None:
        s = s + bias[None, :]
    if causal:
        bq, bk = s.shape
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        visible = qpos >= kpos
        if window:
            visible = visible & (qpos - kpos < window)
        s = jnp.where(visible, s, -jnp.inf)
    elif coarse:
        bq, bk = s.shape
        s = jnp.where(_coarse_visible(
            q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0),
            k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1),
            coarse), s, -jnp.inf)
    elif relation:
        bq, bk = s.shape
        s = jnp.where(_block_visible(
            q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0),
            k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1),
            relation), s, -jnp.inf)
    u = None
    if rate:
        u = jnp.where(_dropout_keep(rows, cols, _keep_threshold(rate)),
                      1.0 / (1.0 - rate), 0.0)
    return s, u


def _coarse_visible(qpos, kpos, coarse):
    """The third mask, coarse in the keys: the keys are one summary a
    chunk of an earlier stretch of the sequence, ``coarse`` = (window,
    keys a window), and query t sees the summaries of every window
    BEFORE its own, keys 0 .. (t // window) * keys - 1: none in the
    first window, and none of its own window's (which an exact,
    causal call over that window covers)."""
    window, keys = coarse
    return kpos < (qpos // window) * keys


def _block_visible(qpos, kpos, relation):
    """The fourth mask, a relation between blocks: ``relation`` =
    (block, inclusive).  Query i sees key j where
    j // block < i // block + inclusive."""
    block, inclusive = relation
    return kpos // block < qpos // block + inclusive


def _relation_keys(q0, bq, relation):
    """The keys 0 .. n-1 are the ones some query of the rows q0 ..
    q0+bq-1 sees under a block relation: those of the last row."""
    block, inclusive = relation
    return ((q0 + bq - 1) // block + inclusive) * block


def relation_keys_seen(t, tk, relation):
    """[t] ints: how many of the ``tk`` keys each query row sees under
    a block relation (its keys are 0 .. n-1); numpy, for the counts a
    lowering publishes."""
    import numpy as np
    block, inclusive = relation
    return np.minimum((np.arange(t) // block + inclusive) * block, tk)


def _count_tiles(calls, t, tk, relation, blocks, passes=1):
    """Add to ``sdar/tiles_visited``, a sum over ONE traced program,
    the [block_q, block_k] score tiles that ``calls`` kernel instances
    (one a head) walk under a block relation, ``passes`` times: the
    tiles that hold a visible pair, as _key_blocks and _query_blocks
    bound the loops."""
    from .. import registry
    block_q, block_k = blocks
    seen = relation_keys_seen(t, tk, relation).reshape(-1, block_q).max(1)
    registry.trace_sum('sdar/tiles_visited', float(
        calls * passes * (-(-seen // block_k)).sum()))


def _loop(lo, hi, step, init, tiles):
    """fori_loop over the tiles lo .. hi-1 of one kernel instance,
    ``tiles`` of them a trip (_second_tile() says how many and why;
    it divides the trip count, and is 1 wherever lo or hi is only
    known on the chip)."""
    if tiles == 1:
        return jax.lax.fori_loop(lo, hi, step, init)

    def trip(t, carry):
        for r in range(tiles):
            carry = step(lo + tiles * t + r, carry)
        return carry
    return jax.lax.fori_loop(0, (hi - lo) // tiles, trip, init)


def _key_blocks(q0, bq, block_k, nk, causal, window, coarse=None,
                relation=None):
    """[lo, hi) of the key blocks that hold a key some query of the
    block q0 .. q0+bq-1 sees: all of them without a mask, up to the
    diagonal's under a causal one, and from the block of key
    q0 - window + 1 on under a banded one.  The blocks outside are
    not visited; the ones on the band's two edges are masked per
    element (_score_tile).  Under a coarse mask: up to the last
    summary the block's last query sees; under a block relation: up to
    the last key any of its queries sees (_relation_keys)."""
    if relation:
        seen = _relation_keys(q0, bq, relation)
        return 0, jnp.minimum(nk, (seen + block_k - 1) // block_k)
    if coarse:
        seen = ((q0 + bq - 1) // coarse[0]) * coarse[1]
        return 0, jnp.minimum(nk, (seen + block_k - 1) // block_k)
    if not causal:
        return 0, nk
    hi = jnp.minimum(nk, (q0 + bq + block_k - 1) // block_k)
    lo = jnp.maximum(q0 - window + 1, 0) // block_k if window else 0
    return lo, hi


def _query_blocks(k0, bk, block_q, nq, causal, window, coarse=None,
                  relation=None):
    """[lo, hi) of the query blocks that hold a query which sees some
    key of the block k0 .. k0+bk-1: _key_blocks() from the other
    side (the last such query is k0 + bk - 1 + window - 1).  Under a
    coarse mask: from the first query of the window after key k0's
    on (possibly none: lo = nq).  Under a block relation: from the
    first query that sees key k0 on."""
    if relation:
        block, inclusive = relation
        first = (k0 // block + 1 - inclusive) * block
        return jnp.minimum(nq, first // block_q), nq
    if coarse:
        first = (k0 // coarse[1] + 1) * coarse[0]
        return jnp.minimum(nq, first // block_q), nq
    if not causal:
        return 0, nq
    hi = jnp.minimum(nq, (k0 + bk + window - 2) // block_q + 1) \
        if window else nq
    return k0 // block_q, hi


def _zero_at_first(member, accs):
    """Where a grid axis walks the ``group`` query heads that share a
    K/V head, dk and dv add up over it in f32 VMEM scratch: zeroed at
    the group's first head ..."""
    @pl.when(member == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)


def _flush_at_last(member, group, accs, outs):
    """... and written out, in the outputs' dtype, at its last."""
    @pl.when(member == group - 1)
    def _():
        for acc, out in zip(accs, outs):
            out[0] = acc[...].astype(out.dtype)


# A grid step of the forward and of the one-pass backward holds ONE
# head of [B*H, T, D] operands (``heads`` = 1), or, where the heads are
# 64 wide and not grouped (_heads_a_step), the PAIR of heads 2p, 2p+1
# as the 128 lanes 128p .. 128p+127 of the op's own [B, T, H*64]
# operands (``heads`` = 2): nothing is transposed around such a call,
# and every load and store is 128 lanes dense.  The per-head chain is
# the same and runs once a head of the step.  At width 64 every product
# half-fills the 128 x 128 array, so a head's products over the pair's
# lanes cost the MXU what they cost over its own: q k^T and dO v^T
# contract over 128 lanes with the other head's zeroed in ONE operand
# (_head_lanes), p v, dv, dk write 128 columns of which the head's
# half is kept (_join_heads), and dq, from the zeroed k, is the sum of
# the two.  No lane moves.  (A non-finite entry in one head of a pair
# reaches the other's scores as 0 x inf.)
PAIRED_HEAD_DIM = 64


def _head_lanes(x, r, heads):
    """Head ``r`` of a pair's [n, 128] rows, the other head's lanes
    zeroed; ``x`` itself where the step holds one head."""
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane // PAIRED_HEAD_DIM == r, x, jnp.zeros_like(x))


def _join_heads(parts):
    """[n, 128] rows whose lanes 64r .. 64r+63 are ``parts[r]``'s: each
    head's half of what its products wrote over the pair's lanes."""
    if len(parts) == 1:
        return parts[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
    return jnp.where(lane < PAIRED_HEAD_DIM, *parts)


def _head_id(step, r, heads):
    """The head index b * H + h (the dropout draw's) of head ``r`` of
    grid step ``step``: pair p of batch b holds heads 2p and 2p + 1."""
    return step if heads == 1 else heads * step + r


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                      block_k, tiles, has_bias, rate, window=0,
                      coarse=None, heads=1, relation=None):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    o_ref, lse_ref = rest
    # q_ref: [1, bq, d]; k_ref: [1, T, d]; v_ref: [1, T, dv]; bias_ref:
    # [1, 1, T]; o_ref: [1, bq, dv]; lse_ref: [1, heads, bq]  (the
    # middle dim satisfies the TPU block-shape rule for 1-D-per-row
    # operands; d = dv = 128, a pair's lanes, where heads = 2)
    # dots consume the native (usually bf16) dtype and accumulate in
    # f32 (_dot): the MXU runs bf16 at 2x f32 throughput and VMEM
    # traffic halves — the pre-cast-to-f32 variant measured ~25%
    # slower at seq 512; f32 operands multiply at full precision
    q = q_ref[0]
    bq, d = q.shape
    t = k_ref.shape[1]
    q_off = pl.program_id(1) * bq
    exact = _scale_is_exact(scale)
    if exact:
        q = q * scale
    qs = [_head_lanes(q, r, heads) for r in range(heads)]
    rows = [_draw_rows(seed_ref, _head_id(pl.program_id(0), r, heads),
                       q_off, bq) for r in range(heads)]

    nk = t // block_k

    def body(i, carry):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        bias = bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32) if has_bias else None
        cols = _draw_cols(seed_ref, i * block_k, block_k)

        def chain(q, rows, m, l, acc):
            s, u = _score_tile(
                q, k, bias, rows, cols,
                scale=None if exact else scale, causal=causal, q0=q_off,
                k0=i * block_k, rate=rate, window=window, coarse=coarse,
                relation=relation)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            # a row with every key masked so far: m_new = -inf, p = 0
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[:, None])
            corr = jnp.exp(m - m_safe)
            # dropout applies AFTER softmax (reference: dropout around
            # the probs, python/paddle/fluid/layers/nn.py): the
            # normalizer l accumulates the UNDROPPED p, only the
            # V-weighting is masked
            l_new = l * corr + jnp.sum(p, axis=1)
            if rate:
                p = p * u
            acc_new = acc * corr[:, None] + _dot(p.astype(v.dtype), v,
                                                 (1, 0))
            return m_new, l_new, acc_new

        return tuple(chain(qs[r], rows[r], *carry[r])
                     for r in range(heads))

    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, v_ref.shape[2]), jnp.float32)
    # skip the K blocks no query of this block sees
    lo, hi = _key_blocks(q_off, bq, block_k, nk, causal, window,
                         coarse, relation)
    state = _loop(lo, hi, body, ((m0, l0, acc0),) * heads, tiles)
    l_safe = [jnp.maximum(l, 1e-20) for _, l, _ in state]
    o_ref[0] = _join_heads([
        acc / ls[:, None] for (_, _, acc), ls in zip(state, l_safe)
    ]).astype(o_ref.dtype)
    for r, ((m, _, _), ls) in enumerate(zip(state, l_safe)):
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        lse_ref[0, r] = (m_safe + jnp.log(ls)).astype(jnp.float32)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                         block_k, tiles, has_bias, has_glse, rate,
                         window=0, coarse=None, relation=None):
    """Grid (BH, T/bq): recompute p row-blocks from q and lse, then
    dq = sum_k (p * (dO V^T - delta)) K * scale."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    do_ref, lse_ref, delta_ref = rest[0], rest[1], rest[2]
    glse_ref = rest[3] if has_glse else None
    dq_ref = rest[-1]
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
    exact = _scale_is_exact(scale)
    q_s = q * scale if exact else q
    rows = _draw_rows(seed_ref, pl.program_id(0), q_off, bq)
    nk = t // block_k

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        bias = bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32) if has_bias else None
        s, u = _score_tile(
            q_s, k, bias, rows,
            _draw_cols(seed_ref, i * block_k, block_k),
            scale=None if exact else scale, causal=causal, q0=q_off,
            k0=i * block_k, rate=rate, window=window, coarse=coarse,
            relation=relation)
        p = jnp.exp(s - lse[:, None])
        dp = _dot(do, v, (1, 1))
        if rate:
            # softmax vjp with post-softmax dropout u: dS = p*(u*dp -
            # delta); delta = rowsum(dO*O) already sees the dropout
            # because O was computed WITH it
            dp = dp * u
        dd = dp - delta[:, None]
        if has_glse:
            dd = dd + glse[:, None]
        ds = p * dd
        if not exact:
            ds = ds * scale
        return dq + _dot(ds.astype(k.dtype), k, (1, 0))

    lo, hi = _key_blocks(q_off, bq, block_k, nk, causal, window,
                         coarse, relation)
    dq = _loop(lo, hi, body, jnp.zeros((bq, d), jnp.float32), tiles)
    if exact:
        dq = dq * scale
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                          block_q, tiles, dp_early, has_bias, has_glse,
                          rate, window=0, group=1, coarse=None,
                          relation=None):
    """Grid (BH, T/bk): for one K/V block, stream Q row-blocks:
    dv = sum_q p^T dO;  ds_raw = p * (dO V^T - delta);
    dk = sum_q ds_raw^T Q * scale;  dbias = sum_q ds_raw (per key).

    With ``group`` > 1 query heads to a K/V head the grid is
    (B*Hkv, T/bk, group): the last axis walks the group's query heads
    over one resident K/V block, and dk, dv add up over it in two f32
    VMEM scratch blocks, written out at the group's last head."""
    rest = list(rest)
    dk_acc, dv_acc = (rest.pop(-2), rest.pop(-1)) if group > 1 \
        else (None, None)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if rate else None
    do_ref, lse_ref, delta_ref = rest[0], rest[1], rest[2]
    glse_ref = rest[3] if has_glse else None
    dk_ref, dv_ref = rest[-3:-1] if has_bias else rest[-2:]
    dbias_ref = rest[-1] if has_bias else None
    k = k_ref[0]
    v = v_ref[0]
    bias = bias_ref[0, 0].astype(jnp.float32) if has_bias else None
    bk, d = k.shape
    t = q_ref.shape[1]
    k_off = pl.program_id(1) * bk
    g_id = pl.program_id(0)
    if group > 1:       # the query head this step holds
        g_id = g_id * group + pl.program_id(2)
    exact = _scale_is_exact(scale)
    k_s = k * scale if exact else k
    cols = _draw_cols(seed_ref, k_off, bk)
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
        s, u = _score_tile(
            q, k_s, bias,
            _draw_rows(seed_ref, g_id, j * block_q, block_q), cols,
            scale=None if exact else scale, causal=causal,
            q0=j * block_q, k0=k_off, rate=rate, window=window,
            coarse=coarse, relation=relation)
        p = jnp.exp(s - lse[:, None])

        def dp_tile():
            dp = _dot(do, v, (1, 1))
            return dp * u if rate else dp

        # dO V^T does not wait for the chain: where a second tile may
        # be alive (_second_tile) it is issued before p^T dO, so the
        # MXU has work while the VPU makes p.  Else after it, as a dp
        # alive across that product put f32 d128 calls over the
        # scoped VMEM (17.47M of 16M at [6, 2048, 16, 128])
        if dp_early:
            dp = dp_tile()
        dv = dv + _dot((p * u if rate else p).astype(do.dtype), do,
                       (0, 0))
        if not dp_early:
            dp = dp_tile()
        dd = dp - delta[:, None]
        if has_glse:
            dd = dd + glse[:, None]
        ds_raw = p * dd
        dk_blk = _dot(ds_raw.astype(q.dtype), q, (0, 0))
        dk = dk + (dk_blk if exact else dk_blk * scale)
        if has_bias:
            dbias = dbias + jnp.sum(ds_raw, axis=0)
        return dk, dv, dbias

    # q blocks whose queries see no key of this block contribute
    # nothing
    j0, j1 = _query_blocks(k_off, bk, block_q, nq, causal, window,
                           coarse, relation)
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    db0 = jnp.zeros((bk,), jnp.float32)
    dk, dv, dbias = _loop(j0, j1, body, (dk0, dv0, db0), tiles)
    if exact:
        dk = dk * scale
    if group == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
    else:
        member = pl.program_id(2)
        _zero_at_first(member, (dk_acc, dv_acc))
        dk_acc[...] += dk
        dv_acc[...] += dv
        _flush_at_last(member, group, (dk_acc, dv_acc), (dk_ref, dv_ref))
    if has_bias:
        dbias_ref[0, 0] = dbias.astype(dbias_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                            block_q, block_k, tiles, dp_early, has_bias,
                            has_glse, rate, window=0, group=1,
                            coarse=None, heads=1, relation=None):
    """Single-pass backward: grid (BH,) only.  The two-pass scheme
    (dq grid over Q blocks, dk/dv grid over K blocks) recomputes the
    score block s AND the prob-cotangent dp = dO V^T in BOTH kernels —
    7 MXU dots per (q,k) tile-pair step instead of 5.  When the whole
    per-head working set fits VMEM (q/k/v/do rows + an f32 dq
    accumulator — true for the long-context shapes this kernel
    exists for), one kernel can walk k-blocks x q-blocks computing s
    and dp ONCE and accumulating all three gradients: dk/dv stream out
    per k-block, dq rides a VMEM carry.

    With ``group`` > 1 query heads to a K/V head the grid is
    (B*Hkv, group): the second axis walks the group's query heads over
    one resident K/V head, and dk, dv add up over it in two more f32
    [T, d] scratch buffers, written out at the group's last head.

    With ``heads`` = 2 the grid is (B*H/2,) and a step holds a pair of
    64-wide heads in 128 lanes (_head_lanes): the chain runs once a
    head on the step's q and dO tiles, lse and delta are [2, T], and
    the key-bias gradient is the pair's sum."""
    rest = list(rest)
    dk_acc, dv_acc = (rest.pop(-2), rest.pop(-1)) if group > 1 \
        else (None, None)
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
    if group > 1:       # the query head this step holds
        g_id = g_id * group + pl.program_id(1)
    g_ids = [_head_id(g_id, r, heads) for r in range(heads)]
    exact = _scale_is_exact(scale)
    nq, nk = t // block_q, k_ref.shape[1] // block_k

    def k_step(i, _):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        bias = bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32) if has_bias else None
        # with an exact scale k carries it into s AND into dq
        k_s = k * scale if exact else k
        cols = _draw_cols(seed_ref, i * block_k, block_k)
        # a head's keys and values: s, dO v^T and dq see no other's
        ks = [_head_lanes(k_s, r, heads) for r in range(heads)]
        vs = [_head_lanes(v, r, heads) for r in range(heads)]

        def q_step(j, carry):
            q = q_ref[0, pl.dslice(j * block_q, block_q), :]
            do = do_ref[0, pl.dslice(j * block_q, block_q), :]

            def chain(r, dk, dv, dbias):
                lse = lse_ref[0, r, pl.dslice(j * block_q,
                                              block_q)].astype(jnp.float32)
                delta = delta_ref[0, r, pl.dslice(j * block_q,
                                                  block_q)].astype(
                    jnp.float32)
                s, u = _score_tile(
                    q, ks[r], bias,
                    _draw_rows(seed_ref, g_ids[r], j * block_q, block_q),
                    cols, scale=None if exact else scale, causal=causal,
                    q0=j * block_q, k0=i * block_k, rate=rate,
                    window=window, coarse=coarse, relation=relation)
                p = jnp.exp(s - lse[:, None])

                def dp_tile():
                    dp = _dot(do, vs[r], (1, 1))
                    return dp * u if rate else dp

                # dO V^T before or after p^T dO: _flash_bwd_dkv_kernel
                if dp_early:
                    dp = dp_tile()
                dv = dv + _dot((p * u if rate else p).astype(do.dtype),
                               do, (0, 0))
                if not dp_early:
                    dp = dp_tile()
                dd = dp - delta[:, None]
                if has_glse:
                    glse = glse_ref[0, r, pl.dslice(j * block_q,
                                                    block_q)].astype(
                        jnp.float32)
                    dd = dd + glse[:, None]
                ds_raw = p * dd
                dk_blk = _dot(ds_raw.astype(q.dtype), q, (0, 0))
                dq_blk = _dot(ds_raw.astype(k.dtype), ks[r], (1, 0))
                if not exact:
                    dk_blk, dq_blk = dk_blk * scale, dq_blk * scale
                dk = dk + dk_blk
                if has_bias:
                    dbias = dbias + jnp.sum(ds_raw, axis=0)
                return (dk, dv, dbias), dq_blk

            new, dq_blks = zip(*(chain(r, *carry[r])
                                 for r in range(heads)))
            # dq accumulates across k-blocks in the f32 VMEM scratch
            # (read-modify-write through the ref: Mosaic supports
            # dynamic slicing on refs, not on carried values); a pair's
            # two blocks are zero in each other's lanes
            cur = acc_ref[pl.dslice(j * block_q, block_q), :]
            acc_ref[pl.dslice(j * block_q, block_q), :] = \
                cur + functools.reduce(jnp.add, dq_blks)
            return new

        j0, j1 = _query_blocks(i * block_k, block_k, block_q, nq,
                               causal, window, coarse, relation)
        dk0 = jnp.zeros((block_k, d), jnp.float32)
        dv0 = jnp.zeros((block_k, v_ref.shape[2]), jnp.float32)
        db0 = jnp.zeros((block_k,), jnp.float32)
        state = _loop(j0, j1, q_step, ((dk0, dv0, db0),) * heads, tiles)
        dk, dv = (_join_heads([c[n] for c in state]) for n in (0, 1))
        dbias = functools.reduce(jnp.add, [c[2] for c in state])
        if exact:
            dk = dk * scale
        here = pl.dslice(i * block_k, block_k)
        if group == 1:
            dk_ref[0, here, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, here, :] = dv.astype(dv_ref.dtype)
        else:
            dk_acc[here, :] += dk
            dv_acc[here, :] += dv
        if has_bias:
            dbias_ref[0, 0, pl.dslice(i * block_k, block_k)] = \
                dbias.astype(dbias_ref.dtype)
        return 0

    acc_ref[...] = jnp.zeros((t, d), jnp.float32)
    if group > 1:
        _zero_at_first(pl.program_id(1), (dk_acc, dv_acc))
    jax.lax.fori_loop(0, nk, k_step, 0)
    dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)
    if group > 1:
        _flush_at_last(pl.program_id(1), group, (dk_acc, dv_acc),
                       (dk_ref, dv_ref))


def _step_shape(q, v, h, heads):
    """(grid steps over heads, T, the width of the q / k rows a step
    holds, that of its v / o / dO rows) of a forward or one-pass
    backward call: a head's of [B*H, T, D] operands, or a pair's 128
    lanes of [B, T, H*64] ones."""
    n, t, width = q.shape
    if heads == 1:
        return n, t, width, v.shape[2]
    return n * h // heads, t, heads * width // h, heads * v.shape[2] // h


def _softmax_scale(q, h, heads):
    """1/sqrt(d) of the heads' own width d, whichever way q lies."""
    d = q.shape[2] if heads == 1 else q.shape[2] // h
    return 1.0 / (d ** 0.5)


def _pair_rows(n, pairs, tiled=False):
    """A pair's rows in a [B, T, H*64] operand: at grid step b * pairs
    + p the lanes 128p .. 128p+127 of batch b, all ``n`` = T rows of
    them, or (``tiled``) the ``n`` rows of the grid's second index."""
    return pl.BlockSpec(
        (1, n, 2 * PAIRED_HEAD_DIM),
        (lambda i, j: (i // pairs, j, i % pairs)) if tiled else
        (lambda i, *_: (i // pairs, 0, i % pairs)))


def _flash_bwd_fused(q, k, v, bias, seed2, do, lse3, delta3, glse3, h,
                     causal, block_q, block_k, interpret, rate,
                     window=0, coarse=None, limit=None, heads=1,
                     relation=None):
    """pallas_call plumbing for the one-pass backward: grid (BH,), or
    (B*Hkv, group) where ``group`` query heads share a K/V head, or
    (B*H/2,) over pairs of heads (``heads`` = 2: q, k, v, do and the
    three outputs are [B, T, H*64], the vectors [B*H/2, 2, T]).
    ``limit``: the scoped VMEM the call asks Mosaic for (_flash_bwd;
    None: its default); a second tile has to fit under THAT."""
    steps, t, d, dv = _step_shape(q, v, h, heads)
    tk = k.shape[1]
    group = 1 if heads > 1 else steps // k.shape[0]
    scale = _softmax_scale(q, h, heads)
    has_bias = bias is not None
    has_glse = glse3 is not None
    tiles, dp_early = _second_tile(
        None if causal or coarse or relation else t // block_q,
        _fused_bwd_resident(t, d, block_k, q.dtype.itemsize, group, dv,
                            tk),
        block_q, block_k, q.dtype.itemsize, limit, heads)
    kernel = functools.partial(
        _flash_bwd_fused_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, tiles=tiles,
        dp_early=dp_early, has_bias=has_bias, has_glse=has_glse,
        rate=rate, window=window, group=group, coarse=coarse,
        heads=heads, relation=relation)

    def head(*ids):     # the query head of a grid step
        return ids[0] if group == 1 else ids[0] * group + ids[1]

    def rows(width, kv=False):      # one head's [t | tk, width] rows
        if heads > 1:               # or a pair's, of its batch
            return _pair_rows(t, h // heads)
        return pl.BlockSpec(
            (1, tk if kv else t, width),
            lambda *ids: (ids[0] if kv else head(*ids), 0, 0))

    vec = pl.BlockSpec((1, heads, t), lambda *ids: (head(*ids), 0, 0))
    in_specs = [rows(d), rows(d, True), rows(dv, True)]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, tk), lambda *ids: (head(*ids) // (h // heads), 0, 0)))
        operands.append(bias[:, None, :])
    if rate:
        in_specs.append(pl.BlockSpec((1, 4), lambda *ids: (0, 0)))
        operands.append(seed2)
    in_specs += [rows(dv), vec, vec]
    operands += [do, lse3, delta3]
    if has_glse:
        in_specs.append(vec)
        operands.append(glse3)
    out_specs = [rows(d), rows(d, True), rows(dv, True)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if has_bias:
        out_specs.append(pl.BlockSpec(
            (1, 1, tk), lambda *ids: (head(*ids), 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((steps, 1, tk),
                                              jnp.float32))
    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((t, d), jnp.float32)]         # dq
    if group > 1:                                       # dk, dv
        scratch += [pltpu.VMEM((tk, d), jnp.float32),
                    pltpu.VMEM((tk, dv), jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=(steps,) if group == 1 else (steps // group, group),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        **_vmem_limit(limit),
    )(*operands)
    if has_bias:
        dq, dk, dv, dbias_bh = res
        # bias is per (batch, key): sum the steps of a batch
        per_b = h // heads
        dbias = dbias_bh[:, 0, :].reshape(steps // per_b, per_b,
                                          tk).sum(axis=1)
    else:
        dq, dk, dv = res
        dbias = None
    return dq, dk, dv, dbias


# The one-pass backward runs where its instance fits the VMEM the call
# may ask Mosaic for (_flash_bwd); False forces the two-pass scheme
# (sweeps / A-B measurement: tools/bench_flash.py --two-pass).
FUSED_BWD = True
# One-pass tile shape (chip-swept round 5 at d=64: 512/512 best).  A
# wider key tile is no longer a question of room (the call asks for
# the VMEM it counts, of the core's 128 MiB, not Mosaic's 16 MiB
# default) but of time: PERF.md section 6, PR 42, has the sweep.
FUSED_BLOCK_Q = 512
FUSED_BLOCK_K = 512
# The widest a coarse call's key block is narrowed to (_window_blocks).
COARSE_BLOCK_K = 512


def _fused_bwd_resident(t, d, block_k, itemsize, group=1, dv=None,
                        tk=None):
    """What a fused-backward instance holds beside its score tiles,
    ONE buffer of each, as common.room_for_second_tile() wants it (it
    doubles the sum for the pipeline's second buffers): q/k (``d``
    wide) and v/do (``dv`` wide) full rows, q and do ``t`` long, k
    and v ``tk`` (t unless the keys are of another length), the f32 dq
    accumulator (and, where ``group`` query heads share a K/V head,
    the dk and dv ones) and the dk/dv f32 blocks (x2 slack for
    compiler temporaries)."""
    dv = d if dv is None else dv
    tk = t if tk is None else tk
    rows = (t + tk) * (d + dv) * itemsize
    accs = t * d * 4 + (0 if group == 1 else tk * (d + dv) * 4)
    return rows + accs + 2 * block_k * (d + dv) * 4 + (1 << 19)


def _one_pass_blocks(t, tk, block_q, block_k):
    """The one-pass backward's tile: the two-pass blocks (clamped, and
    narrowed by a band or a coarse mask) no larger than FUSED_BLOCK_*,
    dividing the two lengths."""
    fq, fk = min(block_q, FUSED_BLOCK_Q), min(block_k, FUSED_BLOCK_K)
    while t % fq:
        fq //= 2
    while tk % fk:
        fk //= 2
    return fq, fk


def _one_pass_vmem(t, tk, d, dv, block_q, block_k, itemsize, group,
                   has_bias, has_glse, heads=1):
    """common.one_pass_backward_vmem() of a call as _flash_bwd_fused
    makes it: lse and delta, the lse cotangent where there is one, the
    key bias and its gradient where there is one."""
    return _common.one_pass_backward_vmem(
        t, tk, d, dv, block_q, block_k, itemsize, group,
        q_vectors=3 if has_glse else 2, k_vectors=2 if has_bias else 0,
        heads=heads)


def _second_tile(trips, resident, block_q, block_k, itemsize,
                 limit=None, heads=1):
    """(tiles a loop trip, dp_early): how a kernel instance uses the
    room for a second score tile, where the VMEM model finds it
    (common.room_for_second_tile) under ``limit``, the scoped VMEM
    the call asks for (None: Mosaic's default).  The tile loop takes
    two tiles a trip where its trip count is even and known at trace
    time (``trips``; None for causal calls, which bound their loops
    by the diagonal).  Where it is not, the backward bodies issue their
    second independent product (dO V^T) before the first tile's
    chain is through, which keeps a second tile alive just the
    same.  An instance that holds a pair of heads runs a chain a
    head, so two tiles are alive in it however this answers; the room
    it asks about is for two of EACH head."""
    room = _common.room_for_second_tile(resident, block_q, block_k,
                                        itemsize, limit, heads)
    tiles = 2 if room and trips is not None and trips % 2 == 0 else 1
    return tiles, room and tiles == 1


def _mosaic_params(t, d, block_q, block_k, itemsize, dv):
    """pallas_call's ``compiler_params`` for a forward, dq or dkv
    call whose resident rows are ``t`` long (K and V in a forward or
    dq call, Q and dO in a dkv one): none but where they ask for more
    scoped VMEM than the compiler's default (common.scoped_vmem)."""
    return _vmem_limit(
        _common.scoped_vmem(t, d, block_q, block_k, itemsize, dv))


def _pair_forward_limit(resident, block_q, block_k, itemsize):
    """The scoped VMEM the forward of a pair of heads asks Mosaic for
    (None: its default): what two tiles of each head's chain hold
    beside the rows, by the one-pass backward's rule."""
    admitted, limit = _common.one_pass_backward_limit(
        _common.two_tiles_vmem(resident, block_q, block_k, itemsize, 2))
    return limit if admitted else None


def _vmem_limit(limit):
    """``compiler_params`` that ask Mosaic for ``limit`` bytes of
    scoped VMEM; nothing at all for None, so a call that fits the
    default lowers to the bytes it always has.  The largest limit any
    flash call of the process asked for is the gauge
    ``pallas/flash_attention/vmem_asked_max`` (common.report())."""
    if limit is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    from ...fluid import monitor
    monitor.set_gauge('pallas/flash_attention/vmem_asked_max', max(
        limit, monitor.gauge_value('pallas/flash_attention/vmem_asked_max')))
    return {'compiler_params': pltpu.CompilerParams(
        vmem_limit_bytes=limit)}


def _backward_params(t, d, block_q, block_k, itemsize, dv):
    """``compiler_params`` of a dq or dkv call whose resident rows are
    ``t`` long.  bfloat16 calls ask what a forward call asks
    (_mosaic_params).  float32 ones always ask for twice their
    estimate and the headroom: their full-precision products split
    every operand into bfloat16 parts that lie beside it, which
    vmem_estimate() does not count, and from a grid of some size on
    the compiler refused them at its default (2048 keys x 128: 16.96
    of 16 MB for a dkv call; ROADMAP S3 (6)).  The one-pass call has a
    count of its own that knows both (common.one_pass_backward_vmem)."""
    if itemsize >= 4:
        return _vmem_limit(min(
            2 * _vmem_estimate(t, d, block_q, block_k, itemsize, dv) +
            _common.VMEM_HEADROOM_BYTES, _common.VMEM_LIMIT_CAP_BYTES))
    return _mosaic_params(t, d, block_q, block_k, itemsize, dv)


def _rows_resident(t, d, block_q, block_k, itemsize, dv=None):
    """What a forward, dq or dkv instance holds beside its score
    tile, as vmem_estimate() counts it."""
    return _vmem_estimate(t, d, block_q, block_k, itemsize, dv) - \
        _common.score_tile_bytes(block_q, block_k)


def _window_blocks(blocks, window, coarse=None, tk=0):
    """A banded call's blocks: the key block no wider than the band
    (and not under 128), since every key block a query block touches
    is computed whole and the band is ``window`` keys of it.  A coarse
    call's likewise, a window's queries seeing whole multiples of
    ``coarse[1]`` summaries, but not under a quarter of its ``tk``
    keys up to COARSE_BLOCK_K: with many windows most query blocks
    see many multiples, and [512, 128] tiles cost a long call twice
    what [512, 512] ones do (forward + backward at 32768 queries over
    2048 summaries: 56.2 ms against 27.2; at 4096 over 256, where a
    query block sees 128 or none, 1.74 against 1.87: my chip run,
    PR 38)."""
    block_q, block_k = blocks
    band = coarse[1] if coarse else window
    floor = min(COARSE_BLOCK_K, max(128, tk // 4)) if coarse else 128
    while band and block_k // 2 >= max(band, floor):
        block_k //= 2
    return block_q, block_k


def _flash_fwd(q, k, v, bias, seed, h, causal, block_q, block_k,
               interpret, rate=0.0, window=0, coarse=None, heads=1,
               relation=None):
    """q: [BH, T, D], k: [B*Hkv, Tk, D], v: [B*Hkv, Tk, Dv] (query
    head i reads K/V head i // (H / Hkv); Tk = T but under a coarse
    mask), bias: [B, Tk] or None, seed: packed (1,4) uint32 [seed,
    q_off, k_off, g_off] (_pack_seed, required when rate>0) ->
    (o [BH,T,Dv], lse [BH,T]).  With ``heads`` = 2 (_heads_a_step)
    q, k, v and o are [B, T, H*64] instead, as the op holds them."""
    steps, t, d, dv = _step_shape(q, v, h, heads)
    blocks = _window_blocks(
        _block_sizes(t, block_q, block_k, d, q.dtype.itemsize, dv,
                     k.shape[1]),
        window, coarse, k.shape[1])
    if relation:
        _count_tiles(steps, t, k.shape[1], relation, blocks)
    return _fwd_call(
        q, k, v, bias, seed, h=h, causal=causal, blocks=blocks,
        interpret=interpret, rate=rate, window=window, coarse=coarse,
        heads=heads, relation=relation)


# The calls are jitted on their static arguments: the layers of a model
# call them with the same shapes, so the kernel bodies are traced once
# a process, not once a layer in each of its programs (a BERT-base
# step holds 24 calls: PERF.md section 6, PR 29, on `setup_s`).
# ``inline``: the cached trace is spliced into the caller's, with no
# call of its own in the program, so a kernel's instruction keeps the
# name of the scope the caller lowered it in (the executor's, the
# fluid op's type), which is how a device trace is read.
@functools.partial(jax.jit, inline=True, static_argnames=(
    'h', 'causal', 'blocks', 'interpret', 'rate', 'window', 'coarse',
    'heads', 'relation'))
def _fwd_call(q, k, v, bias, seed, *, h, causal, blocks, interpret,
              rate, window=0, coarse=None, heads=1, relation=None):
    steps, t, d, dv = _step_shape(q, v, h, heads)
    tk = k.shape[1]
    group = 1 if heads > 1 else steps // k.shape[0]
    block_q, block_k = blocks
    scale = _softmax_scale(q, h, heads)
    has_bias = bias is not None
    resident = _rows_resident(tk, d, block_q, block_k, q.dtype.itemsize,
                              dv)
    # a pair's call asks for what its two chains' tiles hold, and its
    # loop takes two tiles a trip under THAT; the others ask by
    # common.scoped_vmem and loop as Mosaic's default allows
    limit = _pair_forward_limit(resident, block_q, block_k,
                                q.dtype.itemsize) if heads > 1 else None
    params = _vmem_limit(limit) if heads > 1 else _mosaic_params(
        tk, d, block_q, block_k, q.dtype.itemsize, dv)
    tiles, _ = _second_tile(
        None if causal or coarse or relation else tk // block_k,
        resident, block_q, block_k, q.dtype.itemsize, limit, heads)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_k=block_k,
        tiles=tiles, has_bias=has_bias, rate=rate, window=window,
        coarse=coarse, heads=heads, relation=relation)
    grid = (steps, t // block_q)
    if heads > 1:       # a pair's lanes of the op's own layout
        q_rows = o_rows = _pair_rows(block_q, h // heads, tiled=True)
        k_rows = v_rows = _pair_rows(tk, h // heads)
    else:
        q_rows = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
        k_rows = pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0))
        v_rows = pl.BlockSpec((1, tk, dv),
                              lambda i, j: (i // group, 0, 0))
        o_rows = pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0))
    in_specs = [q_rows, k_rows, v_rows]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, tk), lambda i, j: (i // (h // heads), 0, 0)))
        operands.append(bias[:, None, :])
    if rate:
        in_specs.append(pl.BlockSpec((1, 4), lambda i, j: (0, 0)))
        operands.append(jnp.asarray(seed, jnp.uint32).reshape(1, 4))
    o, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            o_rows,
            pl.BlockSpec((1, heads, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:2] + v.shape[2:], q.dtype),
            jax.ShapeDtypeStruct((steps, heads, t), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(*operands)
    # [BH, T]: a pair's two rows are heads 2p and 2p + 1
    return o, lse3[:, 0, :] if heads == 1 else lse3.reshape(-1, t)


def _backward_plan(t, tk, d, dv, itemsize, group, has_bias, has_glse,
                   block_q, block_k, window=0, coarse=None, heads=1):
    """(one pass?, its scoped VMEM to ask for, the blocks) of the
    backward of a call whose grid steps hold rows ``d`` / ``dv`` wide:
    one pass where an instance's rows, outputs, scratch and tiles fit
    the VMEM the call may ask for: the shape decides, through the
    count, and nothing else does.  Else the dq + dkv kernels at the
    forward's blocks."""
    block_q, block_k = _window_blocks(
        _block_sizes(t, block_q, block_k, d, itemsize, dv, tk),
        window, coarse, tk)
    fq, fk = _one_pass_blocks(t, tk, block_q, block_k)
    admitted, limit = _common.one_pass_backward_limit(_one_pass_vmem(
        t, tk, d, dv, fq, fk, itemsize, group, has_bias, has_glse,
        heads))
    if FUSED_BWD and admitted:
        return True, limit, (fq, fk)
    return False, None, (block_q, block_k)


def _flash_bwd(q, k, v, bias, seed, o, lse, do, g_lse, h, causal,
               block_q, block_k, interpret, rate=0.0, window=0,
               coarse=None, heads=1, relation=None):
    steps, t, d, dv = _step_shape(q, v, h, heads)
    fused, limit, blocks = _backward_plan(
        t, k.shape[1], d, dv, q.dtype.itemsize,
        1 if heads > 1 else steps // k.shape[0], bias is not None,
        g_lse is not None, block_q, block_k, window, coarse, heads)
    from ...fluid import monitor
    monitor.add('pallas/flash_attention/backward_%s'
                % ('one_pass' if fused else 'two_pass'), 1)
    if relation:    # one pass over the tiles, or the dq and the dkv call's
        _count_tiles(steps, t, k.shape[1], relation, blocks,
                     passes=1 if fused else 2)
    return _bwd_call(
        q, k, v, bias, seed, o, lse, do, g_lse, h=h, causal=causal,
        blocks=blocks, fused=fused, limit=limit, interpret=interpret,
        rate=rate, window=window, coarse=coarse, heads=heads,
        relation=relation)


@functools.partial(jax.jit, inline=True, static_argnames=(
    'h', 'causal', 'blocks', 'fused', 'limit', 'interpret', 'rate',
    'window', 'coarse', 'heads', 'relation'))
def _bwd_call(q, k, v, bias, seed, o, lse, do, g_lse, *, h, causal,
              blocks, fused, interpret, rate, window=0, coarse=None,
              limit=None, heads=1, relation=None):
    block_q, block_k = blocks
    has_bias = bias is not None
    has_glse = g_lse is not None
    if heads > 1:
        # delta over each head's 64 lanes of [B, T, H*64], into the
        # [B*H/2, 2, T] the pairs' steps read (lse and its cotangent
        # come [B*H, T]); always one pass (_heads_a_step).  A product
        # with the heads' 0/1 lane selector, not a reduce over a
        # [B, T, H, 64] view: that view half-fills its lanes, and XLA
        # wrote the f32 product out and transposed it to sum it
        b, t, width = q.shape
        lanes = jnp.arange(width)[:, None] // (width // h) == \
            jnp.arange(h)[None, :]
        delta = jnp.einsum(
            'btc,ch->bht',
            do.astype(jnp.float32) * o.astype(jnp.float32),
            lanes.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        return _flash_bwd_fused(
            q, k, v, bias,
            jnp.asarray(seed, jnp.uint32).reshape(1, 4) if rate else None,
            do, lse.reshape(-1, heads, t), delta.reshape(-1, heads, t),
            g_lse.astype(jnp.float32).reshape(-1, heads, t)
            if has_glse else None, h, causal, block_q, block_k,
            interpret, rate, window, coarse, limit, heads, relation)
    bh, t, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    scale = 1.0 / (d ** 0.5)
    # delta = rowsum(dO * O): one fused elementwise+reduce in XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    glse3 = g_lse.astype(jnp.float32)[:, None, :] if has_glse else None
    seed2 = jnp.asarray(seed, jnp.uint32).reshape(1, 4) if rate else None
    seed_spec = pl.BlockSpec((1, 4), lambda i, j: (0, 0))

    if fused:
        return _flash_bwd_fused(q, k, v, bias, seed2, do, lse3, delta3,
                                glse3, h, causal, block_q, block_k,
                                interpret, rate, window, coarse, limit,
                                relation=relation)

    # the dq call keeps a head's K and V rows resident (tk long), the
    # dkv call its Q and dO rows (t long)
    resident = _rows_resident(tk, d, block_q, block_k, q.dtype.itemsize,
                              dv)
    tiles, _ = _second_tile(
        None if causal or coarse or relation else tk // block_k,
        resident, block_q, block_k, q.dtype.itemsize)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal,
        block_k=block_k, tiles=tiles, has_bias=has_bias,
        has_glse=has_glse, rate=rate, window=window, coarse=coarse,
        relation=relation)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, tk, d), lambda i, j: (i // group, 0, 0)),
        pl.BlockSpec((1, tk, dv), lambda i, j: (i // group, 0, 0)),
    ]
    dq_operands = [q, k, v]
    if has_bias:
        dq_specs.append(pl.BlockSpec((1, 1, tk),
                                     lambda i, j: (i // h, 0, 0)))
        dq_operands.append(bias[:, None, :])
    if rate:
        dq_specs.append(seed_spec)
        dq_operands.append(seed2)
    dq_specs += [
        pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
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
        **_backward_params(tk, d, block_q, block_k, q.dtype.itemsize,
                           dv),
    )(*dq_operands)

    if t != tk:
        resident = _rows_resident(t, d, block_q, block_k,
                                  q.dtype.itemsize, dv)
    tiles, dp_early = _second_tile(
        None if causal or coarse or relation else t // block_q,
        resident, block_q, block_k, q.dtype.itemsize)
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, tiles=tiles, dp_early=dp_early,
        has_bias=has_bias, has_glse=has_glse, rate=rate, window=window,
        group=group, coarse=coarse, relation=relation)

    # grid (BH, T/bk), or (B*Hkv, T/bk, group): ids[0] is the K/V head
    def head(*ids):     # the query head of a grid step
        return ids[0] if group == 1 else ids[0] * group + ids[2]

    def q_rows(width):      # the query head's [t, width] rows
        return pl.BlockSpec((1, t, width),
                            lambda *ids: (head(*ids), 0, 0))

    def kv_block(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda *ids: (ids[0], ids[1], 0))

    q_vec = pl.BlockSpec((1, 1, t), lambda *ids: (head(*ids), 0, 0))
    dkv_specs = [q_rows(d), kv_block(d), kv_block(dv)]
    dkv_operands = [q, k, v]
    if has_bias:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, block_k),
            lambda *ids: (head(*ids) // h, 0, ids[1])))
        dkv_operands.append(bias[:, None, :])
    if rate:
        dkv_specs.append(pl.BlockSpec((1, 4), lambda *ids: (0, 0)))
        dkv_operands.append(seed2)
    dkv_specs += [q_rows(dv), q_vec, q_vec]
    dkv_operands += [do, lse3, delta3]
    if has_glse:
        dkv_specs.append(q_vec)
        dkv_operands.append(glse3)
    out_specs = [kv_block(d), kv_block(dv)]
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda *ids: (head(*ids), 0, ids[1])))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, tk), jnp.float32))
    scratch = []
    if group > 1:
        from jax.experimental.pallas import tpu as pltpu
        scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, dv), jnp.float32)]
    res = pl.pallas_call(
        dkv_kernel,
        grid=(bh, tk // block_k) if group == 1
        else (bh // group, tk // block_k, group),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        **_backward_params(t, d, block_q, block_k, q.dtype.itemsize,
                           dv),
    )(*dkv_operands)
    if has_bias:
        dk, dv, dbias_bh = res
        # bias is per (batch, key): sum head lanes
        b = bh // h
        dbias = dbias_bh.reshape(b, h, tk).sum(axis=1)
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


def _flash_primitive(with_lse):
    """The kernels as one differentiable function of q, k, v and the
    key bias.  Two of them share this body: ``_flash`` returns o only
    and has its OWN vjp, so the common path never ships a zeros g_lse
    operand into the backward kernels; ``_flash_lse`` returns (o,
    lse) with lse a first-class differentiable output, whose cotangent
    folds into dS inside the backward kernels, so that per-block
    results can be merged in log-sum-exp space (ring attention's
    blocks, EVA's two key sets).  ``interpret`` is the one
    common.dispatch() decision the public entry made; forward and
    backward kernels all read it.  q, k, v (and o, dq, dk, dv) are
    [B*H, T, D] copies, or with ``heads`` = 2 the op's own operands
    seen as [B, T, H*64] (_heads_a_step): the residuals are then the
    op's inputs and its output, no copy of them."""
    def outputs(o, lse):
        return (o, lse) if with_lse else o

    def primitive(q, k, v, bias, seed, h, causal, rate, interpret,
                  window=0, coarse=None, heads=1, relation=None):
        return outputs(*_flash_fwd(
            q, k, v, bias, seed, h, causal, DEFAULT_BLOCK_Q,
            DEFAULT_BLOCK_K, interpret, rate, window, coarse, heads,
            relation))

    # the name a jaxpr (and an instruction's metadata) shows
    primitive.__name__ = '_flash_lse' if with_lse else '_flash'
    primitive = jax.custom_vjp(primitive,
                               nondiff_argnums=(5, 6, 7, 8, 9, 10, 11,
                                                12))

    def fwd_rule(q, k, v, bias, seed, h, causal, rate, interpret,
                 window, coarse, heads, relation):
        o, lse = _flash_fwd(q, k, v, bias, seed, h, causal,
                            DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret,
                            rate, window, coarse, heads, relation)
        return outputs(o, lse), (q, k, v, bias, seed, o, lse)

    def bwd_rule(h, causal, rate, interpret, window, coarse, heads,
                 relation, res, g):
        q, k, v, bias, seed, o, lse = res
        g, g_lse = g if with_lse else (g, None)
        dq, dk, dv, dbias = _flash_bwd(
            q, k, v, bias, seed, o, lse, g, g_lse, h, causal,
            DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret, rate, window,
            coarse, heads, relation)
        return dq, dk, dv, (None if bias is None
                            else dbias.astype(bias.dtype)), None

    primitive.defvjp(fwd_rule, bwd_rule)
    return primitive


_flash, _flash_lse = _flash_primitive(False), _flash_primitive(True)


def _dense_path(q, k, v, causal, key_bias, dropout_rate=0.0,
                dropout_seed=None, dropout_offsets=None,
                dropout_g_offset=0, with_lse=False, window=0,
                coarse=None, relation=None):
    """Fused-by-XLA dense chain on [B, T, H, D] (bf16 dots, f32
    softmax) — the measured winner below FLASH_MIN_SEQ, where the
    whole chain fits VMEM outright.  Differentiable via XLA autodiff.
    Dropout draws the SAME counter-hash mask as the Pallas kernels, so
    the two dispatch arms are bit-identical stochastic functions of
    (seed, element position).  ``with_lse`` also returns the per-row
    log-sum-exp [B, H, T] of the undropped scores (flash_attention's
    ``with_lse`` contract).  K/V of fewer heads than q are repeated
    over their group here (the kernels read them through their index
    maps instead); ``window`` bands the causal mask, ``coarse`` is the
    third mask (_coarse_visible) over keys of another length than the
    queries, ``relation`` the fourth (_block_visible), and this arm,
    unlike the kernels, builds its [T, Tk] scores.  v may be of another width than q and k: the scale is
    q's."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    if k.shape[2] != h:
        k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum('bthd,bshd->bhts', q, k,
                   precision=_precision(q.dtype),
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    if key_bias is not None:
        s = s + key_bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window:
            mask = mask & ~jnp.tril(jnp.ones((t, t), bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    lse = None
    if coarse or relation:
        rows, cols = jnp.arange(t)[:, None], jnp.arange(tk)[None, :]
        s = jnp.where(_coarse_visible(rows, cols, coarse) if coarse
                      else _block_visible(rows, cols, relation),
                      s, -jnp.inf)
        # a query of the first window sees no key: p = 0 and lse =
        # -inf there, not softmax's 0 / 0 (the row's max moves
        # neither, so it carries no gradient)
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        total = jnp.sum(e, axis=-1, keepdims=True)
        p = e / jnp.maximum(total, 1e-20)
        lse = jnp.where(total > 0, m + jnp.log(jnp.maximum(total, 1e-20)),
                        -jnp.inf)[..., 0]
    else:
        p = jax.nn.softmax(s, axis=-1)
    if dropout_rate:
        # SAME hash as the kernels (per-element head-index array here,
        # the grid program_id there)
        qo, ko = dropout_offsets if dropout_offsets is not None \
            else (0, 0)
        keep = dropout_keep_dense(dropout_seed, b, h, t, tk, qo, ko,
                                  dropout_g_offset, dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    p = p.astype(q.dtype)
    o = jnp.einsum('bhts,bshd->bthd', p, v,
                   precision=_precision(q.dtype))
    if with_lse:
        return o, jax.nn.logsumexp(s, axis=-1) if lse is None else lse
    return o


BLOCK_RELATIONS = ('causal', 'strict')


def block_relation(block, kind):
    """``block_mask`` = (block, kind) -> the kernels' static
    ``relation`` = (block, inclusive)."""
    return int(block), int(kind == 'causal')


def _check_mask_and_heads(q, k, v, causal, window, coarse=None,
                          block_mask=None):
    """The argument checks every entry shares -> (window, coarse,
    relation) as the kernels' static arguments."""
    h, hkv = q.shape[2], k.shape[2]
    if k.shape[:3] != v.shape[:3] or k.shape[3] != q.shape[3] or \
            k.shape[0] != q.shape[0] or hkv < 1 or h % hkv:
        raise ValueError(
            'attention: K and V must have Q\'s batch, one length and '
            'head count, which divides Q\'s, and K the width of Q (V '
            'may have its own); got Q %r, K %r, V %r'
            % (q.shape, k.shape, v.shape))
    if window and not causal:
        raise ValueError('attention: a window (%r) bands the causal '
                         'mask; causal is False' % (window,))
    t, tk = q.shape[1], k.shape[1]
    if block_mask:
        block, kind = int(block_mask[0]), block_mask[1]
        if causal or window or coarse:
            raise ValueError('attention: the block mask is a mask of '
                             'its own; causal, window and coarse must '
                             'be off')
        if block < 1 or kind not in BLOCK_RELATIONS:
            raise ValueError(
                'attention: block_mask=(block, kind) wants a block of '
                '1 or more positions and a kind of %r; got %r'
                % (BLOCK_RELATIONS, tuple(block_mask)))
        if t != tk:
            raise ValueError(
                'attention: %d queries over %d keys under the %r block '
                'relation, which wants as many of each' % (t, tk, kind))
        return 0, None, block_relation(block, kind)
    if not coarse:
        if tk != t:
            raise ValueError(
                'attention: %d keys for %d queries; keys of another '
                'length than the queries need the coarse mask' % (tk, t))
        return int(window or 0), None, None
    span, chunk = (int(n) for n in coarse)
    if causal or window:
        raise ValueError('attention: the coarse mask is a mask of its '
                         'own; causal and window must be off')
    if chunk < 1 or span < chunk or span % chunk:
        raise ValueError(
            'attention: coarse=(window, chunk) wants a window that is '
            'a multiple of the chunk; got %r' % (tuple(coarse),))
    if tk * chunk < t:
        raise ValueError(
            'attention: %d summaries of %d-key chunks do not cover %d '
            'queries' % (tk, chunk, t))
    return 0, (span, span // chunk), None


def _heads_a_step(q, k, v, has_bias, with_lse, window, coarse,
                  relation=None):
    """2 where the kernels address the op's own [B, T, H*64] operands,
    a pair of heads a grid step (no transposed copy goes in or comes
    out): heads 64 wide, values too, as many K/V heads as query heads,
    an even number of them, no band, coarse or block mask, and a backward
    that is one pass by its VMEM count (a call the count refuses, or
    FUSED_BWD off, takes the [B*H, T, D] path whole).  1 everywhere
    else.  The shape decides; there is nothing to set."""
    _, t, h, d = q.shape
    if not (d == v.shape[3] == PAIRED_HEAD_DIM and k.shape[2] == h
            and h % 2 == 0 and not window and not coarse
            and not relation):
        return 1
    one_pass, _, _ = _backward_plan(
        t, t, 2 * d, 2 * d, q.dtype.itemsize, 1, has_bias, with_lse,
        DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, heads=2)
    return 2 if one_pass else 1


def _checks(t, min_seq=None):
    """flash_attention()'s own gates for common.dispatch()."""
    return (('below_floor',
             t >= (FLASH_MIN_SEQ if min_seq is None else min_seq)),)


def flash_attention(q, k, v, causal=False, key_bias=None,
                    min_seq=None, dropout_rate=0.0, dropout_seed=None,
                    dropout_offsets=None, dropout_g_offset=0,
                    auto_partitioned=False, window=0, with_lse=False,
                    coarse=None, block_mask=None):
    """q: [B, T, H, D]; k: [B, T, Hkv, D], v: [B, T, Hkv, Dv] with Hkv
    a divisor of H (grouped K/V: query head i attends K/V head
    i // (H / Hkv)) and Dv = D unless the values are narrower or wider
    than the keys (scores are scaled by 1/sqrt(D); nothing is padded);
    key_bias: optional [B, T] additive score bias (e.g. padding mask
    as 0 / -10000) -> [B, T, H, Dv].

    The four masks, and none by default (every query sees every key):

    - ``causal``: query i sees the keys j <= i; the kernels skip the
      blocks above the diagonal;
    - ``causal`` with ``window`` > 0, a band: the keys j with
      0 <= i - j < window; the blocks wholly outside the band are
      skipped like those above the diagonal;
    - ``coarse`` = (window, chunk), without ``causal``: k and v are
      Tk >= T / chunk SUMMARIES, one a chunk of ``chunk`` positions,
      and query i sees the summaries of every window of ``window``
      positions before its own, keys 0 .. (i // window) * (window /
      chunk) - 1.  A query of the first window sees none: its output
      is 0 and its lse -inf.  The kernels walk only the key blocks a
      query block sees; no [T, Tk] tensor reaches HBM;
    - ``block_mask`` = (block, kind), without ``causal``: a relation
      between blocks of ``block`` positions.  Kind ``'causal'``: query
      i sees the keys j with j // block <= i // block; ``'strict'``:
      those with j // block < i // block, none in the first block
      (output 0, lse -inf).  The kernels walk only the tiles that
      hold a visible pair.

    ``with_lse`` also returns the per-row log-sum-exp [B, H, T]
    (float32), the merge state for blockwise composition:
    ring attention's blocks, an exact and a coarse call over two key
    sets.  Both outputs are differentiable (the lse cotangent folds
    into dS inside the backward kernels).  lse is computed from the
    UNDROPPED probabilities (dropout scales only the V-weighting), so
    merges stay exact under dropout.

    dropout_rate > 0 applies dropout to the attention probabilities
    INSIDE the kernels (reference default: dropout around softmax,
    operators/dropout_op.cu used by layers/nn.py) — the [T, T] probs
    still never materialize.  The mask is a counter hash of
    (dropout_seed, head, q, k): forward, both backward kernels, and
    any replay regenerate it bit-for-bit, so per-op grad replay and
    whole-program vjp see the same network.  dropout_seed must be a
    uint32 scalar (fold the op seed with the step).

    Auto-dispatch through common.dispatch(): a call of SMALL_KEYS
    keys or fewer under no mask, key bias or dropout runs the
    small-keys kernels (small_keys.py; one call forward, one backward,
    no [T, T] scores in HBM) where its shape passes their gates and
    the platform's, counted in ``dispatch_small_keys`` beside
    ``dispatch_fused``; other sequences shorter than
    `min_seq` (default FLASH_MIN_SEQ, the measured crossover) run the
    dense XLA chain, and so does every call off a TPU unless
    FLAGS_pallas_force asks for the interpreter (tests), and every
    call that says ``auto_partitioned`` (the GSPMD runner's trace
    with no shard_map around the call; see common.dispatch().  An op
    lowering under that runner calls mesh_flash_attention(), which
    opens one).  Pass min_seq=0 to drop the floor (benchmark
    sweeps, a ring's blocks)."""
    b, t, h, d = q.shape
    window, coarse, relation = _check_mask_and_heads(
        q, k, v, causal, window, coarse, block_mask)
    rate = float(dropout_rate or 0.0)
    if rate and dropout_seed is None:
        raise ValueError('dropout_rate > 0 needs a dropout_seed')
    if k.shape[1] <= SMALL_KEYS and key_bias is None and not (
            causal or coarse or relation or rate):
        from . import small_keys
        fused, reason, interpret = _common.decide(
            True, small_keys.checks(q, k, v),
            auto_partitioned=auto_partitioned)
        if fused:       # else: the dispatch as it stands
            from ...fluid import monitor
            _common.record_dispatch('flash_attention', True, reason,
                                    interpret)
            _common._LAST['flash_attention']['arm'] = 'small_keys'
            monitor.add('pallas/flash_attention/dispatch_small_keys', 1)
            return small_keys.attention(q, k, v, with_lse, interpret)
    fused, interpret = _common.dispatch(
        'flash_attention', True, checks=_checks(t, min_seq),
        auto_partitioned=auto_partitioned)
    if not fused:
        return _dense_path(q, k, v, causal, key_bias, rate,
                           dropout_seed, dropout_offsets,
                           dropout_g_offset, with_lse=with_lse,
                           window=window, coarse=coarse,
                           relation=relation)

    heads = _heads_a_step(q, k, v, key_bias is not None, with_lse,
                          window, coarse, relation)
    from ...fluid import monitor
    monitor.add('pallas/flash_attention/layout_%s'
                % ('paired' if heads > 1 else 'transposed'), 1)
    if relation:
        monitor.add('pallas/flash_attention/mask_block', 1)

    if heads > 1:       # views of the op's own layout: no copy
        def to_bh(x):
            return x.reshape(b, x.shape[1], -1)

        def to_bthd(x):
            return x.reshape(b, t, h, -1)
    else:
        def to_bh(x):
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(
                -1, x.shape[1], x.shape[3])

        def to_bthd(x):
            return jnp.transpose(x.reshape(b, h, t, x.shape[2]),
                                 (0, 2, 1, 3))

    if key_bias is not None:
        key_bias = key_bias.astype(jnp.float32)
    seed = _pack_seed(dropout_seed, dropout_offsets,
                      dropout_g_offset) if rate else None
    if not with_lse:
        return to_bthd(_flash(to_bh(q), to_bh(k), to_bh(v), key_bias,
                              seed, h, causal, rate, interpret, window,
                              coarse, heads, relation))
    o, lse = _flash_lse(to_bh(q), to_bh(k), to_bh(v), key_bias, seed, h,
                        causal, rate, interpret, window, coarse, heads,
                        relation)
    lse = lse.reshape(b, h, t)
    # the rows that saw no key: the dense arm's -inf
    if coarse:
        lse = jnp.where(jnp.arange(t) >= coarse[0], lse, -jnp.inf)
    elif relation and not relation[1]:      # the strict rows' first block
        lse = jnp.where(jnp.arange(t) >= relation[0], lse, -jnp.inf)
    return to_bthd(o), lse


def mesh_flash_attention(q, k, v, auto_partitioned, scope, causal=False,
                         key_bias=None, dropout_rate=0.0,
                         dropout_seed=None, window=0, with_lse=False,
                         coarse=None, block_mask=None):
    """flash_attention() as an op lowering calls it, with
    ``ctx.auto_partitioned``: the one place a flash call is wrapped
    for the GSPMD runner (fused_multihead_attention, ring_attention's
    one-device-a-sequence flash arm).

    Outside that runner's trace (one device, or code already inside a
    shard_map) it IS flash_attention().  Under it, where the kernels
    would be chosen but for the mesh (every other gate of
    common.dispatch() passes), the call is made inside a shard_map
    over the trace mesh: q, k, v, the key bias and the output split
    along dimension 0 over the axes the runner split the batch over
    (parallel.mesh.trace_batch_axes()), nothing else split, the seed
    replicated.  Each device then runs the unchanged kernels on its
    share of the batch, with ``dropout_g_offset`` its first global
    (batch x head) index, so every shard draws the mask a one-device
    run draws; the gradient is shard_map's transpose of the same
    call.  Heads are never split: the hash's head index is b * H + h,
    and a split of h is no single offset.  Over the mesh's further
    axes (a model axis) the call is replicated, and GSPMD gathers an
    operand that was split over one.

    ``scope`` is the innermost named scope the caller lowers this
    call in (the fluid op's type, or the scope of its own the
    lowering opened under it).  The compiler names a Mosaic call after
    the innermost scope around it, a device trace is read by those
    names, and shard_map opens a scope of its own: the body enters
    ``scope`` again, so a wrapped call is named as a bare one is.  In
    the program's scope table the wrapped calls of a scopeless
    lowering read ``<op type>/shard_map``.

    Where the runner split the batch over no axis of more than one
    device (a tp-only plan), or this call's batch does not divide by
    their product, there is no shard to hand the kernels: the dense
    chain answers, counted as ``fallback/batch_not_split``.  Calls
    lowered inside the wrap count in ``dispatch_sharded`` (and, by
    flash_attention() inside, in ``dispatch_fused``)."""
    if not auto_partitioned or \
            not _common.decide(True, _checks(q.shape[1]))[0]:
        return flash_attention(
            q, k, v, causal=causal, key_bias=key_bias,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            auto_partitioned=auto_partitioned, window=window,
            with_lse=with_lse, coarse=coarse, block_mask=block_mask)
    from jax.sharding import PartitionSpec as P
    from ...compat import shard_map
    from ...fluid import monitor, trace
    from ...parallel import mesh as pmesh
    mesh = pmesh.trace_mesh()
    axes = tuple(a for a in pmesh.trace_batch_axes()
                 if mesh.shape[a] > 1)
    shards = math.prod(mesh.shape[a] for a in axes)
    if not axes or q.shape[0] % shards:
        window, coarse, relation = _check_mask_and_heads(
            q, k, v, causal, window, coarse, block_mask)
        _common.record_dispatch('flash_attention', False,
                                'batch_not_split')
        return _dense_path(q, k, v, causal, key_bias,
                           float(dropout_rate or 0.0), dropout_seed,
                           with_lse=with_lse, window=window,
                           coarse=coarse, relation=relation)
    split = P(axes if len(axes) > 1 else axes[0])
    operands = [(x, spec) for x, spec in (
        (q, split), (k, split), (v, split), (key_bias, split),
        (dropout_seed, P())) if x is not None]

    def local(q_, k_, v_, *rest):
        rest = list(rest)
        seed_ = rest.pop() if dropout_seed is not None else None
        first = jax.lax.axis_index(axes) * q_.shape[0] * q_.shape[2]
        with jax.named_scope(scope):
            return flash_attention(
                q_, k_, v_, causal=causal,
                key_bias=rest.pop() if rest else None,
                dropout_rate=dropout_rate, dropout_seed=seed_,
                dropout_g_offset=first, window=window,
                with_lse=with_lse, coarse=coarse, block_mask=block_mask)

    with trace.span('pallas/flash_attention/shard_map',
                    axes=','.join(axes), shards=shards):
        monitor.add('pallas/flash_attention/dispatch_sharded', 1)
        return shard_map(
            local, mesh=mesh, in_specs=tuple(spec for _, spec in operands),
            out_specs=(split, split) if with_lse else split)(
                *(x for x, _ in operands))
