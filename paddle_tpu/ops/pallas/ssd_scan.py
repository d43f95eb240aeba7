"""Mamba-2's chunked recurrence on the chip (``ops/ssd_ops.py`` has the
equations): per sequence and head, with a float32 state ``S`` [P, N]
that is zero at the sequence's start, the running sum ``L`` of ``a
delta`` inside a chunk and the state ``S`` the chunk starts from,

    y_t = sum_(s<=t) exp(L_t - L_s) (C_t . B_s) delta_s x_s
          + exp(L_t) S C_t + D x_t
    S <- exp(L_Q) S + sum_s exp(L_Q - L_s) delta_s x_s B_s^T

a chunk after the other inside ONE call a pass.  The dense form
(``ssd_ops._read`` / ``_local`` / ``_walk``) makes every chunk's [Q, Q]
scores, gaps and decay weights at once ([B, T / Q, H, Q, Q] float32,
268 MB each at Nemotron's [1, 8192, 64, 64]), every chunk's write [B,
T / Q, H, P, N], and walks the states through HBM; its backward makes
all of it again with the cotangents beside.  Here a chunk's scores and
weights live in VMEM for the one grid step that uses them and the state
never leaves the core between a sequence's first chunk and its last:
what crosses HBM is the operands, ``y`` and the state at each chunk's
start (the residual the ``custom_vjp`` keeps on either path).

THE GRID is (sequence, group, chunk), the chunks LAST and sequential;
where the chunk index is 0 the state is zeroed, so nothing crosses from
one sequence into the next.  A grid step holds one chunk of one GROUP:
``B``, ``C`` and ``B C^T`` [Q, Q] once, then a static loop over the
group's R heads (independent chains for the scheduler).

LAYOUT: THE TOKENS LIE ON THE LANES.  Every [B, T, .] operand and
result crosses as its transpose, [B, H x P, T] (a group's block [R x P,
Q]) and [B, G x N, T] (a group's block [N, Q]); what comes a number a
token and head (``delta``, ``L`` and the cotangents that come back so)
as [B, H, T], a group's block [R, Q].  That is the order the compiled
step holds these arrays in: XLA lays Nemotron's whole Mamba-2 mixer
token-minor ([1, 8192, 10304]{1,2,0} from ``W_in`` through the filter
to ``W_out``: the norm over a group's 512 channels is then a sum over
sublanes and the filter a shift along lanes), so the ``swapaxes``
around a call are bitcasts and the calls pin the layout the neighbours
had.  The first version of these kernels took [B, T, H x P] rows as
the model's reshapes suggest; every neighbour followed the calls'
layout and lost what the calls won (``rms_norm`` 9.5 -> 18.9 ms a
step, ``mul`` 72.8 -> 80.3: PERF.md section 6, PR 66; PRs 61, 62 and
64 each met the same).  In this order a head is P ROWS of its group's
block (a static, tile-aligned sublane slice: no lane select), a number
a token is a [1, Q] row that multiplies a [P, Q] block by a sublane
broadcast, and every sum over a head's channels (the step's and the
running sums' cotangents) is a sum over sublanes.

THE STATE lies as the dense form keeps it, ``S`` [R x P, N] a group, in
the scratch, in ``starts`` [B, T / Q, G, R x P, N] and in the
backward's carry.

THE MATHEMATICS is the dense form's, term for term: float32 steps,
decays, sums, state and accumulation; every product multiplies operands
in x's dtype (the float32 weights cast as ``ssd_ops._product`` casts
them) and float32 operands at full precision (``_dot``).  The backward
runs the same grid with the chunks counted DOWN and the cotangent
``dS`` of the state a chunk LEAVES in scratch.  With ``u = delta x``,
``W`` the weights, ``m = exp(L_Q - L) delta`` and ``V = B dS^T``:

    dU = W^T dy                      dx = D dy + delta dU + m V
    dCB = sum_heads (dy u^T) exp(L_t - L_s)
    dC = dCB B + (exp(L) dy) S       dB = dCB^T C + (m x) dS
    dS <- exp(L_Q) dS + (exp(L) dy)^T C
    pd = x . (dU + exp(L_Q - L) V)   the step's direct cotangent
    r  = dy . (y - D x)              and with the last token's
    dL = r - delta pd  (+ <dS, S'>)  the running sums' cotangent

(every pull-back through ``exp(L_t - L_s)``, ``exp(L_t)`` and ``exp(L_Q
- L_s)`` folds into those two sums over a head's channels).  ``delta
pd`` is taken on ``u`` and ``m x`` as the products took them, rounded
to x's dtype: a weight's pull-back is then the same number in ``r`` at
t and in ``delta pd`` at s, and the running sum of ``dL`` cancels pair
by pair as the dense path's does (taken on the unrounded x, bfloat16's
``d_a`` lay 5.7e-3 from the float32 one where the dense path's lies
5.9e-4: PERF.md section 6, PR 66).  ``pd``, ``dL`` [B, H, T], the last
token's share and ``D``'s cotangent a row a head leave the call; the
reverse running sum that turns ``dL`` into ``d_delta`` and ``d_a`` is
[B, H, T]-sized and stays in XLA, as the forward's ``cumsum`` does.

Dispatch is ``ssd_ops``'s (``common.dispatch``, once a call).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common
from .flash_attention import _dot

LANES, SUBLANES = 128, 8
_F32 = jnp.float32
_common.register_kernel(
    'ssd_scan',
    dense_fallback='paddle_tpu.ops.ssd_ops._forward',
    has_vjp=True,
    doc='Mamba-2\'s chunked recurrence, forward and backward, a chunk\'s '
        'scores and decay weights in VMEM and the [R x P, N] state of a '
        'group held on the core from a sequence\'s first chunk to its '
        'last; dispatches dense off float32 / chunk, N % 128 / P % 16 / '
        'a ragged tail',
    op_types=('ssd_scan',))


def backward_vmem(chunk, width, states, itemsize):
    """Bytes one instance of the backward call holds in VMEM, as they
    lie: x, dy, dx [R x P, Q] and B, C, dB, dC [N, Q] in their dtype,
    the start state [R x P, N] float32 and the [R, .] rows in the
    pipeline's two buffers each, the carry, and what the compiler lays
    beside them for the head it works on (a dozen [Q, Q] float32
    arrays and two states).  The forward holds less."""
    rows = chunk * (3 * width + 4 * states) * itemsize
    state = 4 * states * width
    small = 4 * SUBLANES * (5 * chunk + states)
    working = 4 * 12 * chunk * max(chunk, LANES)
    return 2 * (rows + state + small) + 3 * state + working


def checks(x_shape, groups, states, chunk, dtype, itemsize):
    """``common.dispatch``'s gates, from what the operands show: a
    float32 working dtype; the chunk (as ``ssd_ops._layout`` runs it)
    and N in whole 128-lane tiles, a head's P rows whole sublane tiles
    of either width, a group's rows of a [B, H, T] array whole sublane
    tiles (or every head), and T whole chunks (a ragged tail runs
    dense, and so do heads that fill no whole groups, which the dense
    path refuses); the backward's count under the budget of a call that
    asks Mosaic for nothing."""
    _, t, h, p = x_shape
    r = h // max(groups, 1)
    size = max(1, min(int(chunk), t))
    whole = all(v > 0 and v % LANES == 0 for v in (size, states))
    return (('dtype', dtype == _F32),
            ('layout', whole and p % (2 * SUBLANES) == 0 and
             t % size == 0 and h == r * groups and
             (r % SUBLANES == 0 or groups == 1)),
            ('vmem_over_budget',
             backward_vmem(size, r * p, states, itemsize) <=
             _common.VMEM_BUDGET_BYTES))


def _chunk_terms(bt_ref, ct_ref, delta_ref, sums_ref, kind):
    """What a chunk's heads share: B^T, C^T [N, Q] in x's dtype; delta
    and L [R, Q] a token a lane, L also a token a sublane [Q, R]; the
    scores B C^T [s, t] float32; s <= t."""
    bt, ct = bt_ref[...].astype(kind), ct_ref[...].astype(kind)
    sums = sums_ref[...]
    q = sums.shape[1]
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) <= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return bt, ct, delta_ref[...], sums, sums.T, _dot(bt, ct, (0, 0)), seen


def _head_terms(delta, sums, sums_cols, scores, seen, i):
    """One head's numbers a token, [1, Q] rows: delta, L, L's last
    entry [1, 1]; and [s, t] float32: exp(L_t - L_s) where s <= t, 0
    elsewhere, and the scores times it."""
    step, total = delta[i:i + 1, :], sums[i:i + 1, :]
    decay = jnp.exp(jnp.where(seen, total - sums_cols[:, i:i + 1],
                              -jnp.inf))
    # L's last entry as a sum over the lanes of the row with the others
    # zeroed: exact, and a [1, 1] the compiler broadcasts either way (a
    # slice at lane Q - 1 it broadcasts over lanes only)
    q = total.shape[1]
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    last = jnp.sum(jnp.where(at_last, total, 0.0), 1, keepdims=True)
    return step, total, last, decay, scores * decay


def _forward_kernel(skip_ref, xt_ref, bt_ref, ct_ref, delta_ref, sums_ref,
                    yt_ref, starts_ref, state_ref, *, p):
    """One chunk of one group: x^T, y^T [R x P, Q]; B^T, C^T [N, Q];
    delta, L [R, Q]; D [H] in SMEM; the state the chunk starts from
    [R x P, N]."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    starts_ref[...] = state_ref[...]
    kind, heads = xt_ref.dtype, delta_ref.shape[0]
    bt, ct, delta, sums, sums_cols, scores, seen = _chunk_terms(
        bt_ref, ct_ref, delta_ref, sums_ref, kind)
    for i in range(heads):
        rows = slice(i * p, (i + 1) * p)
        step, total, last, _, weights = _head_terms(
            delta, sums, sums_cols, scores, seen, i)
        x = xt_ref[rows, :].astype(_F32)
        state = state_ref[rows, :]
        y = _dot((x * step).astype(kind), weights.astype(kind), (1, 0)) + \
            _dot(state.astype(kind), ct, (1, 0)) * jnp.exp(total) + \
            skip_ref[pl.program_id(1) * heads + i] * x
        yt_ref[rows, :] = y.astype(yt_ref.dtype)
        leaving = (x * (jnp.exp(last - total) * step)).astype(kind)
        state_ref[rows, :] = jnp.exp(last) * state + _dot(leaving, bt, (1, 1))


def _backward_kernel(skip_ref, xt_ref, bt_ref, ct_ref, delta_ref, sums_ref,
                     starts_ref, dyt_ref, dxt_ref, dbt_ref, dct_ref, pd_ref,
                     r_ref, gone_ref, dlast_ref, dskip_ref, carry_ref, *,
                     p):
    """One chunk of one group, the chunks counted down: what the
    forward kernel saw, the start it wrote and y^T's cotangent -> dx^T
    [R x P, Q], dB^T, dC^T [N, Q]; [R, Q] rows: pd, the running sums'
    cotangent but for the last token's share, and what the tokens'
    writes pull back from the state the chunk leaves; that share's
    other half exp(L_Q) <dS, S> [R, N] (a head's row: its sum over the
    lanes is XLA's) and D's cotangent [R, Q] (summed over the chunks;
    the same); ``carry_ref`` [R x P, N] is the cotangent of the state
    the chunk leaves."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    kind, heads = xt_ref.dtype, delta_ref.shape[0]
    bt, ct, delta, sums, sums_cols, scores, seen = _chunk_terms(
        bt_ref, ct_ref, delta_ref, sums_ref, kind)
    d_scores = jnp.zeros_like(scores)
    d_bt = jnp.zeros(dbt_ref.shape, _F32)
    d_ct = jnp.zeros(dct_ref.shape, _F32)
    for i in range(heads):
        rows, row = slice(i * p, (i + 1) * p), slice(i, i + 1)
        step, total, last, decay, weights = _head_terms(
            delta, sums, sums_cols, scores, seen, i)
        weights = weights.astype(kind)
        x, dy_own = xt_ref[rows, :].astype(_F32), dyt_ref[rows, :].astype(kind)
        dy = dy_own.astype(_F32)
        grown, ahead, kept = jnp.exp(total), jnp.exp(last - total), \
            jnp.exp(last)
        to_end = ahead * step
        written, leaving = (x * step).astype(kind), (x * to_end).astype(kind)
        state, d_state = starts_ref[rows, :], carry_ref[rows, :]
        state_own, d_state_own = state.astype(kind), d_state.astype(kind)
        y = _dot(written, weights, (1, 0)) + _dot(state_own, ct, (1, 0)) * grown
        via_state = _dot(d_state_own, bt, (1, 0))           # V^T [P, s]
        d_written = _dot(dy_own, weights, (1, 1))           # dU^T [P, s]
        dxt_ref[rows, :] = (
            skip_ref[pl.program_id(1) * heads + i] * dy + step * d_written +
            to_end * via_state).astype(dxt_ref.dtype)
        pd_ref[row, :] = jnp.sum(x * (d_written + ahead * via_state), 0,
                                 keepdims=True)
        # dL = r - delta pd on the operands AS THE PRODUCTS TOOK THEM
        # (u and m x rounded to x's dtype): every term exp(L_t - L_s)
        # pulls back is then in r at t and here at s as the SAME
        # number, and their running sums cancel as the dense path's do
        gone = jnp.sum(leaving.astype(_F32) * via_state, 0, keepdims=True)
        r_ref[row, :] = jnp.sum(
            dy * y - written.astype(_F32) * d_written, 0,
            keepdims=True) - gone
        gone_ref[row, :] = gone
        dskip_ref[row, :] = dskip_ref[row, :] + \
            jnp.sum(dy * x, 0, keepdims=True)
        d_scores = d_scores + _dot(written, dy_own, (0, 0)) * decay
        grown_dy = (dy * grown).astype(kind)
        d_ct = d_ct + _dot(state_own, grown_dy, (0, 0))
        d_bt = d_bt + _dot(d_state_own, leaving, (0, 0))
        dlast_ref[row, :] = kept * jnp.sum(d_state * state, 0, keepdims=True)
        carry_ref[rows, :] = kept * d_state + _dot(grown_dy, ct, (1, 1))
    d_scores = d_scores.astype(kind)                        # dCB [s, t]
    dct_ref[...] = (d_ct + _dot(bt, d_scores, (1, 0))).astype(dct_ref.dtype)
    dbt_ref[...] = (d_bt + _dot(ct, d_scores, (1, 1))).astype(dbt_ref.dtype)


def _rows(delta, a, size):
    """delta [B, T, H], a [H] -> (delta, the running sum of a * delta
    inside each chunk of ``size`` tokens, inclusive), float32 [B, H,
    T]."""
    delta = jnp.swapaxes(delta.astype(_F32), 1, 2)
    b, h, t = delta.shape
    steps = (delta * a.astype(_F32)[:, None]).reshape(b, h, t // size, size)
    return delta, jnp.cumsum(steps, axis=-1).reshape(b, h, t)


def _tokens_last(v):
    """[B, T, H or G, .] -> [B, (H or G) x ., T]."""
    return jnp.swapaxes(v.reshape(v.shape[:2] + (-1,)), 1, 2)


@functools.partial(jax.jit, inline=True,
                   static_argnames=('size', 'interpret'))
def _call(x, delta, a, bm, cm, skip, *rest, size, interpret):
    """The forward kernel over the op's operands -> (y [B, T, H, P] in
    x's dtype, S at each chunk's start [B, T / size, G, R x P, N]),
    or, given those starts and y's cotangent, the backward one and the
    [B, H, T]-sized sums around it -> the six gradients.  Under a jit
    cache of its own, ``inline`` (as kda_chunk._call): a body is traced
    once a process and shape and its instruction keeps the name of the
    scope the caller lowered it in."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    r, chunks = h // g, t // size
    width = r * p
    at = chunks - 1 if rest else 0          # the pass's first chunk

    def chunk(c):
        return at - c if rest else c

    tokens = pl.BlockSpec((None, width, size), lambda i, j, c: (i, j, chunk(c)))
    vectors = pl.BlockSpec((None, n, size), lambda i, j, c: (i, j, chunk(c)))
    rows = pl.BlockSpec((None, r, size), lambda i, j, c: (i, j, chunk(c)))
    start = pl.BlockSpec((None, None, None, width, n),
                         lambda i, j, c: (i, chunk(c), j, 0, 0))
    delta_rows, sums = _rows(delta, a, size)
    operands = (skip.astype(_F32), _tokens_last(x), _tokens_last(bm),
                _tokens_last(cm), delta_rows, sums)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), tokens, vectors,
                vectors, rows, rows]
    starts = jax.ShapeDtypeStruct((b, chunks, g, width, n), _F32)
    kwargs = dict(
        grid=(b, g, chunks), interpret=interpret,
        scratch_shapes=[pltpu.VMEM((width, n), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))

    def tokens_first(v, like):
        return jnp.swapaxes(v, 1, 2).reshape(like.shape)

    if not rest:
        yt, kept = pl.pallas_call(
            functools.partial(_forward_kernel, p=p), in_specs=in_specs,
            out_specs=[tokens, start],
            out_shape=[jax.ShapeDtypeStruct((b, h * p, t), x.dtype), starts],
            name='ssd_scan_forward', **kwargs)(*operands)
        return tokens_first(yt, x), kept
    kept, d_y = rest
    by_head = jax.ShapeDtypeStruct((b, h, t), _F32)
    d_xt, d_bt, d_ct, direct, d_sums, gone, d_last, d_skip = pl.pallas_call(
        functools.partial(_backward_kernel, p=p),
        in_specs=in_specs + [start, tokens],
        out_specs=[tokens, vectors, vectors, rows, rows, rows,
                   pl.BlockSpec((None, None, r, n),
                                lambda i, j, c: (i, chunk(c), j, 0)),
                   pl.BlockSpec((None, r, size), lambda i, j, c: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h * p, t), x.dtype),
                   jax.ShapeDtypeStruct((b, g * n, t), bm.dtype),
                   jax.ShapeDtypeStruct((b, g * n, t), cm.dtype),
                   by_head, by_head, by_head,
                   jax.ShapeDtypeStruct((b, chunks, h, n), _F32),
                   jax.ShapeDtypeStruct((b, h, size), _F32)],
        name='ssd_scan_backward', **kwargs)(
            *operands, kept, _tokens_last(d_y))
    # the last token's share of the running sums' cotangent (<dS, S'>:
    # through exp(L_Q) S and through every token's exp(L_Q - L_s)), then
    # the reverse running sum inside each chunk that takes the cotangent
    # back to the steps: [B, H, chunks, size]
    d_sums = d_sums.reshape(b, h, chunks, size)
    d_last = jnp.swapaxes(jnp.sum(d_last, -1), 1, 2) + \
        jnp.sum(gone.reshape(d_sums.shape), -1)
    d_sums = d_sums.at[..., -1].add(d_last)
    back = jax.lax.cumsum(d_sums, axis=3, reverse=True).reshape(b, h, t)
    d_delta = direct + a.astype(_F32)[:, None] * back
    return (tokens_first(d_xt, x),
            jnp.swapaxes(d_delta, 1, 2).astype(delta.dtype),
            jnp.sum(delta_rows * back, (0, 2)).astype(a.dtype),
            tokens_first(d_bt, bm), tokens_first(d_ct, cm),
            jnp.sum(d_skip, (0, 2)).astype(skip.dtype))


def forward(x, delta, a, bm, cm, skip, size, interpret=False):
    """x [B, T, H, P], delta [B, T, H], a [H], bm, cm [B, T, G, N],
    skip [H] (``checks`` holds) -> (y [B, T, H, P] in x's dtype, S
    at each chunk's START [B, T / size, G, R x P, N] float32), in
    chunks of ``size`` tokens."""
    return _call(x, delta, a, bm, cm, skip, size=size, interpret=interpret)


def backward(x, delta, a, bm, cm, skip, starts, d_y, size,
             interpret=False):
    """``forward``'s operands, the starts it kept and y's cotangent ->
    the cotangents of x, delta, a, bm, cm, skip in their dtypes."""
    return _call(x, delta, a, bm, cm, skip, starts, d_y, size=size,
                 interpret=interpret)
