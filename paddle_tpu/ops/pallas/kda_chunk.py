"""The gated delta rule's in-chunk scores on the chip (``ops/kda_ops.py``
has the equations): for every chunk and head, from ``q``, ``k`` and the
running log decay ``G`` of the chunk ([C, dk] float32 each),

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <  t
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <= t

as two [C, C] tiles, and their backward.  The decay is per channel, so
inside a sub-chunk of ``SUB`` tokens the weight is a [SUB, SUB, dk]
block (128 KB at dk 128): the dense form (``kda_ops._scores``) writes
those blocks to HBM, sixteen times the op's operands; here a block is
built a key at a time, used and dropped in VMEM.

The mathematics is the dense form's, term for term.  Inside a sub-chunk
the difference ``G_t - G_j`` is taken first and masked in the exponent
(to -inf); between a row of sub-chunk I and the columns of the earlier
ones the weight is ``exp(G_t - r_I) exp(r_I - G_j)`` about the running
sum ``r_I`` at I's start, both factors <= 1, and the sum over channels
a float32 product at ``Precision.HIGHEST`` (Mosaic's is XLA's: on a
v5e both lie 2.1e-6 from float64, PERF.md section 6, PR 47).  No clamp,
no floor, no dropped term, no factored ``exp(-G_j)``.

Both kernels walk a sub-chunk a KEY j at a time on [SUB, dk] tiles,
the rows t on sublanes and the channel on lanes: ``G_j``, ``k_j`` are
one row broadcast over the sublanes, the weights of all SUB rows
against that key two vector registers.  The forward sums each row's
channels (a lane reduction a row and key) into column j of the tile.
The backward takes column j of the cotangents ``dA``, ``dB``, builds
the weights again, and adds up ``dq``, ``dk``, ``dG``: sums over the
keys are vector adds, the sum over the rows one sublane reduction a
key.  With ``dk_row`` / ``dq`` the gradients through the row factor and
``dk_col`` through the column factor, the decay's is ``dG = k (dk_row -
dk_col) + q dq``: every weight is an exponential of ``G_t - G_j``.
(``r_I`` cancels in ``exp(G_t - r_I) exp(r_I - G_j)``: its cotangent is
zero, and the dense form's is rounding.)

A grid step is one chunk-head.  Inside it the sub-chunks are a loop
(the first one apart: nothing stands before it) and a sub-chunk's keys
a loop that Mosaic unrolls, so a kernel's jaxpr holds two sub-chunks'
operations and one key's, not C / SUB x SUB of them; the calls sit
under a jit cache of their own (``_call``).  Both for ``setup_s``: a
train step holds nine of these calls and every program traces and
lowers each.

Tried on the chip and dropped (PERF.md section 6, PR 47): the channel
on sublanes and the (t, j) pair on 256 lanes, reached through exact
0/1 selector products on the MXU (2.4 times this forward's time);
several chunk-heads a grid step (3%); Python loops over sub-chunks and
keys (as fast as it gets, 0.41 + 0.54 ms a layer where these take 0.46
+ 0.69, but 39 s of ``setup_s``); the keys' loop rolled (3.5 ms); a
``lax.cond`` around the first sub-chunk's absent columns (0.71 + 1.04).

Dispatch is ``kda_ops``'s (``common.dispatch``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import common as _common
from .flash_attention import _dot

SUB = 16
_F32 = jnp.float32
_common.register_kernel(
    'kda_chunk',
    dense_fallback='ops.kda_ops._scores',
    has_vjp=True,
    doc='the gated delta rule\'s in-chunk scores A, B from q, k and the '
        'running log decay; dispatches dense off float32 / dk % 128',
    op_types=('kda_attention',))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _between(g_ref, first):
    """The two factors between the rows of the sub-chunk that starts
    at token ``first`` and the columns before it: ``exp(G_t - r)``
    [SUB, dk] and ``exp(r - G_j)`` [C, dk], 0 from ``first`` on (masked
    in the exponent).  ``r`` is the running sum at the sub-chunk's
    start, the last token's of the sub-chunk before it."""
    start = g_ref[pl.ds(first - 1, 1), :]
    row = jnp.exp(g_ref[pl.ds(first, SUB), :] - start)
    earlier = _iota((g_ref.shape[0], 1), 0) < first
    return row, jnp.exp(jnp.where(earlier, start - g_ref[...], -jnp.inf))


def _key(g_ref, k_ref, g_t, at, keep):
    """Key ``at`` of the chunk against the rows of its own sub-chunk:
    (exp(G_t - G_j) [SUB, dk], 0 off ``keep`` (the rows before the key),
    masked in the exponent; k_j [1, dk])."""
    g_j, k_j = g_ref[pl.ds(at, 1), :], k_ref[pl.ds(at, 1), :]
    return jnp.exp(jnp.where(keep, g_t - g_j, -jnp.inf)), k_j


def _over_sub_chunks(c, body, init=None):
    """``body(first, tile, later, carry)`` over a chunk's sub-chunks:
    the first one (``later`` False: no sub-chunk stands before it),
    then a loop over the others; inside each ``_over_keys``.  A loop,
    so that a kernel's jaxpr holds two sub-chunks' operations and not
    C / SUB of them: the bodies are lowered at every call site of every
    program (Python loops over both cost the Solar cell 39 s of
    ``setup_s``)."""
    def sub_chunk(n, carry):
        first = pl.multiple_of(n * SUB, SUB)
        return body(first, pl.ds(first, SUB), True, carry)

    return jax.lax.fori_loop(1, c // SUB, sub_chunk,
                             body(0, pl.ds(0, SUB), False, init))


def _over_keys(key, init):
    """``key(j, carry)`` over the SUB keys of a sub-chunk, as a loop
    that Mosaic unrolls whole: every key's chain lies in one basic
    block for the scheduler (rolled, each key's latency is waited out:
    3.5 ms where this takes 0.41, PERF.md section 6, PR 47)."""
    return jax.lax.fori_loop(0, SUB, key, init, unroll=True)


def _forward_kernel(q_ref, k_ref, g_ref, a_ref, b_ref):
    """One chunk-head: q, k, g [C, dk] -> A, B [C, C], a [SUB, C] row
    block a sub-chunk."""
    c = g_ref.shape[0]
    token = _iota((SUB, 1), 0)
    column = _iota((SUB, c), 1)

    def sub_chunk(first, tile, later, _):
        g_t, k_t, q_t = g_ref[tile, :], k_ref[tile, :], q_ref[tile, :]

        def key(j, blocks):
            a_in, b_in = blocks
            w, k_j = _key(g_ref, k_ref, g_t, first + j, token >= j)
            k_w = k_j * w
            a_j = jnp.sum(k_t * k_w, 1, keepdims=True)
            b_j = jnp.sum(q_t * k_w, 1, keepdims=True)
            here = column == first + j
            return (jnp.where(here, jnp.where(token > j, a_j, 0.0), a_in),
                    jnp.where(here, b_j, b_in))

        zero = jnp.zeros((SUB, c), _F32)
        a_in, b_in = _over_keys(key, (zero, zero))
        if later:
            row, col = _between(g_ref, first)
            off = _dot(jnp.concatenate([k_t * row, q_t * row], 0),
                       k_ref[...] * col, (1, 1))
            a_in, b_in = a_in + off[:SUB], b_in + off[SUB:]
        a_ref[tile, :] = a_in
        b_ref[tile, :] = b_in

    _over_sub_chunks(c, sub_chunk)


def _backward_kernel(q_ref, k_ref, g_ref, da_ref, db_ref,
                     dq_ref, dk_ref, dg_ref):
    """One chunk-head: q, k, g [C, dk] and the cotangents of A, B [C,
    C] -> dq, dk, dG [C, dk].  Until every sub-chunk is walked,
    ``dk_ref`` holds ``dk_row`` and ``dg_ref`` the keys' share of
    ``dk_col`` inside their own sub-chunk."""
    c, dk = g_ref.shape
    token = _iota((SUB, 1), 0)
    column = _iota((SUB, c), 1)

    def sub_chunk(first, tile, later, dk_col):
        g_t, k_t, q_t = g_ref[tile, :], k_ref[tile, :], q_ref[tile, :]
        da_t, db_t = da_ref[tile, :], db_ref[tile, :]

        def key(j, sums):
            dk_row, dq, dk_in = sums
            w, k_j = _key(g_ref, k_ref, g_t, first + j, token >= j)
            # column first + j of the cotangents, a row's on its sublane
            here = column == first + j
            da = jnp.sum(jnp.where(here & (token > j), da_t, 0.0), 1,
                         keepdims=True)
            db = jnp.sum(jnp.where(here, db_t, 0.0), 1, keepdims=True)
            k_w = k_j * w
            through_key = jnp.sum((da * k_t + db * q_t) * w, 0,
                                  keepdims=True)
            return (dk_row + da * k_w, dq + db * k_w,
                    jnp.where(token == j, through_key, dk_in))

        zero = jnp.zeros((SUB, dk), _F32)
        dk_row, dq, dk_in = _over_keys(key, (zero, zero, zero))
        if later:
            row, col = _between(g_ref, first)
            both = jnp.concatenate([da_t, db_t], 0)            # [2 SUB, C]
            d_rows = _dot(both, k_ref[...] * col, (1, 0))
            dk_row += d_rows[:SUB] * row
            dq += d_rows[SUB:] * row
            # through the column factor, every key before this sub-chunk
            dk_col += col * _dot(
                both, jnp.concatenate([k_t * row, q_t * row], 0), (0, 0))
        dq_ref[tile, :] = dq
        dk_ref[tile, :] = dk_row
        dg_ref[tile, :] = dk_in
        return dk_col

    dk_col = _over_sub_chunks(c, sub_chunk, jnp.zeros((c, dk), _F32))
    dk_row, dk_col = dk_ref[...], dk_col + dg_ref[...]
    dk_ref[...] = dk_row + dk_col
    dg_ref[...] = k_ref[...] * (dk_row - dk_col) + q_ref[...] * dq_ref[...]


@functools.partial(jax.jit, inline=True,
                   static_argnames=('backward', 'interpret'))
def _call(*operands, backward, interpret):
    """The forward kernel over q, k, g [n, C, dk] -> A, B [n, C, C], or
    the backward one over q, k, g, dA, dB -> dq, dk, dG [n, C, dk]: a
    chunk-head a grid step (more of them a step bought 3% of the
    kernels' time on a v5e: PERF.md section 6, PR 47).  Under a jit
    cache of its own, ``inline`` (as flash_attention._fwd_call): a
    kernel's body is traced once a process and shape, not once a call
    (a train step holds nine), and its instruction keeps the name of
    the scope the caller lowered it in."""
    n, c, dk = operands[0].shape
    widths = (dk, dk, dk) if backward else (c, c)

    def spec(width):
        return pl.BlockSpec((None, c, width), lambda i: (i, 0, 0))

    return pl.pallas_call(
        _backward_kernel if backward else _forward_kernel,
        grid=(n,),
        in_specs=[spec(x.shape[2]) for x in operands],
        out_specs=[spec(w) for w in widths],
        out_shape=[jax.ShapeDtypeStruct((n, c, w), _F32) for w in widths],
        interpret=interpret,
    )(*operands)


def checks(c, dk, dtype):
    """``common.dispatch``'s gates: what the kernels' layout asks of a
    chunk as it is run: float32, whole sub-chunks, the channel in
    whole 128-lane tiles."""
    return (('dtype', dtype == jnp.float32),
            ('layout', c % SUB == 0 and dk % 128 == 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunk_scores(q, k, g, interpret=False):
    """q, k and the running log decay g [..., C, dk] float32 of whole
    chunks -> (A strictly lower, B lower) [..., C, C]."""
    return _scores_fwd(q, k, g, interpret)[0]


def _flat(x):
    return x.reshape((-1,) + x.shape[-2:])


def _scores_fwd(q, k, g, interpret):
    c = g.shape[-2]
    a, b = _call(*(_flat(x) for x in (q, k, g)), backward=False,
                 interpret=interpret)
    lead = g.shape[:-2]
    return (a.reshape(lead + (c, c)), b.reshape(lead + (c, c))), (q, k, g)


def _scores_bwd(interpret, saved, cotangents):
    grads = _call(*(_flat(x) for x in saved + tuple(cotangents)),
                  backward=True, interpret=interpret)
    return tuple(x.reshape(saved[2].shape) for x in grads)


chunk_scores.defvjp(_scores_fwd, _scores_bwd)
