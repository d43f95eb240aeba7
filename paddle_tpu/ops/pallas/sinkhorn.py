"""The hyper-connections' Sinkhorn projection on the chip
(``ops/hyper_connection_ops.py`` has the equations): a positive ``n x n``
matrix a token, M_0 = exp(clamp(R~)), is brought to the doubly stochastic
matrices by ``iters`` x (rows, then columns) normalisations,

    M <- M / (rowsum(M) + hc_eps);    M <- M / (colsum(M) + hc_eps)

and every trip is differentiated exactly.  The dense form
(``hyper_connection_ops.sinkhorn``) is one ``lax.scan``: each of its
trips is seven small fusions over an array that fits VMEM forty times
over, 4.7 us a trip and 960 trips a step of the Xing4 cell: 4.5 ms.
Here ALL the trips of a tile of tokens run inside one call, 6 us a call
and 36 calls a step: 0.2 ms (my chip runs, PR 55; PERF.md section 6).

LAYOUT.  The op's maps lie tokens-last, [n, n, S] float32.  The calls
see them as [n, n, S / 128, 128]: an entry (i, j) of ``ROW_TILE`` x 128
tokens is whole (8, 128) registers, a row or column sum is n - 1 vector
adds of such entries and nothing moves across sublanes or lanes.  A grid
step holds all n x n entries of ``ROW_TILE`` x 128 tokens (or of all the
tokens, where S / 128 is no multiple of ``ROW_TILE``): at n = 4 and a
tile of 1024 tokens the matrix is sixteen registers, and the trips' loop
carries it in them.

THE MATHEMATICS is the dense form's, term for term, in float32: the same
sums, ``hc_eps`` added to each, a true division (no reciprocal, no
fewer trips, no fixed point).  For ``y = m / (s + hc_eps)``, ``s`` the
sum of ``m`` over an axis, the cotangent is

    dm = (dy - sum(dy * y)) / (s + hc_eps)          (over the same axis)

THE BACKWARD is one call too.  The forward keeps M_0 alone (what the
dense form's ``jax.checkpoint`` keeps); the backward call runs the trips
again with every half-trip's INPUT in a VMEM scratch (2 x iters x n x n x
tile x 4 bytes: 2.6 MB at 20 trips of a 4 x 4 matrix over 1024 tokens),
then walks them in reverse: a half-trip's output is the input of the one
after it, so a step loads one kept matrix, sums it again for ``s`` and
applies the line above.

Dispatch is ``hyper_connection_ops``'s (``common.dispatch``, once a call
of ``project``): ``checks`` asks float32, a square matrix, S in whole
128-lane rows, and the backward's count under the budget every kernel
here keeps to; the scan otherwise, with the reason counted.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common

LANES = 128
ROW_TILE = 8        # rows of 128 tokens a grid step: one register an entry
_F32 = jnp.float32
_common.register_kernel(
    'sinkhorn',
    dense_fallback='paddle_tpu.ops.hyper_connection_ops.sinkhorn',
    has_vjp=True,
    doc='the hyper-connections\' H_res: iters x (row, column) '
        'normalisations of a positive n x n matrix a token in one call, '
        'and their exact backward in one; dispatches dense off float32 / '
        'tokens % 128',
    op_types=('hyper_connection_pre',))


def tile_rows(rows):
    """The rows of 128 tokens a grid step holds, of ``rows`` in all:
    ``ROW_TILE`` where that divides them, else all of them (a block
    that is no multiple of 8 sublanes has to be the whole axis)."""
    return ROW_TILE if rows % ROW_TILE == 0 else rows


def backward_vmem(n, iters, rows):
    """Bytes one instance of the backward call holds in VMEM, as they
    lie (a tile's rows in whole 8-sublane registers): the kept
    half-trips' scratch, and M_0, the cotangent and the result in the
    pipeline's two buffers each.  The forward holds less."""
    entry = -(-tile_rows(rows) // 8) * 8 * LANES * 4
    return (2 * iters + 3 * 2) * n * n * entry


def checks(shape, dtype, iters):
    """``common.dispatch``'s gates, from what the operand shows:
    float32; [n, n, S] with S in whole 128-lane rows; the backward's
    count within the budget at the tile ``tile_rows`` gives."""
    square = len(shape) == 3 and shape[0] == shape[1]
    layout = square and shape[2] > 0 and shape[2] % LANES == 0
    return (('dtype', dtype == _F32),
            ('layout', layout),
            ('vmem_over_budget', not layout or backward_vmem(
                shape[0], iters, shape[2] // LANES) <=
                _common.VMEM_BUDGET_BYTES))


def _normalise(m, axis, hc_eps):
    return m / (jnp.sum(m, axis, keepdims=True) + hc_eps)


def _forward_kernel(m_ref, out_ref, *, iters, hc_eps):
    """One tile of tokens: m [n, n, rows, 128] (rows, columns of the
    matrix first) -> the same after ``iters`` trips."""
    def trip(_, m):
        return _normalise(_normalise(m, 1, hc_eps), 0, hc_eps)

    out_ref[...] = jax.lax.fori_loop(0, iters, trip, m_ref[...])


def _backward_kernel(m_ref, dy_ref, dm_ref, kept_ref, *, iters, hc_eps):
    """One tile of tokens: M_0 and the cotangent of the projection ->
    the cotangent of M_0.  ``kept_ref`` [2 iters, n, n, rows, 128]
    holds the input of every half-trip: of trip k's row normalisation
    at 2 k, of its column normalisation at 2 k + 1."""
    def trip(k, m):
        kept_ref[2 * k] = m
        m = _normalise(m, 1, hc_eps)
        kept_ref[2 * k + 1] = m
        return _normalise(m, 0, hc_eps)

    def pull(m, y, dy, axis):
        s = jnp.sum(m, axis, keepdims=True) + hc_eps
        return (dy - jnp.sum(dy * y, axis, keepdims=True)) / s

    def back(i, carry):
        y, dy = carry
        k = iters - 1 - i
        m = kept_ref[2 * k + 1]
        dy = pull(m, y, dy, 0)
        y, m = m, kept_ref[2 * k]
        return m, pull(m, y, dy, 1)

    y = jax.lax.fori_loop(0, iters, trip, m_ref[...])
    dm_ref[...] = jax.lax.fori_loop(0, iters, back, (y, dy_ref[...]))[1]


@functools.partial(jax.jit, inline=True,
                   static_argnames=('iters', 'hc_eps', 'backward',
                                    'interpret'))
def _call(*operands, iters, hc_eps, backward, interpret):
    """The forward kernel over m [n, n, S] -> H_res, or the backward
    one over M_0 and H_res's cotangent -> M_0's: a tile of tokens a
    grid step.  Under a jit cache of its own, ``inline`` (as
    kda_chunk._call): a body is traced once a process and shape, not
    once a call (a train step holds 36), and its instruction keeps the
    name of the scope the caller lowered it in."""
    n, _, s = operands[0].shape
    rows = s // LANES
    tile = tile_rows(rows)
    block = pl.BlockSpec((n, n, tile, LANES), lambda i: (0, 0, i, 0))
    kernel, scratch = _forward_kernel, []
    if backward:
        kernel = _backward_kernel
        scratch = [pltpu.VMEM((2 * iters, n, n, tile, LANES), _F32)]
    out = pl.pallas_call(
        functools.partial(kernel, iters=iters, hc_eps=hc_eps),
        grid=(rows // tile,),
        in_specs=[block] * len(operands),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n, n, rows, LANES), _F32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*(x.reshape(n, n, rows, LANES) for x in operands))
    return out.reshape(n, n, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def sinkhorn(m, iters, hc_eps, interpret=False):
    """m [n, n, S] float32 > 0 (rows, columns, tokens; ``checks``
    holds) -> the same after ``iters`` x (rows, then columns)
    normalisations."""
    return _call(m, iters=iters, hc_eps=hc_eps, backward=False,
                 interpret=interpret)


def _sinkhorn_fwd(m, iters, hc_eps, interpret):
    return sinkhorn(m, iters, hc_eps, interpret), m


def _sinkhorn_bwd(iters, hc_eps, interpret, m, d_out):
    return (_call(m, d_out, iters=iters, hc_eps=hc_eps, backward=True,
                  interpret=interpret),)


sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)
