"""The experts' grouped matmuls on the chip: rows sorted by group
(expert), ``group_sizes`` [E] int32 saying how many rows each group
holds, and one weight matrix a group.  Three products, the ones
``jax.lax.ragged_dot`` and its two transposes compute:

  1. ``forward``          rows [M, K] x w [E, K, N]   -> [M, N]
  2. ``transposed``       rows [M, N] x w [E, K, N]^T -> [M, K]
  3. ``weight_gradient``  rows [M, K]^T x cot [M, N]  -> [E, K, N]

each per group, bfloat16 (the operands' dtype) times bfloat16 with
float32 accumulation and a result in the operands' dtype: what
``ragged_dot`` and its transposes give bfloat16 operands (form 3
writing its float32 sums straight out was tried on the chip and cost
the Moonlight cell 3.4 ms a step: twice the bytes out of the kernel
and into AdamW; PERF.md section 6, PR 48).  Form 2 reads the [E, K, N]
array as it lies (the MXU contracts either axis of its weight block),
so no transposed copy of a weight is written to HBM.

Rows past ``sum(group_sizes)`` belong to no group: no product reads
them into a result or writes them (parallel/moe.py's held layers hand
a worst-case buffer whose tail is unwritten, NaN for all anyone
knows).  Forms 1 and 2 store only the rows of the visit's group; form
3 zeroes every row outside it in BOTH operands before the product (a
NaN times zero is a NaN), and writes zeros for an empty group.

The shape is megablox's (``jax.experimental.pallas.ops.tpu.megablox``):
the row axis is cut into tiles of ``ROW_TILE``, and the grid walks
VISITS, (group, tile) pairs in row order, a tile that straddles two
groups once for each.  The visits' groups and tiles are scalar
prefetch (``visits()``), and the grid's extent along them is a DEVICE
scalar, the visits that hold a row, so the tiles past the last group
cost nothing, as the compiler's own calls skip them.  A visit's weight
block is its group's, so consecutive visits of one group re-use the
resident block and the next group's is fetched behind the last tile's
product.

Tiles, chosen from the static shapes alone (``_column_block``): the
row tile is ``ROW_TILE``; the weight block is a whole expert's matrix
where the instance's count fits the VMEM a call may ask for
(``common.one_pass_backward_limit``: the count and the headroom, under
the cap), else the widest multiple of 128 lanes of its OUTPUT columns
that divides them and fits; the contraction is never cut.  Form 3
keeps one [K, n] float32 accumulator in VMEM over a group's visits and
writes it once, rounded, at the group's last.

Each form sits under one jit cache of its static shapes (``_rows_call``,
``_weights_call``; ``inline``, as flash_attention._fwd_call): a train
step holds 11 products a routed layer and every program traces and
lowers each.

Dispatch is parallel/moe.py's (``common.dispatch``, ``checks``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common

# Rows a visit multiplies.  A tile that straddles two groups is
# multiplied once for each, so a group costs up to one tile more than
# its rows: an eighth more at 1,024-row groups, and a 100-row group
# costs a tile whatever the tile (PERF.md section 6, PR 48 has the
# chip's readings over 128, 256 and 512).
ROW_TILE = 128
# Rows of the [K, n] accumulator one product of form 3 adds to: the
# product's float32 result lies in VMEM beside the accumulator until
# it is added.
_ACC_ROWS = 512
_F32 = jnp.float32

_common.register_kernel(
    'grouped_matmul',
    dense_fallback='jax.lax.ragged_dot',
    has_vjp=True,
    doc='the routed experts\' grouped products (rows x w, rows x w^T, '
        'rows^T x cot per group); dispatches dense off bfloat16, off '
        '128-lane widths and off whole row tiles',
    op_types=('moe_experts',))


def checks(m, widths, dtypes):
    """``common.dispatch``'s gates, from what the operands show: every
    operand bfloat16 (float32 operands multiply at full precision,
    which is ``ragged_dot``'s to do), every width of the weights whole
    128-lane tiles, the rows whole row tiles."""
    return (('dtype', all(d == jnp.bfloat16 for d in dtypes)),
            ('layout', all(w % 128 == 0 for w in widths)
             and m % ROW_TILE == 0))


def visits(group_sizes, m):
    """What the kernels' grids walk, for ``m`` rows in tiles of
    ROW_TILE -> (offsets [E + 1], group [V], tile [V], count []) int32:
    group e holds rows offsets[e] to offsets[e + 1]; visit v multiplies
    tile ``tile[v]`` for group ``group[v]``; the first ``count`` visits
    are real.  In row order, so a tile's visits are consecutive, and
    an empty group has one visit (form 3 writes its zeros; the others
    store nothing there), of the tile its neighbours touch.  V = m /
    ROW_TILE + E - 1 bounds the count: every group but the first can
    begin inside a tile the one before it ends in."""
    e = group_sizes.shape[0]
    tiles = m // ROW_TILE
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // ROW_TILE, tiles - 1)
    n = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 1)
    upto = jnp.cumsum(n)
    v = jax.lax.iota(jnp.int32, tiles + e - 1)
    # compare_all: one [V, E] comparison (parallel.moe has why)
    group = jnp.minimum(jnp.searchsorted(
        upto, v, side='right', method='compare_all').astype(jnp.int32),
        e - 1)
    tile = first[group] + v - (upto - n)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group, jnp.minimum(tile, tiles - 1),
            upto[-1].astype(jnp.int32))


def _visit(offsets, group, tile, v):
    """(group, [ROW_TILE, 1] mask of the rows of visit ``v``'s tile
    that its group holds)."""
    g = group[v]
    row = tile[v] * ROW_TILE + jax.lax.broadcasted_iota(
        jnp.int32, (ROW_TILE, 1), 0)
    return g, (row >= offsets[g]) & (row < offsets[g + 1])


def _rows_kernel(offsets, group, tile, rows_ref, w_ref, out_ref, *,
                 contract):
    """One visit of forms 1 and 2: the tile's rows times the group's
    weight block (contracted on its axis ``contract``), stored where
    the rows are the group's; the others keep what an earlier visit of
    the tile stored."""
    _, held = _visit(offsets, group, tile, pl.program_id(1))
    product = jax.lax.dot_general(
        rows_ref[...], w_ref[...], (((1,), (contract,)), ((), ())),
        preferred_element_type=_F32)
    out_ref[...] = jnp.where(held, product.astype(out_ref.dtype),
                             out_ref[...])


def _weights_kernel(offsets, group, tile, rows_ref, cot_ref, out_ref,
                    acc_ref):
    """One visit of form 3: the group's rows of the tile, transposed,
    times its cotangent rows, added to the group's accumulator; the
    first visit of a group zeroes it and the last writes it out."""
    v = pl.program_id(1)
    g, held = _visit(offsets, group, tile, v)

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        cot = jnp.where(held, cot_ref[...], jnp.zeros_like(cot_ref))
        k = acc_ref.shape[0]
        step = min(k, _ACC_ROWS)
        for at in range(0, k, step):
            rows = jnp.where(held, rows_ref[:, at:at + step], 0)
            acc_ref[at:at + step, :] += jax.lax.dot_general(
                rows, cot, (((0,), (0,)), ((), ())),
                preferred_element_type=_F32)

    last = pl.num_programs(1) - 1

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _rows_vmem(contraction, block, itemsize):
    """Bytes an instance of forms 1 and 2 holds in VMEM: the row tile,
    the weight block and the output tile in the pipeline's two buffers
    each, and the product's float32 tile."""
    return 2 * itemsize * (ROW_TILE * contraction + contraction * block
                           + ROW_TILE * block) + 4 * ROW_TILE * block


def _weights_vmem(k, block, itemsize):
    """Bytes an instance of form 3 holds: both row tiles and the
    output block in two buffers each, the float32 accumulator, and one
    product's float32 result beside it."""
    return 2 * itemsize * (ROW_TILE * (k + block) + k * block) + \
        4 * (k + min(k, _ACC_ROWS)) * block


def _column_block(width, count):
    """(block, ``vmem_limit_bytes``): the widest block of ``width``
    output columns, whole or a multiple of 128 lanes that divides
    them, whose instance (``count(block)`` bytes) a call may ask Mosaic
    for (common.one_pass_backward_limit: the count and the headroom,
    under the cap every call here keeps to)."""
    lanes = width // 128
    for parts in range(1, lanes + 1):
        if lanes % parts:
            continue
        block = width // parts
        admitted, limit = _common.one_pass_backward_limit(count(block))
        if admitted or parts == lanes:
            return block, limit


def _params(limit, interpret):
    """pallas_call's keywords that both calls share: the grid's first
    axis walks independent column blocks, the second the visits in
    order (a tile's stores and a group's accumulator depend on it)."""
    if limit is not None:
        from ...fluid import monitor
        monitor.set_gauge('pallas/grouped_matmul/vmem_asked_max', max(
            limit,
            monitor.gauge_value('pallas/grouped_matmul/vmem_asked_max')))
    return dict(
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=limit))


@functools.partial(jax.jit, inline=True,
                   static_argnames=('transpose', 'interpret'))
def _rows_call(rows, w, offsets, group, tile, count, *, transpose,
               interpret):
    """Forms 1 (``transpose`` False: rows [M, K] x w [E, K, N]) and 2
    (True: rows [M, N] x w^T) over the visits ``visits()`` laid out."""
    m, contraction = rows.shape
    width = w.shape[1] if transpose else w.shape[2]
    block, limit = _column_block(width, functools.partial(
        _rows_vmem, contraction, itemsize=rows.dtype.itemsize))
    if transpose:
        w_spec = pl.BlockSpec((None, block, contraction),
                              lambda j, v, o, g, t: (g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, contraction, block),
                              lambda j, v, o, g, t: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, contract=1 if transpose else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(width // block, count),
            in_specs=[
                pl.BlockSpec((ROW_TILE, contraction),
                             lambda j, v, o, g, t: (t[v], 0)),
                w_spec],
            out_specs=pl.BlockSpec((ROW_TILE, block),
                                   lambda j, v, o, g, t: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, width), rows.dtype),
        **_params(limit, interpret),
    )(offsets, group, tile, rows, w)


@functools.partial(jax.jit, inline=True, static_argnames=('interpret',))
def _weights_call(rows, cot, offsets, group, tile, count, *, interpret):
    """Form 3: rows [M, K]^T x cot [M, N] per group -> [E, K, N]."""
    k, n = rows.shape[1], cot.shape[1]
    e = offsets.shape[0] - 1
    block, limit = _column_block(n, functools.partial(
        _weights_vmem, k, itemsize=rows.dtype.itemsize))
    return pl.pallas_call(
        _weights_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // block, count),
            in_specs=[
                pl.BlockSpec((ROW_TILE, k),
                             lambda j, v, o, g, t: (t[v], 0)),
                pl.BlockSpec((ROW_TILE, block),
                             lambda j, v, o, g, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, k, block),
                                   lambda j, v, o, g, t: (g[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, block), _F32)]),
        out_shape=jax.ShapeDtypeStruct((e, k, n), rows.dtype),
        **_params(limit, interpret),
    )(offsets, group, tile, rows, cot)


def forward(rows, w, walk, interpret=False):
    """Form 1: rows [M, K] x w [E, K, N] -> [M, N]; ``walk`` is
    ``visits()``'s for these groups and M."""
    return _rows_call(rows, w, *walk, transpose=False,
                      interpret=interpret)


def transposed(rows, w, walk, interpret=False):
    """Form 2: rows [M, N] x w [E, K, N]^T -> [M, K]."""
    return _rows_call(rows, w, *walk, transpose=True,
                      interpret=interpret)


def weight_gradient(rows, cot, walk, interpret=False):
    """Form 3: rows [M, K]^T x cot [M, N] per group -> [E, K, N]."""
    return _weights_call(rows, cot, *walk, interpret=interpret)
