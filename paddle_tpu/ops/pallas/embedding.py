"""Fused sparse embedding lookup + update kernels (PS/recsys path).

Forward: one grid step per looked-up id; the scalar-prefetch index map
DMAs exactly the touched row of the table into VMEM (``_row_spec``:
a (1, 1, D) block of the [V, 1, D] view, row ``ids[i]``) — XLA's
gather is fine, but the
backward's dense lowering is not: ``jnp.zeros_like(w).at[ids].add(g)``
materializes a full [V, D] scatter the size of the table per step.

Backward / fused update: ids are sorted once (XLA argsort), so equal
ids form consecutive grid steps that revisit the SAME output block —
Pallas keeps a revisited block resident in VMEM between consecutive
steps, which turns duplicate-id accumulation into first-visit
initialization + in-VMEM adds (no read-modify-write races, no one-hot
matmul).  The scatter-add vjp writes cotangent sums into a zeroed
[V, D] buffer; the fused adagrad update goes further and applies
``m += sum(g)**2; w -= lr*sum(g)/(sqrt(m)+eps)`` to only the touched
rows at each id's LAST visit, passing untouched rows through via
input/output aliasing — zero-grad rows are exact no-ops under adagrad,
so this equals the dense full-table update bit-for-bit in semantics
(float tolerance in practice: the row sums reduce in sorted order).

Dense fallbacks: ``jnp.take`` (+ padding mask) for lookup — bitwise
the historical lowering — and scatter-into-zeros + the registered
dense adagrad for the update.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common

common.register_kernel(
    'embedding_lookup',
    dense_fallback='jnp.take row gather (ops.tensor_ops.lookup_table_v2)',
    has_vjp=True,
    doc='scalar-prefetch row gather; sorted scatter-add backward',
    op_types=('lookup_table', 'lookup_table_v2'))

common.register_kernel(
    'embedding_update',
    dense_fallback='dense scatter-add + ops.optimizer_ops.adagrad',
    has_vjp=False,
    doc='sorted-run adagrad update over only the touched rows',
    op_types=('adagrad',))


def _dense_lookup(w, ids, padding_idx):
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros_like(out), out)
    return out


def _gather_kernel(ids_ref, w_ref, out_ref):
    del ids_ref
    out_ref[...] = w_ref[...]


def _scatter_kernel(sids_ref, g_ref, base_ref, out_ref):
    # base is the zeroed [V, D] buffer aliased into the output: rows
    # no grid step visits stay zero without a full-table epilogue
    del base_ref
    i = pl.program_id(0)
    first = jnp.logical_or(
        i == 0, sids_ref[i] != sids_ref[jnp.maximum(i - 1, 0)])
    # consecutive equal ids revisit this output block: accumulate in
    # VMEM; the first visit overwrites whatever the block held
    out_ref[...] = jnp.where(first, g_ref[...],
                             out_ref[...] + g_ref[...])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(w, ids, interpret):
    return _gather(w, ids, interpret)


def _row_spec(d, index):
    """One [1, 1, D] row of a table viewed as [N, 1, D].  Mosaic
    refuses a (1, D) block of a 2-D [N, D] array (second-to-last block
    dim neither a multiple of 8 nor the array's); with the unit middle
    axis the block's last two dims EQUAL the array's, which it admits.
    ``index(i, *prefetch_refs)`` names the row."""
    return pl.BlockSpec((1, 1, d),
                        lambda i, *refs: (index(i, *refs), 0, 0))


def _gather(w, ids, interpret):
    n, (v, d) = ids.shape[0], w.shape
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[_row_spec(d, lambda i, ids_ref: ids_ref[i])],
            out_specs=_row_spec(d, lambda i, ids_ref: i)),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), w.dtype),
        interpret=interpret)(ids, w[:, None, :])[:, 0, :]


def scatter_add(nrows, ids, g, interpret):
    """[nrows, D] buffer with g's rows summed at ids (duplicates
    accumulate) — the lookup's cotangent.  ids: [n] int32 in-range."""
    n, d = g.shape
    order = jnp.argsort(ids)
    sids = jnp.take(ids, order)
    sg = jnp.take(g, order, axis=0)
    row = _row_spec(d, lambda i, sids_ref: sids_ref[i])
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[_row_spec(d, lambda i, sids_ref: i), row],
            out_specs=row),
        out_shape=jax.ShapeDtypeStruct((nrows, 1, d), g.dtype),
        input_output_aliases={2: 0},
        interpret=interpret)(sids, sg[:, None, :],
                             jnp.zeros((nrows, 1, d), g.dtype))[:, 0, :]


def _lookup_fwd(w, ids, interpret):
    return _gather(w, ids, interpret), (w.shape[0], ids)


def _lookup_bwd(interpret, res, g):
    nrows, ids = res
    dw = scatter_add(nrows, ids, g, interpret)
    return dw, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embedding_lookup(w, ids, padding_idx=-1, auto_partitioned=False):
    """Auto-dispatched [V, D] row gather for arbitrary-rank ids ->
    ids.shape + (D,).  Padding masking stays an XLA epilogue on both
    paths (bit-identical; its vjp zeroes padding cotangents before
    they reach the scatter).  ``auto_partitioned``: see
    common.dispatch()."""
    from ...fluid.flags import get_flag
    v, d = w.shape
    n = int(np.prod(ids.shape)) if ids.shape else 1
    fused, interpret = common.dispatch(
        'embedding_lookup',
        bool(get_flag('FLAGS_pallas_embedding', True)),
        checks=(
            ('below_floor',
             v >= int(get_flag('FLAGS_pallas_embedding_min_rows',
                               512))),
            ('dtype', jnp.issubdtype(ids.dtype, jnp.integer)),
            # on real TPUs keep the lane dim aligned; the interpreter
            # has no layout constraint
            ('layout', d % 128 == 0 or not common.on_tpu()),
        ), auto_partitioned=auto_partitioned)
    if not fused:
        return _dense_lookup(w, ids, padding_idx)
    # jnp.take clips out-of-range ids; mirror it so the paths agree
    sids = jnp.clip(ids.reshape(-1), 0, v - 1).astype(jnp.int32)
    out = _lookup(w, sids, interpret).reshape(ids.shape + (d,))
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros_like(out), out)
    return out


# ------------------------------------------------- fused row update

def _update_kernel(sids_ref, lr_ref, g_ref, w_ref, m_ref,
                   wo_ref, mo_ref, acc_ref, *, epsilon):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    first = jnp.logical_or(
        i == 0, sids_ref[i] != sids_ref[jnp.maximum(i - 1, 0)])
    last = jnp.logical_or(
        i == n - 1,
        sids_ref[i] != sids_ref[jnp.minimum(i + 1, n - 1)])
    g = g_ref[...]
    acc = jnp.where(first, g, acc_ref[...] + g)
    acc_ref[...] = acc
    # adagrad on the merged row gradient, applied at the run's last
    # visit; intermediate visits pass the original row through (the
    # out block is only flushed to HBM when the id changes)
    m_new = m_ref[...] + acc * acc
    w_new = w_ref[...] - lr_ref[0] * acc / (jnp.sqrt(m_new) +
                                            epsilon)
    wo_ref[...] = jnp.where(last, w_new, w_ref[...])
    mo_ref[...] = jnp.where(last, m_new, m_ref[...])


def _fused_rows_update(w, mom, ids, g, lr, epsilon, interpret):
    """Apply adagrad to only the rows named by ids (duplicates merged
    by summing their grads first — the dense scatter-add semantics).
    Untouched rows ride through via input/output aliasing."""
    (n, d), v = g.shape, w.shape[0]
    order = jnp.argsort(ids)
    sids = jnp.take(ids, order)
    sg = jnp.take(g, order, axis=0)
    # the learning rate rides in SMEM beside the sorted ids (a second
    # scalar-prefetch operand): the kernel reads it as a scalar
    lr1 = lr.reshape((1,)).astype(jnp.float32)
    row = _row_spec(d, lambda i, sids_ref, lr_ref: sids_ref[i])
    w_out, m_out = pl.pallas_call(
        functools.partial(_update_kernel, epsilon=epsilon),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[
                _row_spec(d, lambda i, sids_ref, lr_ref: i),
                row, row],
            out_specs=[row, row],
            scratch_shapes=[pltpu.VMEM((1, 1, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((v, 1, d), w.dtype),
                   jax.ShapeDtypeStruct((v, 1, d), mom.dtype)],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret)(sids, lr1, sg[:, None, :],
                             w[:, None, :], mom[:, None, :])
    return w_out[:, 0, :], m_out[:, 0, :]


def apply_update(ctx, ins, attrs):
    """The registered fused_emb_update lowering: Param [V, D], Moment
    [V, D], Ids [...], Grad ids.shape + [D], LearningRate -> ParamOut,
    MomentOut.  Dense fallback scatter-adds Grad into a zero table and
    runs the registered dense adagrad over the WHOLE table — zero-grad
    rows are exact adagrad no-ops, so both paths agree."""
    from ...fluid.flags import get_flag
    from ..optimizer_ops import adagrad
    w = ins['Param'][0]
    mom = ins['Moment'][0]
    ids = ins['Ids'][0]
    g = ins['Grad'][0]
    epsilon = attrs.get('epsilon', 1e-6)
    padding_idx = attrs.get('padding_idx', -1)
    v, d = w.shape
    # v1 lookup_table ids come as [..., 1] while Grad follows the
    # squeezed Out shape — align ids to Grad's leading dims
    if ids.shape != g.shape[:-1]:
        ids = ids.reshape(g.shape[:-1])
    if padding_idx is not None and padding_idx >= 0:
        g = jnp.where((ids == padding_idx)[..., None],
                      jnp.zeros_like(g), g)
    flat_ids = jnp.clip(ids.reshape(-1), 0, v - 1).astype(jnp.int32)
    flat_g = g.reshape(-1, d).astype(w.dtype)
    fused, interpret = common.dispatch(
        'embedding_update',
        bool(get_flag('FLAGS_pallas_embedding', True)),
        checks=(
            ('below_floor',
             v >= int(get_flag('FLAGS_pallas_embedding_min_rows',
                               512))),
            ('dtype', w.dtype == jnp.float32 and
             mom.dtype == jnp.float32),
            ('layout', d % 128 == 0 or not common.on_tpu()),
        ), auto_partitioned=ctx.auto_partitioned)
    if fused:
        w_out, m_out = _fused_rows_update(
            w, mom, flat_ids, flat_g, ins['LearningRate'][0],
            epsilon, interpret)
        return {'ParamOut': [w_out], 'MomentOut': [m_out]}
    dense_g = jnp.zeros_like(w).at[flat_ids].add(flat_g)
    return adagrad(ctx, {'Param': [w], 'Grad': [dense_g],
                         'Moment': [mom],
                         'LearningRate': ins['LearningRate']},
                   {'epsilon': epsilon})
