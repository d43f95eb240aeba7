"""Tensor creation / manipulation op lowerings.

Reference kernels: paddle/fluid/operators/{fill_constant,gaussian_random,
uniform_random,assign,cast,reshape,transpose,concat,split,slice,squeeze,
unsqueeze,expand,stack,gather,scatter,shape,one_hot,lookup_table_v2,
cumsum,range,...}_op.cc|.cu — here each is a few lines of jnp and the
gradients come from jax.vjp (registry.grad_op_def).
"""

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register


def _x(ins, slot='X'):
    return ins[slot][0]


@register('fill_constant')
def fill_constant(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs['shape'])
    dtype = attrs.get('dtype', 'float32')
    from ..fluid import core
    value = attrs.get('value', 0.0)
    if attrs.get('str_value'):
        value = float(attrs['str_value'])
    return {'Out': [jnp.full(shape, value, core.convert_dtype(dtype))]}


@register('fill_constant_batch_size_like')
def fill_constant_batch_size_like(ctx, ins, attrs):
    from ..fluid import core
    ref = _x(ins, 'Input')
    shape = list(attrs['shape'])
    in_idx = attrs.get('input_dim_idx', 0)
    out_idx = attrs.get('output_dim_idx', 0)
    shape[out_idx] = ref.shape[in_idx]
    return {'Out': [jnp.full(tuple(shape), attrs.get('value', 0.0),
                             core.convert_dtype(attrs.get('dtype',
                                                          'float32')))]}


@register('fill_zeros_like')
def fill_zeros_like(ctx, ins, attrs):
    return {'Out': [jnp.zeros_like(_x(ins))]}


@register('fill_any_like')
def fill_any_like(ctx, ins, attrs):
    return {'Out': [jnp.full_like(_x(ins), attrs.get('value', 0.0))]}


@register('gaussian_random')
def gaussian_random(ctx, ins, attrs):
    from ..fluid import core
    shape = tuple(int(s) for s in attrs['shape'])
    dtype = core.convert_dtype(attrs.get('dtype', 'float32'))
    mean = attrs.get('mean', 0.0)
    std = attrs.get('std', 1.0)
    out = mean + std * jax.random.normal(ctx.rng(), shape, jnp.float32)
    return {'Out': [out.astype(dtype)]}


@register('uniform_random')
def uniform_random(ctx, ins, attrs):
    from ..fluid import core
    shape = tuple(int(s) for s in attrs['shape'])
    dtype = core.convert_dtype(attrs.get('dtype', 'float32'))
    lo = attrs.get('min', -1.0)
    hi = attrs.get('max', 1.0)
    out = jax.random.uniform(ctx.rng(), shape, jnp.float32, lo, hi)
    return {'Out': [out.astype(dtype)]}


@register('truncated_gaussian_random')
def truncated_gaussian_random(ctx, ins, attrs):
    from ..fluid import core
    shape = tuple(int(s) for s in attrs['shape'])
    dtype = core.convert_dtype(attrs.get('dtype', 'float32'))
    mean = attrs.get('mean', 0.0)
    std = attrs.get('std', 1.0)
    out = jax.random.truncated_normal(ctx.rng(), -2.0, 2.0, shape,
                                      jnp.float32)
    return {'Out': [(mean + std * out).astype(dtype)]}


@register('assign')
def assign(ctx, ins, attrs):
    return {'Out': [_x(ins)]}


@register('share_data')
def share_data(ctx, ins, attrs):
    return {'Out': [_x(ins)]}


@register('recompute_barrier')
def recompute_barrier(ctx, ins, attrs):
    """Identity that XLA cannot CSE through: makes recomputed forward
    spans (RecomputeOptimizer) actually rematerialize instead of being
    deduped against the original forward, which would keep the
    activations alive and void the memory savings.  The TPU-native
    analog of the reference's explicit recompute sub-graphs
    (backward.py:618 _append_backward_ops_with_checkpoints_)."""
    import jax
    return {'Out': [jax.lax.optimization_barrier(_x(ins))]}


@register('cast')
def cast(ctx, ins, attrs):
    from ..fluid import core
    return {'Out': [_x(ins).astype(core.convert_dtype(attrs['out_dtype']))]}


def _resolve_shape(shape, x):
    """Paddle reshape semantics: 0 -> copy dim from x, -1 -> inferred."""
    shape = list(int(s) for s in shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        shape[shape.index(-1)] = int(np.prod(x.shape)) // known
    return tuple(shape)


@register('reshape2', no_grad_out_slots=('XShape',))
def reshape2(ctx, ins, attrs):
    x = _x(ins)
    out = {'Out': [jnp.reshape(x, _resolve_shape(attrs['shape'], x))]}
    return out


@register('reshape')
def reshape(ctx, ins, attrs):
    x = _x(ins)
    return {'Out': [jnp.reshape(x, _resolve_shape(attrs['shape'], x))]}


@register('flatten2')
def flatten2(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', 1)
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return {'Out': [jnp.reshape(x, (lead, -1))]}


@register('flatten_contiguous_range')
def flatten_contiguous_range(ctx, ins, attrs):
    x = _x(ins)
    start = attrs.get('start_axis', 1)
    stop = attrs.get('stop_axis', -1)
    if stop < 0:
        stop += x.ndim
    shape = x.shape[:start] + (-1,) + x.shape[stop + 1:]
    return {'Out': [jnp.reshape(x, shape)]}


@register('transpose2')
def transpose2(ctx, ins, attrs):
    return {'Out': [jnp.transpose(_x(ins), attrs['axis'])]}


@register('transpose')
def transpose(ctx, ins, attrs):
    return {'Out': [jnp.transpose(_x(ins), attrs['axis'])]}


@register('concat')
def concat(ctx, ins, attrs):
    axis = attrs.get('axis', 0)
    return {'Out': [jnp.concatenate(ins['X'], axis=axis)]}


@register('split')
def split(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', 0)
    num = attrs.get('num', 0)
    sections = attrs.get('sections', [])
    if sections:
        sections = list(sections)
        if -1 in sections:
            known = sum(s for s in sections if s != -1)
            sections[sections.index(-1)] = x.shape[axis] - known
        idx = np.cumsum(sections[:-1]).tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {'Out': list(outs)}


@register('slice')
def slice_op(ctx, ins, attrs):
    x = ins['Input'][0]
    axes = attrs['axes']
    starts = attrs['starts']
    ends = attrs['ends']
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        dim = x.shape[ax]
        st = max(st + dim, 0) if st < 0 else min(st, dim)
        en = max(en + dim, 0) if en < 0 else min(en, dim)
        idx[ax] = slice(st, en)
    out = x[tuple(idx)]
    for ax in sorted(attrs.get('decrease_axis', []), reverse=True):
        out = jnp.squeeze(out, axis=ax)
    return {'Out': [out]}


@register('strided_slice')
def strided_slice(ctx, ins, attrs):
    x = ins['Input'][0]
    idx = [slice(None)] * x.ndim
    for ax, st, en, sd in zip(attrs['axes'], attrs['starts'], attrs['ends'],
                              attrs['strides']):
        idx[ax] = slice(st, en, sd)
    return {'Out': [x[tuple(idx)]]}


@register('squeeze2', no_grad_out_slots=('XShape',))
def squeeze2(ctx, ins, attrs):
    x = _x(ins)
    axes = attrs.get('axes', [])
    if not axes:
        return {'Out': [jnp.squeeze(x)]}
    axes = tuple(a for a in axes if x.shape[a] == 1)
    return {'Out': [jnp.squeeze(x, axis=axes)]}


@register('unsqueeze2', no_grad_out_slots=('XShape',))
def unsqueeze2(ctx, ins, attrs):
    x = _x(ins)
    for a in sorted(attrs['axes']):
        x = jnp.expand_dims(x, a)
    return {'Out': [x]}


# v1 op name, same semantics minus the XShape output
# (operators/unsqueeze_op.cc)
register('unsqueeze')(unsqueeze2)


@register('expand')
def expand(ctx, ins, attrs):
    x = _x(ins)
    times = attrs['expand_times']
    return {'Out': [jnp.tile(x, times)]}


@register('expand_as')
def expand_as(ctx, ins, attrs):
    x = _x(ins)
    target = ins['target_tensor'][0]
    reps = [t // s for t, s in zip(target.shape, x.shape)]
    return {'Out': [jnp.tile(x, reps)]}


@register('tile')
def tile(ctx, ins, attrs):
    return {'Out': [jnp.tile(_x(ins), attrs['repeat_times'])]}


@register('stack')
def stack(ctx, ins, attrs):
    return {'Y': [jnp.stack(ins['X'], axis=attrs.get('axis', 0))]}


@register('unstack')
def unstack(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', 0)
    num = x.shape[axis]
    return {'Y': [jnp.squeeze(s, axis) for s in jnp.split(x, num, axis)]}


@register('gather')
def gather(ctx, ins, attrs):
    x = _x(ins)
    idx = ins['Index'][0]
    axis = attrs.get('axis', 0)
    return {'Out': [jnp.take(x, idx, axis=axis)]}


@register('gather_nd')
def gather_nd(ctx, ins, attrs):
    x = _x(ins)
    idx = ins['Index'][0]
    return {'Out': [x[tuple(jnp.moveaxis(idx, -1, 0))]]}


@register('scatter')
def scatter(ctx, ins, attrs):
    x = _x(ins)
    ids = ins['Ids'][0]
    upd = ins['Updates'][0]
    if attrs.get('overwrite', True):
        return {'Out': [x.at[ids].set(upd)]}
    return {'Out': [x.at[ids].add(upd)]}


@register('shape', no_grad_out_slots=('Out',))
def shape_op(ctx, ins, attrs):
    x = ins['Input'][0]
    return {'Out': [jnp.asarray(np.array(x.shape, np.int32))]}


@register('range')
def range_op(ctx, ins, attrs):
    start = ins['Start'][0].reshape(())
    end = ins['End'][0].reshape(())
    step = ins['Step'][0].reshape(())
    # XLA needs static sizes: range inputs must be compile-time constants,
    # so the layer stores them as attrs too when literal.
    if '__static__' in attrs:
        s, e, st = attrs['__static__']
        return {'Out': [jnp.arange(s, e, st,
                                   dtype=ins['Start'][0].dtype)]}
    raise NotImplementedError(
        'range with traced bounds is not supported under XLA; '
        'pass python scalars to layers.range')


@register('one_hot', no_grad_out_slots=('Out',))
def one_hot(ctx, ins, attrs):
    x = _x(ins)
    depth = attrs['depth']
    if x.ndim > 1 and x.shape[-1] == 1:
        x = jnp.squeeze(x, -1)
    return {'Out': [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register('one_hot_v2', no_grad_out_slots=('Out',))
def one_hot_v2(ctx, ins, attrs):
    return one_hot(ctx, ins, attrs)


@register('lookup_table_v2')
def lookup_table_v2(ctx, ins, attrs):
    # XLA's gather; the gradient is jnp.take's own vjp (scatter-add
    # into zeros), the same on one chip and under a mesh.  An id
    # outside [-V, V) reads NaN and trains nothing (take's 'fill':
    # the reference op raises there, a compiled program cannot)
    w = ins['W'][0]
    ids = ins['Ids'][0]
    padding_idx = attrs.get('padding_idx', -1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros_like(out), out)
    return {'Out': [out]}


@register('lookup_table')
def lookup_table(ctx, ins, attrs):
    # v1 requires ids shape [..., 1] (reference operators/lookup_table_op.cc)
    w = ins['W'][0]
    ids = ins['Ids'][0]
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, -1)
    out = lookup_table_v2(ctx, {'W': [w], 'Ids': [ids]}, attrs)
    return out


@register('embedding')
def embedding(ctx, ins, attrs):
    return lookup_table_v2(ctx, ins, attrs)


@register('cumsum')
def cumsum(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', -1)
    if attrs.get('flatten', False):
        x = x.reshape(-1)
        axis = 0
    out = jnp.cumsum(x, axis=axis)
    if attrs.get('reverse', False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if attrs.get('exclusive', False):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (1, 0)
        out = jnp.pad(out, pad)[tuple(
            slice(0, -1) if i == axis % x.ndim else slice(None)
            for i in range(x.ndim))]
    return {'Out': [out]}


@register('increment')
def increment(ctx, ins, attrs):
    return {'Out': [_x(ins) + attrs.get('step', 1.0)]}


@register('pad')
def pad(ctx, ins, attrs):
    x = _x(ins)
    p = attrs['paddings']
    widths = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {'Out': [jnp.pad(x, widths,
                            constant_values=attrs.get('pad_value', 0.0))]}


@register('pad2d')
def pad2d(ctx, ins, attrs):
    x = _x(ins)
    p = attrs['paddings']  # [top, bottom, left, right]
    mode = attrs.get('mode', 'constant')
    widths = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if attrs.get('data_format', 'NCHW') == 'NHWC':
        widths = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    if mode == 'constant':
        return {'Out': [jnp.pad(x, widths,
                                constant_values=attrs.get('pad_value', 0.0))]}
    jmode = {'reflect': 'reflect', 'edge': 'edge'}[mode]
    return {'Out': [jnp.pad(x, widths, mode=jmode)]}


@register('where')
def where(ctx, ins, attrs):
    return {'Out': [jnp.where(ins['Condition'][0], ins['X'][0],
                              ins['Y'][0])]}


@register('where_index', no_grad_out_slots=('Out',))
def where_index(ctx, ins, attrs):
    """Reference operators/where_index_op.cc: indices of nonzero
    elements, [k, rank] int64.  The true op has a data-dependent output
    shape, which XLA cannot compile; the TPU-native variant is
    CAPACITY-PADDED: attrs['capacity'] bounds k, rows beyond the real
    count are filled with -1 (callers mask on `out[:, 0] >= 0`).
    Without a capacity the op raises with guidance instead of silently
    shipping a wrong shape."""
    cap = attrs.get('capacity')
    if cap is None:
        raise NotImplementedError(
            'where_index has a data-dependent output shape; on TPU '
            "pass attrs={'capacity': K} for a [K, rank] result padded "
            'with -1 rows (mask on out[:, 0] >= 0), or use masking')
    cond = ins['Condition'][0]
    idx = jnp.nonzero(cond != 0, size=int(cap), fill_value=-1)
    return {'Out': [jnp.stack([i.astype(jnp.int64) for i in idx],
                              axis=1)]}


@register('diag')
def diag_op(ctx, ins, attrs):
    """Reference operators/diag_op.cc: 1-D diagonal -> square matrix.
    Differentiable for float diagonals (the grad reads the diagonal
    back out); int diagonals produce no grad via the dtype rule."""
    return {'Out': [jnp.diag(ins['Diagonal'][0])]}


@register('flip')
def flip(ctx, ins, attrs):
    return {'Out': [jnp.flip(_x(ins), attrs['axis'])]}


@register('roll')
def roll(ctx, ins, attrs):
    return {'Out': [jnp.roll(_x(ins), attrs['shifts'],
                             tuple(attrs['axis']) if attrs.get('axis')
                             else None)]}


@register('tril_triu')
def tril_triu(ctx, ins, attrs):
    x = _x(ins)
    diag = attrs.get('diagonal', 0)
    if attrs.get('lower', True):
        return {'Out': [jnp.tril(x, diag)]}
    return {'Out': [jnp.triu(x, diag)]}


@register('index_select')
def index_select(ctx, ins, attrs):
    return {'Out': [jnp.take(_x(ins), ins['Index'][0],
                             axis=attrs.get('dim', 0))]}


@register('uniform_random_batch_size_like')
def uniform_random_batch_size_like(ctx, ins, attrs):
    from ..fluid import core
    ref = ins['Input'][0]
    shape = list(attrs['shape'])
    shape[attrs.get('output_dim_idx', 0)] = ref.shape[
        attrs.get('input_dim_idx', 0)]
    out = jax.random.uniform(ctx.rng(), tuple(shape), jnp.float32,
                             attrs.get('min', -1.0), attrs.get('max', 1.0))
    return {'Out': [out.astype(core.convert_dtype(
        attrs.get('dtype', 'float32')))]}


@register('assign_value')
def assign_value(ctx, ins, attrs):
    from ..fluid import core
    dtype = core.convert_dtype(attrs.get('dtype', 'float32'))
    vals = np.asarray(attrs['values'], dtype=dtype).reshape(
        tuple(int(s) for s in attrs['shape']))
    return {'Out': [jnp.asarray(vals)]}


# ---------------------------------------------------------------------------
# v1-style shape ops (no XShape output) + misc parity ops
# ---------------------------------------------------------------------------


@register('squeeze')
def squeeze(ctx, ins, attrs):
    """Reference operators/squeeze_op.cc (v1: no XShape output)."""
    x = _x(ins)
    axes = attrs.get('axes', [])
    if axes:
        shape = [d for i, d in enumerate(x.shape)
                 if not (d == 1 and (i in axes or i - x.ndim in axes))]
    else:
        shape = [d for d in x.shape if d != 1]
    return {'Out': [x.reshape(shape)]}


@register('flatten')
def flatten(ctx, ins, attrs):
    """Reference operators/flatten_op.cc (v1): fold dims up to `axis`."""
    x = _x(ins)
    axis = attrs.get('axis', 1)
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return {'Out': [x.reshape(lead, -1)]}


@register('reverse')
def reverse(ctx, ins, attrs):
    """Reference operators/reverse_op.cc: flip along `axis` list."""
    x = _x(ins)
    return {'Out': [jnp.flip(x, axis=tuple(attrs.get('axis', [0])))]}


@register('coalesce_tensor', no_grad_out_slots=('FusedOutput',))
def coalesce_tensor(ctx, ins, attrs):
    """Reference operators/coalesce_tensor_op.cc: fuse a list of grads
    into one contiguous buffer for a single fused collective
    (coalesce_grad_tensor_pass analog).  On XLA the flat buffer is a
    concat of the flattened inputs; outputs alias the inputs."""
    xs = ins['Input']
    flat = jnp.concatenate([v.reshape(-1) for v in xs])
    return {'Output': list(xs), 'FusedOutput': [flat]}


@register('shuffle_batch', no_grad_out_slots=('ShuffleIdx', 'SeedOut'))
def shuffle_batch(ctx, ins, attrs):
    """Reference operators/shuffle_batch_op.cc: random row permutation.
    Permutation is a pure function of (op_seed, step) via ctx.rng."""
    x = _x(ins)
    idx = jax.random.permutation(ctx.rng(), x.shape[0])
    return {'Out': [x[idx]], 'ShuffleIdx': [idx.astype(jnp.int64)],
            'SeedOut': [jnp.asarray([ctx.op_seed], jnp.int32)]}


@register('minus')
def minus(ctx, ins, attrs):
    """Reference operators/minus_op.cc."""
    return {'Out': [ins['X'][0] - ins['Y'][0]]}


# ---------------------------------------------------------------------------
# Tensor-array family (reference operators/controlflow/tensor_array_*,
# lod_tensor_to_array_op.cc, shrink_rnn_memory_op.cc).
#
# TPU-native re-design: a LoDTensorArray of T same-shaped items is a
# stacked dense tensor with leading time axis [T, ...]; reads/writes are
# lax dynamic slicing so the whole RNN unrolls inside one XLA
# computation (dynamic-length python lists cannot be traced).
# ---------------------------------------------------------------------------


@register('write_to_array')
def write_to_array(ctx, ins, attrs):
    x = _x(ins)
    i = ins['I'][0].reshape(()).astype(jnp.int32)
    arr = ins['Array'][0]
    return {'Out': [jax.lax.dynamic_update_index_in_dim(
        arr, x.astype(arr.dtype), i, 0)]}


@register('read_from_array')
def read_from_array(ctx, ins, attrs):
    arr = _x(ins)
    i = ins['I'][0].reshape(()).astype(jnp.int32)
    return {'Out': [jax.lax.dynamic_index_in_dim(arr, i, 0,
                                                 keepdims=False)]}


@register('lod_tensor_to_array')
def lod_tensor_to_array(ctx, ins, attrs):
    """[B, T, ...] batch -> time-major stack [T, B, ...] (the reference
    splits by LoD rank table; padded+mask makes it a transpose)."""
    x = _x(ins)
    return {'Out': [jnp.swapaxes(x, 0, 1)]}


@register('array_to_lod_tensor')
def array_to_lod_tensor(ctx, ins, attrs):
    x = _x(ins)
    return {'Out': [jnp.swapaxes(x, 0, 1)]}


@register('shrink_rnn_memory')
def shrink_rnn_memory(ctx, ins, attrs):
    """Reference operators/shrink_rnn_memory_op.cc keeps the first
    `rank_table[i]` rows at step I.  Dense form: zero out finished rows
    (RankTable -> per-row lengths vector)."""
    x = _x(ins)
    i = ins['I'][0].reshape(()).astype(jnp.int32)
    lengths = ins['RankTable'][0].astype(jnp.int32)
    keep = (lengths > i).astype(x.dtype)
    return {'Out': [x * keep.reshape((-1,) + (1,) * (x.ndim - 1))]}


@register('split_lod_tensor')
def split_lod_tensor(ctx, ins, attrs):
    """Dense form of operators/controlflow/split_lod_tensor_op.cc: both
    branches get the full tensor with non-selected rows zeroed."""
    x = _x(ins)
    m = ins['Mask'][0].reshape((-1,) + (1,) * (x.ndim - 1))
    m = m.astype(x.dtype)
    return {'OutTrue': [x * m], 'OutFalse': [x * (1 - m)]}


@register('merge_lod_tensor')
def merge_lod_tensor(ctx, ins, attrs):
    x_t = ins['InTrue'][0]
    x_f = ins['InFalse'][0]
    m = ins['Mask'][0].reshape((-1,) + (1,) * (x_t.ndim - 1))
    return {'Out': [jnp.where(m.astype(bool), x_t, x_f)]}


@register('select_input')
def select_input(ctx, ins, attrs):
    """Reference operators/controlflow/select_input_op.cc: Out = X[mask].
    Dense: stack the candidates and index with the traced scalar."""
    xs = jnp.stack(ins['X'])
    m = ins['Mask'][0].reshape(()).astype(jnp.int32)
    return {'Out': [jax.lax.dynamic_index_in_dim(xs, m, 0,
                                                 keepdims=False)]}


@register('select_output')
def select_output(ctx, ins, attrs):
    """Route X to branch `mask`; unselected branches read zeros."""
    x = _x(ins)
    m = ins['Mask'][0].reshape(()).astype(jnp.int32)
    n = attrs.get('branches', 2)
    return {'Out': [jnp.where(m == k, x, jnp.zeros_like(x))
                    for k in range(n)]}


@register('split_byref')
def split_byref(ctx, ins, attrs):
    """Reference operators/split_byref_op.cc — same math as split, the
    by-ref aliasing is meaningless under XLA's value semantics."""
    from .tensor_ops import split as _split
    return _split(ctx, ins, attrs)


@register('while')
def while_op(ctx, ins, attrs):
    """Control-flow marker: lowered by the executor itself
    (fluid/executor.py _lower_while -> lax.while_loop); the registry
    entry exists for dispatch/coverage, never invoked directly."""
    raise RuntimeError('while op is lowered by the executor, not the '
                       'registry; a bare registry call is a bug')


@register('conditional_block')
def conditional_block_op(ctx, ins, attrs):
    """Control-flow marker (executor _lower_conditional_block ->
    lax.cond); see while_op."""
    raise RuntimeError('conditional_block is lowered by the executor, '
                       'not the registry; a bare registry call is a bug')
