"""Dense math, elementwise (+broadcast), reduction, comparison lowerings.

Reference kernels: paddle/fluid/operators/{matmul,mul,scale,sum,clip}_op.*,
operators/elementwise/ (6.2k LoC CUDA broadcast machinery — here jnp
broadcasting + one reshape helper), operators/reduce_ops/.

All matmuls flow to the MXU through jnp.matmul/lax.dot_general with
float32 accumulation; gradients via jax.vjp (registry.grad_op_def).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register


def _x(ins, slot='X'):
    return ins[slot][0]


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------


@register('matmul')
def matmul(ctx, ins, attrs):
    x, y = ins['X'][0], ins['Y'][0]
    tx = attrs.get('transpose_X', False)
    ty = attrs.get('transpose_Y', False)
    if tx:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ty:
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    if attrs.get('__amp__') and x.dtype in (jnp.float32, jnp.bfloat16):
        # AMP: bf16 matmul (f32 MXU accumulation internally); the bf16
        # output propagates so downstream activations stay bf16 in HBM
        out = jnp.matmul(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16))
    else:
        out = jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST
                         if x.dtype == jnp.float32 else None)
    alpha = attrs.get('alpha', 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {'Out': [out]}


@register('matmul_v2')
def matmul_v2(ctx, ins, attrs):
    a = dict(attrs)
    a['transpose_X'] = attrs.get('trans_x', False)
    a['transpose_Y'] = attrs.get('trans_y', False)
    return matmul(ctx, ins, a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dot_float32_out(x, y, dims):
    """bfloat16 operands, the MXU's float32 accumulator as the result
    (``mixed_precision.float32_output``).  Backward as a bfloat16
    product's: the cotangent rounded to bfloat16 first, so both
    gradient products have bfloat16 operands like every AMP `mul`'s."""
    return jax.lax.dot_general(x, y, dims,
                               preferred_element_type=jnp.float32)


def _dot_float32_out_fwd(x, y, dims):
    return _dot_float32_out(x, y, dims), (x, y)


def _dot_float32_out_bwd(dims, operands, cotangent):
    _, vjp = jax.vjp(lambda a, b: jax.lax.dot_general(a, b, dims),
                     *operands)
    return vjp(cotangent.astype(jnp.bfloat16))


_dot_float32_out.defvjp(_dot_float32_out_fwd, _dot_float32_out_bwd)


@register('mul')
def mul(ctx, ins, attrs):
    """Reference operators/mul_op.cc: x flattened to 2-D by
    x_num_col_dims times y flattened by y_num_col_dims.

    Lowered WITHOUT flattening x: the dot contracts x's trailing dims
    against the (small) weight unfolded to match them.  The
    reshape-to-2D form pins the activation — and, worse, its backward
    COTANGENT — to the flattened matmul layout, which XLA satisfies
    with a full layout-change copy whenever the producer prefers a
    different tiling (measured ~1 GB/step on BERT's [B,T,V] MLM head);
    the multi-dim contraction lets the dW gradient consume the
    cotangent in whatever layout its producer chose."""
    x, y = ins['X'][0], ins['Y'][0]
    xn = attrs.get('x_num_col_dims', 1)
    yn = attrs.get('y_num_col_dims', 1)
    xs, ys = x.shape, y.shape
    tail = tuple(xs[xn:])
    y3 = y.reshape(tail + (int(np.prod(ys[yn:])),))
    dims = ((tuple(range(xn, len(xs))), tuple(range(len(tail)))),
            ((), ()))
    if attrs.get('__amp__') and x.dtype in (jnp.float32, jnp.bfloat16):
        dot = _dot_float32_out if attrs.get('__amp_float32_out__') \
            else jax.lax.dot_general
        out = dot(x.astype(jnp.bfloat16), y3.astype(jnp.bfloat16), dims)
    else:
        if x.dtype != y3.dtype:
            # dot_general rejects mixed operand dtypes; preserve jnp
            # promotion semantics for e.g. a bf16 activation times an
            # f32 weight with AMP off (ADVICE r4)
            ct = jnp.promote_types(x.dtype, y3.dtype)
            x, y3 = x.astype(ct), y3.astype(ct)
        out = jax.lax.dot_general(
            x, y3, dims, precision=jax.lax.Precision.HIGHEST
            if x.dtype == jnp.float32 else None)
    return {'Out': [out.reshape(tuple(xs[:xn]) + tuple(ys[yn:]))]}


@register('bmm')
def bmm(ctx, ins, attrs):
    return {'Out': [jnp.matmul(ins['X'][0], ins['Y'][0])]}


@register('dot')
def dot(ctx, ins, attrs):
    x, y = ins['X'][0], ins['Y'][0]
    return {'Out': [jnp.sum(x * y, axis=-1, keepdims=x.ndim == 1)]}


@register('scale')
def scale(ctx, ins, attrs):
    x = _x(ins)
    s = attrs.get('scale', 1.0)
    b = attrs.get('bias', 0.0)
    if attrs.get('bias_after_scale', True):
        return {'Out': [x * s + b]}
    return {'Out': [(x + b) * s]}


@register('sum')
def sum_op(ctx, ins, attrs):
    """Add N tensors (gradient aggregation). Reference operators/sum_op."""
    xs = ins['X']
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {'Out': [out]}


@register('clip')
def clip(ctx, ins, attrs):
    return {'Out': [jnp.clip(_x(ins), attrs.get('min'), attrs.get('max'))]}


@register('clip_by_norm')
def clip_by_norm(ctx, ins, attrs):
    x = _x(ins)
    max_norm = attrs['max_norm']
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.minimum(max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {'Out': [x * scale]}


@register('isfinite', no_grad_out_slots=('Out',))
def isfinite(ctx, ins, attrs):
    """Reference operators/isfinite_op.cc: all-finite reduction over inputs."""
    ok = jnp.array(True)
    for x in ins['X']:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(x)))
    return {'Out': [ok]}


@register('isinf', no_grad_out_slots=('Out',))
def isinf(ctx, ins, attrs):
    any_inf = jnp.array(False)
    for x in ins['X']:
        any_inf = jnp.logical_or(any_inf, jnp.any(jnp.isinf(x)))
    return {'Out': [any_inf]}


@register('isnan', no_grad_out_slots=('Out',))
def isnan(ctx, ins, attrs):
    any_nan = jnp.array(False)
    for x in ins['X']:
        any_nan = jnp.logical_or(any_nan, jnp.any(jnp.isnan(x)))
    return {'Out': [any_nan]}


@register('squared_l2_norm')
def squared_l2_norm(ctx, ins, attrs):
    x = _x(ins)
    return {'Out': [jnp.sum(x.astype(jnp.float32) ** 2).reshape(1)]}


@register('p_norm')
def p_norm(ctx, ins, attrs):
    x = _x(ins)
    p = attrs.get('porder', 2.0)
    axis = attrs.get('axis', -1)
    keep = attrs.get('keepdim', False)
    out = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keep) ** (1.0 / p)
    return {'Out': [out]}


# ---------------------------------------------------------------------------
# elementwise binary with paddle axis-broadcast semantics
# ---------------------------------------------------------------------------


def _bcast(x, y, axis):
    """Reference broadcast rule (operators/elementwise/elementwise_op.h):
    y's dims align to x starting at `axis` (default: trailing)."""
    if x.shape == y.shape:
        return x, y
    if y.ndim > x.ndim:
        y2, x2 = _bcast(y, x, axis)
        return x2, y2
    if axis is None or axis == -1:
        return x, y  # numpy trailing broadcast
    yshape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return x, y.reshape(yshape)


def _ew(name, fn):
    @register(name)
    def op(ctx, ins, attrs, _fn=fn):
        x, y = _bcast(ins['X'][0], ins['Y'][0], attrs.get('axis', -1))
        return {'Out': [_fn(x, y)]}
    return op


_ew('elementwise_add', lambda x, y: x + y)
_ew('elementwise_sub', lambda x, y: x - y)
_ew('elementwise_mul', lambda x, y: x * y)
_ew('elementwise_div', lambda x, y: x / y)
_ew('elementwise_min', jnp.minimum)
_ew('elementwise_max', jnp.maximum)
_ew('elementwise_pow', jnp.power)
_ew('elementwise_mod', jnp.mod)
_ew('elementwise_floordiv', jnp.floor_divide)


# comparisons (outputs bool, no grad)
def _cmp(name, fn):
    @register(name, no_grad_out_slots=('Out',))
    def op(ctx, ins, attrs, _fn=fn):
        x, y = _bcast(ins['X'][0], ins['Y'][0], attrs.get('axis', -1))
        return {'Out': [_fn(x, y)]}
    return op


_cmp('equal', lambda x, y: x == y)
_cmp('not_equal', lambda x, y: x != y)
_cmp('less_than', lambda x, y: x < y)
_cmp('less_equal', lambda x, y: x <= y)
_cmp('greater_than', lambda x, y: x > y)
_cmp('greater_equal', lambda x, y: x >= y)


def _logical(name, fn, unary=False):
    @register(name, no_grad_out_slots=('Out',))
    def op(ctx, ins, attrs, _fn=fn, _u=unary):
        if _u:
            return {'Out': [_fn(ins['X'][0])]}
        return {'Out': [_fn(ins['X'][0], ins['Y'][0])]}
    return op


_logical('logical_and', jnp.logical_and)
_logical('logical_or', jnp.logical_or)
_logical('logical_xor', jnp.logical_xor)
_logical('logical_not', jnp.logical_not, unary=True)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def _reduce(name, fn, int_out=False):
    @register(name, no_grad_out_slots=('Out',) if int_out else ())
    def op(ctx, ins, attrs, _fn=fn):
        x = _x(ins)
        if attrs.get('reduce_all', False):
            axis = None
        else:
            axis = attrs.get('dim', [0])
            axis = tuple(a if a >= 0 else a + x.ndim for a in axis)
        keep = attrs.get('keep_dim', False)
        return {'Out': [_fn(x, axis=axis, keepdims=keep)]}
    return op


_reduce('reduce_sum', jnp.sum)
_reduce('reduce_mean', jnp.mean)
_reduce('reduce_max', jnp.max)
_reduce('reduce_min', jnp.min)
_reduce('reduce_prod', jnp.prod)
_reduce('reduce_all', jnp.all, int_out=True)
_reduce('reduce_any', jnp.any, int_out=True)


@register('mean')
def mean(ctx, ins, attrs):
    return {'Out': [jnp.mean(_x(ins))]}


@register('arg_max', no_grad_out_slots=('Out',))
def arg_max(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', -1)
    out = jnp.argmax(x, axis=axis).astype(jnp.int64)
    if attrs.get('keepdims', False):
        out = jnp.expand_dims(out, axis)
    return {'Out': [out]}


@register('arg_min', no_grad_out_slots=('Out',))
def arg_min(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', -1)
    return {'Out': [jnp.argmin(x, axis=axis).astype(jnp.int64)]}


@register('top_k', no_grad_out_slots=('Indices',))
def top_k(ctx, ins, attrs):
    x = _x(ins)
    k = attrs.get('k', 1)
    vals, idx = jax.lax.top_k(x, k)
    return {'Out': [vals], 'Indices': [idx.astype(jnp.int64)]}


@register('top_k_v2', no_grad_out_slots=('Indices',))
def top_k_v2(ctx, ins, attrs):
    return top_k(ctx, ins, attrs)


@register('argsort', no_grad_out_slots=('Indices',))
def argsort(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', -1)
    desc = attrs.get('descending', False)
    idx = jnp.argsort(-x if desc else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {'Out': [out], 'Indices': [idx.astype(jnp.int64)]}


# ---------------------------------------------------------------------------
# linalg extras
# ---------------------------------------------------------------------------


@register('norm')
def norm(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get('axis', -1)
    eps = attrs.get('epsilon', 1e-10)
    n = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {'Out': [x / n], 'Norm': [n]}


@register('cholesky')
def cholesky(ctx, ins, attrs):
    return {'Out': [jnp.linalg.cholesky(_x(ins))]}


@register('inverse')
def inverse(ctx, ins, attrs):
    return {'Output': [jnp.linalg.inv(ins['Input'][0])]}
