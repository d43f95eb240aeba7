"""Attention over a handful of keys: two Mosaic calls, no [T, T] scores
in HBM, no copy of K and V over the query group.

The third arm of ``flash_attention.flash_attention()``, beside the
flash kernels and the dense chain: a call whose WHOLE key length is
``flash_attention.SMALL_KEYS`` positions or fewer, with no mask, key
bias or dropout (block diffusion's own-block part: 4 queries over 4
keys, the blocks folded into the batch, N = B L / 4 of them).  At such
a shape the dense chain repeats K and V over the query group in HBM,
runs N x H products of [T, D] x [D, T], each padded onto an MXU tile,
holds a softmax over T of 128 lanes and builds [N, H, T, T] scores in
HBM: 0.97 ms a pass at the SDAR cell's shape where q, k, v and o are
0.09 ms of bytes (PERF.md section 6, PR 64).

LAYOUT.  The calls read q [H, N T, D], k [G, N T, D], v [G, N T, Dv]
and write o [H, N T, Dv]: heads first, the layout of the flash kernels'
own [B H, T, D] operands.  ``attention`` transposes the op's [N, T, H,
D] on the way in and out, and the compiler lays the producer's output
out that way instead of copying where it can: in a block-diffusion
layer the rotary fusion writes q ONCE, heads first, for the strict
block-mask call and for this one, and ``attention_merge`` reads both
results as they lie (described-chip compile of the SDAR cell's step:
no ``copy`` or ``transpose`` makes an operand of the 13 calls; reading
the op's [N T, H, D] rows as they are logically, a first form of these
kernels got a 33.6 MB re-laid copy of q and of the cotangent a call,
PERF.md section 6, PR 64).  A grid step takes 128 / T blocks: 128
(block, position) rows of every head and the same 128 key rows.  For
each of the G K/V heads, the H / G query heads that share it are one
[128 H / G, D] operand, rows (head, row): whole tiles of a [H / G, 128,
D] block, merged without a move.  ONE MXU product against the step's
128 key rows gives the scores transposed, [128 keys, 128 H / G
queries]; a query sees the T keys of its own block and the other 128 -
T rows of its column are masked.  The softmax then reduces over
SUBLANES (vector operations between registers, not a lane reduction a
row), its statistics are lane-dense [1, queries] rows, and p v is a
second product.  The MXU does 128 / T times the pairs the mask lets
through; at these sizes that is microseconds, and a call is bound by
reading q and writing o once.

THE BACKWARD is one call of the same tiling: the scores and p again
from the kept log-sum-exp, dv = p do, dp = v do^T, delta = sum(p dp)
over the keys (= rowsum(do o): o is not a residual), ds = p (dp - delta
+ the log-sum-exp's cotangent), dq = ds^T k, dk = ds q; the sums of dk
and dv over a query group happen inside the products.

PRECISION is the flash kernels' (and the dense chain's): operands
multiply in their own dtype with float32 accumulation (float32 ones at
full precision), the softmax is float32, p and ds are rounded to the
operands' dtype before their products.

``checks`` are the gates ``common.decide()`` asks before the
platform's; a call that fails one goes the way it went before these
kernels (``flash_attention()``'s dispatch as it stands: the dense chain
below ``FLASH_MIN_SEQ``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common
from .flash_attention import _dot

LANES = 128         # (block, position) rows and key rows a grid step
_F32 = jnp.float32


def _group(ref, g, r):
    """ref [H, rows, W] -> K/V head g's query heads as [r * rows, W],
    row-major (head, row): whole tiles, nothing moves."""
    part = ref[g * r:(g + 1) * r]
    return part.reshape(r * part.shape[1], part.shape[2])


def _own_block(rows, r, t):
    """[rows keys, r * rows queries] booleans: the key of a query's own
    block of ``t`` positions; the query rows lie (head, row)."""
    shape = (rows, r * rows)
    key = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1) & (rows - 1)
    return key // t == row // t


def _scores(q, k, own, scale):
    """q [r * rows, D], k [rows, D] -> the scores TRANSPOSED, [rows
    keys, r * rows] float32, -inf outside a query's own block."""
    return jnp.where(own, _dot(k, q, (1, 1)) * scale, -jnp.inf)


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, t, scale):
    groups = k_ref.shape[0]
    r = q_ref.shape[0] // groups
    own = _own_block(LANES, r, t)
    for g in range(groups):
        s = _scores(_group(q_ref, g, r), k_ref[g], own, scale)
        m = jnp.max(s, axis=0, keepdims=True)           # [1, r * rows]
        e = jnp.exp(s - m)
        total = jnp.sum(e, axis=0, keepdims=True)
        o = _dot((e / total).astype(v_ref.dtype), v_ref[g], (0, 0))
        o_ref[g * r:(g + 1) * r] = o.reshape(r, LANES, -1).astype(
            o_ref.dtype)
        lse_ref[g] = m + jnp.log(total)


def _backward_kernel(q_ref, k_ref, v_ref, lse_ref, do_ref, glse_ref,
                     dq_ref, dk_ref, dv_ref, *, t, scale):
    groups = k_ref.shape[0]
    r = q_ref.shape[0] // groups
    dtype = q_ref.dtype
    own = _own_block(LANES, r, t)
    for g in range(groups):
        q, do = _group(q_ref, g, r), _group(do_ref, g, r)
        k, v = k_ref[g], v_ref[g]
        p = jnp.exp(_scores(q, k, own, scale) - lse_ref[g])
        dp = _dot(v, do, (1, 1))                        # [keys, r * rows]
        delta = jnp.sum(p * dp, axis=0, keepdims=True)
        ds = (p * (dp - delta + glse_ref[g]) * scale).astype(dtype)
        dv_ref[g] = _dot(p.astype(dtype), do, (1, 0)).astype(dv_ref.dtype)
        dk_ref[g] = _dot(ds, q, (1, 0)).astype(dk_ref.dtype)
        dq_ref[g * r:(g + 1) * r] = _dot(ds, k, (0, 0)).reshape(
            r, LANES, -1).astype(dq_ref.dtype)


def _vmem_count(h, groups, d, dv, itemsize):
    """Bytes a backward instance holds, counted generously: both
    buffers of every block (q, do, dq and k, v, dk, dv), and for one
    K/V head a float32 copy of its three wide operands and a dozen
    [128, 128 H / G] float32 tiles."""
    wide, narrow = LANES * h * max(d, dv), LANES * groups * max(d, dv)
    return 2 * (3 * wide + 4 * narrow) * itemsize + \
        (3 * wide * 4 + 12 * LANES * LANES * h * 4) // groups


def checks(q, k, v):
    """small-keys attention's gates for common.decide(): T a divisor
    of the 128 rows of a grid step and the batch whole grid steps of
    128 / T blocks, widths in whole lanes, float32 or bfloat16
    throughout, and what an instance holds under the most a call may
    ask Mosaic for."""
    n, t, h, d = q.shape
    groups, dv = k.shape[2], v.shape[3]
    return (
        ('layout', LANES % t == 0 and n % (LANES // t) == 0
         and d % LANES == 0 and dv % LANES == 0),
        ('dtype', q.dtype == k.dtype == v.dtype
         and q.dtype in (jnp.bfloat16, jnp.float32)),
        ('vmem_over_budget', _common.one_pass_backward_limit(_vmem_count(
            h, groups, d, dv, q.dtype.itemsize))[0]))


def _call(kernel, t, q, k, v, more, outs, interpret, name):
    """One call over grid steps of 128 rows: q [H, N T, D], k and v [G,
    N T, .] then ``more`` in; ``outs`` and ``more`` are (array or its
    shape struct, 'rows' | 'stat') pairs, 'rows' [heads, N T, W] and
    'stat' [G, 1, N T H / G] float32 ordered (grid step, head, row)."""
    h, m, d = q.shape
    groups = k.shape[0]
    specs = {
        'rows': lambda x: pl.BlockSpec((x.shape[0], LANES, x.shape[2]),
                                       lambda i: (0, i, 0)),
        'stat': lambda x: pl.BlockSpec((groups, 1, LANES * h // groups),
                                       lambda i: (0, 0, i))}
    ins = [(q, 'rows'), (k, 'rows'), (v, 'rows')] + list(more)
    limit = _common.one_pass_backward_limit(_vmem_count(
        h, groups, d, v.shape[2], q.dtype.itemsize))[1]
    return pl.pallas_call(
        functools.partial(kernel, t=t, scale=d ** -0.5),
        grid=(m // LANES,),
        in_specs=[specs[kind](x) for x, kind in ins],
        out_specs=[specs[kind](x) for x, kind in outs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x, _ in outs],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            **({'vmem_limit_bytes': limit} if limit else {})),
        name=name)(*(x for x, _ in ins))


def _forward(q, k, v, t, interpret):
    h, m, _ = q.shape
    return _call(
        _forward_kernel, t, q, k, v, [],
        [(jax.ShapeDtypeStruct((h, m, v.shape[2]), q.dtype), 'rows'),
         (jax.ShapeDtypeStruct((k.shape[0], 1, m * h // k.shape[0]),
                               _F32), 'stat')],
        interpret, 'small_keys_forward')


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, t, interpret):
    """Heads first: q [H, N T, D], k [G, N T, D], v [G, N T, Dv] ->
    (o [H, N T, Dv], the log-sum-exps as the calls hold them)."""
    return _forward(q, k, v, t, interpret)


def _attend_fwd(q, k, v, t, interpret):
    o, lse = _forward(q, k, v, t, interpret)
    return (o, lse), (q, k, v, lse)


def _attend_bwd(t, interpret, res, cot):
    q, k, v, lse = res
    do, glse = cot
    return tuple(_call(
        _backward_kernel, t, q, k, v,
        [(lse, 'stat'), (do, 'rows'), (glse, 'stat')],
        [(q, 'rows'), (k, 'rows'), (v, 'rows')],
        interpret, 'small_keys_backward'))


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention(q, k, v, with_lse=False, interpret=False):
    """q [N, T, H, D], k [N, T, G, D], v [N, T, G, Dv], G a divisor of
    H (query head i attends K/V head i // (H / G)), every query sees
    every key -> [N, T, H, Dv]; ``with_lse`` also the rows'
    log-sum-exp [N, H, T] (float32), differentiable, as
    flash_attention()'s contract says.  The caller has asked
    ``checks``."""
    n, t, h, _ = q.shape

    def heads_first(x):
        return jnp.transpose(x.reshape(n * t, x.shape[2], x.shape[3]),
                             (1, 0, 2))

    o, lse = _attend(heads_first(q), heads_first(k), heads_first(v), t,
                     interpret)
    o = jnp.transpose(o, (1, 0, 2)).reshape(n, t, h, -1)
    if not with_lse:
        return o
    groups = k.shape[2]
    lse = lse.reshape(groups, n * t // LANES, h // groups, LANES)
    return o, jnp.transpose(lse, (1, 3, 0, 2)).reshape(
        n, t, h).transpose(0, 2, 1)
