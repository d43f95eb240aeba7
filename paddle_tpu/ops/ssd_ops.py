"""Mamba-2's recurrence in its state-space-dual form (SSD, Dao & Gu,
arXiv:2405.21060: the mixer of Nemotron-H's ``M`` layers), forward and
backward, as matrix products over chunks of tokens.

Per sequence and head h of P channels, with a state ``S`` [P, N] that
is zero at the sequence's start (N states; the write and read vectors
``B``, ``C`` [N] are shared by the heads of a GROUP, g(h) = h // (H /
G)):

    a_t = A_h delta_t,h                         one SCALAR a head and token, <= 0
    S_t = exp(a_t) S_(t-1) + delta_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

It is NOT ``selective_scan`` (``ssm_ops.py``) at other numbers: there
the decay is per channel AND state (no matmul form, 16 states a channel
in registers); here it is one scalar a head, so everything between two
tokens of a head is a number and a chunk of Q tokens is three matrix
products.  With ``L_t`` the running sum of ``a`` inside a chunk
(inclusive) and ``S_(c-1)`` the state at the chunk's start:

    Y_intra[t] = sum_(s<=t) exp(L_t - L_s) (C_t . B_s) delta_s x_s
    Y_inter[t] = exp(L_t) S_(c-1) C_t
    S_c = exp(L_Q) S_(c-1) + sum_s exp(L_Q - L_s) delta_s x_s B_s^T

Every chunk's products run at once (``_read``: the [Q, Q] scores, the
read of the start state; ``_local``: what the chunk writes); only the
last line walks the chunks, an elementwise ``lax.scan`` over [H, P, N].
Exponents are only ever of differences <= 0 (later minus earlier sums
of a <= 0), so nothing overflows whatever the steps.  delta, the
decays, the state and every sum are float32 whatever x, B, C arrive in
(float64 under x64); the products multiply operands in x's dtype and
accumulate in float32 (float32 operands at full precision); the output
is in x's dtype (the ``rms_norm`` / ``short_conv`` policy).

The backward is a ``custom_vjp`` of the whole op: it keeps what the op
was handed and the state at each chunk's START (T / Q x [H, P, N] a
sequence), computes the inside of every chunk again under ``jax.vjp``
(``_read`` with dy, then ``_local`` with what the reverse walk over the
chunks hands it), and no [T, H, P, N] array exists on either pass.  T
need be no whole number of chunks: the tail is padded with tokens of
step 0, which neither decay nor write.

WHICH PATH RUNS WHERE.  The functions of this module are the DENSE
path and the definition: every chunk's [Q, Q] scores and decay weights
at once, as XLA lowers them.  It is what runs off a TPU (every tier-1
test but the forced ones), in float64, under the GSPMD runner (XLA
partitions no Mosaic call), and for operands the kernels' layout does
not hold: a chunk or N off whole 128-lane tiles, a head's P off
whole 16-row tiles, a tail that fills no chunk.  On a
TPU everything else (the model's bfloat16 step, its float32 ``for_test``
program and gradient checks) runs the ``ssd_scan`` kernels
(``ops/pallas/ssd_scan.py``): one Mosaic call forward and one backward,
a chunk's scores and weights in VMEM and a group's state on the core
from a sequence's first chunk to its last.  ``_scan_path`` asks
``common.dispatch`` ONCE a call (counters
``pallas/ssd_scan/dispatch_{fused,dense}``, ``fallback/<reason>``) and
the forward and the backward of that call follow the one answer; the
state at each chunk's start is the residual on both paths (the same
bytes, in the path's own order), and ``ssd/chunks`` /
``ssd/boundary_state_mb`` read the same on both.
"""

import functools

import jax
import jax.numpy as jnp

from . import registry
from .registry import register

CHUNK = 128


def _working_dtype(x):
    return jnp.float64 if x.dtype == jnp.float64 else jnp.float32


def _product(spec, a, b, like):
    """einsum of operands in ``like``'s dtype, accumulated in the
    working dtype; float32 operands at full precision."""
    f = _working_dtype(like)
    precision = jax.lax.Precision.HIGHEST \
        if like.dtype == jnp.float32 else None
    return jnp.einsum(spec, a.astype(like.dtype), b.astype(like.dtype),
                      precision=precision, preferred_element_type=f)


def _layout(t, chunk):
    """-> (chunk size as run, chunks)."""
    chunk = max(1, min(int(chunk), t))
    return chunk, -(-t // chunk)


def _chunked(v, size, n, heads):
    """[B, T, H or G, ...] -> [B, n, size, *heads, ...] (``heads``: (G,
    R) for what comes a head, (G,) for what comes a group), the tail
    padded with zeros."""
    b, t = v.shape[:2]
    v = jnp.pad(v, ((0, 0), (0, n * size - t)) + ((0, 0),) * (v.ndim - 2))
    return v.reshape((b, n, size) + heads + v.shape[3:])


def _sums(delta, a):
    """delta [B, n, Q, G, R], a [G, R] -> the running sum L of a *
    delta inside each chunk, inclusive."""
    return jnp.cumsum(delta * a, axis=2)


def _local(x, delta, a, bm):
    """What each chunk writes, all chunks at once -> (z [B, n, G, R,
    P, N]: sum_s exp(L_Q - L_s) delta_s x_s B_s^T; g [B, n, G, R]:
    exp(L_Q), what the chunk leaves of the state it started from)."""
    f = _working_dtype(x)
    sums = _sums(delta, a)
    last = sums[:, :, -1]
    to_end = jnp.exp(last[:, :, None] - sums) * delta   # [B, n, Q, G, R]
    written = x.astype(f) * to_end[..., None]
    return _product('bnsgrp,bnsgk->bngrpk', written, bm, x), jnp.exp(last)


def _read(x, delta, a, bm, cm, dskip, starts):
    """y [B, n, Q, G, R, P] in the working dtype, all chunks at once,
    from the state at each chunk's start [B, n, G, R, P, N]."""
    f = _working_dtype(x)
    sums = _sums(delta, a)
    q = x.shape[2]
    scores = _product('bnqgk,bnsgk->bngqs', cm, bm, x)
    # exp(L_t - L_s) where s <= t, 0 elsewhere: [B, n, G, R, Q, S]
    t_major = jnp.moveaxis(sums, 2, -1)
    gap = t_major[..., :, None] - t_major[..., None, :]
    seen = jnp.tril(jnp.ones((q, q), bool))
    weights = scores[:, :, :, None] * jnp.exp(jnp.where(seen, gap, -jnp.inf))
    written = x.astype(f) * delta[..., None]
    intra = _product('bngrqs,bnsgrp->bnqgrp', weights, written, x)
    inter = _product('bnqgk,bngrpk->bnqgrp', cm, starts, x) * \
        jnp.exp(sums)[..., None]
    return intra + inter + dskip[:, :, None] * x.astype(f)


def _walk(z, g):
    """The state at each chunk's START [B, n, G, R, P, N], zero at the
    first: the one sequential pass, elementwise over the chunks."""
    registry.trace_sum('ssd/chunks', z.shape[1])

    def step(state, item):
        z_c, g_c = item
        return g_c[..., None, None] * state + z_c, state

    _, starts = jax.lax.scan(step, jnp.zeros_like(z[:, 0]),
                             (jnp.moveaxis(z, 1, 0), jnp.moveaxis(g, 1, 0)))
    return jnp.moveaxis(starts, 0, 1)


def _walk_back(d_starts, starts, g):
    """The reverse of ``_walk``: the cotangent of each chunk's START
    state, as its own chunk's read gave it -> (d z, d g)."""
    registry.trace_sum('ssd/chunks', g.shape[1])

    def step(after, item):
        direct, start, g_c = item
        # ``after``: the cotangent of the state this chunk leaves
        return direct + g_c[..., None, None] * after, \
            (after, jnp.sum(after * start, axis=(-2, -1)))

    _, (d_z, d_g) = jax.lax.scan(
        step, jnp.zeros_like(starts[:, 0]),
        tuple(jnp.moveaxis(v, 1, 0) for v in (d_starts, starts, g)),
        reverse=True)
    return jnp.moveaxis(d_z, 0, 1), jnp.moveaxis(d_g, 0, 1)


def _operands(x, delta, a, bm, cm, dskip, chunk):
    """-> the six in chunks of the working layout: heads as [G, R]."""
    f = _working_dtype(x)
    groups = bm.shape[2]
    if x.shape[2] % groups:
        raise ValueError('ssd_scan: %d heads are no whole number of %d '
                         'groups' % (x.shape[2], groups))
    size, n = _layout(x.shape[1], chunk)
    by_group = (groups, x.shape[2] // groups)
    return (_chunked(x, size, n, by_group),
            _chunked(delta.astype(f), size, n, by_group),
            a.astype(f).reshape(by_group),
            _chunked(bm, size, n, (groups,)),
            _chunked(cm, size, n, (groups,)),
            dskip.astype(f).reshape(by_group))


def _unchunked(v, like):
    """[B, n, Q, G, ...] -> ``like``'s [B, T, ...]."""
    b, t = like.shape[:2]
    return v.reshape((b, -1) + v.shape[3:])[:, :t].reshape(like.shape)


def _scan_path(x, bm, chunk, auto_partitioned):
    """How this call runs its chunks: 'dense' (the functions above, the
    definition), 'fused' (the ``ssd_scan`` kernels) or 'interpret'
    (their bodies under the Pallas interpreter: FLAGS_pallas_force off
    a TPU).  One ``common.dispatch`` decision a call, which its forward
    and its backward both follow, from what the operands show
    (``ssd_scan.checks``)."""
    from .pallas import common, ssd_scan as kernels
    fused, interpret = common.dispatch(
        'ssd_scan', True,
        checks=kernels.checks(x.shape, bm.shape[2], bm.shape[3], chunk,
                              _working_dtype(x), x.dtype.itemsize),
        auto_partitioned=auto_partitioned)
    return ('interpret' if interpret else 'fused') if fused else 'dense'


def _fused(x, chunk, path):
    """-> (the kernels' module, the keywords of its calls: the chunk as
    they run it, and whether under the interpreter); a pass of theirs
    counts its trips over chunks as a dense walk does."""
    from .pallas import ssd_scan as kernels
    size, n = _layout(x.shape[1], chunk)
    registry.trace_sum('ssd/chunks', n)
    return kernels, dict(size=size, interpret=path == 'interpret')


def ssd_scan(x, delta, a, bm, cm, dskip, chunk=CHUNK,
             auto_partitioned=False):
    """x [B, T, H, P], delta [B, T, H] (> 0), a [H] (< 0), bm, cm [B, T,
    G, N] (H a whole number of G), dskip [H] -> y [B, T, H, P] in x's
    dtype.  T need be no whole number of chunks.  ``auto_partitioned``:
    ``common.dispatch``'s (the caller's word that XLA will partition
    this program over a mesh)."""
    return _scan(x, delta, a, bm, cm, dskip, chunk,
                 _scan_path(x, bm, chunk, auto_partitioned))


def _forward(x, delta, a, bm, cm, dskip, chunk):
    """-> (y, the state at each chunk's START [B, n, G, R, P, N])."""
    xc, dc, ac, bc, cc, sc = _operands(x, delta, a, bm, cm, dskip, chunk)
    starts = _walk(*_local(xc, dc, ac, bc))
    y = _read(xc, dc, ac, bc, cc, sc, starts)
    return _unchunked(y, x).astype(x.dtype), starts


def _pass(x, delta, a, bm, cm, dskip, chunk, path):
    """-> (y, the state at each chunk's START, in the path's own
    order) by the path's forward."""
    if path == 'dense':
        return _forward(x, delta, a, bm, cm, dskip, chunk)
    kernels, how = _fused(x, chunk, path)
    return kernels.forward(x, delta, a, bm, cm, dskip, **how)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, delta, a, bm, cm, dskip, chunk, path):
    return _pass(x, delta, a, bm, cm, dskip, chunk, path)[0]


def _scan_fwd(x, delta, a, bm, cm, dskip, chunk, path):
    y, starts = _pass(x, delta, a, bm, cm, dskip, chunk, path)
    registry.trace_sum('ssd/boundary_state_mb',
                       starts.size * starts.dtype.itemsize / 1e6)
    return y, ((x, delta, a, bm, cm, dskip), starts)


def _scan_bwd(chunk, path, saved, d_y):
    """Every chunk's inside again.  Dense: twice under ``jax.vjp``, the
    read with y's cotangent, which also gives each start state's own;
    the chunks in reverse for the states' full cotangents; the writes
    with those.  Fused: inside one kernel call, the chunks counted
    down."""
    inputs, starts = saved
    if path != 'dense':
        kernels, how = _fused(inputs[0], chunk, path)
        return kernels.backward(*inputs, starts, d_y, **how)
    operands = _operands(*inputs, chunk)
    xc, dc, ac, bc = operands[:4]
    f = _working_dtype(inputs[0])
    size, n = _layout(inputs[0].shape[1], chunk)
    d_yc = _chunked(d_y.astype(f), size, n, xc.shape[3:5])
    _, pull_read = jax.vjp(_read, *operands, starts)
    *d_read, d_starts = pull_read(d_yc)
    (z, g), pull_local = jax.vjp(_local, xc, dc, ac, bc)
    d_local = pull_local(_walk_back(d_starts, starts, g))
    d_x, d_delta, d_a, d_b = (r + w for r, w in zip(d_read, d_local))
    d_c, d_skip = d_read[4:]
    x, delta, a, bm, cm, dskip = inputs
    return (_unchunked(d_x, x).astype(x.dtype),
            _unchunked(d_delta, delta).astype(delta.dtype),
            d_a.reshape(a.shape).astype(a.dtype),
            _unchunked(d_b, bm).astype(bm.dtype),
            _unchunked(d_c, cm).astype(cm.dtype),
            d_skip.reshape(dskip.shape).astype(dskip.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


@register('ssd_scan')
def ssd_scan_op(ctx, ins, attrs):
    """X [B, T, H, P], Delta [B, T, H], A [H], B, C [B, T, G, N], D [H]
    -> Out [B, T, H, P] in X's dtype, in chunks of attrs['chunk'] tokens
    (default ``CHUNK``): the module's docstring has the equations."""
    from ..fluid import monitor
    monitor.add('ssd/calls', 1)
    return {'Out': [ssd_scan(
        ins['X'][0], ins['Delta'][0], ins['A'][0], ins['B'][0],
        ins['C'][0], ins['D'][0], int(attrs.get('chunk', CHUNK)),
        auto_partitioned=ctx.auto_partitioned)]}
