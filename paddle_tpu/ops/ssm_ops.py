"""The selective state-space scan (Mamba, arXiv:2312.00752: the ``S6``
recurrence of the Mamba layers of Phi-4-mini-flash), forward and
backward, chunked over time.

Per sequence, with a state ``h`` [D, N] that is zero at the sequence's
start (D channels, N states a channel):

    h_t = exp(delta_t[d] A[d, n]) h_(t-1) + (delta_t[d] x_t[d]) B_t[n]
    m_t[d] = sum_n h_t[d, n] C_t[n] + Dskip[d] x_t[d]

``delta`` > 0 is the step (float32 under AMP), ``A`` < 0.  THE DECAY IS
PER CHANNEL AND STATE, D x N numbers a token, so the recurrence has no
matmul form (the delta rule's chunks, ``kda_ops.py``, are MXU work;
this is elementwise work and bytes): a token's step is a handful of
operations on [N, D] (the channels in the lanes) and one sum over N.
A ``lax.scan`` over CHUNKS of ``CHUNK`` tokens carries the state; inside
a chunk a second scan walks the tokens, ``UNROLL`` a trip, and the
state never leaves the chip's fast memory but at a chunk's boundary.
No exponent is ever a sum over tokens: each token's decay is taken of
its own ``delta_t A`` <= 0, so nothing overflows whatever the steps.

The backward is a ``custom_vjp`` of the whole op: it keeps what the op
was handed (x, delta, A, B, C, Dskip as they arrived) and the state at
each chunk's START (T / chunk x [N, D] a sequence).  The reverse walk
over the chunks runs a chunk's forward again from its start, keeping
the state BEFORE each of its tokens ([chunk, N, D], a transient of
that trip), then walks its tokens in reverse with the state's
cotangent, each token's step under a ``jax.vjp`` of its own.  No [B, T,
D, N] array exists on either pass.

float32 inside whatever arrives (float64 under x64): delta, A, the
state, every product and sum; the output in x's dtype (the
``rms_norm`` / ``short_conv`` policy).
"""

import functools

import jax
import jax.numpy as jnp

from . import registry
from .registry import register

CHUNK = 256
UNROLL = 8


def _working_dtype(x):
    return jnp.float64 if x.dtype == jnp.float64 else jnp.float32


def _token_step(a, dskip, h, token):
    """One token: a [N, D], dskip [D], the state h [B, N, D] before it,
    token = (x [B, D], delta [B, D], b [B, N], c [B, N]) as they
    arrived -> (the state after it, m [B, D] in the working dtype)."""
    x, delta, b, c = (v.astype(h.dtype) for v in token)
    h = jnp.exp(delta[:, None, :] * a) * h + \
        (delta * x)[:, None, :] * b[:, :, None]
    return h, jnp.sum(h * c[:, :, None], axis=1) + dskip * x


def _layout(t, chunk):
    """-> (chunk size as run, chunks)."""
    chunk = max(1, min(int(chunk), t))
    return chunk, -(-t // chunk)


def _chunked(x, chunk, n):
    """[B, T, ...] -> [n, chunk, B, ...], the tail padded with zeros:
    tokens of step 0, which neither decay nor write."""
    b, t = x.shape[:2]
    x = jnp.pad(x, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 0, 2)


def _unchunked(x, t):
    """[n, chunk, B, ...] -> [B, T, ...]."""
    n, chunk, b = x.shape[:3]
    return jnp.moveaxis(x, 2, 0).reshape((b, n * chunk) + x.shape[3:])[:, :t]


def _operands(x, delta, a, bm, cm, dskip, chunk):
    """-> (``chunked``: [B, T, ...] -> [n, chunk, B, ...]; the tokens in
    chunks; A^T and Dskip in the working dtype)."""
    f = _working_dtype(x)
    size, n = _layout(x.shape[1], chunk)
    # the sequential trips over chunks of this walk, forward or reverse
    registry.trace_sum('ssm/chunks', n)
    chunked = functools.partial(_chunked, chunk=size, n=n)
    return (chunked, tuple(chunked(v) for v in (x, delta, bm, cm)),
            a.astype(f).T, dskip.astype(f))


def _forward(x, delta, a, bm, cm, dskip, chunk):
    """-> (m [B, T, D] in x's dtype, the state at each chunk's START
    [n, B, N, D])."""
    _, tokens, a_t, skip = _operands(x, delta, a, bm, cm, dskip, chunk)
    step = functools.partial(_token_step, a_t, skip)

    def one_chunk(h, chunk_tokens):
        after, m = jax.lax.scan(step, h, chunk_tokens, unroll=UNROLL)
        return after, (m.astype(x.dtype), h)

    zero = jnp.zeros((x.shape[0],) + a_t.shape, a_t.dtype)
    _, (m, starts) = jax.lax.scan(one_chunk, zero, tokens)
    return _unchunked(m, x.shape[1]), starts


def _scan_path(x, a, chunk, auto_partitioned):
    """How this call walks its tokens: 'dense' (the two ``lax.scan``s
    below), 'fused' (the ``ssm_scan`` kernels) or 'interpret' (their
    bodies under the Pallas interpreter: FLAGS_pallas_force off a
    TPU).  One ``common.dispatch`` decision a call, which its forward
    and its backward both follow, from what the operands show
    (``ssm_scan.checks``)."""
    from .pallas import common, ssm_scan
    fused, interpret = common.dispatch(
        'ssm_scan', True,
        checks=ssm_scan.checks(x.shape, a.shape[-1], _working_dtype(x),
                               chunk, x.dtype.itemsize),
        auto_partitioned=auto_partitioned)
    return ('interpret' if interpret else 'fused') if fused else 'dense'


def _fused(x, chunk, path):
    """-> (the kernels' module, the keywords of its calls: the chunk
    as they run it, and whether under the interpreter); a walk of
    theirs counts its trips over chunks as a dense one does."""
    from .pallas import ssm_scan
    size, n = ssm_scan.layout(x.shape[1], chunk)
    registry.trace_sum('ssm/chunks', n)
    return ssm_scan, dict(size=size, interpret=path == 'interpret')


def selective_scan(x, delta, a, bm, cm, dskip, chunk=CHUNK,
                   auto_partitioned=False):
    """x, delta [B, T, D], a [D, N], bm, cm [B, T, N], dskip [D] -> m
    [B, T, D] in x's dtype.  T need be no whole number of chunks.
    ``auto_partitioned``: ``common.dispatch``'s (the caller's word that
    XLA will partition this program over a mesh)."""
    return _scan(x, delta, a, bm, cm, dskip, chunk,
                 _scan_path(x, a, chunk, auto_partitioned))


def _walk(x, delta, a, bm, cm, dskip, chunk, path):
    """-> (m, the state at each chunk's START [n, B, N, D]) by the
    path's forward."""
    if path == 'dense':
        return _forward(x, delta, a, bm, cm, dskip, chunk)
    kernels, how = _fused(x, chunk, path)
    return kernels.forward(x, delta, a, bm, cm, dskip, **how)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, delta, a, bm, cm, dskip, chunk, path):
    return _walk(x, delta, a, bm, cm, dskip, chunk, path)[0]


def _scan_fwd(x, delta, a, bm, cm, dskip, chunk, path):
    m, starts = _walk(x, delta, a, bm, cm, dskip, chunk, path)
    registry.trace_sum('ssm/boundary_state_mb',
                       starts.size * starts.dtype.itemsize / 1e6)
    return m, ((x, delta, a, bm, cm, dskip), starts)


def _scan_bwd(chunk, path, saved, d_m):
    """The chunks in reverse.  A trip runs its chunk's forward again
    from the kept start (``before``: the state before each token), then
    its tokens in reverse, carrying the cotangents of the state, of A
    and of Dskip: the dense path in the scans below, the fused one
    inside one kernel call."""
    inputs, starts = saved
    if path != 'dense':
        kernels, how = _fused(inputs[0], chunk, path)
        return kernels.backward(*inputs, starts, d_m, **how)
    x, a = inputs[0], inputs[2]
    chunked, tokens, a_t, skip = _operands(*inputs, chunk)

    def before_each(h, token):
        return _token_step(a_t, skip, h, token)[0], h

    def one_token(carry, item):
        d_h, d_a, d_skip = carry
        token, h, d_out = item
        _, pull = jax.vjp(_token_step, a_t, skip, h, token)
        g_a, g_skip, d_h, d_token = pull((d_h, d_out.astype(d_h.dtype)))
        return (d_h, d_a + g_a, d_skip + g_skip), d_token

    def one_chunk(carry, item):
        chunk_tokens, start, d_out = item
        _, before = jax.lax.scan(before_each, start, chunk_tokens,
                                 unroll=UNROLL)
        return jax.lax.scan(one_token, carry, (chunk_tokens, before, d_out),
                            reverse=True, unroll=UNROLL)

    zeros = (jnp.zeros_like(starts[0]), jnp.zeros_like(a_t),
             jnp.zeros_like(skip))
    (_, d_a, d_skip), d_tokens = jax.lax.scan(
        one_chunk, zeros, (tokens, starts, chunked(d_m)),
        reverse=True)
    d_x, d_delta, d_b, d_c = (_unchunked(v, x.shape[1]) for v in d_tokens)
    return (d_x, d_delta, d_a.T.astype(a.dtype), d_b, d_c,
            d_skip.astype(inputs[5].dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


@register('selective_scan')
def selective_scan_op(ctx, ins, attrs):
    """X, Delta [B, T, D], A [D, N], B, C [B, T, N], D [D] -> Out [B, T,
    D] in X's dtype, in chunks of ``CHUNK`` tokens: the module's
    docstring has the equations."""
    from ..fluid import monitor
    monitor.add('ssm/calls', 1)
    return {'Out': [selective_scan(
        ins['X'][0], ins['Delta'][0], ins['A'][0], ins['B'][0],
        ins['C'][0], ins['D'][0],
        auto_partitioned=ctx.auto_partitioned)]}
