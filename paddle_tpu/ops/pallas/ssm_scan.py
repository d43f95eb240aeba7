"""The selective state-space scan on the chip (``ops/ssm_ops.py`` has
the equations): per sequence, with a float32 state ``h`` [N, D] that is
zero at the sequence's start,

    h_t = exp(delta_t A) h_(t-1) + (delta_t x_t) B_t
    m_t = h_t . C_t + Dskip x_t

walked a token at a time inside ONE call a walk.  The dense form
(``ssm_ops._forward`` / ``_scan_bwd``) is two nested ``lax.scan``s: 1024
sequential trips a layer and walk, each a handful of fusions around a
327 KB state that crosses HBM at every fusion's boundary.  Here the
state never leaves the core between a sequence's first token and its
last.

LAYOUT.  The channels are independent, so a grid step holds ``BLOCK`` =
1024 of them: one (8, 128) float32 register a state, N = 16 registers
the whole state, which the token loop carries in registers over a chunk
and a VMEM scratch carries from chunk to chunk.  A token's 1024
channels reach the loop as one register (``_at``).  A float32 [B, T, D]
array (``delta`` and its gradient always; ``x``, ``m`` and their
cotangents in a float32 program) lies in HBM in (8, 128) tiles of 8
tokens by 128 channels, so seen as [B, T / 8, D / 1024, 64, 128] it is
the same bytes (XLA makes no copy) and token u of a block's trip is
every 8th row from u: ONE load, or store, with a sublane stride.  A
16-bit array's tiles pack two tokens a word; it goes as [B, T, D /
1024, 8, 128], a token a row ``ref[t]`` on an untiled axis, a copy XLA
makes on the way in and out (84 MB at the cell's shapes where the
float32 one would be 168).  ``B_t[n]``, ``C_t[n]`` are scalars in SMEM
([N, chunk] windows, the tokens last: SMEM pads the last axis), read
``b_ref[n, t]`` and splat over a register, so the sum over N is N plain
vector multiply-adds and nothing crosses lanes or sublanes in the
forward.  ``A^T`` lies as [D / 1024, N, 8, 128].  The grid is (batch,
channel blocks, chunks), the chunks LAST and sequential; where the
chunk index is 0 the state is zeroed, so nothing crosses from one
sequence of a batch into the next.  A trip of the token loop is
``TRIP`` = 8 tokens, one tile's: a loop of 8 that Mosaic unrolls whole
(it unrolls a loop whole or not at all), so the kernel's jaxpr holds
one token's operations and the scheduler sees eight; a tile-major
block's trip is staged a token a row first (8 strided loads with
static strides), the loop's index being no Python number.

THE MATHEMATICS is the dense form's: float32 ``delta``, ``A``, state,
exponentials, products and sums whatever ``x``, ``B``, ``C`` arrive in;
each token's decay the exponential of its own ``delta_t A`` <= 0; the
tokens in their order, one step each.  A tail that fills no chunk is
tokens of step 0 (and x, B, C, cotangent 0): they neither decay nor
write.

THE BACKWARD is one call too, the same grid with the chunks counted
DOWN.  A grid step runs its chunk forward again from the kept start
(``starts[c]``, the residual the dense form keeps) with the state after
every token in a VMEM scratch ([chunk + 1, N, 8, 128] float32: 16.8 MB
at 256 tokens, so the call asks Mosaic for its count,
``backward_vmem``), then walks the tokens in reverse with the state's
cotangent in registers (a scratch between chunks).  With ``g = dh_t +
dm_t C_t`` the cotangent of ``h_t`` and ``e = exp(delta_t A)``:

    dC_t[n] = sum_d dm_t h_t[n]        dB_t[n] = sum_d g[n] delta_t x_t
    dh_(t-1) = g e                     dA += g e h_(t-1) delta_t
    ddelta_t = sum_n g e h_(t-1) A + x_t sum_n g[n] B_t[n]
    dx_t = delta_t sum_n g[n] B_t[n] + Dskip dm_t     dDskip += dm_t x_t

``dA`` and ``dDskip`` accumulate in output blocks that do not move along
the chunk axis (one a sequence: the batch axis is parallel).  ``dB_t``,
``dC_t`` are sums over ALL channels: a channel block writes its partial
([D / 1024, B, chunks, N, chunk] float32) and one XLA sum outside folds
them.  A block's 1024 products a token and state become one number
without a reduction a token: a trip stores its 8 tokens' product
registers, reads them back with a sublane stride (sublane s of all 8
tokens in one register, a token a sublane: 7 adds fold the sublanes of
8 tokens at once) into a [N, chunk, 128] scratch, and the chunk's lanes
are summed once at its end.  (Summing each register to a scalar in SMEM
as it was made, 32 reductions a token, read 9.70 ms forward + backward
where this read 8.30: PERF.md section 6, PR 57.)  No [B, T, D, N] array
on either pass.

Dispatch is ``ssm_ops``'s (``common.dispatch``, once a call).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common

LANES, SUBLANES = 128, 8
BLOCK = SUBLANES * LANES    # channels a grid step: one register a state
TRIP = 8                    # tokens a loop trip: one tile's, unrolled
MAX_STATES = 16             # states a channel the registers carry
_F32 = jnp.float32
_common.register_kernel(
    'ssm_scan',
    dense_fallback='paddle_tpu.ops.ssm_ops.selective_scan',
    has_vjp=True,
    doc='the selective state-space scan, a token at a time with the '
        'state held on the core from a sequence\'s first token to its '
        'last, and its exact reverse walk; dispatches dense off float32 '
        '/ D % 1024 / N > 16',
    op_types=('selective_scan',))


def layout(t, chunk):
    """-> (the chunk as the calls run it: whole trips, no longer than
    the sequence needs; chunks)."""
    whole = -(-t // TRIP) * TRIP
    size = max(TRIP, min(int(chunk), whole) // TRIP * TRIP)
    return size, -(-t // size)


def backward_vmem(size, states, itemsize):
    """Bytes one instance of the backward call holds in VMEM, as they
    lie: the kept states' scratch, the partial sums' scratches of a
    trip and of a chunk, x, the cotangent and dx in x's dtype and
    delta, ddelta in float32 in the pipeline's two buffers each, and
    A, the start, dA and the cotangent's carry, and 1 MB for what the
    compiler lays beside them (the chunk's lane sums).  The forward
    holds less."""
    state = states * BLOCK * 4
    kept = (size + 1) * state
    rows = 2 * size * BLOCK * (3 * itemsize + 2 * 4)
    partial = 2 * states * (TRIP * SUBLANES + size) * LANES * 4
    return kept + rows + partial + 7 * state + (1 << 20)


def checks(shape, states, dtype, chunk, itemsize):
    """``common.dispatch``'s gates, from what the operands show: a
    float32 working dtype; D in whole blocks of 1024 channels and N a
    size the registers carry; the backward's count, with the headroom
    every call that asks adds, under the cap."""
    d = shape[-1]
    count = backward_vmem(layout(shape[1], chunk)[0], states, itemsize)
    return (('dtype', dtype == _F32),
            ('layout', d > 0 and d % BLOCK == 0 and
             1 <= states <= MAX_STATES),
            ('vmem_over_budget', count + _common.VMEM_HEADROOM_BYTES <=
             _common.VMEM_LIMIT_CAP_BYTES))


def _view(tokens, blocks, dtype):
    """The shape past the batch of the view of a [B, tokens, blocks x
    1024] array of this dtype that the calls take blocks of
    (``_tokens``): tile-major for a 32-bit dtype, a token a row
    otherwise."""
    if jnp.dtype(dtype).itemsize == 4:
        return tokens // TRIP, blocks, SUBLANES * TRIP, LANES
    return tokens, blocks, SUBLANES, LANES


def _tokens(x, size, chunks):
    """[B, T, D] -> the view of it the calls take blocks of, the tail
    zeros.  A 32-bit array lies in HBM in (8, 128) tiles, 8 tokens by
    128 channels: seen as [B, T / 8, D / 1024, 64, 128] (row 8 k + u of
    the 64 is token u's channels 128 k .. 128 k + 127) it is the SAME
    bytes, no copy, and a token's 1024 channels are every 8th row from
    u: one strided load.  A 16-bit array's tiles pack two tokens a word:
    it goes as [B, T, D / 1024, 8, 128], a copy XLA makes, a token a
    row."""
    b, t, d = x.shape
    x = jnp.pad(x, ((0, 0), (0, chunks * size - t), (0, 0)))
    view = (b,) + _view(chunks * size, d // BLOCK, x.dtype)
    if view[3] == SUBLANES:
        return x.reshape(view)
    x = x.reshape(b, view[1], TRIP, d // BLOCK, SUBLANES, LANES)
    return x.transpose(0, 1, 3, 4, 2, 5).reshape(view)


def _untokens(x, t):
    """``_tokens``' view -> [B, T, D]."""
    b, rows, blocks = x.shape[:3]
    if x.shape[3] == SUBLANES:
        return x.reshape(b, rows, blocks * BLOCK)[:, :t]
    x = x.reshape(b, rows, blocks, SUBLANES, TRIP, LANES)
    return x.transpose(0, 1, 4, 2, 3, 5).reshape(
        b, rows * TRIP, blocks * BLOCK)[:, :t]


# The bodies below are written in ``lax`` primitives, not ``jnp``
# functions or operators: every ``jnp`` call on a tracer goes through a
# jit wrapper of its own, and a body of 8 tokens x 16 states holds four
# thousand of them (9 s of ``setup_s`` on the chip's host where these
# take 1: PERF.md section 6, PR 57).
_mul, _add, _exp = jax.lax.mul, jax.lax.add, jax.lax.exp


def _splat(scalar):
    return jax.lax.broadcast(scalar, (SUBLANES, LANES))


def _f32(v):
    return v if v.dtype == _F32 else jax.lax.convert_element_type(v, _F32)


def _reader(ref, stage_ref, trip, first):
    """-> ``read(u)``: the register of token ``first + u``, the u-th of
    trip ``trip``, from a chunk's block of either view (``_tokens``).
    A tile-major block is read here, every 8th row from each u with a
    static stride, into ``stage_ref`` [8, 8, 128], a token a row: the
    token loop's index is not static."""
    if ref.shape[1] == SUBLANES:
        return lambda u: ref[_add(first, u)]
    for k in range(TRIP):
        stage_ref[k] = ref[trip, pl.ds(k, SUBLANES, TRIP), :]
    return lambda u: stage_ref[u]


def _writer(ref, stage_ref, trip, first):
    """-> (``write(u, register)``, ``flush()``), ``_reader``'s
    reverse: a tile-major block's trip is staged a token a row and
    stored with the stride once its 8 tokens are written."""
    if ref.shape[1] == SUBLANES:
        def write(u, value):
            ref[_add(first, u)] = value
        return write, lambda: None

    def write(u, value):
        stage_ref[u] = value

    def flush():
        for k in range(TRIP):
            ref[trip, pl.ds(k, SUBLANES, TRIP), :] = stage_ref[k]
    return write, flush


def _advance(a_ref, b_ref, t, h, delta, dx):
    """The states after token ``t`` from those before it."""
    return tuple(
        _add(_mul(_exp(_mul(delta, a_ref[n])), h[n]),
             _mul(dx, _splat(b_ref[n, t])))
        for n in range(len(h)))


def _over_tokens(token, init):
    """``token(u, carry)`` over a trip's 8 tokens as a loop that Mosaic
    unrolls whole: one basic block for the scheduler, and a jaxpr that
    holds ONE token's operations (written out in Python the bodies
    cost 9 s of ``setup_s``: PERF.md section 6, PR 57)."""
    return jax.lax.fori_loop(0, TRIP, token, init, unroll=True)


def _forward_kernel(x_ref, delta_ref, a_ref, skip_ref, b_ref, c_ref,
                    m_ref, starts_ref, h_ref, x_stage, delta_stage,
                    m_stage, *, states, size):
    """One chunk of one block of channels: x, delta, m a chunk's block
    of ``_tokens``' views; A^T, the start [N, 8, 128]; B, C [N, size]
    scalars."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    starts_ref[...] = h_ref[...]
    skip = skip_ref[...]

    def trip(i, h):
        first = i * TRIP
        read_x = _reader(x_ref, x_stage, i, first)
        read_delta = _reader(delta_ref, delta_stage, i, first)
        write_m, flush_m = _writer(m_ref, m_stage, i, first)

        def token(u, h):
            t = _add(first, u)
            x, delta = _f32(read_x(u)), read_delta(u)
            h = _advance(a_ref, b_ref, t, h, delta, _mul(delta, x))
            m = _mul(skip, x)
            for n in range(states):
                m = _add(m, _mul(h[n], _splat(c_ref[n, t])))
            write_m(u, jax.lax.convert_element_type(m, m_ref.dtype))
            return h

        h = _over_tokens(token, h)
        flush_m()
        return h

    h = jax.lax.fori_loop(0, size // TRIP, trip,
                          tuple(h_ref[n] for n in range(states)))
    for n in range(states):
        h_ref[n] = h[n]


def _backward_kernel(x_ref, delta_ref, a_ref, skip_ref, b_ref, c_ref,
                     starts_ref, dm_ref, dx_ref, ddelta_ref, da_ref,
                     dskip_ref, db_ref, dc_ref, dh_ref, kept_ref,
                     trip_b_ref, trip_c_ref, chunk_b_ref, chunk_c_ref,
                     x_stage, delta_stage, dm_stage, dx_stage,
                     ddelta_stage, *, states, size):
    """One chunk of one block of channels, the chunks counted down:
    what the forward kernel saw, the start it wrote and m's cotangent
    -> dx, ddelta (a chunk's block of ``_tokens``' views), this block's
    partial dB, dC [N, size] and, added up over the chunks, dA^T [N, 8,
    128] and dDskip.  ``kept_ref[t]`` is the state before token t,
    ``kept_ref[t + 1]`` after it."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    kept_ref[0] = starts_ref[...]

    def forth(i, h):
        first = i * TRIP
        read_x = _reader(x_ref, x_stage, i, first)
        read_delta = _reader(delta_ref, delta_stage, i, first)

        def token(u, h):
            t = _add(first, u)
            delta = read_delta(u)
            h = _advance(a_ref, b_ref, t, h, delta,
                         _mul(delta, _f32(read_x(u))))
            after = _add(t, 1)
            for n in range(states):
                kept_ref[after, n] = h[n]
            return h

        return _over_tokens(token, h)

    jax.lax.fori_loop(0, size // TRIP, forth,
                      tuple(starts_ref[n] for n in range(states)))
    skip = skip_ref[...]
    zero = jnp.zeros((SUBLANES, LANES), _F32)

    def back(i, carry):
        dh, dskip = carry
        trip = size // TRIP - 1 - i
        first = trip * TRIP                 # this trip's earliest token
        read_x = _reader(x_ref, x_stage, trip, first)
        read_delta = _reader(delta_ref, delta_stage, trip, first)
        read_dm = _reader(dm_ref, dm_stage, trip, first)
        write_dx, flush_dx = _writer(dx_ref, dx_stage, trip, first)
        write_ddelta, flush_ddelta = _writer(ddelta_ref, ddelta_stage,
                                             trip, first)

        def token(k, carry):
            dh, da, dskip = carry
            u = TRIP - 1 - k
            t = _add(first, u)
            after = _add(t, 1)
            x, delta, dm = _f32(read_x(u)), read_delta(u), _f32(read_dm(u))
            dx = _mul(delta, x)
            rows = pl.ds(pl.multiple_of(u * SUBLANES, SUBLANES), SUBLANES)
            through_b = through_a = None
            before, da_after = [], []
            for n in range(states):
                a = a_ref[n]
                g = _add(dh[n], _mul(dm, _splat(c_ref[n, t])))
                trip_b_ref[n, rows, :] = _mul(g, dx)
                trip_c_ref[n, rows, :] = _mul(dm, kept_ref[after, n])
                via_b = _mul(g, _splat(b_ref[n, t]))
                through_b = via_b if n == 0 else _add(through_b, via_b)
                g = _mul(g, _exp(_mul(delta, a)))
                before.append(g)
                g = _mul(g, kept_ref[t, n])
                via_a = _mul(g, a)
                through_a = via_a if n == 0 else _add(through_a, via_a)
                da_after.append(_add(da[n], _mul(g, delta)))
            write_dx(u, jax.lax.convert_element_type(
                _add(_mul(through_b, delta), _mul(dm, skip)), dx_ref.dtype))
            write_ddelta(u, jax.lax.convert_element_type(
                _add(through_a, _mul(through_b, x)), ddelta_ref.dtype))
            return (tuple(before), tuple(da_after),
                    _add(dskip, _mul(dm, x)))

        dh, da, dskip = _over_tokens(token, (dh, (zero,) * states, dskip))
        flush_dx()
        flush_ddelta()
        for n in range(states):
            da_ref[n] = _add(da_ref[n], da[n])
        # row u * 8 + s of a trip's scratch is sublane s of token first
        # + u: a load of every 8th row from s holds the 8 tokens'
        # sublane s, a token a sublane
        at = pl.ds(pl.multiple_of(first, TRIP), TRIP)
        for trip_ref, chunk_ref in ((trip_b_ref, chunk_b_ref),
                                    (trip_c_ref, chunk_c_ref)):
            for n in range(states):
                folded = trip_ref[n, pl.ds(0, TRIP, SUBLANES), :]
                for s in range(1, SUBLANES):
                    folded = _add(
                        folded, trip_ref[n, pl.ds(s, TRIP, SUBLANES), :])
                chunk_ref[n, at, :] = folded
        return dh, dskip

    dh, dskip = jax.lax.fori_loop(
        0, size // TRIP, back,
        (tuple(dh_ref[n] for n in range(states)), zero))
    for n in range(states):
        dh_ref[n] = dh[n]
    dskip_ref[...] = _add(dskip_ref[...], dskip)
    db_ref[...] = jnp.sum(chunk_b_ref[...], axis=-1)
    dc_ref[...] = jnp.sum(chunk_c_ref[...], axis=-1)


def _scalars(v, size, chunks):
    """B or C [B, T, N] -> float32 [B, chunks, N, size]."""
    b, t, n = v.shape
    v = jnp.pad(v.astype(_F32), ((0, 0), (0, chunks * size - t), (0, 0)))
    return jnp.swapaxes(v.reshape(b, chunks, size, n), 2, 3)


@functools.partial(jax.jit, inline=True,
                   static_argnames=('size', 'backward', 'interpret'))
def _call(x, delta, a, bm, cm, skip, *rest, size, backward, interpret):
    """The forward kernel over the op's operands -> (m [B, T, D] in x's
    dtype, the state at each chunk's start [chunks, B, N, D]), or the
    backward one over them, those starts and m's cotangent -> the six
    gradients.  Under a jit cache of its own, ``inline`` (as
    kda_chunk._call): a body is traced once a process and shape and its
    instruction keeps the name of the scope the caller lowered it in."""
    b, t, d = x.shape
    states = a.shape[1]
    blocks, chunks = d // BLOCK, -(-t // size)
    at = chunks - 1 if backward else 0          # the walk's first chunk

    def chunk(c):
        return at - c if backward else c

    def rows(dtype):
        """A [B, T, D] result in ``_tokens``' view."""
        return jax.ShapeDtypeStruct(
            (b,) + _view(chunks * size, blocks, dtype), dtype)

    def row(dtype):
        """A chunk of one block of channels of such a view."""
        tokens, _, sublanes, lanes = _view(size, blocks, dtype)
        return pl.BlockSpec((None, tokens, None, sublanes, lanes),
                            lambda i, j, c: (i, chunk(c), j, 0, 0))

    state = pl.BlockSpec((None, states, SUBLANES, LANES),
                         lambda i, j, c: (j, 0, 0, 0))
    vector = pl.BlockSpec((None, SUBLANES, LANES), lambda i, j, c: (j, 0, 0))
    scalar = pl.BlockSpec((None, None, states, size),
                          lambda i, j, c: (i, chunk(c), 0, 0),
                          memory_space=pltpu.SMEM)
    start = pl.BlockSpec((None, None, states, None, SUBLANES, LANES),
                         lambda i, j, c: (chunk(c), i, 0, j, 0, 0))
    operands = [
        _tokens(x, size, chunks), _tokens(delta.astype(_F32), size, chunks),
        jnp.swapaxes(a.astype(_F32).T.reshape(
            states, blocks, SUBLANES, LANES), 0, 1),
        skip.astype(_F32).reshape(blocks, SUBLANES, LANES),
        _scalars(bm, size, chunks), _scalars(cm, size, chunks)]
    in_specs = [row(x.dtype), row(_F32), state, vector, scalar, scalar]
    starts = jax.ShapeDtypeStruct(
        (chunks, b, states, blocks, SUBLANES, LANES), _F32)
    scratch = [pltpu.VMEM((states, SUBLANES, LANES), _F32)]

    def stages(*dtypes):
        """A trip's staging rows of each [B, T, D] operand, in order
        (``_reader``; 32 KB each)."""
        return [pltpu.VMEM((TRIP, SUBLANES, LANES), dtype)
                for dtype in dtypes]

    params = {'dimension_semantics': ('parallel', 'parallel', 'arbitrary')}
    if not backward:
        m, kept = pl.pallas_call(
            functools.partial(_forward_kernel, states=states, size=size),
            grid=(b, blocks, chunks), in_specs=in_specs,
            out_specs=[row(x.dtype), start],
            out_shape=[rows(x.dtype), starts],
            scratch_shapes=scratch + stages(x.dtype, _F32, x.dtype),
            compiler_params=pltpu.CompilerParams(**params),
            interpret=interpret)(*operands)
        return _untokens(m, t), kept.reshape(chunks, b, states, d)
    kept, d_m = rest
    per_sequence = pl.BlockSpec((None, None, states, SUBLANES, LANES),
                                lambda i, j, c: (i, j, 0, 0, 0))
    partial = pl.BlockSpec((None, None, None, states, size),
                           lambda i, j, c: (j, i, chunk(c), 0, 0))
    scratch.append(pltpu.VMEM((size + 1, states, SUBLANES, LANES), _F32))
    scratch += [pltpu.VMEM((states, TRIP * SUBLANES, LANES), _F32)] * 2
    scratch += [pltpu.VMEM((states, size, LANES), _F32)] * 2
    sums = jax.ShapeDtypeStruct((blocks, b, chunks, states, size), _F32)
    count = backward_vmem(size, states, x.dtype.itemsize)
    if count > _common.SCOPED_VMEM_BYTES // 2:
        params['vmem_limit_bytes'] = count + _common.VMEM_HEADROOM_BYTES
    d_x, d_delta, d_a, d_skip, d_b, d_c = pl.pallas_call(
        functools.partial(_backward_kernel, states=states, size=size),
        grid=(b, blocks, chunks),
        in_specs=in_specs + [start, row(d_m.dtype)],
        out_specs=[row(x.dtype), row(delta.dtype), per_sequence,
                   pl.BlockSpec((None, None, SUBLANES, LANES),
                                lambda i, j, c: (i, j, 0, 0)),
                   partial, partial],
        out_shape=[rows(x.dtype), rows(delta.dtype),
                   jax.ShapeDtypeStruct(
                       (b, blocks, states, SUBLANES, LANES), _F32),
                   jax.ShapeDtypeStruct((b, blocks, SUBLANES, LANES), _F32),
                   sums, sums],
        scratch_shapes=scratch + stages(x.dtype, _F32, d_m.dtype, x.dtype,
                                        delta.dtype),
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret)(
            *operands, kept.reshape(starts.shape), _tokens(d_m, size, chunks))

    def folded(v, like):
        """The blocks' partial sums [blocks, B, chunks, N, size] -> [B,
        T, N]."""
        v = jnp.swapaxes(jnp.sum(v, 0), 2, 3)
        return v.reshape(b, chunks * size, states)[:, :t].astype(like.dtype)

    d_a = jnp.sum(d_a, 0).transpose(1, 0, 2, 3).reshape(states, d).T
    return (_untokens(d_x, t), _untokens(d_delta, t), d_a.astype(a.dtype),
            folded(d_b, bm), folded(d_c, cm),
            jnp.sum(d_skip, 0).reshape(d).astype(skip.dtype))


def forward(x, delta, a, bm, cm, skip, size, interpret=False):
    """x, delta [B, T, D], a [D, N], bm, cm [B, T, N], skip [D]
    (``checks`` holds) -> (m [B, T, D] in x's dtype, the state at each
    chunk's START [chunks, B, N, D] float32), in chunks of ``size``
    tokens (``layout``)."""
    return _call(x, delta, a, bm, cm, skip, size=size, backward=False,
                 interpret=interpret)


def backward(x, delta, a, bm, cm, skip, starts, d_m, size,
             interpret=False):
    """``forward``'s operands, the starts it kept and m's cotangent ->
    the cotangents of x, delta, a, bm, cm, skip in their dtypes."""
    return _call(x, delta, a, bm, cm, skip, starts, d_m, size=size,
                 backward=True, interpret=interpret)
