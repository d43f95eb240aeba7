"""The gated delta rule's preparation of its chunks on the chip
(``ops/kda_ops.py`` has the equations): for every chunk and head, from
q, k, v, the log decay ``a`` and the write strength beta AS THE OP IS
HANDED THEM, everything the walk over the chunks and the system's
solve read but the solution itself.  With ``G`` the inclusive running
sum of ``a`` over the chunk's rows,

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <  t
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <= t
    Qbar_t = q_t exp(G_t)      Khat_t = k_t exp(G_C - G_t)
    rhs = Diag(beta) [k exp(G) | v]        system = Diag(beta) A

and ``exp(G_C)``, float32, and their backward.  XLA keeps the inverse
of ``I + system`` and the products that apply it
(``kda_ops._solve``), nothing else of the preparation.

WHAT A CALL READS AND WRITES.  q, k, v [B, T, H x d] and beta [B, T, H]
in the dtype they arrive in (bfloat16 under AMP) and ``a`` float32 are
read where the projections wrote them: a block is a chunk's C rows of
ONE head's lanes, addressed by a block map; nothing is cast, padded,
chunked or transposed in HBM first (``kda_ops._chunked`` did all four:
a float32 [N, B, H, C, d] copy of each operand, 3.5 GB across HBM a
preparation at Kimi Linear's shape where the walk and the solve need
about 1).  Where T is no whole number of chunks the last block reads
past the array and the rows past T are replaced on the chip before
anything is computed from them (a = 0, beta = 0, k = 0: tokens that
neither decay nor write, what ``_chunked`` padded).  The results leave
chunk-major, [N, B, H, C, .], the order the walk's kernels and the
solve's product read: the right-hand side ONE [.., C, dk + dv] array
(no ``concatenate`` after the call), ``exp(G_C)`` [N, B, H, dk].  The
backward reads the same five inputs again, the A the forward kept for
it (the one residual, a transient of the op's backward: dbeta is
``rowsum(dM . A)``) and the six cotangents, and writes dq, dk, dv, da,
dbeta in the op's own order and dtypes.  The heads are the grid's LAST
axis: beta's and dbeta's [C, H] and ``exp(G_C)``'s [H, dk] blocks hold
every head and stay on the core while it is walked (a head's beta is a
masked lane sum of the block, its dbeta a masked select into it).

THE RUNNING SUM is a product with a [C, C] triangle of ones at
``Precision.HIGHEST`` (the ones are exact in every pass: a float32 sum
in the MXU's order), and da the triangle's transpose times dG.  Two
float32 sums in different orders lie an ulp of |G| apart (6e-5 at |G|
of 1000, rate 16) and every exponent inherits it: against the dense
form's ``cumsum`` the results read 2.4e-6 at rate 1 and 1.6e-5 at rate
16 of the largest entry, against the token loop the whole op stays
inside the limits it had (``tests/test_kda_kernel.py``).  Inside the
step the ``cumsum`` was a ``reduce_window`` with a pass and a copy of
its own (1.62 ms a layer, PERF.md section 6, PR 61).

The scores' mathematics is the dense form's, term for term.  The decay
is per channel, so inside a sub-chunk of ``SUB`` tokens the weight is a
[SUB, SUB, dk] block (128 KB at dk 128): the dense form
(``kda_ops._scores``) writes those blocks to HBM, sixteen times the
op's operands; here a block is built a key at a time, used and dropped
in VMEM.  Inside a sub-chunk the difference ``G_t - G_j`` is taken
first and masked in the exponent (to -inf); between a row of sub-chunk
I and the columns of the earlier ones the weight is ``exp(G_t - r_I)
exp(r_I - G_j)`` about the running sum ``r_I`` at I's start, both
factors <= 1, and the sum over channels a float32 product at
``Precision.HIGHEST`` (Mosaic's is XLA's: on a v5e both lie 2.1e-6 from
float64, PERF.md section 6, PR 47).  No clamp, no floor, no dropped
term, no factored ``exp(-G_j)``.

Both kernels walk a sub-chunk a KEY j at a time on [SUB, dk] tiles,
the rows t on sublanes and the channel on lanes: ``G_j``, ``k_j`` are
one row broadcast over the sublanes, the weights of all SUB rows
against that key two vector registers.  The forward sums each row's
channels (a lane reduction a row and key) into column j of the tile.
The backward takes column j of the cotangents ``dA = beta dM``, ``dB``,
builds the weights again, and adds up ``dq``, ``dk``, ``dG``: sums over
the keys are vector adds, the sum over the rows one sublane reduction a
key.  With ``dk_row`` / ``dq`` the gradients through the row factor and
``dk_col`` through the column factor, the scores' share of the decay's
is ``dG = k (dk_row - dk_col) + q dq``: every weight is an exponential
of ``G_t - G_j``.  (``r_I`` cancels in ``exp(G_t - r_I) exp(r_I -
G_j)``: its cotangent is zero, and the dense form's is rounding.)  The
elementwise results add ``dQbar . Qbar + dKbar . Kbar - dKhat . Khat``
a row, and the last row takes what flows through ``G_C``: ``sum_t
dKhat . Khat + d exp(G_C) . exp(G_C)``.

A grid step is one chunk-head.  Inside it the sub-chunks are a loop
(the first one apart: nothing stands before it) and a sub-chunk's keys
a loop that Mosaic unrolls, so a kernel's jaxpr holds two sub-chunks'
operations and one key's, not C / SUB x SUB of them; the calls sit
under a jit cache of their own (``_call``).  Both for ``setup_s``: a
train step holds fifteen of these calls and every program traces and
lowers each.

Tried on the chip and dropped (PERF.md section 6, PR 47): the channel
on sublanes and the (t, j) pair on 256 lanes, reached through exact
0/1 selector products on the MXU (2.4 times the forward's time);
several chunk-heads a grid step (3%); Python loops over sub-chunks and
keys (as fast as it gets, 0.41 + 0.54 ms a layer where these took 0.46
+ 0.69, but 39 s of ``setup_s``); the keys' loop rolled (3.5 ms); a
``lax.cond`` around the first sub-chunk's absent columns (0.71 + 1.04).

Dispatch is ``kda_ops``'s (``common.dispatch``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common
from .flash_attention import _dot

SUB = 16
_F32 = jnp.float32
_common.register_kernel(
    'kda_chunk',
    dense_fallback='ops.kda_ops._prepare',
    has_vjp=True,
    doc='the gated delta rule\'s preparation of its chunks from the op\'s '
        'inputs as they arrive (running decay, scores, Qbar, Khat, the '
        'system and its right-hand side); dispatches dense off float32 / '
        'dk, dv % 128',
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


def _prelude(t, q_ref, k_ref, a_ref, beta_ref, q_s, k_s, g_s):
    """What both kernels start with, for the chunk and head of this
    grid step: q, k as float32 and the inclusive running sum G of ``a``
    over the chunk's rows into the scratch (the loops below slice them
    by row), -> (beta [C, 1] float32, ``keep``: a block as float32 with
    the rows past the sequence's end zeroed, the triangle of ones).
    ``t`` is the sequence's length: where it is no whole number of
    chunks the last block reads past the array, and what it reads there
    is replaced before anything is computed from it (a = 0, beta = 0, k
    = 0: tokens that neither decay nor write, as ``kda_ops._chunked``
    pads them).  G is the triangle's product with ``a`` at
    ``Precision.HIGHEST``: ones are exact in every pass, so it is a
    float32 sum in the MXU's order."""
    c = a_ref.shape[0]
    if t % c:
        valid = _iota((c, 1), 0) < t - pl.program_id(1) * c

        def keep(x):
            return jnp.where(valid, x.astype(_F32), 0.0)
    else:
        def keep(x):
            return x.astype(_F32)

    every = keep(beta_ref[...])                         # [C, H]
    beta = jnp.sum(jnp.where(_iota(every.shape, 1) == pl.program_id(2),
                             every, 0.0), 1, keepdims=True)
    q_s[...] = keep(q_ref[...])
    k_s[...] = keep(k_ref[...])
    lower = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)
    g_s[...] = _dot(lower, keep(a_ref[...]), (1, 0))
    return beta, keep, lower


def _forward_kernel(t, q_ref, k_ref, v_ref, a_ref, beta_ref,
                    m_ref, b_ref, q_bar_ref, k_hat_ref, rhs_ref, decay_ref,
                    *rest):
    """One chunk-head: q, k, a [C, dk], v [C, dv] as the op was handed
    them, beta [C, H] -> the system beta A and B [C, C], Qbar, Khat [C,
    dk], the right-hand side beta [Kbar | V] [C, dk + dv], row h of
    exp(G_C) [H, dk] (the block stays while the grid walks the heads)
    and, where the pull-back will want it, A."""
    *kept, q_s, k_s, g_s, a_s = rest
    beta, keep, _ = _prelude(t, q_ref, k_ref, a_ref, beta_ref, q_s, k_s, g_s)
    c, dk = g_s.shape
    g, k = g_s[...], k_s[...]
    g_end = g_s[c - 1:c, :]
    grown = jnp.exp(g)
    q_bar_ref[...] = q_s[...] * grown
    k_hat_ref[...] = k * jnp.exp(g_end - g)
    rhs_ref[:, :dk] = beta * (k * grown)
    rhs_ref[:, dk:] = beta * keep(v_ref[...])
    decay_ref[pl.ds(pl.program_id(2), 1), :] = jnp.exp(g_end)
    token = _iota((SUB, 1), 0)
    column = _iota((SUB, c), 1)

    def sub_chunk(first, tile, later, _):
        g_t, k_t, q_t = g_s[tile, :], k_s[tile, :], q_s[tile, :]

        def key(j, blocks):
            a_in, b_in = blocks
            w, k_j = _key(g_s, k_s, g_t, first + j, token >= j)
            k_w = k_j * w
            a_j = jnp.sum(k_t * k_w, 1, keepdims=True)
            b_j = jnp.sum(q_t * k_w, 1, keepdims=True)
            here = column == first + j
            return (jnp.where(here, jnp.where(token > j, a_j, 0.0), a_in),
                    jnp.where(here, b_j, b_in))

        zero = jnp.zeros((SUB, c), _F32)
        a_in, b_in = _over_keys(key, (zero, zero))
        if later:
            row, col = _between(g_s, first)
            off = _dot(jnp.concatenate([k_t * row, q_t * row], 0),
                       k_s[...] * col, (1, 1))
            a_in, b_in = a_in + off[:SUB], b_in + off[SUB:]
        a_s[tile, :] = a_in
        b_ref[tile, :] = b_in

    _over_sub_chunks(c, sub_chunk)
    m_ref[...] = beta * a_s[...]
    if kept:
        kept[0][...] = a_s[...]


def _backward_kernel(t, q_ref, k_ref, v_ref, a_ref, beta_ref, a_mat_ref,
                     dm_ref, db_ref, dq_bar_ref, dk_hat_ref, drhs_ref,
                     ddecay_ref, dq_ref, dk_ref, dv_ref, da_ref, dbeta_ref,
                     q_s, k_s, g_s, da_s, dq_s, dk_s, dg_s):
    """One chunk-head: what the forward kernel read, the A it kept and
    the cotangents of its six results -> dq, dk, dv, da [C, .] and
    column h of dbeta [C, H], in the op's own order and dtypes.  G and
    the exponentials are computed again; the scores' share is the loop
    of before over dA = beta dM and dB (until every sub-chunk is walked
    ``dk_s`` holds ``dk_row`` and ``dg_s`` the keys' share of ``dk_col``
    inside their own sub-chunk), the elementwise results' share is added
    to it, and da is the REVERSE running sum of dG over the chunk's
    rows, the triangle's transpose times dG."""
    beta, keep, lower = _prelude(t, q_ref, k_ref, a_ref, beta_ref,
                                 q_s, k_s, g_s)
    c, dk = g_s.shape
    dm = dm_ref[...]
    da_s[...] = beta * dm
    token = _iota((SUB, 1), 0)
    column = _iota((SUB, c), 1)

    def sub_chunk(first, tile, later, dk_col):
        g_t, k_t, q_t = g_s[tile, :], k_s[tile, :], q_s[tile, :]
        da_t, db_t = da_s[tile, :], db_ref[tile, :]

        def key(j, sums):
            dk_row, dq, dk_in = sums
            w, k_j = _key(g_s, k_s, g_t, first + j, token >= j)
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
            row, col = _between(g_s, first)
            both = jnp.concatenate([da_t, db_t], 0)            # [2 SUB, C]
            d_rows = _dot(both, k_s[...] * col, (1, 0))
            dk_row += d_rows[:SUB] * row
            dq += d_rows[SUB:] * row
            # through the column factor, every key before this sub-chunk
            dk_col += col * _dot(
                both, jnp.concatenate([k_t * row, q_t * row], 0), (0, 0))
        dq_s[tile, :] = dq
        dk_s[tile, :] = dk_row
        dg_s[tile, :] = dk_in
        return dk_col

    dk_col = _over_sub_chunks(c, sub_chunk, jnp.zeros((c, dk), _F32))
    q, k, g = q_s[...], k_s[...], g_s[...]
    dk_row, dk_col, dq = dk_s[...], dk_col + dg_s[...], dq_s[...]
    g_end = g_s[c - 1:c, :]
    grown, shrunk = jnp.exp(g), jnp.exp(g_end - g)
    q_bar, k_bar = q * grown, k * grown
    dq_bar, dk_hat = dq_bar_ref[...], dk_hat_ref[...]
    drhs_k, drhs_v = drhs_ref[:, :dk], drhs_ref[:, dk:]
    dk_bar, v = beta * drhs_k, keep(v_ref[...])
    dq_ref[...] = (dq + dq_bar * grown).astype(dq_ref.dtype)
    dk_ref[...] = (dk_row + dk_col + dk_bar * grown +
                   dk_hat * shrunk).astype(dk_ref.dtype)
    dv_ref[...] = (beta * drhs_v).astype(dv_ref.dtype)
    through_end = dk_hat * (k * shrunk)         # dKhat . Khat: G_C - G_t
    d_end = jnp.sum(through_end, 0, keepdims=True) + \
        ddecay_ref[pl.ds(pl.program_id(2), 1), :] * jnp.exp(g_end)
    dg = k * (dk_row - dk_col) + q * dq + dq_bar * q_bar + \
        dk_bar * k_bar - through_end + \
        jnp.where(_iota((c, 1), 0) == c - 1, d_end, 0.0)
    da_ref[...] = _dot(lower, dg, (0, 0)).astype(da_ref.dtype)
    dbeta = jnp.sum(dm * a_mat_ref[...], 1, keepdims=True) + \
        jnp.sum(drhs_k * k_bar, 1, keepdims=True) + \
        jnp.sum(drhs_v * v, 1, keepdims=True)
    # column h of the block, which stays while the grid walks the heads
    h = pl.program_id(2)
    so_far = jnp.where(h > 0, dbeta_ref[...].astype(_F32), 0.0)
    dbeta_ref[...] = jnp.where(_iota(so_far.shape, 1) == h, dbeta,
                               so_far).astype(dbeta_ref.dtype)


@functools.partial(jax.jit, inline=True,
                   static_argnames=('c', 'keep_a', 'interpret'))
def _call(q, k, v, a, beta, *rest, c, keep_a=False, interpret):
    """The forward kernel over q, k, a [B, T, H, dk], v [B, T, H, dv],
    beta [B, T, H] in chunks of ``c`` tokens -> (beta A, B [N, B, H, C,
    C], Qbar, Khat [N, B, H, C, dk], beta [Kbar | V] [N, B, H, C, dk +
    dv], exp(G_C) [N, B, H, dk]) float32 and, with ``keep_a``, A; or,
    given A and those six results' cotangents, the backward one -> dq,
    dk, dv, da, dbeta shaped and typed as the five inputs.  A grid step
    is a chunk-head: the inputs are read where the projections wrote
    them, a chunk's C rows of one head's lanes of the [B, T, H x d]
    view, the heads the grid's LAST axis (``exp(G_C)``'s and beta's
    blocks hold every head and stay while it is walked).  Under a jit
    cache of its own, ``inline`` (as flash_attention._fwd_call): a
    kernel's body is traced once a process and shape, not once a call
    (a train step holds fifteen), and its instruction keeps the name of
    the scope the caller lowered it in."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    n = -(-t // c)

    def rows(width):
        """A chunk's rows of one head of a [B, T, H x width] view."""
        return pl.BlockSpec((None, c, width), lambda i, s, j: (i, s, j))

    def per_chunk(width):
        return pl.BlockSpec((None, None, None, c, width),
                            lambda i, s, j: (s, i, j, 0, 0))

    def made(width):
        return jax.ShapeDtypeStruct((n, b, h, c, width), _F32)

    every_head = pl.BlockSpec((None, c, h), lambda i, s, j: (i, s, 0))
    decays = pl.BlockSpec((None, None, h, dk), lambda i, s, j: (s, i, 0, 0))
    inputs = [x.reshape(b, t, -1) for x in (q, k, v, a)] + [beta]
    specs = [rows(dk), rows(dk), rows(dv), rows(dk), every_head]
    widths = [c, c, dk, dk, dk + dv]
    results = [per_chunk(w) for w in widths] + [decays]
    scratch = [pltpu.VMEM((c, dk), _F32)] * 3
    kwargs = dict(
        grid=(b, n, h), interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))
    if not rest:
        kept = [c] if keep_a else []    # A once more, for the pull-back
        return pl.pallas_call(
            functools.partial(_forward_kernel, t), in_specs=specs,
            out_specs=results + [per_chunk(w) for w in kept],
            out_shape=[made(w) for w in widths] +
            [jax.ShapeDtypeStruct((n, b, h, dk), _F32)] +
            [made(w) for w in kept],
            scratch_shapes=scratch + [pltpu.VMEM((c, c), _F32)],
            name='kda_chunk_forward', **kwargs)(*inputs)
    grads = pl.pallas_call(
        functools.partial(_backward_kernel, t),
        in_specs=specs + [per_chunk(c)] + results,
        out_specs=specs,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in inputs],
        scratch_shapes=scratch + [pltpu.VMEM((c, c), _F32)] +
        [pltpu.VMEM((c, dk), _F32)] * 3,
        name='kda_chunk_backward', **kwargs)(*inputs, *rest)
    return [x.reshape(y.shape) for x, y in zip(grads, (q, k, v, a, beta))]


def checks(c, dk, dv, dtype):
    """``common.dispatch``'s gates: what the kernels' layout asks of a
    chunk as it is run: a float32 working dtype, whole sub-chunks, and
    both widths in whole 128-lane tiles (a block is one head's lanes of
    a [B, T, H x d] array)."""
    return (('dtype', dtype == jnp.float32),
            ('layout', c % SUB == 0 and dk % 128 == 0 and dv % 128 == 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def prepare(q, k, v, a, beta, c, interpret=False):
    """q, k, a [B, T, H, dk], v [B, T, H, dv], beta [B, T, H] as the op
    was handed them, in chunks of ``c`` tokens (``checks`` holds) ->
    (beta A, B, Qbar, Khat, beta [Kbar | V], exp(G_C)) of every chunk,
    float32 and chunk-major ([N, B, H, C, .]; the last [N, B, H, dk]):
    everything of ``kda_ops._prepare`` but the system's solution."""
    return tuple(_call(q, k, v, a, beta, c=c, interpret=interpret))


def _prepare_fwd(q, k, v, a, beta, c, interpret):
    *made, a_mat = _call(q, k, v, a, beta, c=c, keep_a=True,
                         interpret=interpret)
    return tuple(made), (q, k, v, a, beta, a_mat)


def _prepare_bwd(c, interpret, saved, cotangents):
    return tuple(_call(*saved, *cotangents, c=c, interpret=interpret))


prepare.defvjp(_prepare_fwd, _prepare_bwd)
