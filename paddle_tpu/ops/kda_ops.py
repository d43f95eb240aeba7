"""The gated delta rule with a per-CHANNEL decay (Kimi Delta Attention,
"KDA": the linear-attention layers of Solar Open 2), forward and
backward, in chunked matmul form.

Per sequence and head, with a state ``S`` [dk, dv] that is zero at the
sequence's start:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``a`` is the LOG of the decay (<= 0, one number a key channel and
token), ``beta`` the write strength (in (0, 2) where negative
eigenvalues are allowed).  A loop over tokens is the reference
(``paddle_tpu/models/reference/solar_open2.py`` ``kda_recurrence``);
here the sequence is cut into chunks of ``chunk`` tokens and only the
state crosses a chunk's boundary.  With ``G_t`` the running sum of ``a``
inside a chunk, ``S_0`` the state the chunk starts from and ``u_t =
beta_t (v_t - (Diag(exp(a_t)) S_(t-1))^T k_t)`` (so that ``S_t =
Diag(exp(a_t)) S_(t-1) + k_t u_t^T``):

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <  t
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])        j <= t
    (I + Diag(beta) A) U = Diag(beta) (V - Kbar S_0)       Kbar_t = k_t exp(G_t)
    O    = Qbar S_0 + B U                                  Qbar_t = q_t exp(G_t)
    S_C  = Diag(exp(G_C)) S_0 + Khat^T U                   Khat_t = k_t exp(G_C - G_t)

``A``, ``B``, the unit lower-triangular system's solution (``W = (I +
Diag(beta) A)^-1 Diag(beta) [Kbar | V]``, so ``U = W_v - W_k S_0``),
``Qbar`` and ``Khat`` are computed for ALL chunks, heads and sequences
at once (the preparation); a walk over the T / chunk chunks carries the
state through three small matmuls a chunk (``_step``).

THE PREPARATION is a Pallas kernel on a TPU (``ops/pallas/kda_chunk.py``,
``kda_chunk`` in the kernel library), all of it but the system's
inverse: the call reads q, k, v, a and beta where the projections
wrote them ([B, T, H x d], bfloat16 under AMP, a chunk's rows of one
head's lanes a block) and writes the system ``beta A``, ``B``,
``Qbar``, ``Khat``, the right-hand side ``beta [Kbar | V]`` as ONE
array and ``exp(G_C)``, float32 and chunk-major as the solve's product
and the walk's kernels read them; the running sum, the exponentials,
the casts, the chunking and the ragged tail's mask happen in VMEM, and
its backward kernel (under ``kda_chunk.prepare``'s ``custom_vjp``)
writes dq, dk, dv, da, dbeta in the op's own order and dtypes.  XLA
keeps ``_unit_lower_inverse`` and the products that apply it
(``_solve`` and its closed-form backward) and nothing else: ``W = T
rhs`` is one product on [.., C, dk + dv], whose two halves the walk's
calls read as lanes of one block.  Until PR 62 the kernel made ``A``
and ``B`` alone and the rest was XLA's elementwise and layout fusions
around it: 9.7 ms of a forward preparation's 16.5 and 7 of a
pull-back's at Kimi Linear's 32 heads x 8192 tokens, the same arrays
across HBM 3.5 GB a preparation where the walk and the solve need
about 1 (PERF.md section 6, PR 62).  The dense form is ``_prepare``
(over ``_chunked`` copies, with ``_scores``): float64, widths off the
128 lanes, under the GSPMD runner and off a TPU.

THE WALK is a Pallas kernel on a TPU (``ops/pallas/kda_walk.py``,
``kda_walk`` in the kernel library): ONE call forward and one in
reverse, the chunk axis the grid's last and sequential, the float32
state (in reverse its cotangent) in VMEM from a sequence's first chunk
to its last; it takes what the scan takes and gives what it gives.  Its
dense form is a ``lax.scan`` over ``_step``
(in reverse over ``jax.vjp(_step)``): a ``while`` of T / chunk trips,
the state through HBM at every fusion's boundary.  ``gated_delta_rule``
asks ``common.dispatch`` once a call for it too (``_paths``): the
kernels where the working dtype is float32, dk and dv whole 128-lane
tiles and the call's VMEM count fits; the scans for float64, other
widths, under the GSPMD runner and off a TPU.

THE PREPARATION HOLDS NO LOOP on either path: only the walks walk
anything. The system is C x C with C at most 64, under the block size at
which the compiler's ``triangular_solve`` multiplies, so that call
inverted each system row by row, 64 dependent steps a call (0.63 to 0.69
ms on a v5e at 512 chunk-heads). Here the inverse ``T = (I + Diag(beta)
A)^-1`` is FORMED, by substitution in blocks of ``SUB``
(``_unit_lower_inverse``: the diagonal blocks row by row, ``ROWS`` rows
a pass, every chunk, head and block in each step; the blocks under the
diagonal by block substitution, products), and ``W = T (Diag(beta) [Kbar
| V])`` is one product; its backward is the closed form ``dM =
-strictly_lower(T^T dW W^T)`` (``_solve``). Substitution and NOT a
series: ``(I + M)^-1 = (I - M)(I + M^2)(I + M^4)..`` is exact on paper
(``M`` is nilpotent) but with ``beta`` up to 2 and keys that nearly
repeat the entries of ``M`` are near 2, its powers pass 1e15 and the
float32 sum has to cancel them; substitution only ever forms entries of
``T`` itself. In ``_prepare`` the running decay ``G`` is a ``cumsum``:
as an XLA product with a triangle of ones it read FASTER alone and
SLOWER in the step it runs in (``PERF.md`` section 6, PR 59); the kernel
takes that product in VMEM, where it costs no pass over HBM.

``gated_delta_rule`` asks ``common.dispatch`` once a call for the
preparation: the kernels where the working dtype is float32, dk and dv
whole numbers of 128-lane tiles and the chunk as run whole sub-chunks;
``_prepare`` for float64, other widths, under the GSPMD runner
(``auto_partitioned``) and off a TPU (the kernels' bodies under the
Pallas interpreter where ``FLAGS_pallas_force`` asks).  ``_scores`` is
the dense form of the in-chunk scores: its [.., SUB, SUB, dk] blocks of
the next paragraph are HBM buffers, sixteen times the operands, where
the kernel builds and drops them in VMEM.

THE DECAY IS PER CHANNEL, so ``exp(G_t - G_j)`` does not factor out of
the sum over channels as a scalar, and the factored form ``(k_t
exp(G_t)) . (k_j exp(-G_j))`` overflows float32: at ``-a`` of 1.6 a
token ``exp(-G_j)`` passes 3e38 inside 64 tokens.  Every exponent taken
here is a DIFFERENCE that is <= 0: a chunk is cut into sub-chunks of
``SUB`` tokens; between a row of sub-chunk I and a column of an earlier
sub-chunk J the weight is ``exp(G_t - r_I) exp(r_I - G_j)`` with ``r_I``
the running sum at I's start, which lies between the two (both factors
<= 1, the product matmul-shaped); inside one sub-chunk the difference
``G_t - G_j`` is taken before the exponential, on a [SUB, SUB, dk]
block.  Positions outside the causal triangle are masked in the
EXPONENT (to -inf), never after it.  No clamp, no floor, no dropped
term: a factor that underflows belongs to a product under 1e-38.

The backward is a ``custom_vjp`` of the whole op: it keeps what the op
was handed (q, k, v, a, beta as they arrived) and the state at each
chunk's START (T / chunk x [dk, dv] a head), and not one byte for the
kernels (the preparation's ``custom_vjp`` keeps its five inputs and A
of the RECOMPUTED preparation, transients of the backward).  It
computes the operands again under ``jax.vjp`` (by the forward kernel
once more), walks the chunks in reverse carrying the state's cotangent
(the reverse kernel, which recomputes ``u`` from the kept start and
writes the operands' cotangents; densely each chunk's ``_step`` under a
``jax.vjp`` of its own), and hands the operands' cotangents back
through the solve's closed form and the preparation's backward kernel.
Nothing saved grows with T x dk x dv, nor with what the preparation
holds inside a chunk.

float32 inside whatever arrives (float64 under x64): the decays and
their sums, the system's inverse, the state and every product
(``Precision.HIGHEST``: the op's FLOPs are a few percent of a layer's
projections; the row substitution multiplies and adds float32 on the
vector unit); the output in V's dtype (the ``rms_norm`` /
``short_conv`` policy).
"""

import functools

import jax
import jax.numpy as jnp

from . import registry
from .registry import register

CHUNK = 64
SUB = 16
ROWS = 4    # rows of a diagonal block's inverse taken in one pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _exp_masked(exponent, keep):
    """exp where ``keep``, 0 elsewhere, masked BEFORE the exponential:
    what is not kept may be a large positive difference."""
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


def _scores(q, k, g):
    """q, k and the running log decay g [..., C, dk] of whole chunks
    -> (A strictly lower, B lower) [..., C, C]: the dense form, and the
    ``kda_chunk`` kernel's fallback.  Its [.., SUB, SUB, dk] blocks are
    XLA buffers in HBM."""
    lead, (c, dk) = k.shape[:-2], k.shape[-2:]
    sub = SUB if c % SUB == 0 else c
    n_sub = c // sub

    def blocks(x):                      # [..., C, d] -> [..., S, s, d]
        return x.reshape(lead + (n_sub, sub, x.shape[-1]))

    gs, qs, ks = blocks(g), blocks(q), blocks(k)
    # r_I: the running sum at sub-chunk I's start (0 at the chunk's)
    start = jnp.concatenate(
        [jnp.zeros_like(gs[..., :1, -1, :]), gs[..., :-1, -1, :]], -2)
    row = jnp.exp(gs - start[..., None, :])             # [..., I, i, dk]
    earlier = jnp.arange(n_sub)[:, None] > jnp.arange(n_sub)[None, :]
    col = _exp_masked(                                  # [..., I, J, j, dk]
        start[..., :, None, None, :] - gs[..., None, :, :, :],
        earlier[:, :, None, None])
    k_col = ks[..., None, :, :, :] * col
    a_off = _mm('...Iic,...IJjc->...IiJj', ks * row, k_col)
    b_off = _mm('...Iic,...IJjc->...IiJj', qs * row, k_col)
    # inside a sub-chunk: the difference first, on [s, s, dk]
    upto = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    within = _exp_masked(gs[..., :, None, :] - gs[..., None, :, :],
                         upto[:, :, None])              # [..., I, i, j, dk]
    k_within = ks[..., None, :, :] * within
    a_in = jnp.sum(ks[..., :, None, :] * k_within, -1)
    b_in = jnp.sum(qs[..., :, None, :] * k_within, -1)
    strictly = jnp.arange(sub)[:, None] > jnp.arange(sub)[None, :]
    same = jnp.eye(n_sub, dtype=k.dtype)[:, None, :, None]

    def whole(off, inside):             # -> [..., C, C]
        return (off + inside[..., :, :, None, :] * same).reshape(
            lead + (c, c))

    return whole(a_off, a_in * strictly), whole(b_off, b_in)


@jax.jit
def _unit_lower_inverse(m):
    """m [..., C, C], strictly lower -> (I + m)^-1 (unit lower) by
    substitution, every chunk and head at once, in two levels.  The
    diagonal blocks of ``SUB`` rows by ROW substitution (row i of a
    block's inverse is ``e_i - m[i, :i] T[:i]``), ``ROWS`` rows a
    pass: what the rows above the pass give is one product-and-sum
    over those rows, what the pass's own rows give each other its
    ``ROWS - 1`` dependent steps.  Then the block rows under the
    diagonal one after the other, ``T[I, :I] = -T_II (m[I, :I] T[:I,
    :I])``: products.  The module's docstring says why no series.
    Jitted so that its hundred-odd equations are traced once a shape
    and process, not at each of a step's calls (``setup_s``)."""
    c = m.shape[-1]
    sub = SUB if c % SUB == 0 else c
    diag = jnp.stack([m[..., i:i + sub, i:i + sub]
                      for i in range(0, c, sub)], -3)   # [..., I, s, s]
    eye = jnp.eye(sub, dtype=m.dtype)
    inv = None                                  # [..., I, rows so far, s]
    for lo in range(0, sub, ROWS):
        hi = min(lo + ROWS, sub)
        among = jnp.broadcast_to(eye[lo:hi],
                                 diag.shape[:-2] + (hi - lo, sub))
        if lo:
            among = among - jnp.sum(diag[..., lo:hi, :lo, None] *
                                    inv[..., None, :, :], -2)
        at_row = jnp.arange(hi - lo)[:, None]
        # m[i, j >= i] is 0: the rows of ``among`` from r on add nothing
        for r in range(1, hi - lo):
            row = among[..., r, :] - jnp.sum(
                diag[..., lo + r, lo:hi, None] * among, -2)
            among = jnp.where(at_row == r, row[..., None, :], among)
        inv = among if inv is None else jnp.concatenate([inv, among], -2)
    t = inv[..., 0, :, :]
    for i in range(1, c // sub):
        lo, t_ii = i * sub, inv[..., i, :, :]
        under = -_mm('...ij,...jk->...ik', t_ii, _mm(
            '...ij,...jk->...ik', m[..., lo:lo + sub, :lo], t))
        t = jnp.concatenate([
            jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, sub),)),
            jnp.concatenate([under, t_ii], -1)], -2)
    return t


@jax.custom_vjp
def _solve(m, rhs):
    """(I + m)^-1 rhs for m [..., C, C] strictly lower, rhs [..., C,
    d]: the inverse formed, then ONE product."""
    return _solve_fwd(m, rhs)[0]


def _solve_fwd(m, rhs):
    t = _unit_lower_inverse(m)
    w = _mm('...ij,...jd->...id', t, rhs)
    return w, (t, w)


def _solve_bwd(saved, d_w):
    """The closed form (d_rhs = T^T d_w, d_m = -strictly_lower(d_rhs
    w^T)): two products, nothing differentiated through the
    substitution."""
    t, w = saved
    d_rhs = _mm('...ji,...jd->...id', t, d_w)
    return -jnp.tril(_mm('...id,...jd->...ij', d_rhs, w), -1), d_rhs


_solve.defvjp(_solve_fwd, _solve_bwd)


def _prepare(q, k, v, a, beta, scores=_scores):
    """q, k, a [..., C, dk], v [..., C, dv], beta [..., C] of whole
    chunks (leading axes: chunk, sequence, head) -> the scan's operands
    (W_k [..., C, dk], W_v [..., C, dv], Qbar, B [..., C, C], Khat,
    exp(G_C) [..., dk]).  ``scores``: ``_scores`` or the kernel."""
    dk = k.shape[-1]
    g = jnp.cumsum(a, axis=-2)                          # G, inclusive
    g_end = g[..., -1:, :]
    q_bar, k_bar = q * jnp.exp(g), k * jnp.exp(g)
    k_hat = k * jnp.exp(g_end - g)
    a_mat, b_mat = scores(q, k, g)
    system = beta[..., None] * a_mat
    written = beta[..., None] * jnp.concatenate([k_bar, v], -1)
    with jax.named_scope('inverse'):
        w = _solve(system, written)
    return (w[..., :dk], w[..., dk:], q_bar, b_mat, k_hat,
            jnp.exp(g_end[..., 0, :]))


def _step(state, operands):
    """One chunk: the state [B, H, dk, dv] it starts from -> (the state
    it ends with, its outputs [B, H, C, dv])."""
    w_k, w_v, q_bar, b_mat, k_hat, decay = operands
    u = w_v - _mm('bhck,bhkv->bhcv', w_k, state)
    out = _mm('bhck,bhkv->bhcv', q_bar, state) + \
        _mm('bhcj,bhjv->bhcv', b_mat, u)
    state = decay[..., None] * state + _mm('bhck,bhcv->bhkv', k_hat, u)
    return state, out


def _count_chunks(operands):
    """``kda/chunks``: the chunk steps the walks of the traced program
    take, the forward's and the reverse walk's alike: a scan's trips or
    a kernel's sequential grid steps a head block."""
    registry.trace_sum('kda/chunks', operands[0].shape[0])


def _layout(t, chunk):
    """-> (chunk size as run, chunks): a sequence shorter than a chunk
    is one chunk of whole sub-chunks."""
    chunk = min(int(chunk), -(-t // SUB) * SUB)
    return chunk, -(-t // chunk)


def _chunked(x, chunk, n, dtype):
    """[B, T, H, ...] -> [N, B, H, C, ...] in ``dtype``, the tail
    padded with zeros: tokens that neither decay nor write (a = 0,
    beta = 0, k = 0)."""
    b, t = x.shape[:2]
    x = jnp.pad(x.astype(dtype), ((0, 0), (0, n * chunk - t)) +
                ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, n, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)


def _unchunked(out, t, dtype):
    """``_chunked``'s reverse for the kernels' o: [N, B, H, C, dv] ->
    [B, t, H, dv] in ``dtype``, the padded tail cut off."""
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)   # [B, N, C, H, dv]
    b, _, _, h, dv = out.shape
    return out.reshape(b, -1, h, dv)[:, :t].astype(dtype)


def _working_dtype(v):
    return jnp.float64 if v.dtype == jnp.float64 else jnp.float32


def _paths(k, v, chunk, auto_partitioned):
    """How this call's chunks are prepared and how they are walked,
    (preparation, walk), each 'dense' (``_prepare`` with ``_scores``;
    the ``lax.scan`` over ``_step``), 'fused' (the ``kda_chunk``
    kernels; the ``kda_walk`` kernels) or 'interpret' (the kernels'
    bodies under the Pallas interpreter: FLAGS_pallas_force off a TPU).
    One ``common.dispatch`` decision a kernel and call, which the call's
    forward and its backward both follow; each kernel's layout decides
    it from what the operands show (``kda_chunk.checks``,
    ``kda_walk.checks``)."""
    from .pallas import common, kda_chunk, kda_walk
    size, dtype = _layout(k.shape[1], chunk)[0], _working_dtype(v)

    def path(kernel, checks):
        fused, interpret = common.dispatch(
            kernel, True, checks=checks, auto_partitioned=auto_partitioned)
        return ('interpret' if interpret else 'fused') if fused else 'dense'

    return (path('kda_chunk', kda_chunk.checks(
                size, k.shape[-1], v.shape[-1], dtype)),
            path('kda_walk', kda_walk.checks(
                k.shape[2], size, k.shape[-1], v.shape[-1], dtype)))


def _operands(q, k, v, a, beta, chunk, path):
    """The walk's operands of every chunk.  Where the chunks are walked
    densely ``_step``'s six, (W_k, W_v, Qbar, B, Khat, exp(G_C)); for
    the walk's kernels five, W = [W_k | W_v] one array as the product
    leaves it (they read its two halves as lanes of one block).  On the
    fused path the ``kda_chunk`` kernel makes everything but W from the
    op's inputs as they arrived, and XLA holds the inverse and its one
    product."""
    dk = k.shape[-1]
    chunk, n = _layout(k.shape[1], chunk)
    if path[0] == 'dense':
        operands = _prepare(*(_chunked(x, chunk, n, _working_dtype(v))
                              for x in (q, k, v, a, beta)))
        if path[1] != 'dense':
            operands = (jnp.concatenate(operands[:2], -1),) + operands[2:]
        return operands
    from .pallas import kda_chunk
    system, b_mat, q_bar, k_hat, written, decay = kda_chunk.prepare(
        q, k, v, a, beta, chunk, path[0] == 'interpret')
    with jax.named_scope('inverse'):
        w = _solve(system, written)
    if path[1] == 'dense':
        return w[..., :dk], w[..., dk:], q_bar, b_mat, k_hat, decay
    return w, q_bar, b_mat, k_hat, decay


def _forward(q, k, v, a, beta, chunk, path):
    """-> (o [B, T, H, dv] in v's dtype, the state at each chunk's
    START [N, B, H, dk, dv]; the walk's kernels keep it transposed)."""
    operands = _operands(q, k, v, a, beta, chunk, path)
    _count_chunks(operands)
    if path[1] != 'dense':
        from .pallas import kda_walk
        out, starts = kda_walk.forward(operands,
                                       interpret=path[1] == 'interpret')
        return _unchunked(out, k.shape[1], v.dtype), starts
    w_k, w_v = operands[0], operands[1]

    def step(state, x):
        after, out = _step(state, x)
        return after, (out, state)

    zero = jnp.zeros(w_k.shape[1:3] + (w_k.shape[-1], w_v.shape[-1]),
                     w_k.dtype)
    _, (out, starts) = jax.lax.scan(step, zero, operands)
    b, t, h = k.shape[:3]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)   # [B, N, C, H, dv]
    out = out.reshape(b, -1, h, v.shape[-1])[:, :t]
    return out.astype(v.dtype), starts


def gated_delta_rule(q, k, v, a, beta, chunk=CHUNK, auto_partitioned=False):
    """q, k, a [B, T, H, dk], v [B, T, H, dv], beta [B, T, H] -> o [B,
    T, H, dv] in v's dtype.  T need be no whole number of chunks.
    ``auto_partitioned``: ``common.dispatch``'s (the caller's word that
    XLA will partition this program over a mesh)."""
    return _rule(q, k, v, a, beta, chunk,
                 _paths(k, v, chunk, auto_partitioned))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, a, beta, chunk, path):
    return _forward(q, k, v, a, beta, chunk, path)[0]


def _rule_fwd(q, k, v, a, beta, chunk, path):
    out, starts = _forward(q, k, v, a, beta, chunk, path)
    return out, ((q, k, v, a, beta), starts)


def _rule_bwd(chunk, path, saved, d_out):
    """What the forward kept is what it was handed and the state at
    each chunk's start: the per-chunk operands are computed again
    (under ``jax.vjp``, which then carries their cotangents back to q,
    k, v, a and beta: through the preparation's kernel by ITS backward
    kernel), and the chunks walked in reverse with the state's
    cotangent: by the walk's reverse kernel, or each chunk's ``_step``
    under a ``jax.vjp`` of its own.

    The barrier ties the saved inputs to the cotangent, so the operands
    are computed again WHEN the backward runs.  Without it the
    recomputation depends on the forward's inputs alone and the
    compiler runs it in the forward, beside the forward's own, and
    holds its results across the step: in ``solar_open2_250b_s4096``
    (no recompute group around the op: a group places this barrier
    itself) seven float32 arrays a layer from the forward to the
    backward, 128 MB of the step's peak and, some of them resident in
    the core's fast memory all that time, the room 24 casts of the
    experts' weights had there (0 of 24 fit where 21 had: the step 4 ms
    slower, PERF.md section 6, PR 62)."""
    inputs, starts = saved
    size, n = _layout(inputs[1].shape[1], chunk)
    # through the barrier in the orders their readers take, the inputs
    # [B, T, H x d] and the cotangent in chunks: a barrier fixes its
    # operands' layouts, and the op's own four-dimensional ones cost
    # Kimi Linear's cell a copy an operand, 13 ms a step
    flat = tuple(x.reshape(x.shape[:2] + (-1,)) for x in inputs)
    flat, d_chunks = jax.lax.optimization_barrier(
        (flat, _chunked(d_out, size, n, starts.dtype)))
    inputs = tuple(x.reshape(y.shape) for x, y in zip(flat, inputs))
    operands, pull = jax.vjp(
        lambda *x: _operands(*x, chunk, path), *inputs)
    _count_chunks(operands)
    if path[1] != 'dense':
        from .pallas import kda_walk
        return pull(kda_walk.reverse(operands, starts, d_chunks,
                                     interpret=path[1] == 'interpret')[0])

    def step(d_state, x):
        chunk_operands, start, d_chunk_out = x
        _, pull_step = jax.vjp(_step, start, chunk_operands)
        return pull_step((d_state, d_chunk_out))

    _, d_operands = jax.lax.scan(
        step, jnp.zeros_like(starts[0]), (operands, starts, d_chunks),
        reverse=True)
    return pull(d_operands)


_rule.defvjp(_rule_fwd, _rule_bwd)


@register('kda_attention')
def kda_attention(ctx, ins, attrs):
    """Q, K, A [B, T, H, dk], V [B, T, H, dv], Beta [B, T, H] -> Out
    [B, T, H, dv] in chunks of ``CHUNK`` tokens: the module's docstring
    has the equations."""
    from ..fluid import monitor
    monitor.add('kda/calls', 1)
    out = gated_delta_rule(
        ins['Q'][0], ins['K'][0], ins['V'][0], ins['A'][0],
        ins['Beta'][0],
        auto_partitioned=ctx.auto_partitioned)
    return {'Out': [out]}
