"""The gated delta rule's walk over the chunks on the chip
(``ops/kda_ops.py`` has the equations): per sequence and head, with a
float32 state ``S`` [dk, dv] that is zero at the sequence's start and
the operands the preparation made for every chunk at once,

    u = W_v - W_k S        o = Qbar S + B u        S <- exp(G_C) S + Khat^T u

a chunk after the other inside ONE call a walk.  The dense form
(``kda_ops._step`` under a ``lax.scan``) is a ``while`` of T / chunk
trips, each three small products (about nine in reverse, ``jax.vjp`` of
the step) in fusions of their own around a state that crosses HBM at
every fusion's boundary, and a stack of per-chunk results the loop
zeroes first.  Here the state never leaves the core between a
sequence's first chunk and its last.  A call takes what the scan takes
and gives what it gives: the operands [N, B, H, C, .] (``W_k`` and
``W_v`` as one array), ``o`` [N, B, H, C, dv] float32 and the state at
each chunk's start; in reverse the starts and ``o``'s cotangent in, the
operands' cotangents out.

THE GRID is (sequence, head block, chunk), the chunks LAST and
sequential; where the chunk index is 0 the state is zeroed, so nothing
crosses from one sequence into the next.  A grid step holds ``HEADS``
heads of one chunk (a static loop in the body: eight independent chains
for the scheduler, and the step's fixed cost is paid once for eight
heads' 1.4 MB of operands); the count divides H and is a whole number
of sublane tiles or H itself, what a block of ``exp(G_C)`` [.., H, dk]
asks.

LAYOUT.  Every operand and every cotangent is chunk-major, [N, B, H,
C, .], the order its producer writes it in and its consumer reads it
in: ``W = [W_k | W_v]`` ONE array [.., C, dk + dv] as the solve's
product leaves it (the body reads its two halves as lanes of one
block, and writes ``dW`` the same way for the solve's pull-back: no
slice and no ``concatenate`` is materialised around a call); ``Qbar``,
``B``, ``Khat`` as the ``kda_chunk`` kernel writes them, and their
cotangents as its backward reads them.  (Until PR 62 ``Qbar`` and
``Khat`` were XLA's elementwise results, which the compiler lays rows
first, [B, C, N, H, dk], and the calls followed THAT order through a
strided block; handed chunk-major each had cost a 134 MB transposing
copy a call at Kimi Linear's shape.  A Mosaic call fixes its operands'
layouts: the order to take is the producer's, PERF.md section 6, PRs
61 and 62.)  ``o`` leaves float32 and chunk-major like the scan's,
and ``d_o`` arrives so: what reads ``o`` next (a norm over each head's
dv) wants the tokens innermost, so the one copy on the way out is the
compiler's to place (it fuses ``d_o``'s way in into the norm's
gradient).  Written through a block map into the op's own [B, T, H x
dv] in v's dtype it cost that copy AFTER the call, 6 ms a step and two
float32 copies of ``o`` kept for the backward (PERF.md section 6, PR
61).

THE STATE LIES TRANSPOSED, ``S^T`` [dv, dk], in the scratch, in
``starts`` [N, B, H, dv, dk] (the residual the dense form keeps as [N,
B, H, dk, dv]: the same bytes, private to the two calls) and in the
reverse walk's carry.  The decay is one number a KEY channel: with dk
on the lanes ``exp(G_C)`` multiplies the state as the row it arrives
as, broadcast over sublanes, and its cotangent ``sum_v dS . S`` is a
sum over sublanes that lands as a row; with dk on the sublanes each
would be a lane-to-sublane relayout a head and step.  The products are
the same ones with the contraction's sides named accordingly.

THE MATHEMATICS is ``_step``'s and its pull-back's, term for term:
float32 state, decays, sums and products (``_dot`` at
``Precision.HIGHEST``, where Mosaic's lie where XLA's do: PERF.md
section 6, PR 47).  The reverse call runs the same grid with the chunks
counted DOWN, recomputes ``u`` from the kept start, and with ``d_u =
B^T d_o + Khat dS`` writes

    dW_k = -d_u S^T    dW_v = d_u    dQbar = d_o S^T    dB = d_o u^T
    dKhat = u dS^T     d exp(G_C) = sum_v dS . S
    dS <- Qbar^T d_o + exp(G_C) dS - W_k^T d_u

The carry ``dS`` is an OUTPUT block that does not move along the chunk
axis (zeroed at the last chunk, resident until the sequence's first):
what it holds at the end is the start state's cotangent, which the op
drops (the start is zero) and the tests read.

A step is bound by the MXU's passes, not by its 2 MB of DMA: six bf16
passes a float32 product, and at C = 64 rows a product fills half the
array's depth.  Products that share their stationary side are ONE
product of 2 C rows (``W_k S`` with ``Qbar S``; ``d_u S^T`` with ``d_o
S^T``; ``Qbar^T d_o - W_k^T d_u`` as one contraction 2 C deep): 15.2 ->
13.7 us a forward trip of 32 heads and 26.6 -> 22.2 a reverse one on a
v5e, where the scans took 27.8 and 69.0 (one pass a product reads 11.1
and 18.8, the DMA alone 10.7).  Tried and dropped (PERF.md, the same
entry): sixteen heads a grid step (no faster: the step's fixed cost is
not what is left), the forward's ``starts`` not written (no faster).

Dispatch is ``kda_ops``'s (``common.dispatch``, once a call).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common as _common
from .flash_attention import _dot

HEADS = 8       # heads a grid step, where H is a multiple of it
_F32 = jnp.float32
_common.register_kernel(
    'kda_walk',
    dense_fallback='ops.kda_ops._step',
    has_vjp=True,
    doc='the gated delta rule\'s walk over the chunks, forward and '
        'reverse, with the [dk, dv] state held on the core from a '
        'sequence\'s first chunk to its last; dispatches dense off '
        'float32 / dk, dv % 128',
    op_types=('kda_attention',))


def heads_a_step(h):
    """Heads a grid step takes: ``HEADS`` where H is a multiple of it,
    else all of H (a block of ``exp(G_C)`` [.., H, dk] is whole sublane
    tiles of heads, or every head)."""
    return HEADS if h % HEADS == 0 else h


def reverse_vmem(heads, c, dk, dv):
    """Bytes one instance of the reverse call holds in VMEM, as they
    lie: a step's operands and their cotangents (B's rows padded to the
    128 lanes), the start, the carry and ``d_o`` in the pipeline's two
    buffers each, and what the compiler lays beside them for the head
    it works on (six states' and eight row blocks' worth: within 10% of
    what Mosaic reports at the cells' shapes).  The forward holds
    less."""
    rows = 4 * c * (3 * dk + dv + max(c, 128))
    decays = 4 * max(heads, 8) * dk
    state = 4 * dk * dv
    blocks = heads * (2 * rows + 2 * state + 4 * c * dv) + 2 * decays
    return 2 * blocks + 6 * state + 8 * 4 * c * max(dk, dv)


def checks(h, c, dk, dv, dtype):
    """``common.dispatch``'s gates, from what the operands show: a
    float32 working dtype; both widths in whole 128-lane tiles and a
    chunk of whole sublane tiles; the reverse call's count under the
    budget of a call that asks Mosaic for nothing."""
    return (('dtype', dtype == _F32),
            ('layout', c % 8 == 0 and dk % 128 == 0 and dv % 128 == 0),
            ('vmem_over_budget',
             reverse_vmem(heads_a_step(h), c, dk, dv) <=
             _common.VMEM_BUDGET_BYTES))


def _forward_kernel(w_ref, q_bar_ref, b_ref, k_hat_ref, decay_ref,
                    o_ref, starts_ref, state_ref):
    """One chunk of ``heads`` heads: W = [W_k | W_v] [heads, C, dk +
    dv] and the other operands [heads, C, .], the decay [heads, dk] ->
    o [heads, C, dv] and the state the chunk starts from, S^T [heads,
    dv, dk]."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    starts_ref[...] = state_ref[...]
    c, dk = q_bar_ref.shape[1:]
    for h in range(w_ref.shape[0]):
        state = state_ref[h]
        # W_k S and Qbar S in one product: the state is the MXU's
        # stationary side, loaded once for 2 C rows
        both = _dot(jnp.concatenate([w_ref[h, :, :dk], q_bar_ref[h]], 0),
                    state, (1, 1))
        u = w_ref[h, :, dk:] - both[:c]
        o_ref[h] = both[c:] + _dot(b_ref[h], u, (1, 0))
        state_ref[h] = decay_ref[h:h + 1, :] * state + \
            _dot(u, k_hat_ref[h], (0, 0))


def _reverse_kernel(w_ref, q_bar_ref, b_ref, k_hat_ref, decay_ref,
                    starts_ref, d_o_ref, d_w_ref, d_q_bar_ref, d_b_ref,
                    d_k_hat_ref, d_decay_ref, d_state_ref):
    """One chunk of ``heads`` heads, the chunks counted down: what the
    forward kernel saw, the start it wrote and o's cotangent -> the five
    operands' cotangents; ``d_state_ref`` [heads, dv, dk] carries the
    state's."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    c, dk = q_bar_ref.shape[1:]
    for h in range(w_ref.shape[0]):
        state, d_state = starts_ref[h], d_state_ref[h]
        w_k, decay, d_o = w_ref[h, :, :dk], decay_ref[h:h + 1, :], d_o_ref[h]
        u = w_ref[h, :, dk:] - _dot(w_k, state, (1, 1))
        d_u = _dot(b_ref[h], d_o, (0, 0)) + \
            _dot(k_hat_ref[h], d_state, (1, 1))
        d_w_ref[h, :, dk:] = d_u
        # d_u S^T and d_o S^T in one product, and below Qbar^T d_o -
        # W_k^T d_u in one of 2 C rows' depth
        through = _dot(jnp.concatenate([d_u, d_o], 0), state, (1, 0))
        d_w_ref[h, :, :dk] = -through[:c]
        d_q_bar_ref[h] = through[c:]
        d_b_ref[h] = _dot(d_o, u, (1, 1))
        d_k_hat_ref[h] = _dot(u, d_state, (1, 0))
        d_decay_ref[h:h + 1, :] = jnp.sum(d_state * state, 0, keepdims=True)
        d_state_ref[h] = decay * d_state + _dot(
            jnp.concatenate([d_o, -d_u], 0),
            jnp.concatenate([q_bar_ref[h], w_k], 0), (0, 0))


@functools.partial(jax.jit, inline=True,
                   static_argnames=('heads', 'interpret'))
def _call(w, q_bar, b_mat, k_hat, decay, *rest, heads, interpret):
    """The forward kernel over the walk's operands -> (o [N, B, H, C,
    dv], S^T at each chunk's start [N, B, H, dv, dk]), or, given those
    starts and o's cotangent, the reverse one -> the five cotangents
    and the start state's [B, H, dv, dk].  Under a jit cache of its
    own, ``inline`` (as kda_chunk._call): a body is traced once a
    process and shape and its instruction keeps the name of the scope
    the caller lowered it in."""
    n, b, h, c, dk = q_bar.shape
    dv = w.shape[-1] - dk
    heads = heads or heads_a_step(h)
    last = n - 1 if rest else 0         # the walk's first chunk

    def per_chunk(x):
        """``heads`` heads of one chunk of a [N, B, H, ..] array."""
        tail = x.shape[3:]
        return pl.BlockSpec(
            (None, None, heads) + tail,
            lambda i, j, s: (last - s if rest else s, i, j) +
            (0,) * len(tail))

    operands = (w, q_bar, b_mat, k_hat, decay)
    specs = [per_chunk(x) for x in operands]
    starts = jax.ShapeDtypeStruct((n, b, h, dv, dk), _F32)
    kwargs = dict(
        grid=(b, h // heads, n), interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))
    if not rest:
        out = jax.ShapeDtypeStruct((n, b, h, c, dv), _F32)
        return pl.pallas_call(
            _forward_kernel, in_specs=specs,
            out_specs=[per_chunk(out), per_chunk(starts)],
            out_shape=[out, starts],
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
            name='kda_walk_forward', **kwargs)(*operands)
    return pl.pallas_call(
        _reverse_kernel, in_specs=specs + [per_chunk(x) for x in rest],
        out_specs=specs + [pl.BlockSpec((None, heads, dv, dk),
                                        lambda i, j, s: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32) for x in operands] +
        [jax.ShapeDtypeStruct((b, h, dv, dk), _F32)],
        name='kda_walk_reverse', **kwargs)(*operands, *rest)


def forward(operands, heads=None, interpret=False):
    """The walk's five operands of whole chunks (W = [W_k | W_v] [N, B,
    H, C, dk + dv], Qbar, B, Khat [N, B, H, C, .], exp(G_C) [N, B, H,
    dk]; ``checks`` holds) -> (o [N, B, H, C, dv], S^T at each chunk's
    START [N, B, H, dv, dk]), both float32: what the scan over ``_step``
    stacks, the state transposed.  ``heads``: heads a grid step
    (``heads_a_step`` of H where None)."""
    return _call(*operands, heads=heads, interpret=interpret)


def reverse(operands, starts, d_out, heads=None, interpret=False):
    """``forward``'s operands, the starts it kept and o's cotangent [N,
    B, H, C, dv] float32 -> (the five operands' cotangents, the start
    state's [B, H, dv, dk])."""
    grads = _call(*operands, starts, d_out, heads=heads, interpret=interpret)
    return tuple(grads[:5]), grads[5]
