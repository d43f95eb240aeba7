"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

NEW capability vs the reference (SURVEY.md §2.4: no expert parallelism
exists in fluid v1.6; the closest analog is the sparse parameter-server
path, `framework/fleet/fleet_wrapper.h:55`, which shards *tables* across
hosts).  TPU-native design follows GShard: experts are sharded over the
'ep' axis, tokens are routed to them with `jax.lax.all_to_all` over ICI,
and the dispatch/combine maps are dense one-hot tensors so everything is
static-shaped MXU work — no scatter with data-dependent shapes.

Differentiable end-to-end: all_to_all and the one-hot einsums are linear,
so jax.vjp routes token grads back through the same ring.

The second half of the file is the DROPLESS path (OLMoE-style, any
top_k): sort-and-group.  The one-hot maps above are [S, E, C]; at
S = 8192, E = 64, k = 8 that is 2 GB a map, so routing there is a sort
of the S*k (token, expert) pairs by expert, a row gather, one grouped
(ragged) matmul per expert matrix and a weighted sum back.  Shapes stay
static (S*k rows whatever the routing), no token is dropped, and every
backward is again a gather: the sort is a permutation, so its inverse
replaces the scatter-add.  A layer that holds a RANGE of its experts
(one chip's share of an expert-parallel layer) fills a small part of
that buffer; there the weighted sum back and both backward bodies walk
the buffer's rows instead of every pair, in chunks up to the last row
held, and the per-token sums are scatter-adds of those few rows
(held_rows_chunk).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as _shard_map


def top1_gating(x, wg, n_experts, capacity):
    """Top-1 gating (Switch-style) producing dense dispatch/combine
    maps; see topk_gating."""
    return topk_gating(x, wg, n_experts, capacity, top_k=1)


def topk_gating(x, wg, n_experts, capacity, top_k=1):
    """Top-k gating (k=1 Switch, k=2 GShard) producing dense
    dispatch/combine maps.

    x: [S, D] local tokens.  wg: [D, E].  Returns
      dispatch [S, E, C] one-hot, combine [S, E, C] gate-weighted,
      aux_loss (load-balance loss).

    k=2 (the GShard design): each token also routes to its
    second-choice expert with the gates RENORMALIZED over the two
    choices; second-choice tokens queue BEHIND every first-choice
    token of that expert, so under capacity pressure the overflow
    drops second choices first — the GShard overflow policy.  The aux
    loss stays the Switch/GShard form over FIRST-choice density."""
    if top_k not in (1, 2):
        raise ValueError('topk_gating supports top_k in (1, 2)')
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # [S, E]
    e1 = jnp.argmax(probs, axis=-1)                         # [S]
    oh1 = jax.nn.one_hot(e1, n_experts, dtype=jnp.float32)
    # position of each token within its expert's first-choice queue
    pos1 = jnp.sum((jnp.cumsum(oh1, axis=0) - 1.0) * oh1, axis=-1)
    keep1 = pos1 < capacity
    g1 = jnp.max(probs * oh1, axis=-1)
    # load-balance aux loss: E * sum_e fraction_e * mean_prob_e
    density = jnp.mean(oh1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * n_experts

    def maps(onehot, pos_in_expert, keep, gate):
        pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32),
                                capacity, dtype=jnp.float32)
        dispatch = onehot[:, :, None] * pos_oh[:, None, :] * \
            keep[:, None, None]
        return dispatch, dispatch * gate[:, None, None]

    if top_k == 1:
        dispatch, combine = maps(oh1, pos1, keep1, g1 * keep1)
        return dispatch, combine, aux

    probs2 = probs * (1.0 - oh1)                            # mask 1st
    e2 = jnp.argmax(probs2, axis=-1)
    oh2 = jax.nn.one_hot(e2, n_experts, dtype=jnp.float32)
    g2 = jnp.max(probs2 * oh2, axis=-1)
    # renormalize the pair (GShard): each kept route carries its share
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1n, g2n = g1 / denom, g2 / denom
    # second-choice positions start after ALL first-choice tokens of
    # that expert
    first_counts = jnp.sum(oh1, axis=0)                     # [E]
    pos2 = jnp.sum((jnp.cumsum(oh2, axis=0) - 1.0) * oh2, axis=-1) + \
        jnp.sum(oh2 * first_counts[None, :], axis=-1)
    keep2 = pos2 < capacity
    d1, c1 = maps(oh1, pos1, keep1, g1n * keep1)
    d2, c2 = maps(oh2, pos2, keep2, g2n * keep2)
    return d1 + d2, c1 + c2, aux


def moe_ffn_inner(x, wg, w1, w2, axis_name, capacity_factor=2.0,
                  top_k=1):
    """Call INSIDE shard_map.  Expert-parallel MoE FFN.

    x:  [S, D] tokens local to this shard (any sharding of the batch).
    wg: [D, E] gate weights (replicated).
    w1: [E_loc, D, H], w2: [E_loc, H, D] — experts sharded over
        `axis_name` (E = n_shards * E_loc).
    Returns ([S, D], aux_loss).
    """
    n_shards = jax.lax.psum(1, axis_name)
    e_loc = w1.shape[0]
    n_experts = n_shards * e_loc
    s, d = x.shape
    # GShard capacity: C = k * cf * S / E — each of a token's k routes
    # needs a slot, so per-expert headroom scales with top_k
    capacity = max(1, int(top_k * capacity_factor * s / n_experts))

    dispatch, combine, aux = topk_gating(x, wg, n_experts, capacity,
                                         top_k)
    # gather expert inputs: [E, C, D]
    expert_in = jnp.einsum('sec,sd->ecd', dispatch, x.astype(jnp.float32))
    # scatter expert dim over shards, concat token dim:
    # [E, C, D] -> [E_loc, n_shards * C, D]
    expert_in = jax.lax.all_to_all(
        expert_in.reshape(n_shards, e_loc, capacity, d), axis_name, 0, 0
    ).transpose(1, 0, 2, 3).reshape(e_loc, n_shards * capacity, d)
    # per-local-expert FFN (vmapped over E_loc -> batched MXU matmuls)
    h = jax.nn.relu(jnp.einsum('ecd,edh->ech', expert_in, w1))
    expert_out = jnp.einsum('ech,ehd->ecd', h, w2)
    # route back: [E_loc, n_shards*C, D] -> [E, C, D] on each shard
    expert_out = jax.lax.all_to_all(
        expert_out.reshape(e_loc, n_shards, capacity, d).transpose(
            1, 0, 2, 3), axis_name, 0, 0).reshape(n_experts, capacity, d)
    out = jnp.einsum('sec,ecd->sd', combine, expert_out)
    return out.astype(x.dtype), aux


def moe_ffn(x, wg, w1, w2, mesh, axis='ep', capacity_factor=2.0,
            top_k=1):
    """Global-array wrapper.  x [B, T, D] with the batch sharded over
    `axis` (the canonical GShard layout: the expert axis doubles as a
    data axis for tokens); experts sharded on `axis` via the leading dim
    of w1 [E, D, H] / w2 [E, H, D].  Returns (out [B, T, D], aux)."""
    b, t, d = x.shape
    b_loc = b // mesh.shape[axis]

    def inner(xf, wg_, w1_, w2_):
        out, aux = moe_ffn_inner(xf.reshape(b_loc * t, d), wg_, w1_, w2_,
                                 axis, capacity_factor, top_k)
        return out.reshape(b_loc, t, d), jax.lax.pmean(aux, axis)

    f = _shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=(P(axis), P()))
    return f(x, wg, w1, w2)


def reference_moe_ffn(x, wg, w1_full, w2_full, capacity_factor=2.0,
                      top_k=1):
    """Dense single-device reference: w1_full [E, D, H], w2_full
    [E, H, D].  Capacity is computed from x's own token count, so to
    reproduce the sharded version's per-shard capacity semantics, call
    this on each shard's batch slice and concatenate."""
    b, t, d = x.shape
    s = b * t
    e = w1_full.shape[0]
    capacity = max(1, int(top_k * capacity_factor * s / e))
    dispatch, combine, aux = topk_gating(x.reshape(s, d), wg, e,
                                         capacity, top_k)
    expert_in = jnp.einsum('sec,sd->ecd', dispatch,
                           x.reshape(s, d).astype(jnp.float32))
    h = jax.nn.relu(jnp.einsum('ecd,edh->ech', expert_in, w1_full))
    expert_out = jnp.einsum('ech,ehd->ecd', h, w2_full)
    out = jnp.einsum('sec,ecd->sd', combine, expert_out)
    return out.reshape(b, t, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Dropless routing: sort, gather, grouped matmuls, weighted sum back
# ---------------------------------------------------------------------------


def route_topk(x, wg, top_k, renormalize=False, scale=1.0,
               score_func='softmax', choice_bias=None,
               renorm_eps=1e-20):
    """The router, in float32 whatever ``x`` is.  x [S, D], wg [D, E] ->
    (idx [S, k] int32, weight [S, k] f32, balance loss, z-loss,
    load [E] int32).

    ``score_func`` 'softmax' (the default): ``weight`` are the top-k of
    the softmax over ALL experts, divided by their sum only under
    ``renormalize`` (OLMoE publishes ``norm_topk_prob: false``), times
    ``scale`` (a routed scaling factor; 1.0 multiplies nothing).

    ``score_func`` 'sigmoid': each expert's score is the sigmoid of its
    own logit.  ``choice_bias`` [E] (sigmoid only) enters the CHOICE
    and nothing else: the k experts are the largest of score + bias,
    and ``weight`` are their plain scores, under ``renormalize``
    divided by (their sum + ``renorm_eps``: 1e-20 as DeepSeek-V3's
    code, 1e-6 as LFM2's), times ``scale``: the bias picks and
    never weighs, and takes no gradient (the auxiliary-loss-free
    balancing of DeepSeek-V3, ``topk_method: noaux_tc`` with one
    group; bias_update() moves it).

    Balance loss: E * sum_e f_e * P_e with f_e the share of tokens that
    picked e among their k (sums to k) and P_e the mean router
    probability (sigmoid: the scores divided by their sum over the
    experts).  z-loss: mean over tokens of logsumexp(logits)^2.
    ``load`` counts the (token, expert) pairs of each expert and sums
    to S*k."""
    n_experts = wg.shape[-1]
    logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    if score_func == 'softmax':
        probs = jnp.exp(logits - lse[:, None])
        weight, idx = jax.lax.top_k(probs, top_k)
        if renormalize:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores if choice_bias is None else \
            scores + jax.lax.stop_gradient(
                choice_bias.astype(jnp.float32))[None, :]
        _, idx = jax.lax.top_k(biased, top_k)
        weight = jnp.take_along_axis(scores, idx, axis=-1)
        if renormalize:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                               + renorm_eps)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    if scale != 1.0:
        weight = weight * scale
    picked = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.int32),
                     axis=1)                                # [S, E]
    load = jnp.sum(picked, axis=0)
    share = load.astype(jnp.float32) / x.shape[0]
    balance = n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
    return (idx.astype(jnp.int32), weight, balance,
            jnp.mean(jnp.square(lse)), load.astype(jnp.int32))


def bias_update(choice_bias, load, rate):
    """The choice bias after one train step: each expert's moves by
    ``rate`` towards the mean load, up where it was picked less than
    the mean and down where more (b += rate * sign(mean - load), the
    DeepSeek-V3 report's rule)."""
    load = load.astype(jnp.float32)
    return choice_bias + rate * jnp.sign(jnp.mean(load) - load).astype(
        choice_bias.dtype)


def held_rows_bound(tokens, top_k, held=None):
    """The static row count of the sorted buffer the grouped matmuls
    are handed: every (token, expert) pair where all experts are held;
    with a held range (first, count) the most pairs that can name a
    held expert, ``tokens * min(top_k, count)`` (a token picks an
    expert at most once), so that nothing held is ever cut."""
    return tokens * (top_k if held is None else min(top_k, held[1]))


def sort_keys(idx, held=None):
    """idx [S, k] expert ids -> [S*k] int32 keys to sort the pairs by:
    the id itself, or with a held range (first, count) the expert's
    index among the held ones and ``count`` for every absent expert,
    so that the held experts' rows come first, grouped, and what the
    absent ones would compute lies past the last group."""
    flat = idx.reshape(-1)
    if held is None:
        return flat
    first, count = held
    local = flat - first
    return jnp.where((local >= 0) & (local < count), local, count)


def sort_by_expert(idx, held=None):
    """idx [S, k] -> (order [S*k], inverse [S*k]) int32: ``order`` lists
    the flat (token, choice) pairs grouped by expert (stable, so a
    group keeps token order; sort_keys() says where a held range puts
    the absent experts' pairs), ``inverse`` undoes it."""
    order = jnp.argsort(sort_keys(idx, held), stable=True).astype(
        jnp.int32)
    # lax.iota, not jnp.arange: shape inference runs this with a huge
    # stand-in for a dynamic batch, past what arange's bound check takes
    inverse = jnp.zeros_like(order).at[order].set(
        jax.lax.iota(jnp.int32, order.shape[0]), unique_indices=True)
    return order, inverse


def rows_outside_their_group(idx, order, group_sizes, held=None):
    """How many of the S*k sorted rows the grouped matmuls hand to
    another expert than the router picked, or to none: 0 when the sort
    and ``group_sizes`` agree.  Row j holds pair ``order[j]``, whose
    expert is ``idx.flat[order[j]]``; ``ragged_dot`` gives row j to the
    group whose running total of ``group_sizes`` first passes j, and to
    no group (a row of zeros) past their sum.  With a held range a pair
    routed to an absent expert belongs to no group and is no drop."""
    picked = sort_keys(idx, held)[order]
    row = jax.lax.iota(jnp.int32, order.shape[0])
    # compare_all: one [S*k, E] comparison; the default's binary search
    # is a gather per step, which a TPU does slowly
    given = jnp.searchsorted(jnp.cumsum(group_sizes), row, side='right',
                             method='compare_all')
    return jnp.sum((given != picked).astype(jnp.int32))


# A layer that holds a range of its experts fills a small part of its
# worst-case buffer (held_rows_bound): 8 of 256 experts, top-10, hold
# about 1/26 of it, 8 of 64, top-6, a seventh; and its router learns to
# pick the held experts (only their outputs reach the loss), so no
# static share of the buffer is safe for long.  The weighted sum back
# and both backward bodies therefore walk the buffer in chunks of
# held_rows_chunk() rows and stop after the chunk that holds the last
# held row: ``held_rows`` (a device scalar) bounds one loop on the
# device, and the bodies cost what the rows held cost.


def held_rows_chunk(n_rows):
    """How many of the buffer's rows one trip of a held layer's loops
    walks: a function of the buffer's static length only.  On the chip
    a trip costs what its rows cost plus a few microseconds of its own
    (PERF.md section 6, PR 33), and the last chunk's dead rows cost
    like live ones."""
    return min(n_rows, 512)


def _walk_held(n_rows, held_rows, trip, carry):
    """``carry`` after ``trip(rows, fresh, carry)`` over every chunk of
    the buffer up to the one that holds row ``held_rows - 1``, in
    order: ``rows`` are the chunk's row numbers (held_rows_chunk of
    them, consecutive) and ``fresh`` marks those no earlier trip has
    been handed.  The last chunk of a buffer its length does not
    divide starts early instead of running past the end, so every
    slice a trip takes at ``rows[0]`` (_chunk) is in range."""
    chunk = held_rows_chunk(n_rows)

    def body(i, carry):
        first = i * chunk
        rows = jnp.minimum(first, n_rows - chunk) + \
            jax.lax.iota(jnp.int32, chunk)
        return trip(rows, rows >= first, carry)

    return jax.lax.fori_loop(0, (held_rows + chunk - 1) // chunk, body,
                             carry)


def _chunk(a, rows):
    """The chunk of ``a``'s leading axis a trip was handed ``rows``
    of."""
    return jax.lax.dynamic_slice_in_dim(a, rows[0], rows.shape[0])


def _zeros(shape, dtype, held_rows):
    """Zeros for a loop over the held rows to write into, broadcast
    from a scalar the compiler cannot fold (``held_rows`` is never
    negative): it merges the constant fills of one shape across layers
    into instructions that carry no op's name, and a device trace then
    cannot say whose time they are."""
    return jnp.broadcast_to(jnp.minimum(held_rows, 0).astype(dtype), shape)


def _sum_per_token(rows, order, top_k, held_rows, tokens, weight=None):
    """rows [R, D], row j that of pair ``order[j]`` -> [tokens, D] f32:
    each token's held rows (the first ``held_rows`` of the buffer)
    summed, times their pair's gate in ``weight`` [S, k] if given.
    XLA's scatter-add into the loop's carry, a chunk a trip and one row
    at a time in the buffer's order: each token's sum is the same chain
    of f32 adds whatever the chunk, the same bits every run.  Rows past
    the held ones are never multiplied, only masked: on the chip the
    grouped matmuls leave them unwritten."""
    def trip(at, fresh, acc):
        pair = _chunk(order, at)
        part = _chunk(rows, at).astype(jnp.float32)
        if weight is not None:
            part = part * weight.reshape(-1)[pair][:, None]
        live = fresh & (at < held_rows)
        return acc.at[pair // top_k].add(
            jnp.where(live[:, None], part, 0))

    return _walk_held(
        rows.shape[0], held_rows, trip,
        _zeros((tokens, rows.shape[1]), jnp.float32, held_rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_rows(x, order, inverse, top_k, held_rows=None):
    """x [S, D] -> rows [len(order), D] in expert order (row j is token
    order[j] // k).  ``order`` may be the first R entries of the sort
    only (held_rows_bound); ``inverse`` is always the whole one.  With
    ``held_rows`` (an int32 scalar: the held experts' rows, the first
    of the buffer) the rows past them take no gradient back to x,
    whatever their cotangent holds: on the chip the grouped matmuls
    leave the rows past their last group UNWRITTEN, in the forward
    pass and in the gradient they hand back alike.  That gradient is
    then summed row-side, over the chunks of the buffer that hold a
    held row (_sum_per_token).  The forward gather is the same either
    way: it is bound by writing the buffer, which a fill costs too (on
    the chip 0.34 ms for 32,768 rows of 3072 against 0.39 for 7,680
    and zeros: PERF.md section 6, PR 31)."""
    return x[order // top_k]


def _dispatch_fwd(x, order, inverse, top_k, held_rows=None):
    return dispatch_rows(x, order, inverse, top_k, held_rows), \
        (order, inverse, held_rows)


def _dispatch_bwd(top_k, res, g):
    order, inverse, held_rows = res
    s = inverse.shape[0] // top_k
    if held_rows is None:
        dx = jnp.sum(g[inverse].reshape(s, top_k, -1).astype(
            jnp.float32), axis=1)
        return dx.astype(g.dtype), None, None, None
    dx = _sum_per_token(g, order, top_k, held_rows, s)
    return dx.astype(g.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(y, weight, order, inverse, held_rows=None):
    """y [R, D] expert-ordered outputs, weight [S, k] f32 ->
    [S, D]: each token's k outputs, weighted, summed in f32.  With
    ``held_rows`` (an int32 scalar: the held experts' rows, the first
    of the buffer) only those rows count: what lies past them, and the
    pairs whose row is not in the buffer at all, add nothing and get
    no gradient, whatever the buffer holds there; the sum and both
    gradients then walk the buffer's rows, not the S*k pairs, and of
    those the chunks that hold a held row (_walk_held)."""
    s, k = weight.shape
    if held_rows is None:
        picked = y[inverse].reshape(s, k, -1).astype(jnp.float32)
        return jnp.sum(picked * weight[:, :, None], axis=1).astype(
            y.dtype)
    return _sum_per_token(y, order, k, held_rows, s, weight).astype(
        y.dtype)


def _combine_fwd(y, weight, order, inverse, held_rows=None):
    return combine_rows(y, weight, order, inverse, held_rows), \
        (y, weight, order, inverse, held_rows)


def _combine_bwd(res, g):
    y, weight, order, inverse, held_rows = res
    s, k = weight.shape
    if held_rows is None:
        gf = g.astype(jnp.float32)
        dy = (gf[:, None, :] * weight[:, :, None]).astype(y.dtype)
        picked = y[inverse].reshape(s, k, -1).astype(jnp.float32)
        dweight = jnp.sum(picked * gf[:, None, :], axis=-1)
        return dy.reshape(s * k, -1)[order], dweight, None, None, None

    def trip(at, fresh, carry):
        # y and g are each read once: row j's gradient is its token's
        # g times its gate, its gate's gradient their product summed.
        # A row handed to two trips is written the same twice.
        dy, dweight = carry
        pair = _chunk(order, at)
        live = at < held_rows
        g_rows = g[pair // k].astype(jnp.float32)
        part = jnp.where(
            live[:, None], g_rows * weight.reshape(-1)[pair][:, None], 0)
        dgate = jnp.where(live, jnp.sum(
            _chunk(y, at).astype(jnp.float32) * g_rows, axis=-1), 0)
        return (jax.lax.dynamic_update_slice_in_dim(
                    dy, part.astype(y.dtype), at[0], 0),
                dweight.at[pair].set(dgate, unique_indices=True))

    # zeros past the chunks walked: a fill, nothing reads them
    dy, dweight = _walk_held(
        y.shape[0], held_rows, trip,
        (_zeros(y.shape, y.dtype, held_rows),
         _zeros((s * k,), jnp.float32, held_rows)))
    return dy, dweight.reshape(s, k), None, None, None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _gated(gate, up, dtype):
    """silu(gate) * up, multiplied in float32, in ``dtype``."""
    return (jax.nn.silu(gate.astype(jnp.float32)) *
            up.astype(jnp.float32)).astype(dtype)


class _RaggedDot:
    """The grouped products as the compiler computes them:
    ``jax.lax.ragged_dot`` and its two transposes (a grouped matmul is
    linear in either operand)."""

    def __init__(self, group_sizes, precision, reason):
        self.reason = reason
        self.groups = group_sizes.shape[0]
        self.dot = functools.partial(
            jax.lax.ragged_dot, group_sizes=group_sizes,
            precision=precision)

    def _counted(self):
        from ..ops.pallas import common
        common.record_dispatch('grouped_matmul', False, self.reason)

    def __call__(self, rows, w):
        """rows [M, K] x w [E, K, N] -> [M, N]."""
        self._counted()
        return self.dot(rows, w)

    # ``ragged_dot`` differentiates as it stands
    with_gradient = __call__

    def transposed(self, cot, w):
        """cot [M, N] x w [E, K, N]^T -> [M, K]."""
        self._counted()
        at = jax.ShapeDtypeStruct((cot.shape[0], w.shape[1]), cot.dtype)
        return jax.linear_transpose(lambda r: self.dot(r, w), at)(cot)[0]

    def weight_gradient(self, rows, cot):
        """rows [M, K]^T x cot [M, N] per group -> [E, K, N]."""
        self._counted()
        at = jax.ShapeDtypeStruct(
            (self.groups, rows.shape[1], cot.shape[1]), rows.dtype)
        return jax.linear_transpose(
            lambda w: self.dot(rows, w), at)(cot)[0]


class _GroupedMatmul:
    """The same three products by the kernels of
    ops/pallas/grouped_matmul.py, over one walk of the groups' row
    tiles (``visits``) that every product of a pass shares."""

    def __init__(self, group_sizes, m, reason, interpret):
        from ..ops.pallas import grouped_matmul
        self.kernels = grouped_matmul
        self.walk = grouped_matmul.visits(group_sizes, m)
        self.reason = reason
        self.interpret = interpret

    def _run(self, form, a, b):
        from ..ops.pallas import common
        common.record_dispatch('grouped_matmul', True, self.reason,
                               self.interpret)
        return getattr(self.kernels, form)(a, b, self.walk,
                                           self.interpret)

    def __call__(self, rows, w):
        return self._run('forward', rows, w)

    def with_gradient(self, rows, w):
        """The product for ``jax.vjp`` to differentiate (a kernel has
        no rule of its own): form 1's two transposes are forms 2 and
        3."""
        @jax.custom_vjp
        def product(rows, w):
            return self(rows, w)

        product.defvjp(
            lambda rows, w: (self(rows, w), (rows, w)),
            lambda kept, cot: (self.transposed(cot, kept[1]),
                               self.weight_gradient(kept[0], cot)))
        return product(rows, w)

    def transposed(self, cot, w):
        return self._run('transposed', cot, w)

    def weight_gradient(self, rows, cot):
        return self._run('weight_gradient', rows, cot)


def _squared_relu(up, dtype):
    """relu(up)^2, multiplied in float32, in ``dtype``."""
    kept = jax.nn.relu(up.astype(jnp.float32))
    return (kept * kept).astype(dtype)


# an expert's FORM: what lies between its input products (one a weight
# set, each [E, D, H]) and its down product, and the ``moe_experts``
# op's slots of those sets.  'gated': down(silu(gate x) * up x);
# 'relu2': down(relu(up x)^2), no gate
EXPERT_FORMS = {'gated': (_gated, ('WGate', 'WUp')),
                'relu2': (_squared_relu, ('WUp',))}


def expert_slots(form):
    """The ``moe_experts`` slots of the form's [E, D, H] weight sets."""
    if form not in EXPERT_FORMS:
        raise ValueError('moe: expert_form is one of %s, got %r'
                         % (sorted(EXPERT_FORMS), form))
    return EXPERT_FORMS[form][1]


def _activation(form, dtype):
    """(the input products) -> the hidden rows, in ``dtype``."""
    return functools.partial(EXPERT_FORMS[form][0], dtype=dtype)


def _operands(rows, group_sizes, weights, low_precision,
              auto_partitioned=False):
    """What the grouped matmuls multiply and how -> (dot, rows,
    weights): ``low_precision`` (AMP) casts everything to bfloat16;
    otherwise float32 operands multiply at full precision.
    ``weights``: the expert's input sets [E, D, H], then down [E, H,
    D].  ``dot(rows, w)`` is the grouped product (``dot.with_gradient``
    where ``jax.vjp`` is to differentiate it), ``dot.transposed(cot,
    w)`` and ``dot.weight_gradient(rows, cot)`` its two transposes: the
    kernels of ops/pallas/grouped_matmul.py where the operands are
    bfloat16 in whole tiles on a TPU (``common.dispatch``'s decision,
    counted a product: ``pallas/grouped_matmul/dispatch_*``), the
    compiler's ``ragged_dot`` otherwise."""
    from ..ops.pallas import common, grouped_matmul
    if low_precision:
        rows = rows.astype(jnp.bfloat16)
        weights = tuple(w.astype(jnp.bfloat16) for w in weights)
        precision = None
    else:
        precision = jax.lax.Precision.HIGHEST \
            if rows.dtype == jnp.float32 else None
    stream, width = weights[0].shape[1:]
    lanes = -(-width // 128) * 128
    fused, reason, interpret = common.decide(
        True, grouped_matmul.checks(
            rows.shape[0], (stream, lanes),
            [x.dtype for x in (rows,) + tuple(weights)]),
        auto_partitioned=auto_partitioned)
    if fused:
        dot = _GroupedMatmul(group_sizes, rows.shape[0], reason,
                             interpret)
        if lanes != width:
            # an expert width that fills no whole 128-lane tiles
            # (Nemotron-H's 1856 = 14.5 of them): zero columns of the
            # input sets and zero rows of down up to the next tile,
            # which the activation keeps zero (``_unpadded`` for the
            # gradients a custom backward forms)
            none, fill = (0, 0), (0, lanes - width)
            weights = tuple(jnp.pad(w, (none, none, fill))
                            for w in weights[:-1]) + \
                (jnp.pad(weights[-1], (none, fill, none)),)
    else:
        dot = _RaggedDot(group_sizes, precision, reason)
    return dot, rows, tuple(weights)


def _unpadded(grad, like):
    """A weight's gradient as ``_operands``'s kernels gave it, cut back
    to the weight's own width and cast to its dtype."""
    if grad.shape != like.shape:
        grad = grad[tuple(slice(0, n) for n in like.shape)]
    return grad.astype(like.dtype)


def grouped_expert_mlp(rows, group_sizes, w_in, w_down, form='gated',
                       low_precision=False, auto_partitioned=False):
    """The experts' MLP for rows grouped by expert: 'gated'
    down(silu(gate x) * up x), 'relu2' down(relu(up x)^2)
    (``EXPERT_FORMS``).

    rows [M, D] (group e is the next group_sizes[e] rows), ``w_in`` the
    form's input sets, each [E, D, H], w_down [E, H, D] -> [M, D].  One
    grouped matmul per weight set (_operands: 2*M*D*H FLOPs whatever
    the grouping).  ``low_precision`` (AMP) multiplies in bfloat16 and
    keeps the [M, H] intermediates in bfloat16; otherwise float32
    operands multiply at full precision.  ``auto_partitioned``:
    ``common.dispatch``'s (the caller's word that XLA will partition
    this program over a mesh)."""
    dot, rows, weights = _operands(
        rows, group_sizes, tuple(w_in) + (w_down,), low_precision,
        auto_partitioned)
    projected = [dot.with_gradient(rows, w) for w in weights[:-1]]
    return dot.with_gradient(
        _activation(form, rows.dtype)(*projected), weights[-1])


def _rewrite_held(held_rows, per_chunk, buffers, *read):
    """``buffers`` (a tuple of [R, .]) with every chunk of theirs up to
    the one that holds row ``held_rows - 1`` (_walk_held) replaced by
    ``per_chunk(*chunks of buffers, *chunks of read)``, in place: the
    buffers are the loop's carry.  A row handed to two trips keeps what
    the first one wrote: the second would read what was written where
    it expects what was there before.  Rows past those chunks stay as
    they were."""
    def trip(at, fresh, buffers):
        old = tuple(_chunk(a, at) for a in buffers)
        new = per_chunk(*old, *(_chunk(a, at) for a in read))
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                a, jnp.where(fresh[:, None], n, o), at[0], 0)
            for a, n, o in zip(buffers, new, old))

    return _walk_held(buffers[0].shape[0], held_rows, trip, buffers)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def held_expert_mlp(rows, group_sizes, w_in, w_down, form='gated',
                    low_precision=False, auto_partitioned=False):
    """grouped_expert_mlp for a layer that holds a range of its
    experts, whose groups fill the first ``sum(group_sizes)`` rows of a
    worst-case buffer (held_rows_bound): the same products, and
    everything between them walks the chunks of the buffer that hold a
    held row (_rewrite_held): the hidden rows are written over the
    first input product's.  Rows past those chunks stay as the grouped
    matmuls leave them, unwritten on the chip: every consumer is a
    grouped matmul that skips them again.

    Its own backward computes the input products again and keeps no
    [M, H] intermediate between the passes (the buffer's cost is its
    static length): one loop turns (the input products, dhidden) into
    (their cotangents, hidden), each over the buffer it came from, and
    where the form has two input sets a second adds the up branch's
    cotangent of ``rows`` to the gate branch's."""
    dot, rows, weights = _operands(
        rows, group_sizes, tuple(w_in) + (w_down,), low_precision,
        auto_partitioned)
    held_rows = jnp.sum(group_sizes)
    projected = tuple(dot(rows, w) for w in weights[:-1])
    hidden, = _rewrite_held(
        held_rows,
        lambda *chunks: (_activation(form, rows.dtype)(*chunks),),
        projected[:1], *projected[1:])
    return dot(hidden, weights[-1])


def _held_fwd(rows, group_sizes, w_in, w_down, form, low_precision,
              auto_partitioned):
    return held_expert_mlp(rows, group_sizes, w_in, w_down, form,
                           low_precision, auto_partitioned), \
        (rows, group_sizes, tuple(w_in), w_down)


def _held_bwd(form, low_precision, auto_partitioned, res, dout):
    # the barrier (jax.checkpoint's own) keeps the compiler from
    # sharing the forward pass's casts and products with the ones
    # computed again here, which would keep them alive in between, and
    # from computing them before ``dout`` is there
    rows, group_sizes, w_in, w_down = res
    rows, *weights, dout = jax.lax.optimization_barrier(
        (rows,) + w_in + (w_down, dout))
    dot, rows_c, weights_c = _operands(
        rows, group_sizes, weights, low_precision, auto_partitioned)
    held_rows = jnp.sum(group_sizes)

    def form_grad(*chunks):         # the input products, then dhidden
        hidden, back = jax.vjp(_activation(form, rows_c.dtype),
                               *chunks[:-1])
        return back(chunks[-1]) + (hidden,)

    projected = tuple(dot(rows_c, w) for w in weights_c[:-1])
    *dprojected, hidden = _rewrite_held(
        held_rows, form_grad,
        projected + (dot.transposed(dout, weights_c[-1]),))
    drows = dot.transposed(dprojected[0], weights_c[0])
    for dp, w in zip(dprojected[1:], weights_c[1:]):
        drows, = _rewrite_held(
            held_rows,
            lambda a, b: ((a.astype(jnp.float32) +
                           b.astype(jnp.float32)).astype(a.dtype),),
            (drows,), dot.transposed(dp, w))
    return (drows.astype(rows.dtype), None,
            tuple(_unpadded(dot.weight_gradient(rows_c, dp), w)
                  for dp, w in zip(dprojected, w_in)),
            _unpadded(dot.weight_gradient(hidden, dout), w_down))


held_expert_mlp.defvjp(_held_fwd, _held_bwd)
