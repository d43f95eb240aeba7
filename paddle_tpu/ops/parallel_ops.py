"""Sequence-parallel and expert-parallel op lowerings.

These make ring attention (context parallelism over 'sp') and GShard
MoE (expert parallelism over 'ep') FIRST-CLASS Program ops: a fluid
layer appends them like any other op, and the SAME program runs

- single-device: the one-chip lowerings (reference attention; the
  capacity-based one-hot MoE on all experts);
- under CompiledProgram.with_mesh on a mesh with 'sp'/'ep' axes: the
  lowering opens a jax.shard_map over the trace-time mesh
  (parallel.mesh.trace_mesh, published by the executor's GSPMD path)
  and runs the ppermute ring / all_to_all dispatch, with GSPMD
  resharding activations at the shard_map boundary.

This mirrors the reference's design law that every parallelism mode is
a program rewrite reachable from the user API (the collective
transpiler inserts c_* ops into the Program the same way —
python/paddle/fluid/transpiler/collective.py:36,178;
operators/collective/c_allreduce_op.h:33) — except here the "rewrite"
is a mesh-conditional lowering, so one program serves every mesh.

Gradients: both lowerings are differentiable (vjp reverses the
ppermute ring / all_to_all), so registry.grad_op_def synthesizes
ring_attention_grad / moe_ffn_grad like for any op.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as _shard_map

from .registry import register


def _nbytes(shape, itemsize=4):
    n = 1
    for d in shape:
        n *= int(d)
    return float(n * itemsize)


def _token_axes(mesh, dims, prefer):
    """Build a PartitionSpec for an activation of shape `dims`:
    dim 0 (batch) over 'dp', dim 1 (time/tokens) over `prefer` axes —
    each axis used only when present in the mesh and the dim divides
    evenly.  Returns (spec, used_axis_names)."""
    used = []
    spec = [None] * len(dims)
    if 'dp' in mesh.axis_names and dims[0] % mesh.shape['dp'] == 0 \
            and mesh.shape['dp'] > 1:
        spec[0] = 'dp'
        used.append('dp')
    taxes = []
    prod = 1
    for ax in prefer:
        if ax in mesh.axis_names and mesh.shape[ax] > 1:
            taxes.append(ax)
            prod *= mesh.shape[ax]
    if len(dims) > 1 and taxes and dims[1] % prod == 0:
        spec[1] = tuple(taxes) if len(taxes) > 1 else taxes[0]
        used.extend(taxes)
    return P(*spec), used


@register('ring_attention', stochastic=True)
def ring_attention_op(ctx, ins, attrs):
    """Q,K,V: [B, T, H, D] -> Out [B, T, H, D].

    attrs:
      causal (bool): causal masking.
      use_flash (bool): per-block engine is the Pallas flash kernel
        (long-context memory profile) instead of the online-softmax
        einsum ring.
      axis (str): mesh axis carrying the sequence shards ('sp').
      dropout_rate (float): attention-prob dropout (round 5).  The
        mask is the flash kernels' counter hash at GLOBAL positions
        (ring shards shift by their k/q offsets), keyed on the op seed
        and step — the ring-sharded and dense-fallback runs draw the
        SAME mask, and the probs still never materialize under flash.
        Skipped in test-mode lowering.

    Under a trace mesh whose `axis` has size > 1, the sequence dim is
    sharded over it and K/V blocks rotate via ppermute
    (parallel/ring_attention.py); otherwise the dense fallback runs the
    identical math on one device, so shape inference and single-chip
    execution never need a mesh.
    """
    from ..parallel import mesh as pmesh
    from ..parallel.ring_attention import (
        reference_attention, ring_attention_inner,
        ring_flash_attention_inner)

    q, k, v = ins['Q'][0], ins['K'][0], ins['V'][0]
    causal = bool(attrs.get('causal', False))
    use_flash = bool(attrs.get('use_flash', False))
    axis = attrs.get('axis', 'sp')
    rate = float(attrs.get('dropout_rate', 0.0) or 0.0)
    seed = ctx.dropout_seed(attrs) if rate else None
    if seed is None:
        rate = 0.0

    mesh = pmesh.trace_mesh()
    sp = pmesh.axis_size(mesh, axis)
    if sp > 1 and q.shape[1] % sp == 0:
        spec = [None, axis, None, None]
        if 'dp' in mesh.axis_names and mesh.shape['dp'] > 1 and \
                q.shape[0] % mesh.shape['dp'] == 0:
            spec[0] = 'dp'
        spec = P(*spec)
        # comms telemetry (trace time): each ring step forwards this
        # shard's K and V blocks to the neighbor, sp-1 rotations total
        from ..fluid import comms
        kv_itemsize = getattr(k.dtype, 'itemsize', 4)
        hop = (_nbytes(k.shape, kv_itemsize) +
               _nbytes(v.shape, kv_itemsize)) / sp
        comms.record_trace('ppermute', hop, dtype=k.dtype, axis=axis,
                           participants=sp, wire=(sp - 1) * hop)
        inner = ring_flash_attention_inner if use_flash \
            else ring_attention_inner
        if rate:
            batch_sharded = spec[0] == 'dp'

            def wrapped(q_, k_, v_, seed_):
                # batch sharded over 'dp': shift the head index to its
                # GLOBAL value or every dp shard draws the same mask
                g_off = jax.lax.axis_index('dp') * q_.shape[0] * \
                    q_.shape[2] if batch_sharded else 0
                return inner(q_, k_, v_, axis_name=axis,
                             causal=causal, dropout_rate=rate,
                             dropout_seed=seed_,
                             dropout_g_offset=g_off)

            f = _shard_map(
                wrapped, mesh=mesh,
                in_specs=(spec, spec, spec, P()), out_specs=spec)
            return {'Out': [f(q, k, v, seed)]}
        f = _shard_map(
            functools.partial(inner, axis_name=axis, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return {'Out': [f(q, k, v)]}
    if use_flash:
        from .pallas.flash_attention import mesh_flash_attention
        return {'Out': [mesh_flash_attention(
            q, k, v, ctx.auto_partitioned, 'ring_attention', causal=causal,
            dropout_rate=rate, dropout_seed=seed)]}
    if rate:
        # dense fallback with the SAME global-position hash mask the
        # ring draws (flash _dense_path implements it)
        from .pallas.flash_attention import _dense_path
        return {'Out': [_dense_path(q, k, v, causal, None, rate,
                                    seed)]}
    return {'Out': [reference_attention(q, k, v, causal=causal)]}


@register('moe_ffn', no_grad_out_slots=())
def moe_ffn_op(ctx, ins, attrs):
    """Capacity-based MoE FFN (Switch top-1 / GShard top-2).

    X [B, T, D] tokens; Gate [D, E]; W1 [E, D, H]; W2 [E, H, D].
    Outs: Out [B, T, D], AuxLoss [] (Switch load-balance loss — add it
    to the training objective scaled by attrs['aux_weight'] upstream).

    attrs['top_k'] (1=Switch, 2=GShard second-choice routing with
    renormalized gates and drop-second-first overflow — round 5).
    Under a trace mesh with an 'ep' axis (attrs['axis']), experts shard
    over 'ep' (leading dim of W1/W2) and tokens route via all_to_all
    (parallel/moe.py); tokens additionally shard over dp/sp/ep when
    divisible so no compute duplicates.  On one chip (no such axis) the
    same one-hot dispatch / combine einsums run over all experts:
    that is the only path a one-chip user of this op gets, not a
    fallback.  Dropless routing with any top_k and gated experts is
    the moe_route / moe_dispatch / moe_experts / moe_combine ops below.

    Capacity semantics match parallel.moe: per-shard capacity =
    capacity_factor * local_tokens / n_experts, so the sharded and
    dense paths agree exactly only when token counts per shard match
    (the parity tests feed shard-divisible shapes).
    """
    from ..parallel import mesh as pmesh
    from ..parallel.moe import moe_ffn_inner, reference_moe_ffn

    x, wg = ins['X'][0], ins['Gate'][0]
    w1, w2 = ins['W1'][0], ins['W2'][0]
    axis = attrs.get('axis', 'ep')
    cf = float(attrs.get('capacity_factor', 2.0))
    top_k = int(attrs.get('top_k', 1))

    mesh = pmesh.trace_mesh()
    ep = pmesh.axis_size(mesh, axis)
    if ep > 1 and w1.shape[0] % ep == 0:
        b, t, d = x.shape
        xspec, token_axes = _token_axes(mesh, (b, t), ('sp', axis))
        xspec = P(*(list(xspec) + [None]))
        b_loc = b // (mesh.shape['dp'] if 'dp' in token_axes else 1)
        t_loc = t
        for ax in token_axes:
            if ax != 'dp':
                t_loc //= mesh.shape[ax]

        # comms telemetry (trace time): dispatch + combine are two
        # all_to_alls of the [E, C, D] expert buffer (einsum promotes
        # tokens to f32), C = per-shard capacity
        from ..fluid import comms
        n_experts = int(w1.shape[0])
        capacity = max(1, int(top_k * cf * (b_loc * t_loc) / n_experts))
        a2a = _nbytes((n_experts, capacity, d), 4)
        for _ in range(2):
            comms.record_trace('all_to_all', a2a, dtype='float32',
                               axis=axis, participants=ep)

        def inner(xl, wg_, w1_, w2_):
            out, aux = moe_ffn_inner(
                xl.reshape(b_loc * t_loc, d), wg_, w1_, w2_, axis, cf,
                top_k)
            # aux is computed from this shard's tokens; average over
            # every axis the tokens are split (or replicated) across
            for ax in mesh.axis_names:
                aux = jax.lax.pmean(aux, ax)
            return out.reshape(b_loc, t_loc, d), aux

        f = _shard_map(
            inner, mesh=mesh,
            in_specs=(xspec, P(), P(axis), P(axis)),
            out_specs=(xspec, P()))
        out, aux = f(x, wg, w1, w2)
        return {'Out': [out], 'AuxLoss': [aux]}
    out, aux = reference_moe_ffn(x, wg, w1, w2, capacity_factor=cf,
                                 top_k=top_k)
    return {'Out': [out], 'AuxLoss': [jnp.asarray(aux, jnp.float32)]}


# ---------------------------------------------------------------------------
# Dropless MoE (any top_k, gated experts): four ops, so that a device
# trace tells routing, the permutation and the expert matmuls apart by
# their named scopes.  The math is parallel/moe.py's second half.
# ---------------------------------------------------------------------------


def _no_expert_axis(attrs):
    """Dropless routing is one-chip (or data-parallel) only so far."""
    from ..parallel import mesh as pmesh
    axis = attrs.get('axis', 'ep')
    if pmesh.axis_size(pmesh.trace_mesh(), axis) > 1:
        raise NotImplementedError(
            'dropless MoE routing (capacity_factor=None) over an %r '
            'mesh axis is not implemented: experts sharded over chips '
            'need a ragged all_to_all, which the olmoe_1b7b_s4096_ep4 '
            'benchmark cell will force (experts_held gives one chip '
            'its share of a layer, without the exchange); use the '
            'capacity-based path '
            '(capacity_factor=2.0, top_k <= 2) under expert parallelism'
            % (axis,))


def _held(attrs):
    """(first, count) of the experts this layer holds, or None for
    all of them."""
    held = attrs.get('experts_held')
    return None if not held else (int(held[0]), int(held[1]))


@register('moe_route',
          no_grad_out_slots=('TopKIdx', 'Load', 'HeldLoad',
                             'ScoreBiasOut'))
def moe_route_op(ctx, ins, attrs):
    """X [..., D], Gate [D, E] -> TopKIdx [S, k] int32, TopKWeight
    [S, k] f32, AuxLoss [] (load-balance), ZLoss [] (router z-loss),
    Load [E] int32 ((token, expert) pairs per expert: the group sizes
    the grouped matmuls are handed).  All float32 whatever X is: the
    router is the part of a routed model that does not survive
    bfloat16.  attrs: top_k, renormalize, scale (a factor on the
    gates), experts_held ((first, count): the router stays E wide, and
    HeldLoad [count] is Load's slice of the held experts, the group
    sizes of a layer that holds only those), score_func ('softmax',
    the default, or 'sigmoid'), renorm_eps (sigmoid under renormalize:
    what is added to the chosen scores' sum; default 1e-20).

    ScoreBias [E] f32 (sigmoid scores only) is added to the scores for
    the choice of the k experts and for nothing else
    (parallel.moe.route_topk); it takes no gradient.  With
    attrs['bias_update_rate'] > 0 the op also emits ScoreBiasOut, the
    bias after this step's loads (parallel.moe.bias_update), which the
    layer writes to the persistable bias as ``batch_norm`` does its
    MeanOut; under attrs['is_test'] (a ``for_test`` clone) it is not
    emitted and the bias stays as it is."""
    from ..parallel.moe import bias_update, route_topk
    _no_expert_axis(attrs)
    x, wg = ins['X'][0], ins['Gate'][0]
    bias = ins['ScoreBias'][0] if ins.get('ScoreBias') else None
    idx, weight, balance, z, load = route_topk(
        x.reshape(-1, x.shape[-1]), wg, int(attrs['top_k']),
        bool(attrs.get('renormalize', False)),
        float(attrs.get('scale', 1.0)),
        attrs.get('score_func', 'softmax'), bias,
        float(attrs.get('renorm_eps', 1e-20)))
    outs = {'TopKIdx': [idx], 'TopKWeight': [weight],
            'AuxLoss': [balance], 'ZLoss': [z], 'Load': [load]}
    held = _held(attrs)
    if held is not None:
        outs['HeldLoad'] = [load[held[0]:held[0] + held[1]]]
    rate = float(attrs.get('bias_update_rate', 0.0) or 0.0)
    if bias is not None and rate and not attrs.get('is_test', False):
        outs['ScoreBiasOut'] = [bias_update(bias, load, rate)]
    return outs


@register('moe_dispatch',
          no_grad_out_slots=('Order', 'Inverse', 'Dropped'))
def moe_dispatch_op(ctx, ins, attrs):
    """X [..., D], TopKIdx [S, k], GroupSizes (moe_route's Load, or
    its HeldLoad under attrs['experts_held']) -> Rows [R, D] grouped
    by expert, Order [R] / Inverse [S*k] int32 (the permutation and
    its inverse), Dropped [1] int32: the rows that sit outside the
    group of the expert their token picked, given the sizes
    moe_experts is handed (0 unless the grouping is broken; computed
    only where something reads it).  R is S*k, or with a held range
    S * min(k, count) (parallel.moe.held_rows_bound): the held
    experts' rows come first and fill it at most; the pairs routed to
    absent experts lie past the last group, are not computed and are
    no drops; the gradient walks that buffer's rows only as far as
    GroupSizes says they are held (parallel.moe._sum_per_token)."""
    from ..parallel.moe import (dispatch_rows, held_rows_bound,
                                rows_outside_their_group,
                                sort_by_expert)
    x, idx = ins['X'][0], ins['TopKIdx'][0]
    held = _held(attrs)
    order, inverse = sort_by_expert(idx, held)
    top_k = int(idx.shape[-1])
    sizes = ins['GroupSizes'][0]
    kept, held_rows = order, None
    if held is not None:
        kept = order[:held_rows_bound(idx.shape[0], top_k, held)]
        held_rows = jnp.sum(sizes)
    rows = dispatch_rows(x.reshape(-1, x.shape[-1]), kept, inverse,
                         top_k, held_rows)
    dropped = rows_outside_their_group(idx, order, sizes, held)
    return {'Rows': [rows], 'Order': [kept], 'Inverse': [inverse],
            'Dropped': [dropped.reshape(1)]}


@register('moe_experts', compiler_named=('ragged-dot',))
def moe_experts_op(ctx, ins, attrs):
    """Rows [M, D] grouped by expert, GroupSizes [E] int32, WGate /
    WUp [E, D, H], WDown [E, H, D] -> Out [M, D]:
    down(silu(gate x) * up x), one grouped matmul per weight set; in
    bfloat16 under AMP (white-listed).  With attrs['expert_form']
    'relu2' there is no WGate and the expert is down(relu(up x)^2): two
    grouped matmuls a pass (parallel.moe.EXPERT_FORMS).  bfloat16
    products on a TPU run
    the kernels of ops/pallas/grouped_matmul.py (parallel.moe._operands
    has the gates); float32 ones, and every program under the GSPMD
    runner, ``lax.ragged_dot``, which the TPU compiler turns into
    Mosaic calls whose whole op_name is its own (``ragged-dot-none``,
    ``ragged-dot-metadata``), forward and backward alike.  In a layer
    that holds a range of the experts
    (attrs['experts_held']) most of the buffer lies past the last
    group, and the grouped matmuls skip those rows: there the op is
    parallel.moe.held_expert_mlp, whose activation, its backward and
    the sum of Rows' two cotangents walk the chunks of the buffer that
    hold a held row, in place, so that what lies between the products
    follows sum(GroupSizes) as the products do (``moe/walked_share``),
    and whose backward computes the input products again instead of
    keeping the [M, H] intermediates, whose cost is M, not the rows
    held."""
    from ..parallel.moe import (expert_slots, grouped_expert_mlp,
                                held_expert_mlp)
    rows = ins['Rows'][0]
    low = bool(attrs.get('__amp__')) and \
        rows.dtype in (jnp.float32, jnp.bfloat16)
    form = attrs.get('expert_form', 'gated')
    w_in = tuple(ins[slot][0] for slot in expert_slots(form))
    mlp = grouped_expert_mlp if _held(attrs) is None else held_expert_mlp
    return {'Out': [mlp(rows, ins['GroupSizes'][0], w_in,
                        ins['WDown'][0], form, low,
                        bool(ctx is not None and ctx.auto_partitioned))]}


@register('moe_combine')
def moe_combine_op(ctx, ins, attrs):
    """Rows [R, D] expert outputs, TopKWeight [S, k], Order /
    Inverse -> Out [S, D]: each token's k outputs weighted and summed
    in float32, emitted in Rows' dtype.  With GroupSizes (a layer that
    holds a range of the experts) only the rows inside the groups
    count: a pair routed to an absent expert adds nothing, and the
    sum and its gradients walk the buffer only as far as the groups
    reach (parallel.moe.combine_rows)."""
    from ..parallel.moe import combine_rows
    held_rows = jnp.sum(ins['GroupSizes'][0]) \
        if ins.get('GroupSizes') else None
    weight = ins['TopKWeight'][0].astype(jnp.float32)
    return {'Out': [combine_rows(
        ins['Rows'][0], weight, ins['Order'][0], ins['Inverse'][0],
        held_rows)]}
