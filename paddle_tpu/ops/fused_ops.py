"""Fused op lowerings.

Reference: paddle/fluid/operators/fused/ (~7.6k LoC CUDA:
multihead_matmul, fused_elemwise_activation, fused_fc_elementwise_
layernorm, fusion_group NVRTC JIT).  On TPU most of these ARE XLA's
automatic fusions; the ones kept here either use a Pallas kernel
(attention) or encode a pattern XLA cannot see (none yet).
"""

import contextlib

import jax
import jax.numpy as jnp

from .registry import register


@register('fused_multihead_attention', stochastic=True)
def fused_multihead_attention(ctx, ins, attrs):
    """Q: [B, T, H, D], K: [B, T, Hkv, D], V: [B, T, Hkv, Dv] (+
    optional KeyBias [B, T] additive score bias, e.g. a padding mask)
    -> Out [B, T, H, Dv] via
    mesh_flash_attention(): the Pallas kernels forward and backward on
    a TPU, the dense chain elsewhere (ops/pallas/common.py
    dispatch()); a call of ``flash_attention.SMALL_KEYS`` keys or
    fewer with no mask, bias or dropout runs the small-keys kernels
    there instead (ops/pallas/small_keys.py; counter
    ``pallas/flash_attention/dispatch_small_keys``).  Under the GSPMD runner's mesh (with_data_parallel /
    with_mesh) the kernels run inside a shard_map on each device's
    share of the batch, split over the axes the runner split the
    batch over; heads are not split, a mesh's further axes see the
    call replicated, and a batch those axes do not divide answers
    dense (``fallback/batch_not_split``).

    Two attributes of the shape, read by every arm alike: K/V of
    fewer heads than Q (Hkv divides H: query head i attends K/V head
    i // (H / Hkv); the kernels read the shared head through their
    index maps and sum its gradient over the group, nothing is
    repeated in HBM), and attrs['window'] (0 = none; with 'causal',
    query i sees keys j with 0 <= i - j < window, and the kernels skip
    the blocks outside the band).  A windowed call is lowered inside a
    scope of its own, ``window<n>``, so a device trace tells it from a
    full one (the compiler names a Mosaic call after the innermost
    scope).  V may be narrower or wider than Q and K (Dv != D: a
    latent-attention head's 192-wide keys over 128-wide values; the
    scores are scaled by 1/sqrt(D), every product runs at its own
    width and nothing is padded); such a call is lowered inside the
    scope ``qk<D>v<Dv>``.

    attrs['dropout_rate'] > 0 applies attention-probability dropout
    INSIDE the kernels (reference default: dropout around softmax,
    python/paddle/fluid/layers/nn.py + operators/dropout_op.cu) with a
    mask keyed on (op seed, step) so per-op replay and whole-program
    vjp regenerate it; skipped in test-mode lowering like the dropout
    op.

    attrs['coarse_window'] with attrs['coarse_chunk'] is the third
    mask (flash_attention()'s ``coarse``): K and V are summaries, one
    a chunk of ``coarse_chunk`` positions ([B, T / chunk, Hkv, .]:
    ``eva_chunk_summary``), and query i sees those of every window of
    ``coarse_window`` positions before its own.  Such a call is
    lowered inside the scope ``remote``; as it is lowered it sets the
    gauges ``eva/remote_pairs`` (the (query, summary) pairs it scores,
    a head and step), ``eva/chunks`` (its summaries) and
    ``eva/local_pairs`` (the pairs of the exact causal call over each
    window that completes it).  attrs['with_lse'] adds the output Lse
    [B, T, H] (float32), every row's log-sum-exp with a gradient of
    its own, -inf where a row sees no key: what ``attention_merge``
    joins two calls by.

    attrs['block_mask'] (a block length) with attrs['block_relation']
    ('causal' or 'strict') is the fourth mask
    (flash_attention()'s ``block_mask``): a relation between blocks of
    positions, block diffusion's (``layers.block_diffusion_attention``).
    Such a call is lowered inside the scope ``block<n>_<relation>``
    and adds to the traced program's sums ``sdar/visible_pairs`` (the
    (query, key) pairs the mask lets through, a head) and, where the
    kernels run, ``sdar/tiles_visited`` (the score tiles their loops
    walk, forward and backward: flash_attention._count_tiles)."""
    from .pallas.flash_attention import mesh_flash_attention
    q = ins['Q'][0]
    k = ins['K'][0]
    v = ins['V'][0]
    bias = ins['KeyBias'][0] if ins.get('KeyBias') else None
    rate = float(attrs.get('dropout_rate', 0.0) or 0.0)
    seed = ctx.dropout_seed(attrs) if rate else None
    if seed is None:
        rate = 0.0
    window = int(attrs.get('window', 0) or 0)
    scopes = ['window%d' % window] if window else []
    if v.shape[-1] != q.shape[-1]:
        scopes.append('qk%dv%d' % (q.shape[-1], v.shape[-1]))
    more = {}
    if attrs.get('coarse_window'):
        more['coarse'] = (int(attrs['coarse_window']),
                          int(attrs['coarse_chunk']))
        scopes.append('remote')
        _coarse_gauges(q.shape[0], q.shape[1], k.shape[1],
                       *more['coarse'])
    if attrs.get('block_mask'):
        more['block_mask'] = (int(attrs['block_mask']),
                              attrs['block_relation'])
        scopes.append('block%d_%s' % more['block_mask'])
        _block_pairs(q.shape[0], q.shape[1], k.shape[1],
                     *more['block_mask'])
    if attrs.get('with_lse'):
        more['with_lse'] = True
    with contextlib.ExitStack() as stack:
        for name in scopes:
            stack.enter_context(jax.named_scope(name))
        out = mesh_flash_attention(
            q, k, v, ctx.auto_partitioned,
            scopes[-1] if scopes else 'fused_multihead_attention',
            causal=attrs.get('causal', False), key_bias=bias,
            dropout_rate=rate, dropout_seed=seed, window=window, **more)
    if not attrs.get('with_lse'):
        return {'Out': [out]}
    return {'Out': [out[0]], 'Lse': [jnp.transpose(out[1], (0, 2, 1))]}


def _block_pairs(batch, t, tk, block, kind):
    """``sdar/visible_pairs``: what a block-mask call adds to the
    traced program's sum, a head."""
    from . import registry
    from .pallas import flash_attention as fa
    seen = fa.relation_keys_seen(t, tk, fa.block_relation(block, kind))
    registry.trace_sum('sdar/visible_pairs', float(batch * seen.sum()))


def _coarse_gauges(batch, t, summaries, window, chunk):
    """Static counts of a coarse call, a head and step (the docstring
    above)."""
    from ..fluid import monitor
    windows = -(-t // window)
    per_window = window // chunk
    monitor.set_gauge('eva/remote_pairs', float(
        batch * sum(min(window, t - w * window) * w * per_window
                    for w in range(windows))))
    monitor.set_gauge('eva/local_pairs', float(batch * sum(
        n * (n + 1) // 2 for n in
        (min(window, t - w * window) for w in range(windows)))))
    monitor.set_gauge('eva/chunks', float(batch * summaries))


@register('fused_elemwise_activation')
def fused_elemwise_activation(ctx, ins, attrs):
    """Reference operators/fused/fused_elemwise_activation_op.cc:
    functor_list like ['elementwise_add', 'relu'].  XLA fuses anyway;
    provided for program-level parity."""
    import jax
    x, y = ins['X'][0], ins['Y'][0]
    functors = attrs.get('functor_list', ['elementwise_add', 'relu'])
    from .math_ops import _bcast
    x, y = _bcast(x, y, attrs.get('axis', -1))
    binary, unary = functors[0], functors[1] if len(functors) > 1 else None
    vals = {'elementwise_add': x + y, 'elementwise_mul': x * y}
    out = vals[binary]
    if unary == 'relu':
        out = jax.nn.relu(out)
    elif unary == 'tanh':
        out = jnp.tanh(out)
    elif unary in (None, 'identity'):
        pass
    else:
        raise NotImplementedError(unary)
    return {'Out': [out], 'IntermediateOut': [vals[binary]]}


# ---------------------------------------------------------------------------
# CPU fusion-op parity (reference operators/fused/fusion_*.cc).  On TPU
# these compose existing lowerings — XLA refuses the composition apart;
# registering them keeps transpiled/saved reference programs loadable.
# ---------------------------------------------------------------------------


def _call(op, ins, attrs, ctx):
    from .registry import get
    return get(op).fn(ctx, ins, attrs)


@register('fusion_gru', no_grad_out_slots=('XX',))
def fusion_gru(ctx, ins, attrs):
    """x@Wx + bias, then the gru scan: X [B,T,D], WeightX [D,3H],
    WeightH [H,3H] (reference operators/fused/fusion_gru_op.cc)."""
    x = ins['X'][0]
    xx = x @ ins['WeightX'][0]
    if ins.get('Bias'):
        xx = xx + ins['Bias'][0].reshape(1, 1, -1)
    sub = {'Input': [xx], 'Weight': ins['WeightH']}
    if ins.get('H0'):
        sub['H0'] = ins['H0']
    if ins.get('Mask'):
        sub['Mask'] = ins['Mask']
    out = _call('gru', sub, attrs, ctx)
    return {'Hidden': out['Hidden'], 'XX': [xx]}


@register('fusion_lstm', no_grad_out_slots=('XX',))
def fusion_lstm(ctx, ins, attrs):
    x = ins['X'][0]
    xx = x @ ins['WeightX'][0]
    if ins.get('Bias'):
        xx = xx + ins['Bias'][0].reshape(1, 1, -1)
    sub = {'Input': [xx], 'Weight': ins['WeightH']}
    for s in ('H0', 'C0', 'Mask'):
        if ins.get(s):
            sub[s] = ins[s]
    out = _call('lstm', sub, attrs, ctx)
    return {'Hidden': out['Hidden'], 'Cell': out['Cell'], 'XX': [xx]}


@register('fused_embedding_fc_lstm')
def fused_embedding_fc_lstm(ctx, ins, attrs):
    """Ids [B,T] -> embedding rows (already x@Wx-fused in the table,
    reference operators/fused/fused_embedding_fc_lstm_op.cc) -> lstm."""
    ids = ins['Ids'][0].astype(jnp.int32)
    emb = ins['Embeddings'][0]          # [V, 4H]
    xx = emb[ids.reshape(ids.shape[:2])]
    if ins.get('Bias'):
        xx = xx + ins['Bias'][0].reshape(1, 1, -1)
    sub = {'Input': [xx], 'Weight': ins['WeightH']}
    for s in ('H0', 'C0', 'Mask'):
        if ins.get(s):
            sub[s] = ins[s]
    out = _call('lstm', sub, attrs, ctx)
    return {'Hidden': out['Hidden'], 'Cell': out['Cell']}


@register('fusion_repeated_fc_relu')
def fusion_repeated_fc_relu(ctx, ins, attrs):
    """Chain of (fc -> relu) (reference fusion_repeated_fc_relu_op.cc —
    the fuse pass only matches consecutive fc+relu pairs, so every
    layer including the last is ReLU'd)."""
    import jax
    x = ins['X'][0]
    for w, b in zip(ins['W'], ins['Bias']):
        x = jax.nn.relu(x @ w + b.reshape(1, -1))
    return {'Out': [x], 'ReluOut': [x]}


@register('fusion_seqconv_eltadd_relu')
def fusion_seqconv_eltadd_relu(ctx, ins, attrs):
    import jax
    sub = {'X': ins['X'], 'Filter': ins['Filter']}
    if ins.get('Mask'):
        sub['Mask'] = ins['Mask']
    conv = _call('sequence_conv', sub, attrs, ctx)['Out'][0]
    out = jax.nn.relu(conv + ins['Bias'][0].reshape(1, 1, -1))
    return {'Out': [out], 'ColMat': [conv]}


@register('fusion_seqexpand_concat_fc')
def fusion_seqexpand_concat_fc(ctx, ins, attrs):
    """Refs fusion_seqexpand_concat_fc_op.cc: broadcast per-batch vectors
    over time, concat with X, one fc + act.  X[0] is [B,T,D]; the rest
    are [B,Dk]."""
    import jax
    xs = ins['X']
    seq = xs[0]
    b, t = seq.shape[:2]
    parts = [seq] + [jnp.broadcast_to(v[:, None, :], (b, t, v.shape[-1]))
                     for v in xs[1:]]
    cat = jnp.concatenate(parts, -1)
    out = cat @ ins['FCWeight'][0]
    if ins.get('FCBias'):
        out = out + ins['FCBias'][0].reshape(1, 1, -1)
    act = attrs.get('fc_activation', 'relu')
    if act == 'relu':
        out = jax.nn.relu(out)
    elif act == 'tanh':
        out = jnp.tanh(out)
    return {'Out': [out], 'FCOut': [out]}


@register('fusion_seqpool_concat')
def fusion_seqpool_concat(ctx, ins, attrs):
    """Pool each input over time and concat (fusion_seqpool_concat_op)."""
    pooled = []
    n_mask = len(ins.get('Mask', []))
    for k, x in enumerate(ins['X']):
        sub = {'X': [x]}
        if k < n_mask:
            sub['Mask'] = [ins['Mask'][k]]
        pooled.append(_call('sequence_pool', sub,
                            {'pooltype': attrs.get('pooltype', 'SUM')},
                            ctx)['Out'][0])
    return {'Out': [jnp.concatenate(pooled, -1)]}


@register('fusion_squared_mat_sub')
def fusion_squared_mat_sub(ctx, ins, attrs):
    """(x@y)^2 - x^2@y^2, scaled (fusion_squared_mat_sub_op.cc)."""
    x, y = ins['X'][0], ins['Y'][0]
    scalar = attrs.get('scalar', 1.0)
    sq_xy = jnp.square(x @ y)
    x2y2 = jnp.square(x) @ jnp.square(y)
    return {'Out': [scalar * (sq_xy - x2y2)],
            'SquaredXY': [sq_xy], 'SquaredX': [jnp.square(x)],
            'SquaredY': [jnp.square(y)]}
