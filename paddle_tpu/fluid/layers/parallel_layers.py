"""Sequence-parallel attention and Mixture-of-Experts layers.

NEW capability vs the reference (SURVEY.md §2.4: fluid v1.6 has no
sequence/context or expert parallelism), surfaced the reference WAY: a
layer call appends ops to the Program, and the parallelism is realized
when the program compiles under a mesh with 'sp'/'ep' axes
(CompiledProgram.with_mesh) — the same contract by which dp/mp reach
the user through CompiledProgram/fleet rather than raw device code
(reference python/paddle/fluid/transpiler/collective.py:36).

The layers also stamp mesh-sharding HINTS for their parameters and
activations on the program (program._sharding_hints), which the GSPMD
executor path picks up so expert weights land sharded over 'ep'
without the user writing a with_param_shardings rule.
"""

import numpy as np

from ..layer_helper import LayerHelper
from ..initializer import Normal

__all__ = ['context_parallel_attention', 'moe']


def _add_hint(program, var_name, axes):
    """Record `axes` (tuple of mesh-axis names / None, one per dim) as
    the preferred sharding for var_name; axes absent from the runtime
    mesh degrade to replication (parallel_executor._hint_to_spec)."""
    hints = getattr(program, '_sharding_hints', None)
    if hints is None:
        hints = program._sharding_hints = {}
    hints[var_name] = tuple(axes)


def context_parallel_attention(q, k, v, causal=False, use_flash=False,
                               axis='sp', dropout_rate=0.0, name=None):
    """Multi-head attention whose sequence dim shards over the `axis`
    mesh axis (ring attention: K/V blocks rotate over the ICI ring via
    ppermute while each device streams its Q block's online softmax).

    q, k, v: [B, T, H, D] variables (batch, time, heads, head_dim).
    use_flash: use the Pallas flash kernel as the per-block engine
        (long-context memory profile; falls back off-TPU to interpret
        mode, so tests keep it False).
    dropout_rate: attention-prob dropout (round 5) — the mask is a
        counter hash at GLOBAL sequence positions keyed on (op seed,
        step), so ring-sharded and dense runs draw the same mask and
        training dropout works under context parallelism; skipped in
        test-mode programs.
    Returns Out [B, T, H, D].

    On a mesh without `axis` (or single-device) the op computes the
    identical dense attention, so programs are portable across meshes.
    """
    helper = LayerHelper(name or 'context_parallel_attention')
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op('ring_attention',
                     inputs={'Q': q, 'K': k, 'V': v},
                     outputs={'Out': out},
                     attrs={'causal': bool(causal),
                            'use_flash': bool(use_flash),
                            'axis': axis,
                            'dropout_rate': float(dropout_rate or 0.0)})
    prog = helper.main_program
    for var in (q, k, v, out):
        _add_hint(prog, var.name, ('dp', axis, None, None))
    return out


def moe(x, num_experts, hidden_size, capacity_factor=2.0,
        aux_weight=0.01, axis='ep', top_k=1, param_attr=None,
        name=None, renormalize=True, z_loss_weight=0.0,
        experts_held=None, gate_scale=1.0, score_func='softmax',
        score_bias=None, bias_update_rate=0.0, renorm_eps=1e-20,
        expert_form='gated'):
    """Mixture-of-Experts feed-forward layer, in two forms.

    **Capacity-based** (``capacity_factor`` a number, the default):
    ``top_k=1`` is Switch routing, ``top_k=2`` GShard's (second choice
    with the pair's gates renormalized, dropped first on overflow);
    experts are ``relu(x W1) W2`` with W1 [E, D, hidden_size] and W2
    [E, hidden_size, D]; tokens over an expert's capacity are dropped.
    Under a mesh with an ``axis`` ('ep') dimension the experts shard
    across it and tokens route via all_to_all over ICI.

    **Dropless** (``capacity_factor=None``): any ``top_k`` up to
    ``num_experts``, no token dropped, gates taken from the softmax
    over all experts and divided by their sum only under
    ``renormalize`` (OLMoE: False).  The experts come in two forms
    (``expert_form``): ``'gated'`` (the default),
    ``down(silu(gate x) * up x)`` with gate and up [E, D, hidden_size],
    down [E, hidden_size, D]; ``'relu2'`` (Nemotron-H's), ``down(relu(up
    x)^2)`` with up and down ONLY: no gate parameter is created and a
    pass runs two grouped matmuls, not three.  Routing sorts the
    (token, expert) pairs by expert and runs one grouped matmul per
    weight set
    (``moe_route`` / ``moe_dispatch`` / ``moe_experts`` /
    ``moe_combine`` ops); it raises NotImplementedError under an
    ``axis`` mesh dimension.  ``gate_scale`` multiplies the gates (a
    routed scaling factor).  ``experts_held=(first, count)`` makes
    this layer one chip's share of an expert-parallel layer, without
    the exchange: the router stays ``num_experts`` wide and picks
    ``top_k`` of all of them, the expert weights are [count, D,
    hidden_size] (experts first .. first + count - 1), and the output
    is the part of the layer's result those experts give: the sum over
    each token's chosen experts HELD HERE of gate x expert(x).  The
    (token, expert) pairs are sorted with the held experts' rows
    first; the grouped matmuls get those groups' sizes only, over a
    buffer of tokens x min(top_k, count) rows (the most that can be
    held), and what lies past the last group is neither computed nor
    counted as dropped; the weighted sum back and the gradients walk
    that buffer only as far as its rows are held, so the permutation
    costs what the layer holds and its time follows the routing.  What
    every chip computes alike (a shared expert) is the model's to add,
    once.

    ``score_func='sigmoid'`` (dropless only) scores each expert by the
    sigmoid of its own logit instead of the softmax over all;
    ``renormalize`` then divides the chosen scores by (their sum +
    ``renorm_eps``: 1e-20 by default, DeepSeek-V3's; LFM2 publishes
    1e-6).  ``score_bias`` (sigmoid only: True, or a ``ParamAttr``
    whose initializer draws its startup values; default zeros) adds a
    persistable, NON-trainable [num_experts] float32 bias to the
    scores for the CHOICE of the ``top_k`` experts only: the gates are
    the plain scores, the bias takes no gradient and is no parameter
    of the optimizer.  With ``bias_update_rate`` gamma > 0 every run
    of the train program moves it, ``b += gamma * sign(mean load -
    load)`` from that run's expert loads (DeepSeek-V3's
    auxiliary-loss-free balancing), as an output of the ``moe_route``
    op; a ``clone(for_test=True)`` leaves it as it is.  Gauge
    ``moe/score_bias_abs_max`` and counter ``moe/bias_updates``
    (``fluid/moe_stats.py``) read it on the runs that fetch.

    x: [B, T, D].  Returns (out [B, T, D], aux []): ``aux`` is the
    load-balance loss times ``aux_weight`` plus, dropless only, the
    router z-loss times ``z_loss_weight``; add it to the training loss.
    """
    top_k, e, h = int(top_k), int(num_experts), int(hidden_size)
    dropless = capacity_factor is None
    if experts_held is not None:
        first, count = (int(n) for n in experts_held)
        if not dropless or not (0 <= first and 1 <= count and
                                first + count <= e):
            raise ValueError(
                'moe: experts_held=(first, count) names a range of the '
                '%d experts of a dropless layer (capacity_factor=None); '
                'got %r with capacity_factor=%r'
                % (e, experts_held, capacity_factor))
        experts_held = (first, count)
    if gate_scale != 1.0 and not dropless:
        raise ValueError('moe: gate_scale needs the dropless path '
                         '(capacity_factor=None)')
    if score_func not in ('softmax', 'sigmoid'):
        raise ValueError("moe: score_func is 'softmax' or 'sigmoid', "
                         'got %r' % (score_func,))
    if score_func != 'softmax' and not dropless:
        raise ValueError(
            'moe: score_func=%r needs the dropless path '
            '(capacity_factor=None): the capacity-based maps are built '
            'from a softmax; got capacity_factor=%r'
            % (score_func, capacity_factor))
    if score_bias and score_func != 'sigmoid':
        raise ValueError(
            'moe: score_bias corrects the CHOICE among sigmoid scores '
            "and never weighs (score_func='sigmoid', "
            "capacity_factor=None); added to a softmax's probabilities "
            "or to the capacity-based path's it would have no such "
            'reading; got score_func=%r, capacity_factor=%r'
            % (score_func, capacity_factor))
    if bias_update_rate and not score_bias:
        raise ValueError('moe: bias_update_rate=%r moves a score_bias, '
                         'and there is none' % (bias_update_rate,))
    from ...parallel.moe import expert_slots
    expert_slots(expert_form)           # raises on a form it does not know
    if expert_form != 'gated' and not dropless:
        raise ValueError(
            'moe: expert_form=%r needs the dropless path '
            '(capacity_factor=None): the capacity-based experts are '
            'relu(x W1) W2; got capacity_factor=%r'
            % (expert_form, capacity_factor))
    if dropless and not 1 <= top_k <= e:
        raise ValueError('moe: dropless top_k must be in 1..num_experts '
                         '(%d), got %r' % (e, top_k))
    if not dropless and top_k not in (1, 2):
        raise ValueError(
            'moe: the capacity-based path routes top_k 1 (Switch) or 2 '
            '(GShard), got %r; any top_k needs capacity_factor=None '
            '(dropless)' % (top_k,))
    helper = LayerHelper(name or 'moe', param_attr=param_attr)
    d = int(x.shape[-1])

    def weight(shape):
        return helper.create_parameter(
            param_attr, shape=shape, dtype=x.dtype,
            default_initializer=Normal(0., 0.02))

    def scaled(var, by):
        # always scale (aux_weight=0.0 must yield a ZEROED term,
        # honoring the "already scaled" contract, not the raw loss)
        out = helper.create_variable_for_type_inference('float32')
        helper.append_op('scale', inputs={'X': var},
                         outputs={'Out': out},
                         attrs={'scale': float(by)})
        return out

    wg = weight([d, e])
    if dropless:
        return _dropless_moe(helper, x, wg, weight, scaled, e, h, top_k,
                             axis, renormalize, aux_weight,
                             z_loss_weight, experts_held,
                             float(gate_scale), score_func, score_bias,
                             float(bias_update_rate), float(renorm_eps),
                             expert_form)
    w1, w2 = weight([e, d, h]), weight([e, h, d])
    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference('float32')
    helper.append_op('moe_ffn',
                     inputs={'X': x, 'Gate': wg, 'W1': w1, 'W2': w2},
                     outputs={'Out': out, 'AuxLoss': aux},
                     attrs={'axis': axis,
                            'capacity_factor': float(capacity_factor),
                            'top_k': top_k})
    prog = helper.main_program
    _add_hint(prog, w1.name, (axis, None, None))
    _add_hint(prog, w2.name, (axis, None, None))
    _add_hint(prog, x.name, ('dp', ('sp', axis), None))
    _add_hint(prog, out.name, ('dp', ('sp', axis), None))
    return out, scaled(aux, aux_weight)


def _dropless_moe(helper, x, wg, weight, scaled, e, h, top_k, axis,
                  renormalize, aux_weight, z_loss_weight, held=None,
                  gate_scale=1.0, score_func='softmax', score_bias=None,
                  bias_update_rate=0.0, renorm_eps=1e-20,
                  expert_form='gated'):
    from ...parallel.moe import expert_slots
    d = int(x.shape[-1])
    here = e if held is None else held[1]      # experts with weights
    # 'gated': gate, up, down; 'relu2': up, down
    slots = expert_slots(expert_form)
    w_in = [weight([here, d, h]) for _ in slots]
    w_down = weight([here, h, d])

    def var(dtype, stop_gradient=False):
        return helper.create_variable_for_type_inference(
            dtype, stop_gradient=stop_gradient)

    idx, load, dropped = (var('int32', True) for _ in range(3))
    gates, balance, z = var('float32'), var('float32'), var('float32')
    route_outs = {'TopKIdx': idx, 'TopKWeight': gates,
                  'AuxLoss': balance, 'ZLoss': z, 'Load': load}
    route_attrs = {'top_k': top_k, 'axis': axis,
                   'renormalize': bool(renormalize)}
    sizes, held_attrs = load, {}
    if gate_scale != 1.0:
        route_attrs['scale'] = gate_scale
    if held is not None:
        # the groups the matmuls are handed: the held experts' loads
        sizes = route_outs['HeldLoad'] = var('int32', True)
        route_attrs['experts_held'] = list(held)
        held_attrs = {'experts_held': list(held)}
    route_ins = {'X': x, 'Gate': wg}
    if score_func != 'softmax':
        route_attrs['score_func'] = score_func
    if renorm_eps != 1e-20:     # the default leaves the op as it was
        route_attrs['renorm_eps'] = renorm_eps
    bias = None
    if score_bias:
        from ..initializer import Constant
        from ..param_attr import ParamAttr
        attr = score_bias if isinstance(score_bias, ParamAttr) \
            else ParamAttr()
        attr.trainable = False
        bias = helper.create_parameter(
            attr, shape=[e], dtype='float32',
            default_initializer=Constant(0.0))
        bias.stop_gradient = True
        # the op reads a copy: its gradient op runs the router again
        # from the op's inputs, after the bias itself has moved
        from . import tensor
        route_ins['ScoreBias'] = read = tensor.assign(bias)
        read.stop_gradient = True
        if bias_update_rate:
            route_outs['ScoreBiasOut'] = bias
            route_attrs['bias_update_rate'] = bias_update_rate
            route_attrs['is_test'] = False
    helper.append_op('moe_route', inputs=route_ins,
                     outputs=route_outs, attrs=route_attrs)
    # rows = tokens x top_k: with a dynamic batch, shape inference's
    # stand-in for it overflows int32 index arithmetic at that size, so
    # the shapes of the permuted tensors are stated, not inferred
    rows, order, inverse = var(x.dtype), var('int32', True), \
        var('int32', True)
    helper.append_op('moe_dispatch',
                     inputs={'X': x, 'TopKIdx': idx, 'GroupSizes': sizes},
                     outputs={'Rows': rows, 'Order': order,
                              'Inverse': inverse, 'Dropped': dropped},
                     attrs=held_attrs, infer_shape=False)
    expert_out = var(x.dtype)
    expert_ins = {'Rows': rows, 'GroupSizes': sizes}
    expert_ins.update(zip(slots, w_in))
    expert_ins['WDown'] = w_down
    expert_attrs = dict(held_attrs)
    if expert_form != 'gated':      # the default leaves the op as it was
        expert_attrs['expert_form'] = expert_form
    helper.append_op('moe_experts', inputs=expert_ins,
                     outputs={'Out': expert_out}, attrs=expert_attrs,
                     infer_shape=False)
    flat = var(x.dtype)
    combine_ins = {'Rows': expert_out, 'TopKWeight': gates,
                   'Order': order, 'Inverse': inverse}
    if held is not None:
        combine_ins['GroupSizes'] = sizes
    helper.append_op('moe_combine', inputs=combine_ins,
                     outputs={'Out': flat}, attrs=held_attrs,
                     infer_shape=False)
    from ...ops.registry import _DYN_SENTINEL
    # a dynamic batch counts as inference's stand-in, products literal
    # (registry.infer_shapes does the same for layer_norm's row count)
    tokens = int(np.prod([_DYN_SENTINEL if n < 0 else int(n)
                          for n in x.shape[:-1]]))
    from ...parallel.moe import held_rows_bound
    n_rows = held_rows_bound(tokens, top_k, held)
    rows.shape = expert_out.shape = (n_rows, d)
    order.shape = (n_rows,)
    inverse.shape = (tokens * top_k,)
    dropped.shape = (1,)
    if held is not None:
        sizes.shape = (held[1],)
    flat.shape = (tokens, d)
    out = flat
    if len(x.shape) != 2:
        inner = [int(n) for n in x.shape[1:-1]]
        if min(inner) < 0:
            raise ValueError('moe: only the first dim of x may be '
                             'dynamic, got shape %r' % (x.shape,))
        from . import nn
        out = nn.reshape(flat, [-1] + inner + [d])
    # read on the runs that fetch and block (Program.watch):
    # moe/tokens_routed, moe/dropped_tokens, moe/load_max_over_mean
    from .. import moe_stats
    helper.main_program.watch([load.name, dropped.name],
                              moe_stats.record)
    if held is not None:
        # moe/rows_held, moe/held_share, moe/held_rows_max,
        # moe/walked_share
        held_layers = moe_stats.HeldLayers.of(helper.main_program)
        held_layers.top_k.append(top_k)
        helper.main_program.watch([load.name, sizes.name], held_layers)
    if bias_update_rate:
        # moe/score_bias_abs_max, moe/bias_updates
        helper.main_program.watch([bias.name], moe_stats.record_bias)
    aux = scaled(balance, aux_weight)
    if z_loss_weight:
        total = var('float32')
        helper.append_op('elementwise_add',
                         inputs={'X': aux,
                                 'Y': scaled(z, z_loss_weight)},
                         outputs={'Out': total}, attrs={'axis': -1})
        aux = total
    return out, aux
