"""Nemotron-H through fluid against its plain reference
(``paddle_tpu/models/reference/nemotron_h.py``): the ``ssd_scan`` op
(Mamba-2's recurrence as matrix products over chunks) against the
token-by-token recurrence, forward and backward; the zoo program's loss
and every parameter's gradient, through its recompute groups; the
ungated squared-ReLU experts of ``layers.moe`` beside the gated form,
whose programs stay as they were; the sixteen expert shares and the
shared expert once adding up to the uncut layer; grouped-query
attention at 16 queries a K/V head on both arms of the attention op;
the pattern string.  CPU, tiny sizes; the published widths are checked
on the chip (``chip_smoke.py --phase nemotron_h``, PERF.md)."""

import copy
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import nemotron_h as zoo
from paddle_tpu.models import olmoe
from paddle_tpu.models.reference import nemotron_h as reference
from paddle_tpu.ops import registry, ssd_ops
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.parallel import moe as pmoe

from op_test import OpTest

SEQ = 21                        # no whole number of TINY's 8-token chunks

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(zoo.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _close(got, want, rtol, what=''):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), what


# --- the op -----------------------------------------------------------

SLOTS = ('X', 'Delta', 'A', 'B', 'C', 'D')


class _Ctx(object):
    auto_partitioned = False


def _scan_operands(seed, b, t, h, p, g, n, dtype='float32'):
    """Steps from 0.007 to 1.6 and decay rates of every size: a head's
    state lives from two tokens to hundreds."""
    rng = np.random.RandomState(seed)
    return [jnp.asarray(v.astype(dtype)) for v in (
        rng.randn(b, t, h, p), np.exp(rng.uniform(-5, 0.5, (b, t, h))),
        -np.exp(rng.randn(h)), rng.randn(b, t, g, n),
        rng.randn(b, t, g, n), rng.randn(h))]


def _op(chunk, *args):
    return registry.get('ssd_scan').run(
        _Ctx(), {s: [x] for s, x in zip(SLOTS, args)},
        {'chunk': chunk})['Out'][0]


def _op_grads(chunk, probe, *args):
    ins = {s: [x] for s, x in zip(SLOTS, args)}
    ins['GRAD::Out'] = [probe]
    out = registry.get('ssd_scan_grad').run(_Ctx(), ins, {'chunk': chunk})
    return tuple(out['GRAD::' + s][0] for s in SLOTS)


def _loop_grads(probe, *args):
    return jax.grad(lambda *x: jnp.sum(reference.recurrence(*x) * probe),
                    argnums=range(6))(*args)


# 64 heads in 8 groups is the model's 8 heads a group; 37 and 300 tokens
# are no whole number of either chunk, 256 is two whole 128-token ones
@pytest.mark.parametrize('chunk', [128, 16], ids=['chunk128', 'chunk16'])
@pytest.mark.parametrize('t', [37, 256, 300])
def test_ssd_scan_is_the_token_by_token_recurrence(t, chunk):
    """The registered op and its gradient op against ``lax.scan`` over
    the recurrence's two lines, float32 both: two sequences a batch, 8
    heads a group, the published chunk and a smaller one, T a whole
    number of chunks and not.  What is left is the order of float32
    sums (a chunk's tokens in one product against one at a time)."""
    args = _scan_operands(t, 2, t, 16, 4, 2, 6)
    probe = jnp.asarray(np.random.RandomState(1).randn(2, t, 16, 4),
                        jnp.float32)
    with jax.default_matmul_precision('highest'):
        want = reference.recurrence(*args)
        want_grads = _loop_grads(probe, *args)
    _close(_op(chunk, *args), want, 2e-5, 'y')
    for slot, got, g in zip(SLOTS, _op_grads(chunk, probe, *args),
                            want_grads):
        _close(got, g, 1e-4, slot)


def test_ssd_scan_at_eight_heads_a_group_in_float64():
    """The model's grouping (64 heads over 8 B / C pairs) at float64,
    where the two forms agree to rounding: the chunked algebra is the
    recurrence, not near it."""
    from jax import config
    config.update('jax_enable_x64', True)
    try:
        args = _scan_operands(5, 1, 45, 64, 2, 8, 3, 'float64')
        probe = jnp.asarray(np.random.RandomState(2).randn(1, 45, 64, 2))
        _close(ssd_ops.ssd_scan(*args, 16),
               reference.recurrence(*args), 1e-12)
        got = jax.grad(lambda *x: jnp.sum(ssd_ops.ssd_scan(*x, 16) * probe),
                       argnums=range(6))(*args)
        for slot, a, b in zip(SLOTS, got, _loop_grads(probe, *args)):
            _close(a, b, 1e-11, slot)
    finally:
        config.update('jax_enable_x64', False)


def test_ssd_scan_holds_the_sequences_of_a_batch_apart():
    """The state is zero at every sequence's start and nothing crosses
    from one sequence to the next: a batch of two is each sequence
    alone, output and gradients, bit for bit in the rows of the other
    sequence's inputs (zero)."""
    args = _scan_operands(9, 2, 40, 8, 4, 2, 6)
    probe = jnp.asarray(np.random.RandomState(3).randn(2, 40, 8, 4),
                        jnp.float32)

    def run(probe, *x):
        out, pull = jax.vjp(lambda *x: ssd_ops.ssd_scan(*x, 16), *x)
        return (out,) + pull(probe)

    both = run(probe, *args)
    for i in (0, 1):
        alone = run(probe[i:i + 1],
                    *(v[i:i + 1] if v.ndim > 1 else v for v in args))
        for slot, a, b in zip(('Out',) + SLOTS, both, alone):
            if a.ndim > 1:      # what comes a sequence
                _close(a[i:i + 1], b, 1e-6, slot)
    # the second sequence's cotangent reaches nothing of the first
    only_second = jax.vjp(lambda *x: ssd_ops.ssd_scan(*x, 16), *args)[1](
        probe.at[0].set(0.0))
    for slot, g in zip(SLOTS, only_second):
        if g.ndim > 1:
            assert not np.asarray(g[0]).any(), slot


def test_ssd_scan_finite_differences():
    """The op's analytic gradients (``custom_vjp``: the chunks' insides
    again, the states in reverse) against central differences through
    the executor, every input."""
    rng = np.random.RandomState(0)
    inputs = {
        'X': rng.randn(1, 11, 4, 2), 'Delta': rng.uniform(0.1, 1, (1, 11, 4)),
        'A': -rng.uniform(0.2, 1.5, 4), 'B': rng.randn(1, 11, 2, 3),
        'C': rng.randn(1, 11, 2, 3), 'D': rng.randn(4)}
    OpTest().check_grad(
        'ssd_scan', {k: v.astype('float32') for k, v in inputs.items()},
        attrs={'chunk': 4}, eps=1e-2, atol=2e-2, rtol=2e-2)


def test_ssd_scan_rejects_heads_that_fill_no_whole_groups():
    args = _scan_operands(0, 1, 8, 6, 2, 4, 3)
    with pytest.raises(ValueError, match='6 heads .* 4 groups'):
        ssd_ops.ssd_scan(*args)


def test_the_layer_runs_bf16_operands_beside_float32_steps():
    """``layers.ssd_scan`` through the executor: bfloat16 x, B, C
    beside float32 steps, decays and skip; the output in x's dtype, the
    float32 recurrence's to bfloat16 products' rounding."""
    args = _scan_operands(4, 2, SEQ, 8, 4, 2, 6)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = [layers.data(s, shape=list(v.shape), dtype='float32',
                             append_batch_size=False)
                 for s, v in zip(SLOTS, args)]
        low = [layers.cast(v, 'bfloat16') if s in 'XBC' else v
               for s, v in zip(SLOTS, feeds)]
        out = layers.ssd_scan(*low, chunk=8)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        got, = exe.run(main, feed=dict(zip(SLOTS, map(np.asarray, args))),
                       fetch_list=[out], return_numpy=False)
    assert jnp.asarray(got).dtype == jnp.bfloat16
    with jax.default_matmul_precision('highest'):
        want = reference.recurrence(*args)
    _close(jnp.asarray(got).astype(jnp.float32), want, 3e-2)


# --- the program ------------------------------------------------------


def _seeded_weights(specs, cfg, seed):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains around 1, filters of order 1,
    steps and decays of every size, a router whose top-k margins are
    wide."""
    rng = np.random.RandomState(seed)
    out = []
    for label, s in specs:
        what = label.split('.')[-1]
        if what == 'a_log':
            w = np.log(rng.uniform(1, 16, s))
        elif what == 'dt_bias':
            w = rng.uniform(-4, 0, s)
        elif what == 'choice_bias':
            w = 0.3 * rng.randn(*s)
        elif what == 'conv_w':
            w = rng.randn(*s)
        elif what in ('conv_b', 'd') or 'norm' in what:
            w = 1 + 0.1 * rng.randn(*s)
        elif what == 'router':
            w = 4.0 * rng.randn(*s) / np.sqrt(s[0])
        elif what == 'embedding':
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _input_of(op_type, slot, nth=0):
    def name(main):
        ops = [op for op in main.global_block().ops if op.type == op_type]
        return ops[nth].inputs[slot][0]
    return name


def _program_and_reference(cfg, seed, amp=False, extra=()):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights -> (loss, {param: grad}, every
    parameter's name in creation order, weights, feed, the ``extra``
    fetches)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, _, loss = zoo.build_pretrain(cfg, SEQ)
            names = [p.name for p in main.all_parameters()]
            optimizer = fluid.optimizer.SGD(0.0)
            if amp:
                optimizer = fluid.contrib.mixed_precision.decorate(
                    optimizer, use_dynamic_loss_scaling=False,
                    init_loss_scaling=1.0)
            pairs = optimizer.minimize(loss)[1]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(zoo.parameter_specs(cfg), cfg, seed)
        for name, w in zip(names, weights):
            fluid.global_scope().set_var(name, jnp.asarray(w))
        feed = zoo.synthetic_batch(cfg, 2, SEQ, np.random.RandomState(seed))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs] +
                      [n(main) for n in extra], return_numpy=False)
    n = 1 + len(pairs)
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:n])}
    return _scalar(out[0]), grads, names, weights, feed, out[n:]


def _sizes(cfg):
    return dict(pattern=cfg.pattern, head_dim=cfg.head_dim,
                top_k=cfg.top_k, routed_scale=cfg.routed_scale,
                eps=cfg.rms_eps, renormalize=cfg.renormalize,
                first=(cfg.experts_held or (0,))[0])


def _reference(cfg, weights, feed, grads=True, **kw):
    f = reference.loss_and_grads if grads else reference.loss
    return jax.jit(functools.partial(f, **dict(_sizes(cfg), **kw)))(
        weights, {k: jnp.asarray(v) for k, v in feed.items()})


@pytest.mark.parametrize('cfg', [HELD, zoo.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision, every kind of layer (``ME*ME``), under a choice
    bias large enough to change the choice: what is left is the order
    of float32 sums through five layers (the recurrence in chunks of 8
    against a token at a time, over 21 tokens: the last chunk is
    short).  The bias is no parameter of the optimizer and gets no
    gradient.  Every layer but the last is a recompute group: the scan
    and its backward run inside ``jax.checkpoint``, routers and their
    bias updates too."""
    groups = monitor.counter_value('executor/recompute_groups') or 0
    loss, grads, names, weights, feed, _ = _program_and_reference(cfg, 3)
    assert (monitor.counter_value('executor/recompute_groups') or
            0) - groups >= 4
    want, want_grads = _reference(cfg, weights, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    specs = zoo.parameter_specs(cfg)
    assert len(names) == len(specs) == 3 + 2 * 9 + 2 * 7 + 5
    biases = [n for n, (label, _) in zip(names, specs)
              if label.endswith('choice_bias')]
    assert len(biases) == 2 and set(grads) == set(names) - set(biases)
    for name, (label, _), g in zip(names, specs, want_grads):
        if name in grads:
            g = np.asarray(g)
            assert np.abs(grads[name] - g).max() <= \
                2e-4 * np.abs(g).max(), label
    # the bias changes the choice, and the reference's loss with it
    unbiased = [0 * w if label.endswith('choice_bias') else w
                for w, (label, _) in zip(weights, specs)]
    other = float(_reference(cfg, unbiased, feed, grads=False))
    assert abs(other - float(want)) > 1e-4 * float(want)


def test_bf16_amp_keeps_steps_and_decays_float32_beside_bf16_x_b_c():
    """Under bf16 AMP the scan's x, B and C arrive bfloat16 and its
    steps, decay rates and skip float32, inside a recompute group; the
    attention operands arrive bfloat16; the loss is the float32
    reference's to bfloat16 matmuls' rounding (eight bits of mantissa
    through five layers: a percent at most, and not zero)."""
    extra = [_input_of('ssd_scan', s) for s in SLOTS] + \
        [_input_of('fused_multihead_attention', s) for s in 'QKV']
    loss, _, _, weights, feed, fetched = _program_and_reference(
        HELD, 5, amp=True, extra=extra)
    dtypes = [jnp.asarray(x).dtype.name for x in fetched]
    assert dtypes == ['bfloat16', 'float32', 'float32', 'bfloat16',
                      'bfloat16', 'float32'] + ['bfloat16'] * 3
    assert (np.asarray(fetched[1]) > 0).all()       # delta
    assert (np.asarray(fetched[2]) < 0).all()       # a
    want = float(_reference(HELD, weights, feed, grads=False))
    assert 0 < abs(loss - want) <= 1e-2 * want


def test_a_train_step_counts_every_scans_walks():
    """``ssd/chunks`` over ONE traced train program: two Mamba-2 layers
    of ceil(21 / 8) = 3 chunks, both in recompute groups (``ME*ME``:
    the last layer run is routed), each walking three times: the
    forward, the group's second forward, the reverse walk (as
    ``ssm/chunks`` counts Phi-4-mini-flash's scans).
    ``ssd/boundary_state_mb``: what the two keep between their passes,
    a [8, 4, 6] float32 state a chunk and sequence (a group's second
    forward is the one that keeps)."""
    _program_and_reference(zoo.TINY, 6)
    assert monitor.gauge_value('ssd/chunks') == 2 * 3 * 3
    assert monitor.gauge_value('ssd/boundary_state_mb') == pytest.approx(
        2 * 2 * 3 * 8 * 4 * 6 * 4 / 1e6)


def test_every_layer_is_one_mixer_and_no_position_enters():
    """One residual add a layer and one pre-norm (plus the last norm and
    the Mamba-2 mixers' grouped ones); the mixers in the pattern's
    order; no ``rotary_embedding`` op and no ``pos_ids`` feed; a
    ``moe_experts`` op without a gate."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, _, _ = zoo.build_pretrain(zoo.TINY, SEQ)
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert sorted(feeds) == ['ids', 'labels']
    assert 'rotary_embedding' not in types
    assert not [v for v in main.global_block().vars if 'pos' in v]
    mixers = [t for t in types if t in ('ssd_scan', 'moe_route',
                                        'fused_multihead_attention')]
    assert mixers == ['ssd_scan', 'moe_route', 'fused_multihead_attention',
                      'ssd_scan', 'moe_route']
    assert types.count('rms_norm') == 5 + 1 + 2
    experts = [op for op in ops if op.type == 'moe_experts']
    assert all(sorted(op.inputs) == ['GroupSizes', 'Rows', 'WDown', 'WUp']
               and op.attrs['expert_form'] == 'relu2' for op in experts)


@pytest.mark.parametrize('pattern,kinds', [
    ('MEMEM*EME', ['mamba', 'moe', 'mamba', 'moe', 'mamba', 'attention',
                   'moe', 'mamba', 'moe']),
    ('*', ['attention']),
    (zoo.PATTERN, None)])
def test_the_pattern_names_each_layers_mixer(pattern, kinds):
    got = zoo.layer_kinds(pattern)
    if kinds is None:           # the published 52: 23 M, 23 E, 6 *
        assert [got.count(k) for k in ('mamba', 'moe', 'attention')] == \
            [23, 23, 6] and len(got) == 52
    else:
        assert got == kinds


@pytest.mark.parametrize('pattern,named', [('MEM-E', "'-'"), ('', 'no layer'),
                                           ('MxE', "'x'")])
def test_a_pattern_with_an_unknown_letter_raises_with_the_pattern(
        pattern, named):
    with pytest.raises(ValueError) as err:
        zoo.NemotronHConfig(pattern=pattern)
    assert repr(pattern) in str(err.value) and named in str(err.value)


# --- the experts without a gate ---------------------------------------


def _expert_operands(seed, m, e, d, h, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*s) / np.sqrt(s[-2]), dtype)
            for s in ((m, 1, d), (e, d, h), (e, h, d))]


def _plain_relu2(rows, sizes, w_up, w_down):
    """Each row through its group's expert, a Python loop."""
    out, at = [], 0
    for e, n in enumerate(np.asarray(sizes)):
        x = rows[at:at + n].astype(jnp.float32)
        hidden = jnp.square(jax.nn.relu(jnp.dot(
            x, w_up[e].astype(jnp.float32), precision='highest')))
        out.append(jnp.dot(hidden, w_down[e].astype(jnp.float32),
                           precision='highest'))
        at += n
    return jnp.concatenate(out)


@pytest.mark.parametrize('low', [False, True], ids=['f32', 'bf16'])
@pytest.mark.parametrize('mlp', ['grouped_expert_mlp', 'held_expert_mlp'])
def test_relu2_experts_are_two_products_and_a_squared_relu(mlp, low):
    """down(relu(up x)^2) for rows grouped by expert, all experts here
    and a held range (rows past the last group are left alone), values
    and all three gradients against a loop over the experts; in
    bfloat16 to its rounding."""
    rows, w_up, w_down = _expert_operands(1, 64, 4, 16, 24)
    rows = rows[:, 0]
    sizes = jnp.asarray([20, 0, 13, 7] if mlp.startswith('held')
                        else [20, 0, 31, 13], jnp.int32)
    live = int(sizes.sum())
    probe = jnp.asarray(np.random.RandomState(2).randn(64, 16),
                        jnp.float32).at[live:].set(0.0)

    def run(f):
        out, pull = jax.vjp(f, rows, w_up, w_down)
        return (out[:live],) + tuple(
            g[:live] if g.shape == rows.shape else g for g in pull(probe))

    got = run(lambda r, u, d: getattr(pmoe, mlp)(
        r, sizes, (u,), d, 'relu2', low).astype(jnp.float32))
    want = run(lambda r, u, d: jnp.pad(
        _plain_relu2(r[:live], sizes, u, d), ((0, 64 - live), (0, 0))))
    for what, a, b in zip(('out', 'drows', 'dup', 'ddown'), got, want):
        _close(a, b, 3e-2 if low else 2e-5, what)


@pytest.mark.parametrize('mlp', ['grouped_expert_mlp', 'held_expert_mlp'])
def test_a_width_off_the_lane_tiles_still_runs_the_kernels(
        mlp, pallas_interpret):
    """Nemotron-H's experts are 1856 wide, 14.5 lane tiles: the grouped
    products still run the kernels of ops/pallas/grouped_matmul.py
    (under the interpreter here), on weights padded with zeros to the
    next tile, and give the unpadded ``ragged_dot`` products' values
    and gradients in the weights' own shapes, to bfloat16's rounding."""
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.ops.pallas import common
    rows, w_up, w_down = _expert_operands(5, 256, 4, 128, 192, jnp.bfloat16)
    rows = rows[:, 0]
    sizes = jnp.asarray([70, 0, 100, 30], jnp.int32)
    probe = jnp.asarray(np.random.RandomState(2).randn(256, 128),
                        jnp.bfloat16).at[200:].set(0.0)

    def run():
        out, pull = jax.vjp(
            lambda r, u, d: getattr(pmoe, mlp)(r, sizes, (u,), d, 'relu2',
                                               True), rows, w_up, w_down)
        return [np.asarray(x[:200] if x.shape[0] == 256 else x, np.float32)
                for x in (out,) + pull(probe)]

    before = monitor.flat().get('pallas/grouped_matmul/dispatch_fused', 0)
    got = run()
    assert common._LAST['grouped_matmul']['path'] == 'fused'
    assert monitor.flat()['pallas/grouped_matmul/dispatch_fused'] > before
    set_flags({'FLAGS_pallas_force': False})
    want = run()
    assert common._LAST['grouped_matmul']['path'] == 'dense'
    for what, a, b in zip(('out', 'drows', 'dup', 'ddown'), got, want):
        _close(a, b, 2e-2, what)


def test_the_relu2_layer_creates_no_gate_and_is_dropless_only():
    """``layers.moe(expert_form='relu2')``: router, up, down and
    nothing else; the capacity-based path and an unknown form raise."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data('x', shape=[4, 8], dtype='float32')
        layers.moe(x, num_experts=8, hidden_size=6, capacity_factor=None,
                   top_k=2, expert_form='relu2', experts_held=(2, 4))
        shapes = [tuple(p.shape) for p in
                  fluid.default_main_program().all_parameters()]
        assert shapes == [(8, 8), (4, 8, 6), (4, 6, 8)]
        with pytest.raises(ValueError, match='dropless'):
            layers.moe(x, num_experts=8, hidden_size=6, top_k=2,
                       expert_form='relu2')
        with pytest.raises(ValueError, match='expert_form'):
            layers.moe(x, num_experts=8, hidden_size=6, top_k=2,
                       capacity_factor=None, expert_form='swish')


def _parents_grouped_gated_mlp(rows, group_sizes, w_gate, w_up, w_down):
    """The all-held experts' MLP as it stood before the experts had
    forms (``grouped_gated_mlp`` then)."""
    dot, rows, (w_gate, w_up, w_down) = pmoe._operands(
        rows, group_sizes, (w_gate, w_up, w_down), False)
    gate = dot.with_gradient(rows, w_gate)
    up = dot.with_gradient(rows, w_up)
    return dot.with_gradient(pmoe._gated(gate, up, rows.dtype), w_down)


def test_the_gated_forms_programs_are_as_they_were():
    """The default form leaves every gated layer's program alone: the
    ``moe_experts`` ops of OLMoE's tiny train program carry no
    ``expert_form`` and their three weight sets, and the op's lowering
    traces to the SAME jaxpr, forward and backward, as the three
    products and the SiLU gate written out as they stood."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        olmoe.build_pretrain(olmoe.TINY, 16)
    experts = [op for op in main.global_block().ops
               if op.type == 'moe_experts']
    assert experts and all(
        list(op.inputs) == ['Rows', 'GroupSizes', 'WGate', 'WUp', 'WDown']
        and 'expert_form' not in op.attrs for op in experts)
    rows, w_up, w_down = _expert_operands(3, 32, 4, 8, 12)
    operands = (rows[:, 0], jnp.asarray([9, 0, 16, 7], jnp.int32),
                w_up + 1.0, w_up, w_down)

    def step(f):
        def both(*x):
            out, pull = jax.vjp(lambda r, g, u, d: f(r, x[1], g, u, d),
                                x[0], *x[2:])
            return (out,) + pull(out)
        return str(jax.make_jaxpr(both)(*operands))

    def as_lowered(r, sizes, g, u, d):
        ins = {'Rows': [r], 'GroupSizes': [sizes], 'WGate': [g],
               'WUp': [u], 'WDown': [d]}
        return registry.get('moe_experts').run(None, ins, {})['Out'][0]

    assert step(as_lowered) == step(_parents_grouped_gated_mlp)


# --- the shares -------------------------------------------------------


def test_sixteen_expert_shares_and_the_shared_expert_once_add_up():
    """One routed layer cut sixteen ways (``experts_held`` = (2 i, 2) of
    32): every share routes over all 32 with the same router and bias
    and computes its own two experts' part; the sixteen parts and the
    shared expert ONCE are the uncut reference's layer."""
    cfg = copy.copy(zoo.TINY)
    cfg.experts, cfg.top_k = 32, 5
    rng = np.random.RandomState(8)
    d, w = cfg.hidden, cfg.expert_hidden
    u = rng.randn(2, SEQ, d).astype('float32')
    router = (4.0 * rng.randn(d, 32) / np.sqrt(d)).astype('float32')
    up = (rng.randn(32, d, w) / np.sqrt(d)).astype('float32')
    down = (rng.randn(32, w, d) / np.sqrt(w)).astype('float32')
    bias = (0.3 * rng.randn(32)).astype('float32')
    shared = [(rng.randn(d, cfg.shared_hidden) / np.sqrt(d)).astype(
        'float32'), (rng.randn(cfg.shared_hidden, d) / np.sqrt(
            cfg.shared_hidden)).astype('float32')]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = layers.data('u', shape=[SEQ, d], dtype='float32')
            total, values = None, []
            for i in range(16):
                share = copy.copy(cfg)
                share.experts_held = (2 * i, 2)
                before = len(main.all_parameters())
                out = zoo.moe_mixer(x, share)
                made = [p.name for p in main.all_parameters()[before:]]
                values += zip(made, [router, up[2 * i:2 * i + 2],
                                     down[2 * i:2 * i + 2], bias] + shared)
                if i:       # the shared expert counts once
                    out = layers.elementwise_sub(
                        out, zoo.relu2_mlp(x, cfg.shared_hidden, cfg,
                                           *(fluid.initializer.Constant(0.)
                                             ,) * 2))
                    values += zip([p.name for p in main.all_parameters()
                                   [before + len(made):]], shared)
                total = out if total is None else \
                    layers.elementwise_add(total, out)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for name, value in values:
            fluid.global_scope().set_var(name, jnp.asarray(value))
        got, = exe.run(main, feed={'u': u}, fetch_list=[total])
    with jax.default_matmul_precision('highest'):
        flat = jnp.asarray(u).reshape(-1, d)
        want = reference.relu2_mlp(jnp.asarray(u), *shared) + \
            reference.routed(flat, router, up, down, bias, 5, 0,
                             cfg.routed_scale).reshape(u.shape)
        one = reference.routed(flat, router, up[:2], down[:2], bias, 5, 0,
                               cfg.routed_scale)
    _close(got, want, 2e-5)
    # a share alone is a small part of the sum: the test can fail
    assert np.abs(np.asarray(one)).max() < 0.5 * np.abs(want).max()


# --- attention at sixteen queries a K/V head --------------------------


def _plain_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    t = q.shape[1]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   precision='highest') / np.sqrt(q.shape[-1])
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v, precision='highest')


@pytest.mark.parametrize('arm', ['fused', 'dense'])
def test_thirty_two_query_heads_over_two_kv_heads(arm, request, monkeypatch):
    """``layers.flash_attention`` at the model's grouping (32 query
    heads over 2 K/V heads, causal, nothing rotated) through the
    executor, on the flash kernels (under the interpreter) and on the
    op's dense chain: the output and the three gradients against the
    plain form with K and V repeated sixteen times."""
    if arm == 'fused':
        request.getfixturevalue('pallas_interpret')
        monkeypatch.setattr(fa, 'FLASH_MIN_SEQ', 128)
    t, h, kv, d = 256, 32, 2, 16
    rng = np.random.RandomState(6)
    feed = {n: rng.randn(1, t, heads, d).astype('float32')
            for n, heads in (('q', h), ('k', kv), ('v', kv), ('w', h))}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v, w = (layers.data(n, shape=list(feed[n].shape),
                                  dtype='float32', append_batch_size=False)
                      for n in 'qkvw')
        for var in (q, k, v):
            var.stop_gradient = False
        out = layers.flash_attention(q, k, v, causal=True)
        fluid.backward.append_backward(
            layers.reduce_sum(layers.elementwise_mul(out, w)))
        grads = [main._grad_name_map[var.name] for var in (q, k, v)]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        got = exe.run(main, feed=feed, fetch_list=[out] + grads)
    assert fa._common._LAST['flash_attention']['path'] == arm
    want, pull = jax.vjp(_plain_attention,
                         *(jnp.asarray(feed[n]) for n in 'qkv'))
    for what, a, b in zip(('o', 'dq', 'dk', 'dv'), got,
                          (want,) + pull(jnp.asarray(feed['w']))):
        _close(a, b, 2e-5, what)
