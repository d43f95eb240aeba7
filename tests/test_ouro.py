"""Ouro through fluid against its plain reference
(``paddle_tpu/models/reference/ouro.py``): the looped program's loss
and every parameter's gradient (a shared layer's is the sum over the
trips), the ``While`` arm against the straight-line arm, the
``for_test`` clone (``lax.while_loop``) against the train program's
masked scan, what each part of the mathematics moves, the loop's
forward run once a step on both gradient paths, the recompute groups
and the bf16 AMP program.  CPU, tiny sizes; the published widths are
checked on the chip (``chip_smoke.py --phase ouro``, PERF.md)."""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor, profiler
from paddle_tpu.fluid.contrib import mixed_precision
from paddle_tpu.models import ouro
from paddle_tpu.models.reference import ouro as reference

CFG = ouro.TINY
SEQ = 32
PARTS = ('post_norms', 'norm_between', 'gate', 'entropy')


def _sizes(cfg):
    return dict(layers=cfg.layers, heads=cfg.heads, steps=cfg.steps,
                eps=cfg.rms_eps, theta=cfg.rope_theta,
                beta=cfg.entropy_weight)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, cfg, seed):
    """Weights large enough that every part of the model moves the
    loss (the zoo's Normal(0.02) at width 64 leaves the logits flat and
    the gate at 1/2): unit-variance matmuls, gains around 1, a gate
    whose logits spread over a few units."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s) if s[0] > 1 else 0.3 * rng.randn(*s)
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[0])
        out.append(w.astype('float32'))
    return out


def _run(cfg=CFG, unrolled=False, amp=False, seed=3, wpg=True,
         optimizer=None, steps=1):
    """The train program (SGD at lr 0 unless given, so the fetched
    gradients are the whole step) on seeded weights -> dict of what the
    tests read."""
    old = fluid.flags.get_flag('FLAGS_whole_program_grad')
    fluid.set_flags({'FLAGS_whole_program_grad': wpg})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.scope_guard(fluid.Scope()):
            with fluid.program_guard(main, startup), \
                    fluid.unique_name.guard():
                _, _, loss = ouro.build_pretrain(cfg, SEQ,
                                                 unrolled=unrolled)
                params = [p.name for p in main.all_parameters()]
                shapes = [tuple(main.global_block().var(p).shape)
                          for p in params]
                test = main.clone(for_test=True)
                opt = optimizer or fluid.optimizer.SGD(0.0)
                if amp:
                    opt = mixed_precision.decorate(
                        opt, use_dynamic_loss_scaling=False,
                        init_loss_scaling=1.0)
                pairs = opt.minimize(loss)[1]
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            weights = _seeded_weights(shapes, cfg, seed)
            scope = fluid.global_scope()
            for name, w in zip(params, weights):
                scope.set_var(name, jnp.asarray(w))
            feed = ouro.synthetic_batch(cfg, 2, SEQ,
                                        np.random.RandomState(seed))
            test_loss = _scalar(exe.run(test, feed=feed,
                                        fetch_list=[loss])[0])
            losses = []
            for _ in range(steps):
                out = exe.run(main, feed=feed, fetch_list=[loss] + [
                    g.name for _, g in pairs])
                losses.append(_scalar(out[0]))
            trips = monitor.gauge_value('loop/trips', None)
    finally:
        fluid.set_flags({'FLAGS_whole_program_grad': old})
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:])}
    return dict(loss=losses[0], losses=losses, test_loss=test_loss,
                grads=grads, params=params, weights=weights, feed=feed,
                main=main, trips=trips)


def _reference(got, cfg=CFG, **kw):
    feed = got['feed']
    return reference.loss_and_grads(
        got['weights'], feed['ids'], feed['pos_ids'], feed['labels'],
        **dict(_sizes(cfg), **kw))


@pytest.fixture(scope='module')
def looped():
    return _run()


def _worst(grads, want, params):
    """The largest entry-wise distance of any gradient from ``want``'s,
    over that gradient's largest entry."""
    return max(np.abs(grads[n] - np.asarray(g)).max() /
               np.abs(np.asarray(g)).max() for n, g in zip(params, want))


def test_loss_matches_the_reference(looped):
    """Both multiply at full float32 precision: what is left is the
    order of float32 sums."""
    want, _ = _reference(looped)
    assert abs(looped['loss'] - float(want)) <= 1e-5 * abs(float(want))


def test_every_gradient_matches_the_reference(looped):
    """EVERY parameter, the shared layers' included: their gradient is
    the sum over the four trips, which ``jax.grad`` gives the reference
    for an array it uses four times.  Measured 8e-7 of each gradient's
    largest entry; a trip's contribution left out moves a layer's
    gradient by tens of percent."""
    _, want = _reference(looped)
    assert set(looped['grads']) == set(looped['params'])
    assert _worst(looped['grads'], want, looped['params']) <= 2e-5


def test_the_parameter_list_holds_each_layer_once(looped):
    params = looped['params']
    assert len(params) == len(set(params)) == 1 + 11 * CFG.layers + 4
    assert params[0] == 'ouro_embedding' and params[-4:] == [
        'ouro_g_f', 'ouro_w_head', 'ouro_w_gate', 'ouro_b_gate']
    ops = [op.type for op in looped['main'].global_block().ops]
    assert ops.count('while') == 1
    # the products live in the sub-block: none of the stack's in the
    # main block, whatever the number of passes
    body = looped['main'].blocks[1].ops
    assert sum(op.type == 'mul' for op in body) == 7 * CFG.layers + 2
    assert not [op for op in looped['main'].global_block().ops
                if op.type == 'mul' and
                op.attrs.get('__op_role__') == 'forward']


@pytest.mark.parametrize('wpg', [True, False],
                         ids=['whole_program_vjp', 'per_op_grad_ops'])
def test_the_loop_arm_equals_the_straight_line_arm(looped, wpg):
    """ONE ``While`` over shared weights against a Python ``for`` over
    the trips, loss and every gradient, on both gradient paths."""
    straight = _run(unrolled=True, wpg=wpg)
    assert 'while' not in [op.type for op in
                           straight['main'].global_block().ops]
    assert abs(straight['loss'] - looped['loss']) <= 1e-6 * looped['loss']
    assert _worst(straight['grads'],
                  [looped['grads'][n] for n in looped['params']],
                  looped['params']) <= 1e-5


def test_the_for_test_clone_equals_the_train_program_s_forward(looped):
    """The clone taken before ``minimize`` lowers the loop as
    ``lax.while_loop``, the train program as a masked scan: the
    benchmark checks the first against the reference and times the
    second."""
    assert abs(looped['test_loss'] - looped['loss']) <= \
        1e-6 * looped['loss']


@pytest.mark.parametrize('wpg', [True, False],
                         ids=['whole_program_vjp', 'per_op_grad_ops'])
def test_the_loop_s_forward_runs_once_a_step(looped, wpg):
    """``loop/trips`` counts the body executions of the traced train
    program: 4, not 8, also where an explicit ``while_grad`` op is
    lowered (it takes the vjp its forward op kept), and the gradients
    are the same."""
    got = looped if wpg else _run(wpg=False)
    assert got['trips'] == CFG.steps
    types = [op.type for op in got['main'].global_block().ops]
    assert types.count('while_grad') == 1
    assert _worst(got['grads'],
                  [looped['grads'][n] for n in looped['params']],
                  looped['params']) <= 1e-5


def test_one_pass_is_a_plain_decoder():
    """``total_ut_steps`` 1: p_1 = 1, no entropy, loss = ce_1 of the
    stack applied once."""
    cfg = ouro.OuroConfig(vocab_size=97, hidden=64, layers=2, heads=4,
                          intermediate=96, steps=1, max_pos=128)
    got = _run(cfg)
    want, probs, ces = reference.forward(
        got['weights'], got['feed']['ids'], got['feed']['pos_ids'],
        got['feed']['labels'], **_sizes(cfg))
    assert abs(got['loss'] - float(want)) <= 1e-5 * float(want)
    assert np.allclose(np.asarray(probs), 1.0)
    valid = got['feed']['labels'] >= 0
    assert abs(float(want) - float(np.asarray(ces)[0][valid].mean())) \
        <= 1e-6 * float(want)
    # the gate trains nothing when there is one exit
    assert not got['grads']['ouro_w_gate'].any()


def test_the_exit_distribution_sums_to_one_and_the_entropy_s_sign(looped):
    feed = looped['feed']
    kw = _sizes(CFG)
    loss, probs, _ = reference.forward(
        looped['weights'], feed['ids'], feed['pos_ids'], feed['labels'],
        **kw)
    probs = np.asarray(probs)
    assert probs.shape[0] == CFG.steps and (probs > 0).all()
    assert np.allclose(probs.sum(0), 1.0, atol=1e-6)
    entropy = -(probs * np.log(probs)).sum(0)
    assert (entropy <= math.log(CFG.steps) + 1e-6).all()
    # loss = E[ce] - beta H: a larger beta lowers it by beta's change
    # times the mean entropy over the labelled positions
    more, _, _ = reference.forward(
        looped['weights'], feed['ids'], feed['pos_ids'], feed['labels'],
        **dict(kw, beta=kw['beta'] + 0.5))
    valid = feed['labels'] >= 0
    assert float(more) < float(loss)
    assert abs(float(loss) - float(more) - 0.5 * entropy[valid].mean()) \
        <= 1e-5


@pytest.mark.parametrize('part', PARTS)
def test_each_part_of_the_mathematics_moves_the_loss(looped, part):
    """The reference WITHOUT one part misses the program's loss by far
    more than the tolerance the whole one meets."""
    want, _ = _reference(looped, without=(part,))
    assert abs(looped['loss'] - float(want)) > 1e-3 * abs(float(want))


def test_the_gauges_of_the_exit_distribution(looped):
    """``ouro/exit_entropy`` and ``ouro/exit_mass_last`` are read on a
    fetching run (``Program.watch``) and are the reference's means over
    all positions."""
    got = _run()
    feed = got['feed']
    _, probs, _ = reference.forward(
        got['weights'], feed['ids'], feed['pos_ids'], feed['labels'],
        **_sizes(CFG))
    probs = np.asarray(probs)
    entropy = -(probs * np.log(probs)).sum(0).mean()
    assert abs(monitor.gauge_value('ouro/exit_entropy') - entropy) <= 1e-5
    assert abs(monitor.gauge_value('ouro/exit_mass_last') -
               probs[-1].mean()) <= 1e-5
    assert 0 < entropy <= math.log(CFG.steps)


def test_bf16_amp_trains_with_bfloat16_products_in_the_sub_block():
    """The bf16 rewrite reaches the sub-block: every product of the
    stack is marked, the head's keeps its float32 accumulator, the
    gate's stays float32; the program is nearer the f32 reference than
    the reference computed in bfloat16 throughout, and its loss falls
    under AdamW."""
    got = _run(amp=True)
    body = got['main'].blocks[1].ops
    muls = [op for op in body if op.type == 'mul']
    marked = [op for op in muls if op.attrs.get('__amp__')]
    assert len(muls) == 7 * CFG.layers + 2
    assert len(marked) == 7 * CFG.layers + 1       # all but the gate's
    assert sum(bool(op.attrs.get(mixed_precision.decorator.FLOAT32_OUTPUT))
               for op in marked) == 1
    want, _ = _reference(got)
    low, _ = _reference(got, dtype=jnp.bfloat16)
    amp_err = abs(got['loss'] - float(want)) / float(want)
    low_err = abs(float(low) - float(want)) / float(want)
    assert amp_err < low_err and amp_err < 5e-3
    trained = _run(amp=True, steps=6, optimizer=fluid.optimizer.AdamW(
        learning_rate=1e-2, weight_decay=0.1))
    assert trained['losses'][-1] < trained['losses'][0]
    assert all(math.isfinite(v) for v in trained['losses'])
    assert trained['trips'] == CFG.steps


def test_float32_output_keeps_the_accumulator_and_a_bfloat16_backward():
    """``mixed_precision.float32_output``: bfloat16 operands, float32
    result; the gradient products take the cotangent in bfloat16 like
    every AMP product.  Without AMP the mark changes nothing."""
    from paddle_tpu.ops import registry
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 16).astype('float32'))
    w = jnp.asarray(rng.randn(16, 12).astype('float32'))
    run = registry.get('mul').run
    ctx = registry.LowerCtx(0)
    attrs = {'x_num_col_dims': 2, 'y_num_col_dims': 1}

    def out(**marks):
        return run(ctx, {'X': [x], 'Y': [w]}, dict(attrs, **marks))[
            'Out'][0]

    kept = out(__amp__=True, __amp_float32_out__=True)
    rounded = out(__amp__=True)
    assert kept.dtype == jnp.float32 and rounded.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(kept.astype(jnp.bfloat16)),
                          np.asarray(rounded))
    assert not np.array_equal(np.asarray(kept),
                              np.asarray(rounded.astype(jnp.float32)))
    plain = out(__amp_float32_out__=True)
    assert np.array_equal(np.asarray(plain), np.asarray(out()))

    def loss(w, **marks):
        return jnp.sum(run(ctx, {'X': [x], 'Y': [w]},
                           dict(attrs, **marks))['Out'][0].astype(
            jnp.float32) ** 2)

    g_kept = jax.grad(loss)(w, __amp__=True, __amp_float32_out__=True)
    g_full = jax.grad(loss)(w)
    assert g_kept.dtype == jnp.float32
    assert np.abs(np.asarray(g_kept - g_full)).max() <= \
        3e-2 * np.abs(np.asarray(g_full)).max()


@pytest.mark.parametrize('grouped', [False, True])
def test_recompute_guard_decides_what_a_loop_keeps(grouped):
    """A differentiable ``While`` whose body is x = tanh(exp(x) * w):
    with the body under ``backward.recompute_guard`` the scan keeps the
    body's INPUT a trip and computes the rest again; without, it keeps
    the intermediates jax names.  Same loss, same gradient."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = layers.data('x', shape=[64], dtype='float32')
            w = layers.create_parameter([64], 'float32', name='w')
            i = layers.fill_constant([1], 'int64', 0)
            n = layers.fill_constant([1], 'int64', 3)
            going = layers.less_than(i, n)
            state = layers.scale(x, scale=1.0)
            loop = layers.While(going, max_trip_count=3)
            with loop.block():
                if grouped:
                    with fluid.backward.recompute_guard():
                        new = layers.tanh(layers.elementwise_mul(
                            layers.exp(state), w))
                else:
                    new = layers.tanh(layers.elementwise_mul(
                        layers.exp(state), w))
                layers.assign(new, state)
                layers.increment(i, 1.0)
                layers.less_than(i, n, cond=going)
            loss = layers.mean(state)
            pairs = fluid.optimizer.SGD(0.0).minimize(loss)[1]
        tagged = [op for op in main.blocks[1].ops
                  if '__recompute__' in op.attrs]
        assert len(tagged) == (3 if grouped else 0)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        scope.set_var('w', jnp.linspace(0.5, 1.5, 64))
        feed = {'x': np.linspace(-1, 1, 2 * 64).reshape(2, 64).astype(
            'float32')}
        got = exe.run(main, feed=feed,
                      fetch_list=[loss, pairs[0][1].name])
        step = exe.compile(main, feed_names=['x'],
                           fetch_names=[loss.name])
        state_vals = {k: fluid.core.as_array(scope.find_var(k))
                      for k in step.state_names}
        data = {k: jnp.asarray(feed[k]) if k in feed else
                fluid.core.as_array(scope.find_var(k))
                for k in step.input_names}

    # the step itself holds its vjp: count the scans of its own trace
    jaxpr = jax.make_jaxpr(step.fn)(jnp.int32(0), state_vals, data)
    stacked = sorted(
        tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
        if eqn.primitive.name == 'scan' and eqn.params['length'] == 3
        for v in eqn.outvars[eqn.params['num_carry']:]
        if len(v.aval.shape) == 3)
    # [3, 2, 64] residuals a trip: the input alone when grouped
    assert len(stacked) == (1 if grouped else 3), stacked
    want_loss, want_grad = jax.value_and_grad(
        lambda wv: jnp.mean(_three_times(jnp.asarray(feed['x']), wv)))(
            jnp.linspace(0.5, 1.5, 64))
    assert abs(_scalar(got[0]) - float(want_loss)) <= 1e-6
    assert np.abs(np.asarray(got[1]) - np.asarray(want_grad)).max() <= 1e-6


def _three_times(x, w):
    for _ in range(3):
        x = jnp.tanh(jnp.exp(x) * w)
    return x


@pytest.mark.parametrize('op_name,scope,side', [
    ('jit(s)/jvp(while)/while/body/loop_body/closed_call/mul/dot_general',
     'mul', 'forward'),
    ('jit(s)/transpose(jvp(while))/while/body/loop_body/closed_call/mul/'
     'dot_general', 'mul_grad', 'backward'),
    ('jit(s)/jvp(while)/while/body/loop_body/checkpoint/rms_norm/mul',
     'rms_norm', 'forward'),
    ('jit(s)/transpose(jvp(while))/while/body/loop_body/checkpoint/'
     'rematted_computation/rms_norm/mul', 'rms_norm', 'backward'),
    ('jit(s)/while/while/body/closed_call/fused_multihead_attention/'
     'pallas_call', 'fused_multihead_attention', None),
    ('jit(s)/jvp(while)/while/body/dynamic_update_slice', 'while/while',
     None),
    ('jit(s)/transpose(jvp(while))/while/body/dynamic_slice',
     'while_grad/while', None),
    ('jit(s)/while_grad/transpose(jvp(while))/while/body/loop_body/mul/'
     'dot_general', 'mul_grad', 'backward'),
    ('jit(s)/gaussian_random/jit(_uniform)/while/body/add',
     'gaussian_random', None),
    ('jit(s)/transpose(jvp(mul))/dot_general', 'mul_grad', None),
    ('jit(s)/mul/dot_general', 'mul', None),
])
def test_the_scope_table_looks_into_a_loop_s_body(op_name, scope, side):
    """An instruction inside a ``while`` body counts to the fluid op it
    was lowered from, backward where the loop's component is
    transposed (a recompute group's second forward there keeps the
    forward's name: its pass says ``recomputed``); only what the loop
    adds counts to ``while``; the loop table tells the forward body
    from the transposed one."""
    assert profiler.fluid_scope(op_name) == scope
    assert profiler.loop_side(op_name) == side
