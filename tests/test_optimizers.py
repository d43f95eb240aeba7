"""Optimizer op math vs numpy references.

Mirrors reference tests test_sgd_op.py, test_momentum_op.py,
test_adam_op.py (python/paddle/fluid/tests/unittests/), plus whole-loop
convergence checks through the Python optimizer classes.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import registry

rng = np.random.RandomState(11)


def run_lowering(op, ins, attrs=None):
    return registry.get(op).fn(registry.LowerCtx(0),
                               {k: [v] for k, v in ins.items()},
                               attrs or {})


def test_sgd_op():
    p = rng.randn(4, 3).astype('float32')
    g = rng.randn(4, 3).astype('float32')
    lr = np.array([0.1], 'float32')
    out = run_lowering('sgd', {'Param': p, 'Grad': g,
                               'LearningRate': lr})
    np.testing.assert_allclose(out['ParamOut'][0], p - 0.1 * g,
                               rtol=1e-6)


def test_momentum_op():
    p = rng.randn(4).astype('float32')
    g = rng.randn(4).astype('float32')
    v = rng.randn(4).astype('float32')
    lr = np.array([0.01], 'float32')
    out = run_lowering('momentum',
                       {'Param': p, 'Grad': g, 'Velocity': v,
                        'LearningRate': lr}, {'mu': 0.9})
    v2 = 0.9 * v + g
    np.testing.assert_allclose(out['VelocityOut'][0], v2, rtol=1e-6)
    np.testing.assert_allclose(out['ParamOut'][0], p - 0.01 * v2,
                               rtol=1e-6)


def test_adam_op():
    p = rng.randn(6).astype('float32')
    g = rng.randn(6).astype('float32')
    m1 = rng.randn(6).astype('float32') * 0.1
    m2 = np.abs(rng.randn(6)).astype('float32') * 0.1
    b1p = np.array([0.9], 'float32')
    b2p = np.array([0.999], 'float32')
    lr = np.array([0.001], 'float32')
    out = run_lowering('adam',
                       {'Param': p, 'Grad': g, 'Moment1': m1,
                        'Moment2': m2, 'Beta1Pow': b1p, 'Beta2Pow': b2p,
                        'LearningRate': lr},
                       {'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8})
    m1n = 0.9 * m1 + 0.1 * g
    m2n = 0.999 * m2 + 0.001 * g * g
    lr_t = 0.001 * np.sqrt(1 - b2p * 0.999) / (1 - b1p * 0.9)
    pn = p - lr_t * m1n / (np.sqrt(m2n) + 1e-8)
    np.testing.assert_allclose(out['ParamOut'][0], pn, rtol=1e-5)
    np.testing.assert_allclose(out['Beta1PowOut'][0], b1p * 0.9,
                               rtol=1e-6)


def _train_quadratic(optimizer, steps=100):
    """Minimize ||Wx - y||^2; returns final loss."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        y = fluid.layers.data('y', shape=[2], dtype='float32')
        pred = fluid.layers.fc(x, 2, bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        optimizer.minimize(loss)
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    W = r.randn(4, 2).astype('float32')
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        final = None
        for _ in range(steps):
            xs = r.randn(16, 4).astype('float32')
            ys = xs @ W
            final, = exe.run(main, feed={'x': xs, 'y': ys},
                             fetch_list=[loss])
    return float(final)


@pytest.mark.parametrize('opt_fn,steps,tol', [
    (lambda: fluid.optimizer.SGD(0.1), 100, 0.05),
    (lambda: fluid.optimizer.Momentum(0.05, momentum=0.9), 100, 0.05),
    (lambda: fluid.optimizer.Momentum(0.05, momentum=0.9,
                                      use_nesterov=True), 100, 0.05),
    (lambda: fluid.optimizer.Adam(0.05), 100, 0.05),
    (lambda: fluid.optimizer.AdamW(0.05, weight_decay=0.001), 100, 0.05),
    (lambda: fluid.optimizer.Adagrad(0.3), 100, 0.05),
    (lambda: fluid.optimizer.RMSProp(0.05), 100, 0.05),
    (lambda: fluid.optimizer.Lamb(0.05), 100, 0.05),
    # adamax / adadelta ramp up slowly by construction
    (lambda: fluid.optimizer.Adamax(0.1), 400, 0.1),
    (lambda: fluid.optimizer.Adadelta(1.0), 900, 0.5),
    (lambda: fluid.optimizer.Ftrl(0.5), 100, 0.05),
])
def test_optimizer_converges(opt_fn, steps, tol):
    final = _train_quadratic(opt_fn(), steps=steps)
    assert final < tol, final


def test_weight_decay_regularizer():
    opt = fluid.optimizer.SGD(
        0.1, regularization=fluid.regularizer.L2Decay(0.01))
    final = _train_quadratic(opt)
    assert final < 0.1


def test_global_norm_clip():
    opt = fluid.optimizer.SGD(
        0.1, grad_clip=fluid.clip.GradientClipByGlobalNorm(0.5))
    final = _train_quadratic(opt, steps=200)
    assert final < 0.1, final


def test_adam_matches_hand_rollout_multi_param():
    """Hand-rollout parity for the shared-beta-pow Adam (round 4): two
    params, three steps, exact bias-corrected trajectory; the shared
    pow advances once per STEP (not once per param)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    b1, b2, lr = 0.8, 0.95, 0.1
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.data('y', shape=[1], dtype='float32')
        h = layers.fc(x, size=3, bias_attr=False,
                      param_attr=fluid.ParamAttr(name='w_a'))
        p = layers.fc(h, size=1, bias_attr=False,
                      param_attr=fluid.ParamAttr(name='w_b'))
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        fluid.optimizer.Adam(lr, beta1=b1, beta2=b2).minimize(loss)
    xd = np.asarray([[1., 2., -1., 0.5], [0.5, -1., 2., 1.]],
                    dtype='float32')
    yd = np.zeros((2, 1), 'float32')
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        wa = np.asarray(fluid.core.as_array(sc.find_var('w_a'))).copy()
        wb = np.asarray(fluid.core.as_array(sc.find_var('w_b'))).copy()
        ma = np.zeros_like(wa); va = np.zeros_like(wa)
        mb = np.zeros_like(wb); vb = np.zeros_like(wb)
        for t in range(1, 4):
            exe.run(main, feed={'x': xd, 'y': yd}, fetch_list=[loss])
            hidden = xd @ wa
            pred = hidden @ wb
            dpred = (2.0 / xd.shape[0]) * (pred - yd)
            gb = hidden.T @ dpred
            ga = xd.T @ (dpred @ wb.T)
            lr_t = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
            for (w, m, v, g) in ((wa, ma, va, ga), (wb, mb, vb, gb)):
                m *= b1; m += (1 - b1) * g
                v *= b2; v += (1 - b2) * g * g
                w -= lr_t * m / (np.sqrt(v) + 1e-8)
        got_a = np.asarray(fluid.core.as_array(sc.find_var('w_a')))
        got_b = np.asarray(fluid.core.as_array(sc.find_var('w_b')))
        # the SHARED pow advanced exactly beta^3 (once per step)
        pows = [float(np.asarray(fluid.core.as_array(v)).ravel()[0])
                for n, v in sc._vars.items() if 'beta1_pow_acc' in n]
    assert len(pows) == 1, pows  # ONE shared accumulator
    assert abs(pows[0] - b1 ** 3) < 1e-6, pows
    np.testing.assert_allclose(got_a, wa, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_b, wb, rtol=1e-4, atol=1e-5)


def test_beta_pow_advances_once_per_step_adam_and_lamb():
    """Regression for the shared-pow refactor: after ONE step with N
    params, beta1_pow must equal beta1 exactly — for Adam (one shared
    pow) AND Lamb (per-param pows advanced by its own op)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    for opt_cls, kw in ((fluid.optimizer.Adam, {}),
                        (fluid.optimizer.Lamb,
                         {'lamb_weight_decay': 0.0})):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[4], dtype='float32')
            h = layers.fc(x, size=3)           # weight + bias
            p = layers.fc(h, size=1)           # weight + bias
            loss = layers.reduce_mean(p)
            opt_cls(0.01, beta1=0.9, **kw).minimize(loss)
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                    fetch_list=[loss])
            pows = [float(np.asarray(fluid.core.as_array(v)).ravel()[0])
                    for n, v in sc._vars.items()
                    if 'beta1_pow_acc' in n]
        assert pows, opt_cls
        for pw in pows:
            assert abs(pw - 0.9) < 1e-6, (opt_cls.__name__, pows)


# ----- adam / adamw / lamb: one registered lowering per parameter -----
# The only way these ops are lowered, on every platform and under every
# runner: each through its own lowering in its own named scope, XLA
# fusing the elementwise chain per parameter and updating the donated
# state in place.

def _adam_family_ins(n_tensors, seed, zero_idx=None):
    """One {slot: array} per tensor: distinct shapes, learning rates
    and beta powers; tensor ``zero_idx`` has zero gradient and
    moments."""
    r = np.random.RandomState(seed)
    shapes = [(33, 47), (128,), (5, 8, 13), (257,)][:n_tensors]
    out = []
    for i, s in enumerate(shapes):
        live = 0.0 if zero_idx == i else 1.0
        out.append({
            'Param': r.randn(*s).astype('float32'),
            'Grad': live * r.randn(*s).astype('float32'),
            'Moment1': live * r.randn(*s).astype('float32'),
            'Moment2': live * np.abs(r.randn(*s)).astype('float32'),
            'LearningRate': np.array([0.001 * (i + 1)], 'float32'),
            'Beta1Pow': np.array([0.9 ** (i + 1)], 'float32'),
            'Beta2Pow': np.array([0.999 ** (i + 1)], 'float32')})
    return out


def _adam_family_reference(kind, ins, attrs):
    """The update in float64 NumPy, from the reference operators'
    formulas (adam_op.h, lamb_op.h)."""
    p, g, m1, m2 = (ins[k].astype('float64') for k in
                    ('Param', 'Grad', 'Moment1', 'Moment2'))
    lr, b1p, b2p = (float(ins[k][0]) for k in
                    ('LearningRate', 'Beta1Pow', 'Beta2Pow'))
    b1, b2 = attrs['beta1'], attrs['beta2']
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * g * g
    if kind == 'lamb':
        eps, wd = attrs.get('epsilon', 1e-6), attrs['weight_decay']
        r = m1n / (1 - b1p * b1) / \
            (np.sqrt(m2n / (1 - b2p * b2)) + eps) + wd * p
        pn, rn = np.sqrt((p * p).sum()), np.sqrt((r * r).sum())
        trust = pn / rn if pn > 0 and rn > 0 else 1.0
        pout = p - lr * trust * r
    else:
        eps = attrs.get('epsilon', 1e-8)
        lr_t = lr * np.sqrt(1 - b2p * b2) / (1 - b1p * b1)
        pout = p - lr_t * m1n / (np.sqrt(m2n) + eps)
        if kind == 'adamw':
            pout = pout - lr * attrs['coeff'] * p
    return {'ParamOut': pout, 'Moment1Out': m1n, 'Moment2Out': m2n,
            'Beta1PowOut': np.array([b1p * b1]),
            'Beta2PowOut': np.array([b2p * b2])}


@pytest.mark.parametrize('kind,attrs', [
    ('adam', {'beta1': 0.9, 'beta2': 0.999}),
    ('adamw', {'beta1': 0.9, 'beta2': 0.999, 'coeff': 0.02}),
    ('lamb', {'beta1': 0.9, 'beta2': 0.999, 'weight_decay': 0.01}),
])
def test_adam_family_per_tensor_lowering_vs_numpy(kind, attrs):
    for ins in _adam_family_ins(4, seed=3):
        got = run_lowering(kind, ins, attrs)
        want = _adam_family_reference(kind, ins, attrs)
        assert set(got) == set(want)
        for slot in want:
            np.testing.assert_allclose(
                np.asarray(got[slot][0]), want[slot], rtol=3e-6,
                atol=3e-7, err_msg='%s %s %s' % (
                    kind, slot, ins['Param'].shape))
            assert got[slot][0].dtype == np.float32


def test_lamb_zero_r_norm_keeps_trust_one():
    """A tensor whose r-norm is zero (zero gradient, moments and weight
    decay) takes the trust = 1 branch and stays where it was; its
    neighbours get ||p|| / ||r||, each its own."""
    attrs = {'beta1': 0.9, 'beta2': 0.999, 'weight_decay': 0.0}
    tensors = _adam_family_ins(3, seed=7, zero_idx=1)
    for i, ins in enumerate(tensors):
        got = np.asarray(run_lowering('lamb', ins, attrs)['ParamOut'][0])
        assert np.isfinite(got).all()
        if i == 1:
            assert np.array_equal(got, ins['Param'])
            continue
        want = _adam_family_reference('lamb', ins, attrs)['ParamOut']
        np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-7)
        # the step's length is lr * ||p||: the trust ratio was applied
        np.testing.assert_allclose(
            np.linalg.norm(got - ins['Param']),
            float(ins['LearningRate'][0]) * np.linalg.norm(ins['Param']),
            rtol=1e-4)


_ADAM_FAMILY = {
    'adam': lambda: fluid.optimizer.Adam(1e-2),
    'adamw': lambda: fluid.optimizer.AdamW(1e-2, weight_decay=0.01),
    'lamb': lambda: fluid.optimizer.Lamb(1e-2),
}


def _mlp_with(kind, width=256):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[64], dtype='float32')
        h = fluid.layers.fc(x, width, act='relu')
        h = fluid.layers.fc(h, width, act='relu')
        loss = fluid.layers.reduce_mean(fluid.layers.fc(h, 4))
        _ADAM_FAMILY[kind]().minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize('kind', sorted(_ADAM_FAMILY))
def test_adam_family_program_lowers_under_the_op_scope(kind):
    """What Executor.run traces for a six-parameter program: six ops of
    the type, lowered under the scope of that name, no fused_* op, no
    Mosaic call, and no concatenate (the packed path built
    parameter-sized ones)."""
    import re
    import jax
    main, startup, loss = _mlp_with(kind)
    block = main.global_block()
    updates = [i for i, op in enumerate(block.ops) if op.type == kind]
    assert len(updates) == 6
    assert not [op.type for op in block.ops
                if op.type.startswith('fused_')]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        step = exe.compile(main, feed_names=['x'], fetch_names=[loss])
        scope = fluid.global_scope()

        def spec(name):
            a = np.asarray(fluid.core.as_array(scope.find_var(name)))
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        state = {n: spec(n) for n in step.state_names}
        data = {n: spec(n) for n in step.input_names if n != 'x'}
    data['x'] = jax.ShapeDtypeStruct((4, 64), np.float32)
    text = jax.jit(step.fn, donate_argnums=(1,)).lower(
        np.int32(0), state, data).as_text(debug_info=True)
    assert re.search(r'/%s/' % kind, text)
    assert 'fused_' + kind not in text
    assert 'tpu_custom_call' not in text
    assert 'concatenate' not in text


def test_adam_segment_temporaries_stay_under_one_parameter_copy():
    """The optimizer ops of a program, compiled alone with their state
    donated: the updates alias their inputs and XLA's temporaries stay
    far under one copy of the parameters (packing four operand sets
    into slabs and slicing three back needed seven)."""
    import jax
    from paddle_tpu.fluid import executor
    main, _, _ = _mlp_with('adam', width=512)
    block = main.global_block()
    ops = [op for op in block.ops if op.type == 'adam']
    state_names = sorted({n for op in ops for ns in op.outputs.values()
                          for n in ns})
    read_names = sorted({n for op in ops for ns in op.inputs.values()
                         for n in ns} - set(state_names))

    def spec(name):
        v = block.var(name)
        return jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))

    def update(state, reads):
        env = dict(reads, **state)
        executor._lower_ops(ops, env, np.int32(0), False)
        return {n: env[n] for n in state_names}

    compiled = jax.jit(update, donate_argnums=(0,)).lower(
        {n: spec(n) for n in state_names},
        {n: spec(n) for n in read_names}).compile()
    param_bytes = sum(
        4 * int(np.prod(block.var(op.inputs['Param'][0]).shape))
        for op in ops)
    mem = compiled.memory_analysis()
    assert param_bytes > 1 << 20
    assert mem.temp_size_in_bytes < param_bytes / 4, mem
    # params and both moments are updated in place
    assert mem.alias_size_in_bytes >= 3 * param_bytes, mem


@pytest.mark.parametrize('suffix,value', [('opt_fuse', False),
                                          ('opt_min_tensors', 8),
                                          ('embedding', False),
                                          ('embedding_min_rows', 8)])
def test_removed_kernel_flags_are_unknown_flags(suffix, value):
    """The knobs of the removed packed optimizer path and of the
    removed embedding row kernels are no flags any more: no default,
    no environment pick-up, and setting one neither re-keys a compiled
    program nor changes what it computes."""
    from paddle_tpu.fluid import executor, flags, monitor
    name = 'FLAGS_pallas_' + suffix
    assert name not in flags._DEFAULTS
    assert fluid.get_flags(name) == {name: None}
    main, startup, loss = _mlp_with('adam', width=16)
    feed = {'x': np.random.RandomState(0).randn(4, 64).astype('float32')}

    def train(flip_after):
        """Three steps on one live executor; the flag is set after
        step ``flip_after``.  Returns the losses and the segments
        lowered since the flag was set."""
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            losses, lowered = [], None
            for i in range(3):
                if i == flip_after:
                    assert executor._pallas_flag_items() == key
                    fluid.set_flags({name: value})
                    assert executor._pallas_flag_items() == key
                    lowered = monitor.counter_value(
                        'executor/segments_lowered')
                losses.append(np.asarray(
                    exe.run(main, feed=feed, fetch_list=[loss])[0]))
            return losses, lowered

    key = executor._pallas_flag_items()
    base, _ = train(None)
    try:
        again, lowered = train(1)
    finally:
        flags._flags.pop(name, None)
    assert monitor.counter_value('executor/segments_lowered') == lowered
    for a, b in zip(base, again):
        assert np.array_equal(a, b)


@pytest.mark.parametrize('whole_program_grad', [True, False])
def test_adagrad_on_an_embedding_with_repeated_ids(whole_program_grad):
    """An embedding parameter under Adagrad is lookup_table_v2_grad (a
    scatter-add of the repeated ids' cotangents) + adagrad over the
    table, nothing fused, and trains as numpy's Adagrad does."""
    vocab, width, lr, eps = 20, 4, 0.1, 1e-6
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data('ids', shape=[6], dtype='int64')
        emb = fluid.layers.embedding(
            ids, size=[vocab, width],
            param_attr=fluid.ParamAttr(name='table'))
        loss = fluid.layers.reduce_mean(fluid.layers.square(emb))
        fluid.optimizer.Adagrad(lr, epsilon=eps).minimize(loss)
    types = [op.type for op in main.global_block().ops]
    assert types[-2:] == ['lookup_table_v2_grad', 'adagrad'], types
    assert not [t for t in types if t.startswith('fused_')]
    fed = np.array([[1, 1, 1, 7, 19, 7], [0, 1, 7, 7, 2, 2]], 'int64')
    old = fluid.get_flags('FLAGS_whole_program_grad')
    fluid.set_flags({'FLAGS_whole_program_grad': whole_program_grad})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            table = np.array(fluid.global_scope().find_var('table'))
            moment = np.zeros_like(table)
            for _ in range(3):
                exe.run(main, feed={'ids': fed}, fetch_list=[loss])
                grad = np.zeros_like(table)
                np.add.at(grad, fed, 2 * table[fed] / (fed.size * width))
                moment += grad * grad
                table -= lr * grad / (np.sqrt(moment) + eps)
            got = np.array(fluid.global_scope().find_var('table'))
    finally:
        fluid.set_flags(old)
    assert np.abs(table - got).max() < 1e-6
    assert np.array_equal(table[3], got[3])     # an untouched row
