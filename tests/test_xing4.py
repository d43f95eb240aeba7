"""Xing4.0 through fluid against its plain reference
(``paddle_tpu/models/reference/xing4.py``): the hyper-connection ops
(``hyper_connection_pre`` / ``hyper_connection_post``) forward and
every gradient, the Sinkhorn projection (doubly stochastic, clamped,
float32 under AMP), the block that degenerates to Moonlight's residual
add, the zoo program's main and module logits, loss and every
parameter's gradient through the recompute groups of a train step, the
prediction module's targets and the gradients of the parameters it
shares, the one latent-attention helper under both models' settings,
the experts' shares.  CPU, tiny sizes; the published widths are
checked on the chip (``chip_smoke.py --phase xing4``, PERF.md)."""

import copy
import functools
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import moonlight, xing4
from paddle_tpu.models.reference import moonlight as moonlight_reference
from paddle_tpu.models.reference import xing4 as reference
from paddle_tpu.ops import hyper_connection_ops as hc_ops

from op_test import OpTest

SEQ = 24
# what benchmark/families/xing4.py holds the cell's loss to where its
# reference finds no router's choice undecided
REFERENCE_RTOL = 1e-6
PRESETS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark', 'tests', 'presets_xing4')

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(xing4.TINY)
HELD.experts_held = (2, 4)
SIZES = reference.sizes_of(xing4.TINY)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


# --- the ops ----------------------------------------------------------

def _op_inputs(seed=0, b=2, t=6, n=4, c=16, spread=1.0):
    rng = np.random.RandomState(seed)
    m = n * n + 2 * n
    return {'x': rng.randn(b, t, n, c).astype('float32'),
            'y': rng.randn(b, t, c).astype('float32'),
            # the parameter: phi at unit size (the op divides by
            # sqrt(n c))
            'phi': (spread * rng.randn(n * c, m)).astype('float32'),
            'alpha': (1 + 0.2 * rng.randn(3)).astype('float32'),
            'b': (0.3 * rng.randn(m)).astype('float32')}


def _op_program(shapes):
    """x, y, phi, alpha, b as fed variables through the two ops ->
    (main, {name: var}, u, h_post, h_res, err, out)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = {}
        for name, shape in shapes.items():
            v[name] = main.global_block().create_var(
                name=name, shape=shape, dtype='float32')
        block = main.global_block()
        outs = {slot: block.create_var(name=slot, dtype='float32')
                for slot in ('U', 'HPost', 'HRes', 'Err', 'XOut')}
        block.append_op(
            'hyper_connection_pre',
            inputs={'X': v['x'], 'Phi': v['phi'], 'Alpha': v['alpha'],
                    'Bias': v['b']},
            outputs={s: outs[s] for s in ('U', 'HPost', 'HRes', 'Err')},
            attrs={'sinkhorn_iters': 20, 'epsilon': 1e-6, 'hc_eps': 1e-6,
                   'clamp_min': -30.0, 'clamp_max': 30.0})
        block.append_op(
            'hyper_connection_post',
            inputs={'X': v['x'], 'Y': v['y'], 'HPost': outs['HPost'],
                    'HRes': outs['HRes']},
            outputs={'XOut': outs['XOut']})
    return main, v, outs


def _reference_op(x, y, phi, alpha, b, iters=None):
    """-> (u, X') by the reference's equations."""
    h_pre, h_post, h_res = reference.hyper_maps(x, phi, alpha, b, SIZES,
                                                iters)
    u = jnp.einsum('btn,btnc->btc', h_pre, x)
    out = jnp.einsum('btij,btjc->btic', h_res, x) + \
        h_post[..., None] * y[:, :, None, :]
    return u, out


def test_the_ops_are_the_reference_forward():
    """U, X', and the maps in their tokens-last layout."""
    ins = _op_inputs()
    main, v, outs = _op_program({k: a.shape for k, a in ins.items()})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        u, h_post, h_res, out = exe.run(
            main, feed=ins,
            fetch_list=[outs[s] for s in ('U', 'HPost', 'HRes', 'XOut')])
    with jax.default_matmul_precision('highest'):
        want_u, want_out = _reference_op(*(ins[k] for k in
                                           ('x', 'y', 'phi', 'alpha', 'b')))
        _, want_post, want_res = reference.hyper_maps(
            ins['x'], ins['phi'], ins['alpha'], ins['b'], SIZES)
    np.testing.assert_allclose(u, want_u, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-6)
    # what a lowering counts and states, and /statusz's section
    assert monitor.counter_value('mhc/calls') >= 1
    assert monitor.gauge_value('mhc/streams') == 4
    assert monitor.gauge_value('mhc/sinkhorn_iters') == 20
    from paddle_tpu.ops.pallas import common
    assert common.report()['hyper_connections']['streams'] == 4
    np.testing.assert_allclose(np.moveaxis(h_post, 1, 2), want_post,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.moveaxis(h_res, 3, 1), want_res,
                               rtol=1e-5, atol=1e-6)


def _gradient_against_the_reference(wrt, t):
    """d sum(w_u U) + sum(w_x X') by the program's gradient ops
    against jax.grad of the reference, through all 20
    normalisations, on 2 x ``t`` tokens."""
    ins = _op_inputs(1, t=t)
    rng = np.random.RandomState(7)
    w_u = rng.randn(*ins['y'].shape).astype('float32')
    w_x = rng.randn(*ins['x'].shape).astype('float32')
    main, v, outs = _op_program({k: a.shape for k, a in ins.items()})
    with fluid.program_guard(main):
        total = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(
                outs['U'], layers.assign(w_u))),
            layers.reduce_sum(layers.elementwise_mul(
                outs['XOut'], layers.assign(w_x))))
        (grad,) = fluid.backward.calc_gradient(total, [v[wrt]])
    with fluid.scope_guard(fluid.Scope()):
        got = fluid.Executor(fluid.CPUPlace()).run(
            main, feed=ins, fetch_list=[grad])[0]

    def f(value):
        args = dict(ins, **{wrt: value})
        u, out = _reference_op(*(args[k] for k in
                                 ('x', 'y', 'phi', 'alpha', 'b')))
        return jnp.sum(u * w_u) + jnp.sum(out * w_x)

    with jax.default_matmul_precision('highest'):
        want = np.asarray(jax.grad(f)(jnp.asarray(ins[wrt])))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # and a gradient that stopped after 19 normalisations is another
    with jax.default_matmul_precision('highest'):
        def short(value):
            args = dict(ins, **{wrt: value})
            u, out = _reference_op(*(args[k] for k in (
                'x', 'y', 'phi', 'alpha', 'b')), iters=0)
            return jnp.sum(u * w_u) + jnp.sum(out * w_x)
        other = np.asarray(jax.grad(short)(jnp.asarray(ins[wrt])))
    if wrt in ('x', 'phi', 'alpha', 'b'):
        assert np.abs(other - want).max() > 1e-2 * np.abs(want).max()


WRT = ['x', 'y', 'phi', 'alpha', 'b']


@pytest.mark.parametrize('wrt', WRT)
def test_the_ops_gradient_is_the_reference(wrt):
    """12 tokens: no whole 128-lane row, so the Sinkhorn loop is the
    scan (the dense path's test)."""
    before = monitor.counter_value('pallas/sinkhorn/fallback/layout') or 0
    _gradient_against_the_reference(wrt, 6)
    assert monitor.counter_value(
        'pallas/sinkhorn/fallback/layout') > before


@pytest.mark.parametrize('wrt', WRT)
def test_the_ops_gradient_through_the_kernels_is_the_reference(
        pallas_interpret, wrt):
    """128 tokens with the ``sinkhorn`` kernels forced (their bodies
    under the interpreter): the same five gradients, through the
    kernel's own backward call."""
    from paddle_tpu.ops.pallas import common
    before = monitor.counter_value('pallas/sinkhorn/dispatch_fused') or 0
    _gradient_against_the_reference(wrt, 64)
    assert monitor.counter_value('pallas/sinkhorn/dispatch_fused') > before
    assert common._LAST['sinkhorn'] == {
        'path': 'fused', 'reason': 'forced_interpret', 'interpret': True}


class TestFiniteDifferences(OpTest):
    """The registry's own gradient audit (tools/check_grad_coverage.py)
    reaches both ops."""

    def test_pre(self):
        ins = _op_inputs(2, b=1, t=3, n=2, c=4)
        self.check_grad(
            'hyper_connection_pre',
            {'X': ins['x'], 'Phi': ins['phi'], 'Alpha': ins['alpha'],
             'Bias': ins['b']},
            attrs={'sinkhorn_iters': 20}, out_slot='U')

    def test_post(self):
        ins = _op_inputs(3, b=1, t=3, n=2, c=4)
        rng = np.random.RandomState(0)
        self.check_grad(
            'hyper_connection_post',
            {'X': ins['x'], 'Y': ins['y'],
             'HPost': rng.rand(1, 2, 3).astype('float32'),
             'HRes': rng.rand(1, 2, 2, 3).astype('float32')},
            out_slot='XOut')


def test_h_res_is_doubly_stochastic_and_the_clamp_bites():
    """After 20 normalisations every row and column of H_res sums to 1
    within 1e-4 (the columns, normalised last, to 1 less hc_eps), Err
    is that distance, and a logit of +-100 gives what +-30 gives."""
    ins = _op_inputs(4, t=64, spread=0.7)
    main, v, outs = _op_program({k: a.shape for k, a in ins.items()})
    exe = fluid.Executor(fluid.CPUPlace())

    def run(feed):
        with fluid.scope_guard(fluid.Scope()):
            return exe.run(main, feed=feed,
                           fetch_list=[outs['HRes'], outs['Err']])

    h_res, err = run(ins)
    rows, cols = h_res.sum(2), h_res.sum(1)
    worst = max(np.abs(rows - 1).max(), np.abs(cols - 1).max())
    assert worst <= 1e-4
    assert abs(_scalar(err) - worst) <= 1e-6
    assert h_res.min() > 0
    # the clamp: phi 0, so R~ is b's own
    n = 4
    feed = dict(ins, phi=0 * ins['phi'])

    def with_logits(big):
        b = ins['b'].copy()
        b[2 * n:] = big * np.sign(np.random.RandomState(5).randn(n * n))
        return run(dict(feed, b=b))[0]

    at_100, at_30, at_29 = (with_logits(v) for v in (100.0, 30.0, 29.0))
    assert np.isfinite(at_100).all()
    np.testing.assert_array_equal(at_100, at_30)
    assert np.abs(at_29 - at_30).max() > 0


def test_one_hot_maps_make_the_block_moonlights_residual_add():
    """H_pre and H_post one-hot on row 0 and H_res the identity (phi 0,
    b at the clamp): row 0 of the stream through one operator is
    x + F(x), Moonlight's ``elementwise_add`` block, and the other rows
    pass through."""
    n, c = 4, 16
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, n, c).astype('float32')
    b = np.full((n * n + 2 * n,), -40.0, 'float32')
    b[0] = 40.0                 # H_pre = e_0
    b[n] = 0.0                  # H_post = 2 sigmoid(0) e_0
    b[2 * n:] = np.where(np.eye(n).ravel() > 0, 30.0, -30.0)
    w = rng.randn(c, c).astype('float32') / 4
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        stream = layers.data('x', shape=[5, n, c], dtype='float32')
        u, carry, _ = layers.hyper_connection_pre(
            stream, param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Constant(0.0)),
            bias_attr=fluid.ParamAttr(
                initializer=fluid.initializer.NumpyArrayInitializer(b)))
        y = layers.tanh(layers.mul(u, layers.assign(w), x_num_col_dims=2))
        out = layers.hyper_connection_post(stream, y, carry)
        row0 = layers.slice(stream, axes=[2], starts=[0], ends=[1])
        row0 = layers.reshape(row0, [0, 0, c])
        plain = layers.elementwise_add(row0, layers.tanh(
            layers.mul(row0, layers.assign(w), x_num_col_dims=2)))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, want = exe.run(main, feed={'x': x}, fetch_list=[out, plain])
    # hc_eps: each of the 40 normalisations divides by 1 + 1e-6
    np.testing.assert_allclose(got[:, :, 0], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[:, :, 1:], x[:, :, 1:], rtol=1e-4)


# --- the model --------------------------------------------------------

def _seeded_weights(shapes, cfg, seed, router_scale=4.0):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls (phi among them: r phi is of unit
    size), gains, alpha and b around 1, a router whose top-k margins
    are wide."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif len(s) == 2 and s == (cfg.hidden, cfg.experts):
            w = router_scale * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _build(cfg, lr=0.0, amp=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, (logits, module_logits), loss = xing4.build_pretrain(cfg, SEQ)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        shapes = [tuple(main.global_block().var(p).shape) for p in params]
        opt = fluid.optimizer.SGD(lr)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(
                opt, use_dynamic_loss_scaling=False,
                init_loss_scaling=1.0)
        pairs = opt.minimize(loss)[1]
    return (main, startup, loss, params, shapes, biases, pairs,
            [logits, module_logits])


def _feed(cfg, seed, batch=2):
    rng = np.random.RandomState(seed)
    return xing4.mtp_batch(rng.randint(0, cfg.vocab_size, (batch, SEQ)))


@functools.lru_cache(None)
def _train_program(held, amp):
    """One built and started train program a (share, AMP) pair, kept
    with its scope and executor: the tests below differ in weights and
    feeds, not in program, and compile it once."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        built = _build(HELD if held else xing4.TINY, amp=amp)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built[1])
    return (scope, exe) + built


def _program_and_reference(cfg, seed, amp=False, bias_scale=0.3):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step, taken by the executor's one vjp through the recompute
    groups) on seeded weights and a seeded choice bias -> (loss,
    {param: grad}, params in creation order, weights, bias values,
    feed, [main logits, module logits])."""
    scope, exe, main, _, loss, params, shapes, biases, pairs, logits = \
        _train_program(cfg.experts_held is not None, amp)
    weights = _seeded_weights(shapes, cfg, seed)
    rng = np.random.RandomState(seed + 100)
    bias_values = [(bias_scale * rng.randn(cfg.experts)).astype(
        'float32') for _ in biases]
    feed = _feed(cfg, seed)
    with fluid.scope_guard(scope):
        for name, w in zip(params + biases, weights + bias_values):
            scope.set_var(name, jnp.asarray(w))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + logits +
                      [g.name for _, g in pairs])
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[3:])}
    return (_scalar(out[0]), grads, params, weights, bias_values, feed,
            out[1:3])


def _reference(cfg, weights, biases, feed, grads=False, **kw):
    args = (weights, biases, feed['ids'], feed['pos_ids'], feed['labels'],
            feed['labels_mtp'])
    sizes = reference.sizes_of(cfg)
    if grads:
        return reference.loss_and_grads(*args, sizes=sizes)
    return reference.losses(*args, sizes=sizes, **kw)


@pytest.mark.parametrize('cfg', [HELD, xing4.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_logits_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision: main AND module logits, the loss, and every
    parameter's gradient (the maps' phi, alpha and b, the query latent
    and its norm, the module's two norms and W_eh among them) as the
    train step's one vjp gives them through the four recompute
    groups."""
    loss, grads, params, weights, biases, feed, logits = \
        _program_and_reference(cfg, 3)
    want, want_grads = _reference(cfg, weights, biases, feed, grads=True)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    main_logits, module_logits, _ = reference.forward(
        weights, biases, feed['ids'], feed['pos_ids'],
        np.maximum(feed['labels'], 0), sizes=reference.sizes_of(cfg))
    for got, ref in zip(logits, (main_logits, module_logits)):
        assert np.abs(got - np.asarray(ref)).max() <= \
            2e-5 * np.abs(np.asarray(ref)).max()
    assert set(grads) == set(params)
    assert len(biases) == 3                 # two sparse layers + the module
    per_layer = reference.LAYER_PARAMS_ATTENTION
    assert len(params) == 1 + (per_layer + reference.LAYER_PARAMS_DENSE) \
        + 3 * (per_layer + reference.LAYER_PARAMS_SPARSE) + 2 + 3
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 1e-4 * np.abs(g).max(), \
            name
        assert np.abs(g).max() > 0, name


def test_every_block_is_one_recompute_group():
    """Three layers and the module's: four groups, every op between the
    embedding and the stream's sum inside one, and the train step's
    lowering runs each under ``jax.checkpoint``."""
    main = _train_program(False, False)[2]
    forward = [op for op in main.global_block().ops
               if op.attrs.get('__op_role__') not in ('backward',
                                                      'optimize')]
    groups = []
    for op in forward:
        g = op.attrs.get('__recompute__')
        if g is not None and (not groups or groups[-1] != g):
            groups.append(g)
    assert len(groups) == len(set(groups)) == 4
    inside = [op.type for op in forward if '__recompute__' in op.attrs]
    assert inside.count('hyper_connection_pre') == 8
    assert inside.count('hyper_connection_post') == 8
    assert inside.count('moe_route') == 3
    assert inside.count('lookup_table_v2') == 2


def test_zeroing_phi_moves_the_loss_by_more_than_the_tolerance():
    """The reference check has to bite on the DYNAMIC part of the
    maps: with the maps' startup values of the cell's configuration
    (alpha 0.5, phi of unit projection, b as ``startup_bias``) under
    weights that let every layer move the loss, a phi of zero, which
    leaves three static maps, moves the loss by far more than
    REFERENCE_RTOL, and so does each of the three blocks of phi alone.
    (Under Normal(0, 0.02) weights at these tiny widths the logits are
    nearly flat and nothing moves a loss of ln 97 by much:
    `chip_smoke.py --phase xing4` prints the same four readings at the
    published widths on the startup state.)"""
    cfg = xing4.TINY
    n, m = cfg.hc_mult, cfg.hc_mult ** 2 + 2 * cfg.hc_mult
    shapes = _train_program(False, False)[6]
    weights = _seeded_weights(shapes, cfg, 3)
    operator = 0
    for i, shape in enumerate(shapes):
        if shape == (3,):
            weights[i] = np.full((3,), cfg.hc_alpha_init, 'float32')
        elif shape == (m,) and shapes[i - 1] == (3,):
            weights[i] = xing4.startup_bias(cfg, operator)
            operator += 1
    assert operator == 8
    biases = [np.zeros((cfg.experts,), 'float32')] * 3
    feed = _feed(cfg, 0)
    fn = jax.jit(lambda w: _reference(cfg, w, biases, feed)[0])
    base = float(fn(weights))
    for columns in (slice(None), slice(0, n), slice(n, 2 * n),
                    slice(2 * n, None)):
        changed = []
        for w, shape in zip(weights, shapes):
            if shape == (n * cfg.hidden, m):
                w = w.copy()
                w[:, columns] = 0
            changed.append(w)
        moved = float(fn(changed))
        floor = {slice(None): 100, slice(2 * n, None): 10}.get(columns, 30)
        assert abs(moved - base) > floor * REFERENCE_RTOL * base, \
            (columns, moved, base)


def test_tiny_bf16_amp_loss_and_a_bf16_sinkhorn_apart():
    """bf16 AMP (bf16 matmuls over a bf16 stream; f32 master weights,
    maps, Sinkhorn loop, router, norms and loss) against the f32
    reference: the loss within 5e-3 on each of two seeds (the size of
    bf16 products and a bf16 stream through four layers on 46
    targets).  The looser tolerance is the LOSS's; the maps keep
    float32's: the ops on a bfloat16 stream give the H_res the float32
    reference gives on that stream within 1e-5, which the reference's
    maps in bfloat16 (its 20 normalisations too) miss by a hundred
    times."""
    for seed in (1, 2):
        loss, _, _, weights, biases, feed, _ = _program_and_reference(
            HELD, seed, True)
        want = float(_reference(HELD, weights, biases, feed)[0])
        assert abs(loss - want) <= 5e-3 * want, (seed, loss, want)
    ins = _op_inputs(5, t=32)
    low = jnp.asarray(ins['x'], jnp.bfloat16)
    got = hc_ops.maps(low.reshape(-1, 64), ins['phi'], ins['alpha'],
                      ins['b'], 4, 1e-6, 20, 1e-6, (-30.0, 30.0))[2]
    assert got.dtype == jnp.float32
    got = np.moveaxis(np.asarray(got).reshape(4, 4, 2, 32), 2, 0)
    got = np.moveaxis(got, 3, 1)                        # [B, T, n, n]
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.hyper_maps(
            low.astype(jnp.float32), ins['phi'], ins['alpha'], ins['b'],
            SIZES)[2])
        crude = np.asarray(reference.hyper_maps(
            low, *(jnp.asarray(ins[k], jnp.bfloat16)
                   for k in ('phi', 'alpha', 'b')), SIZES)[2], np.float32)
    assert np.abs(got - want).max() <= 1e-5
    assert np.abs(crude - want).max() > 1e-3


def test_the_maps_stay_float32_under_decorate():
    """No list of ``mixed_precision.decorate`` marks the two ops, the
    carried maps are float32 variables, and under AMP the stream is
    bfloat16 after the first write-back while H_res still sums to 1 to
    float32's precision, not bfloat16's."""
    scope, exe, main = _train_program(True, True)[:3]
    block = main.global_block()
    seen = 0
    for op in block.ops:
        if op.type in ('hyper_connection_pre', 'hyper_connection_post'):
            seen += 1
            assert not any(k.startswith('__amp') for k in op.attrs), \
                op.attrs
        if op.type == 'hyper_connection_pre':
            for slot in ('HPost', 'HRes', 'Err'):
                assert block.var(op.outputs[slot][0]).dtype in (
                    'float32', np.dtype('float32'))
    assert seen == 16
    first = next(op for op in block.ops
                 if op.type == 'hyper_connection_post')
    with fluid.scope_guard(scope):
        stream, h_res = exe.run(
            main, feed=_feed(xing4.TINY, 0),
            fetch_list=[first.outputs['XOut'][0], first.inputs['HRes'][0]],
            return_numpy=False)
    assert jnp.asarray(stream).dtype == jnp.bfloat16
    h_res = np.asarray(h_res)
    assert h_res.dtype == np.float32
    # normalised last, a column sums to 1 / (1 + hc_eps) to float32
    assert np.abs(h_res.sum(1) - 1).max() < 1e-5


def test_the_gauges_of_a_run_that_fetches():
    monitor.reset()
    loss, _, _, weights, biases, feed, _ = _program_and_reference(
        xing4.TINY, 2)
    total, main_loss, module_loss = (
        float(v) for v in _reference(xing4.TINY, weights, biases, feed))
    stats = monitor.flat()
    assert 0 < stats['mhc/stochastic_err'] < 1e-3
    assert abs(stats['mtp/loss'] - module_loss) <= 1e-5 * module_loss
    assert abs(stats['mtp/loss_share'] -
               0.3 * module_loss / total) <= 1e-5
    assert abs(total - (main_loss + 0.3 * module_loss)) <= 1e-6 * total


# --- the prediction module --------------------------------------------

def test_the_module_predicts_the_token_after_next():
    """Changing t_{i+2} of one position i changes that position's
    module loss term and no main-loss term at i; the last position
    carries no main loss and the last two no module loss: the loss does
    not move when their labels' tokens change (ids beyond the sequence
    do not exist; the labels are what says so)."""
    cfg = xing4.TINY
    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = 11
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, (logits, module_logits), loss = xing4.build_pretrain(
                cfg, SEQ)
            main = main.clone(for_test=True)    # the biases stay
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feed(cfg, 4, batch=1)
        base, lg, mlg = exe.run(main, feed=feed,
                                fetch_list=[loss, logits, module_logits])

        def logp(z, label):
            z = z.astype('float64')
            return z[label] - np.log(np.exp(z - z.max()).sum()) - z.max()

        # the loss by hand from the fetched logits: main against
        # t_{i+1} over T - 1 positions, module against t_{i+2} over
        # T - 2
        ids = feed['ids'][0]
        main_loss = -np.mean([logp(lg[0, i], ids[i + 1])
                              for i in range(SEQ - 1)])
        module_loss = -np.mean([logp(mlg[0, i], ids[i + 2])
                                for i in range(SEQ - 2)])
        assert abs(_scalar(base) - (main_loss + 0.3 * module_loss)) <= \
            1e-5 * _scalar(base)
        # the labels of the last positions are -1, whatever follows
        assert feed['labels'][0, -1] == -1
        assert (feed['labels_mtp'][0, -2:] == -1).all()
        # and the module's input at i is t_{i+1}: another token there
        # moves the module's logits at i, not the main stack's
        other = dict(feed, labels=feed['labels'].copy())
        other['labels'][0, 5] = (other['labels'][0, 5] + 1) % cfg.vocab_size
        lg2, mlg2 = exe.run(main, feed=other,
                            fetch_list=[logits, module_logits])
        np.testing.assert_array_equal(lg2, lg)
        assert np.abs(mlg2[0, 5] - mlg[0, 5]).max() > 1e-3
        np.testing.assert_array_equal(mlg2[0, :5], mlg[0, :5])


def test_shared_parameters_take_the_sum_of_their_two_gradients():
    """The embedding (read by ``ids`` and by the next tokens), the final
    norm's gain and the head (read by the main stack and by the module)
    exist ONCE in the program, and each one's gradient is the sum of
    the gradients of its two uses, which the reference tells apart by
    handing the module copies."""
    cfg = xing4.TINY
    loss, grads, params, weights, biases, feed, _ = \
        _program_and_reference(cfg, 3)
    assert params.count('xing4_embedding') == 1
    shared = ['xing4_embedding', 'xing4_g_final', 'xing4_w_head']
    index = [params.index(n) for n in shared]
    copies = [jnp.asarray(weights[i]) for i in index]
    args = (biases, feed['ids'], feed['pos_ids'], feed['labels'],
            feed['labels_mtp'])

    def f(weights, copies):
        return reference.losses(weights, *args,
                                sizes=reference.sizes_of(cfg),
                                module_copies=copies)[0]

    first, second = jax.grad(f, (0, 1))(
        [jnp.asarray(w) for w in weights], copies)
    for name, i, g2 in zip(shared, index, second):
        g1, g2 = np.asarray(first[i]), np.asarray(g2)
        assert np.abs(g1).max() > 0 and np.abs(g2).max() > 0, name
        want = g1 + g2
        assert np.abs(grads[name] - want).max() <= \
            1e-4 * np.abs(want).max(), name
        assert np.abs(grads[name] - g1).max() > 1e-2 * np.abs(want).max()


# --- the one latent-attention helper -----------------------------------

def _attention_program(cfg, u, pos):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 9
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data('u', shape=list(u.shape[1:]), dtype='float32')
        p = layers.data('pos', shape=[u.shape[1]], dtype='int64')
        out = moonlight.attention(x, p, cfg)
        names = [q.name for q in main.all_parameters()]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(n))) * 20
                   for n in names]
        for n, w in zip(names, weights):
            scope.set_var(n, jnp.asarray(w))
        got = exe.run(main, feed={'u': u, 'pos': pos}, fetch_list=[out])[0]
    return got, weights, [op.type for op in main.global_block().ops]


@pytest.mark.parametrize('which', ['moonlight', 'xing4', 'xing4_plain'])
def test_the_helper_is_both_models_attention(which):
    """``models.moonlight.attention`` under Moonlight's settings (no
    query latent, no rotary scaling) builds the ops it always built and
    is Moonlight's reference; under Xing4.0's (a normed 20-wide query
    latent, YaRN's table, the softmax scale times mscale^2) it is
    Xing4.0's reference; and Xing4.0's with the latent and the scaling
    taken away is Moonlight's again."""
    rng = np.random.RandomState(0)
    u = rng.randn(2, SEQ, 64).astype('float32')
    pos = np.tile(np.arange(SEQ), (2, 1))
    if which == 'moonlight':
        cfg = moonlight.TINY
    else:
        cfg = copy.copy(xing4.TINY)
        if which == 'xing4_plain':
            cfg.q_rank, cfg.yarn = None, None
    got, weights, ops = _attention_program(cfg, u, pos)
    plain = cfg.q_rank is None
    assert ('scale' in ops) == (not plain)
    assert ops.count('rms_norm') == (1 if plain else 2)
    assert sum(o.startswith('assign') for o in ops) == \
        (0 if plain else 1)                 # YaRN's table
    with jax.default_matmul_precision('highest'):
        if plain:
            sizes = dict(moonlight_reference.sizes_of(moonlight.TINY),
                         heads=cfg.heads, qk_nope=cfg.qk_nope,
                         qk_rope=cfg.qk_rope, v_dim=cfg.v_dim,
                         kv_rank=cfg.kv_rank, rms_eps=cfg.rms_eps,
                         rope_theta=cfg.rope_theta)
            want = moonlight_reference.attention(u, pos, *weights, sizes)
        else:
            want = reference.attention(u, pos, *weights,
                                       reference.sizes_of(cfg))
    want = np.asarray(want)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    if which == 'xing4':
        # the scale and the table are no small print: Moonlight's
        # frequencies and 1 / sqrt(qk) give another result
        sizes = dict(reference.sizes_of(cfg), yarn=None)
        with jax.default_matmul_precision('highest'):
            off = np.asarray(reference.attention(u, pos, *weights, sizes))
        assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()
        assert abs(reference.softmax_scale(reference.sizes_of(
            xing4.BASE)) * 192 ** 0.5 - 2.00474) < 1e-4
        assert abs(moonlight.softmax_scale(xing4.BASE) -
                   reference.softmax_scale(reference.sizes_of(
                       xing4.BASE))) < 1e-9


# --- the experts' shares -----------------------------------------------

def test_the_eight_shares_and_the_shared_expert_add_up_to_the_layer():
    """64 experts top-4 in eight shares of 8, as the deployment holds
    them, under a nonzero choice bias: the parts of the routed sum the
    eight shares give (``layers.moe(experts_held=...)``) add up to what
    the uncut reference gives for the whole layer, the shared expert
    counted once."""
    rng = np.random.RandomState(0)
    b, t, d, experts, top_k, hidden = 2, 16, 32, 64, 4, 16
    x = rng.randn(b, t, d).astype('float32')
    wg = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    bias = (0.3 * rng.randn(experts)).astype('float32')
    shared = [rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(hidden, d).astype('float32') / np.sqrt(hidden)]
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        whole, _ = reference.routed_share(
            flat, wg, bias, gate, up, down, top_k, 2.0, None)
        once = np.asarray(reference.gated_mlp(flat, *shared))
        whole = np.asarray(whole) + once
    total = once.copy()
    for first in range(0, experts, 8):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            xv = layers.data('x', shape=[t, d], dtype='float32')
            out, _ = layers.moe(
                xv, num_experts=experts, hidden_size=hidden,
                capacity_factor=None, top_k=top_k, renormalize=True,
                gate_scale=2.0, experts_held=(first, 8), aux_weight=0.0,
                score_func='sigmoid', score_bias=True)
            names = [p.name for p in main.all_parameters()]
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            scope = fluid.global_scope()
            values = [wg, gate[first:first + 8], up[first:first + 8],
                      down[first:first + 8], bias]
            assert len(names) == len(values)
            for n, v in zip(names, values):
                scope.set_var(n, jnp.asarray(v))
            part = exe.run(main.clone(for_test=True), feed={'x': x},
                           fetch_list=[out])[0]
        total = total + part.reshape(b * t, d)
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()


def test_base_is_the_published_model_and_counts_what_the_issue_counts():
    """``BASE`` is the catalog row; the cut of the benchmark's
    configuration counts 913.4 M parameters."""
    base = xing4.BASE
    assert (base.hidden, base.layers, base.heads, base.q_rank,
            base.kv_rank, base.experts, base.top_k, base.hc_mult,
            base.hc_iters, base.vocab_size) == (
        3584, 40, 32, 768, 512, 64, 4, 4, 20, 131072)
    h, m = base.hidden, base.hc_mult
    attention = h * 768 + 768 + 768 * 32 * 192 + h * 576 + 512 + \
        512 * 32 * 256 + 32 * 128 * h
    maps = 2 * (m * h * 24 + 3 + 24)
    gains = 2 * h
    dense = attention + maps + gains + 3 * h * 9216
    sparse = attention + maps + gains + h * 64 + 3 * h * 1024 + \
        8 * 3 * h * 1024
    module = sparse + 2 * h * h + 2 * h
    total = dense + 4 * sparse + module + 2 * 16384 * h + h
    assert abs(total / 1e6 - 913.4) < 0.1


# --- what the reviewer of PR 54 asked for -----------------------------

def test_the_sinkhorn_loop_is_one_loop_of_the_program():
    """The 20 normalisations are ONE ``scan`` of the lowering (unrolled
    they were a third of the step's code), inside the ``checkpoint``,
    and give what the plain Python loop gives, gradient too."""
    m0 = jnp.asarray(np.exp(np.random.RandomState(0).randn(4, 4, 7)),
                     jnp.float32)

    def plain(m):
        for _ in range(20):
            m = m / (jnp.sum(m, 1, keepdims=True) + 1e-6)
            m = m / (jnp.sum(m, 0, keepdims=True) + 1e-6)
        return m

    text = str(jax.make_jaxpr(lambda m: hc_ops.sinkhorn(m, 20, 1e-6))(m0))
    assert text.count('scan[') == 1 and 'length=20' in text
    assert text.count(' div ') == 2
    np.testing.assert_allclose(hc_ops.sinkhorn(m0, 20, 1e-6), plain(m0),
                               rtol=1e-6)
    weight = jnp.asarray(np.random.RandomState(1).randn(4, 4, 7),
                         jnp.float32)
    got, want = (jax.grad(lambda m: jnp.sum(weight * f(m)))(m0)
                 for f in (lambda m: hc_ops.sinkhorn(m, 20, 1e-6), plain))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_the_mean_square_is_summed_row_by_row():
    """No reduction of the lowering reads the whole [S, n C] stream: on
    the chip one that does takes the write-back's ``concatenate`` into
    its fusion as n pads to full width (PERF.md section 6, PR 55).
    The values are ``test_the_ops_are_the_reference_forward``'s."""
    ins = _op_inputs(6, t=8)
    x2 = jnp.asarray(ins['x'].reshape(-1, 64))

    def fn(x):
        return hc_ops.maps(x, ins['phi'], ins['alpha'], ins['b'], 4, 1e-6,
                           20, 1e-6, (-30.0, 30.0))

    def reductions(jaxpr):
        from jax._src import core
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith('reduce_'):
                yield eqn.invars[0].aval.shape
            for sub in core.jaxprs_in_params(eqn.params):
                yield from reductions(sub)

    shapes = list(reductions(jax.make_jaxpr(fn)(x2).jaxpr))
    assert (16, 16) in shapes and x2.shape not in shapes, shapes


def test_phi_is_stored_at_unit_size():
    """The op divides the projection by sqrt(n C): with alpha 1 and b 0
    H_pre's logits are vec(X) Phi over the LENGTH of vec(X)."""
    ins = _op_inputs(3, b=1, t=5)
    x2 = ins['x'].reshape(5, 64)
    h_pre = hc_ops.maps(jnp.asarray(x2), ins['phi'], jnp.ones(3),
                        jnp.zeros(24), 4, 0.0, 20, 1e-6, (-30., 30.))[0]
    logits = np.log(h_pre / (1 - h_pre)).T              # [S, n]
    want = (x2 @ ins['phi'][:, :4]) / np.linalg.norm(x2, axis=1)[:, None]
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize('k', [0, 1, 5])
def test_startup_values_make_the_rows_of_the_stream_differ(k):
    """b's startup values: H_pre reads mostly row k, H_post writes
    mostly into rows k and k + 1, R~ keeps d on the diagonal and t above
    it; so after ONE operator on a stream of four like rows the rows
    differ (rows that stay alike are left alike by every doubly
    stochastic H_res, and no loss could see that map)."""
    cfg = xing4.TINY
    n = cfg.hc_mult
    b = xing4.startup_bias(cfg, k)
    assert b.shape == (n * n + 2 * n,) and b.dtype == np.float32
    assert np.argmax(b[:n]) == k % n and sorted(b[:n]) == [-1, -1, -1, 1]
    high = sorted([k % n, (k + 1) % n])
    assert sorted(np.flatnonzero(b[n:2 * n] > 0)) == high
    assert sorted(b[n:2 * n]) == [-1, -1, 1, 1]
    d, t = cfg.hc_res_init
    res = b[2 * n:].reshape(n, n)
    np.testing.assert_array_equal(np.diag(res), d)
    np.testing.assert_array_equal(res[np.triu_indices(n, 1)], t)
    np.testing.assert_array_equal(res[np.tril_indices(n, -1)], 0)
    rng = np.random.RandomState(k)
    x = np.repeat(rng.randn(1, 3, 1, 16), n, 2).astype('float32')
    y = rng.randn(1, 3, 16).astype('float32')
    _, out = _reference_op(x, y, np.zeros((n * 16, 24), 'float32'),
                           np.ones(3, 'float32'), b)
    rows = np.asarray(out)[0, 0]
    assert np.abs(rows[high[0]] - rows[(high[1] + 1) % n]).max() > 0.1


def _tiny_family():
    from benchmark.families import xing4 as family
    config = json.load(open(os.path.join(PRESETS, 'configs',
                                         'xing4-tiny.json')))
    traffic = json.load(open(os.path.join(PRESETS, 'workloads',
                                          'tiny_s128_xing4.json')))
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 11
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            family.build(config, traffic)
            names = [p.name for p in main.all_parameters()]
        fluid.Executor(fluid.CPUPlace()).run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in names]
    feed = {k: jnp.asarray(v) for k, v in family.batch(
        config, traffic, 1, 7).items()}
    return family, config, traffic, weights, feed


@pytest.mark.parametrize('margin', [0.0, 1e-3, 1e-2])
def test_the_tolerance_follows_the_references_undecided_choices(margin):
    """The family's rule: with no router's choice within the margin
    the tolerance is BASE_RTOL and the second pass is the first; the
    wider the margin, the more tokens count as undecided and the more
    the loss may move, token by token, with each given the other
    expert; the loss itself is the first pass's whatever the margin."""
    family, config, traffic, weights, feed = _tiny_family()
    fn = jax.jit(lambda w, f, tau: family.reference_readings(
        config, traffic, w, f, tie_margin=tau))
    loss, moved, undecided = (float(x) for x in fn(weights, feed, margin))
    base = float(fn(weights, feed, 0.0)[0])
    assert loss == base
    if not margin:
        assert (moved, undecided) == (0.0, 0.0)
        assert family.allowed(loss, moved) == family.BASE_RTOL == 1e-6
    else:
        narrower = [float(x) for x in fn(weights, feed, margin / 10)]
        assert undecided > narrower[2] >= 0 and moved > narrower[1] >= 0
        assert family.allowed(loss, moved) == pytest.approx(
            1e-6 + moved / loss)


def test_the_harness_reads_the_tolerance_the_reference_set():
    """``reference_loss`` under jit, as ``benchmark/run.py`` calls it,
    hands back the reference's loss and leaves ``REFERENCE_RTOL`` at
    this comparison's tolerance before the result is there."""
    family, config, traffic, weights, feed = _tiny_family()
    old = family.TIE_MARGIN
    try:
        for margin in (1e-2, old):
            family.TIE_MARGIN = margin
            family.REFERENCE_RTOL = None
            want = float(jax.jit(lambda w, f: family.reference_loss(
                config, traffic, w, f))(weights, feed))
            loss, moved, _ = (float(x) for x in family.reference_readings(
                config, traffic, weights, feed, tie_margin=margin))
            assert want == loss
            assert family.REFERENCE_RTOL == pytest.approx(
                family.allowed(loss, moved), rel=1e-6)
        assert family.REFERENCE_RTOL == family.BASE_RTOL
    finally:
        family.TIE_MARGIN = old
        family.REFERENCE_RTOL = family.BASE_RTOL


@pytest.mark.parametrize('tokens,n,hidden', [(4096, 4, 3584), (8, 2, 64)])
def test_the_mix_s_hand_count_is_forward_and_backward_alone(tokens, n,
                                                            hidden):
    """``mhc_roofline``'s yardstick counts what ``mfu``'s does: no
    forward run again.  (3 n + 2) C elements forward, (5 n + 3) C
    backward, bfloat16; the maps and phi three passes."""
    from benchmark.lib import xing_flops
    m = n * n + 2 * n
    flop, byte = xing_flops.mhc_train_cost(tokens, n, hidden)
    assert flop == 3 * 2 * tokens * n * hidden * m
    assert byte == 2 * tokens * hidden * (8 * n + 5) + \
        3 * (2 * 4 * tokens * m + 4 * n * hidden * m)
    share = xing_flops.mhc_forward_share(n)
    assert share == pytest.approx((3 * n + 2) / (8. * n + 5))


def test_a_lowered_recompute_group_is_counted():
    """``executor/recompute_groups``: one a group a lowering (what
    ``mhc_ms``'s note reads)."""
    from paddle_tpu.fluid.backward import recompute_guard
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[3], dtype='float32')
        with recompute_guard():
            y = layers.scale(x, scale=1.2345678)
        with recompute_guard():
            z = layers.scale(y, scale=8.7654321)
    before = monitor.flat().get('executor/recompute_groups', 0)
    with fluid.scope_guard(fluid.Scope()):
        out = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={'x': np.ones((2, 3), 'float32')}, fetch_list=[z])
    np.testing.assert_allclose(out[0], 1.2345678 * 8.7654321, rtol=1e-6)
    assert monitor.flat()['executor/recompute_groups'] == before + 2
