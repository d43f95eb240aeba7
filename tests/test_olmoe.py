"""OLMoE through fluid against its plain reference
(``paddle_tpu/models/reference/olmoe.py``): the zoo program's loss and
every parameter's gradient, the new ops against their closed forms,
dropless routing under adversarial routers, the counters, and a short
training run.  CPU, tiny sizes; the published widths are checked on
the chip (``chip_smoke.py --phase olmoe``, PERF.md)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import olmoe
from paddle_tpu.models.reference import olmoe as reference

CFG = olmoe.TINY
SEQ = 32
SIZES = dict(layers=CFG.layers, heads=CFG.heads, top_k=CFG.top_k)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, seed, router_scale=4.0):
    """Weights large enough that every part of the model moves the
    loss (the zoo's Normal(0.02) at width 64 leaves the logits flat):
    unit-variance matmuls, gains around 1, a router whose top-k
    margins are wide."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif len(s) == 2 and s[-1] == CFG.experts:
            w = router_scale * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == CFG.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _program_and_reference(seed, amp):
    """TINY's train program (SGD at lr 0, so the fetched gradients are
    the whole step) on seeded weights -> (loss, {param: grad}, params in
    creation order, weights, feed)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            params = [p.name for p in main.all_parameters()]
            shapes = [tuple(main.global_block().var(p).shape)
                      for p in params]
            opt = fluid.optimizer.SGD(0.0)
            if amp:
                opt = fluid.contrib.mixed_precision.decorate(
                    opt, use_dynamic_loss_scaling=False,
                    init_loss_scaling=1.0)
            pairs = opt.minimize(loss)[1]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, seed)
        scope = fluid.global_scope()
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        feed = olmoe.synthetic_batch(CFG, 2, SEQ,
                                     np.random.RandomState(seed))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs])
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:])}
    return _scalar(out[0]), grads, params, weights, feed


def _reference(weights, feed, **kw):
    return reference.loss_and_grads(weights, feed['ids'],
                                    feed['pos_ids'], feed['labels'],
                                    **dict(SIZES, **kw))


def test_tiny_f32_loss_and_every_gradient_match_the_reference():
    """Float32 program against the float32 reference.  Both multiply
    at full precision, so what is left is the order of float32 sums
    (the grouped matmul sums a token's experts in sorted order, the
    reference in expert order): measured 6e-7 of each gradient's
    largest entry; the bound is 30x that, and a wrong rotary pairing,
    a renormalised gate or a dropped auxiliary loss move gradients by
    whole percents."""
    loss, grads, params, weights, feed = _program_and_reference(3, False)
    want, want_grads = _reference(weights, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 2e-5 * np.abs(g).max(), \
            name


def test_tiny_bf16_amp_is_nearer_the_reference_than_all_bf16():
    """bf16 AMP (bf16 matmuls; f32 master weights, router, norms,
    rotary, softmax cross-entropy and auxiliary losses) against the f32
    reference, and beside it the reference computed in bfloat16
    THROUGHOUT.  Relative loss error, mean over four seeds, measured
    here: AMP 3.8e-5 (5e-6 .. 1.1e-4), all-bf16 2.5e-4 (1.3e-4 ..
    4.1e-4).  The bound 1e-4 sits between: the program passes it and a
    model that also rounds the router, the norms and the weights to
    bfloat16 does not.  A single gradient entry is no fair measure
    under bf16 (a token whose k-th and (k+1)-th router probabilities are
    close changes expert and moves whole rows): the whole gradient's
    relative L2 distance is, measured 0.012 .. 0.017, bound 0.05."""
    amp_err, low_err = [], []
    for seed in (1, 2, 3, 4):
        loss, grads, params, weights, feed = _program_and_reference(
            seed, True)
        want, want_grads = _reference(weights, feed)
        low, _ = _reference(weights, feed, dtype=jnp.bfloat16)
        amp_err.append(abs(loss - float(want)) / float(want))
        low_err.append(abs(float(low) - float(want)) / float(want))
        num = sum(float(np.sum((grads[n] - np.asarray(g)) ** 2))
                  for n, g in zip(params, want_grads))
        den = sum(float(np.sum(np.asarray(g) ** 2)) for g in want_grads)
        assert (num / den) ** 0.5 <= 0.05, seed
    assert np.mean(amp_err) <= 1e-4 < np.mean(low_err), (amp_err,
                                                          low_err)


def _moe_against_reference(x, router, top_k, experts=8, hidden=16):
    b, t, d = x.shape
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 7
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            xv = layers.data('x', shape=[t, d], dtype='float32')
            out, aux = layers.moe(xv, num_experts=experts,
                                  hidden_size=hidden, top_k=top_k,
                                  capacity_factor=None,
                                  renormalize=False, aux_weight=0.01,
                                  z_loss_weight=0.001)
            params = [p.name for p in main.all_parameters()]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        rng = np.random.RandomState(11)
        weights = [np.asarray(router, 'float32')] + [
            rng.randn(*main.global_block().var(p).shape).astype(
                'float32') / 4 for p in params[1:]]
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        got, got_aux = exe.run(main, feed={'x': x},
                               fetch_list=[out, aux])
    with jax.default_matmul_precision('highest'):
        want, balance, z, load = reference.sparse_moe(
            jnp.asarray(x).reshape(b * t, d), *map(jnp.asarray, weights),
            top_k)
    np.testing.assert_allclose(got.reshape(b * t, d), want, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(_scalar(got_aux),
                               0.01 * balance + 0.001 * z, rtol=1e-5)
    return np.asarray(load)


@pytest.mark.parametrize('top_k', [1, 3])
@pytest.mark.parametrize('routing', ['all_to_one', 'one_left_empty'])
def test_adversarial_routing_drops_nothing(routing, top_k):
    """A router that sends every token's first choice to expert 2, and
    one that no token ever picks expert 5: the output is the
    reference's, and ``moe/dropped_tokens`` stays 0 with every pair
    counted."""
    rng = np.random.RandomState(5)
    x = rng.randn(2, 16, 12).astype('float32')
    x[..., 0] = 1.0                     # a feature the router can key on
    router = 0.1 * rng.randn(12, 8).astype('float32')
    if routing == 'all_to_one':
        router[0, 2] = 60.0
    else:
        router[0, 5] = -60.0
    routed0 = monitor.counter_value('moe/tokens_routed')
    dropped0 = monitor.counter_value('moe/dropped_tokens')
    load = _moe_against_reference(x, router, top_k)
    assert load.sum() == 32 * top_k
    if routing == 'all_to_one':
        assert load[2] == 32
        assert monitor.gauge_value('moe/load_max_over_mean') == \
            pytest.approx(8.0 / top_k)
    else:
        assert load[5] == 0
    assert monitor.counter_value('moe/tokens_routed') - routed0 == \
        32 * top_k
    assert monitor.counter_value('moe/dropped_tokens') == dropped0 == 0


def test_top_8_of_64_builds_and_runs():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 32, 32).astype('float32')
    load = _moe_against_reference(
        x, rng.randn(32, 64).astype('float32'), top_k=8, experts=64,
        hidden=16)
    assert load.sum() == 64 * 8 and load.shape == (64,)


def test_a_quiet_run_reads_no_loads():
    """The loads ride on runs that fetch; ``fetch_list=[]`` adds no
    fetch, so the counters stand still."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = olmoe.synthetic_batch(CFG, 2, SEQ,
                                     np.random.RandomState(0))
        before = monitor.counter_value('moe/tokens_routed')
        fetches = monitor.counter_value('executor/fetch_vars')
        exe.run(main, feed=feed, fetch_list=[])
        assert monitor.counter_value('moe/tokens_routed') == before
        assert monitor.counter_value('executor/fetch_vars') == fetches
        exe.run(main, feed=feed, fetch_list=[loss])
        assert monitor.counter_value('moe/tokens_routed') - before == \
            CFG.layers * 2 * SEQ * CFG.top_k


@pytest.mark.parametrize('broken', ['nothing', 'sizes', 'sort'])
def test_dropped_count_reads_the_grouping_the_experts_are_handed(broken):
    """``moe/dropped_tokens`` is not 0 by arithmetic: it counts the
    sorted rows that lie outside the group of the expert their token
    picked, given the group sizes the grouped matmuls get.  Sizes that
    move one row from expert 1 to expert 2, or a sort that swaps two
    rows of different experts, show as that many rows."""
    from paddle_tpu.parallel import moe
    rng = np.random.RandomState(3)
    idx = jnp.asarray(np.stack([rng.permutation(8)[:3]
                                for _ in range(40)]), jnp.int32)
    order, _ = moe.sort_by_expert(idx)
    sizes = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert sizes.min() > 1
    want = 0
    if broken == 'sizes':
        sizes[1] -= 1
        sizes[2] += 1
        want = 1        # expert 2's first row now counts to expert 1
    elif broken == 'sort':
        order = order.at[jnp.array([0, 119])].set(order[
            jnp.array([119, 0])])
        want = 2
    got = moe.rows_outside_their_group(idx, order,
                                       jnp.asarray(sizes, jnp.int32))
    assert int(got) == want
    if broken == 'sizes':
        # rows past the sizes' sum belong to no group at all
        assert int(moe.rows_outside_their_group(
            idx, order, jnp.asarray(sizes, jnp.int32).at[7].add(-2))) \
            == 3


@pytest.mark.parametrize('runner', ['data_parallel', 'collective'])
def test_the_mesh_runners_read_what_the_program_watches(runner):
    """``Program.watch`` is honoured by ``Executor.run`` before it
    picks a runner: under ``with_data_parallel`` the loads are the
    whole batch's; the collective (shard_map) runner reads the first
    device's share."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            if runner == 'collective':
                from paddle_tpu.fluid.transpiler.collective import \
                    GradAllReduce
                opt = fluid.optimizer.SGD(0.1)
                opt.minimize(loss)
                GradAllReduce().transpile(startup, main, 0,
                                          ['127.0.0.1:0'], '127.0.0.1:0')
            else:
                fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        target = main if runner == 'collective' else \
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        feed = olmoe.synthetic_batch(CFG, 8, SEQ,
                                     np.random.RandomState(0))
        routed = monitor.counter_value('moe/tokens_routed')
        dropped = monitor.counter_value('moe/dropped_tokens')
        exe.run(target, feed=feed, fetch_list=[])
        assert monitor.counter_value('moe/tokens_routed') == routed
        (got,) = exe.run(target, feed=feed, fetch_list=[loss])
        assert np.isfinite(got).all()
        sequences = 8 if runner == 'data_parallel' else \
            8 // len(jax.devices())
        assert monitor.counter_value('moe/tokens_routed') - routed == \
            CFG.layers * sequences * SEQ * CFG.top_k
        assert monitor.counter_value('moe/dropped_tokens') == dropped


def test_program_watch_hands_each_reader_its_values_in_order():
    main, startup = fluid.Program(), fluid.Program()
    seen = []
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data('x', shape=[3], dtype='float32')
        double = layers.scale(x, scale=2.0)
        triple = layers.scale(x, scale=3.0)
        main.watch([double.name], lambda v: seen.append(('a', v)))
        main.watch([triple.name, double.name],
                   lambda v: seen.append(('b', v)))
    assert main.clone(for_test=True)._watched == {}
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {'x': np.ones((2, 3), 'float32')}
    assert exe.run(main, feed=feed, fetch_list=[]) == []
    exe.run(main, feed=feed, fetch_list=[triple], return_numpy=False)
    assert seen == []
    (got,) = exe.run(main, feed=feed, fetch_list=[triple])
    assert got.shape == (2, 3) and float(got[0, 0]) == 3.0
    (a, va), (b, vb) = seen
    assert (a, b) == ('a', 'b') and len(va) == 1 and len(vb) == 2
    assert float(va[0][0, 0]) == 2.0
    assert [float(v[0, 0]) for v in vb] == [3.0, 2.0]


def _run_op(build, feed, wrt):
    """Build ``loss = build(vars)`` over data vars named as ``feed``
    -> (outputs, {name: d loss / d feed[name]} for ``wrt``)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            data = {}
            for name, value in feed.items():
                data[name] = layers.data(
                    name, shape=list(value.shape),
                    dtype=str(value.dtype), append_batch_size=False)
                data[name].stop_gradient = name not in wrt
            outs, loss = build(data)
            grads = fluid.gradients([loss], [data[n] for n in wrt])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=list(outs) + grads)
    return got[:len(outs)], dict(zip(wrt, got[len(outs):]))


# bfloat16 keeps 8 bits: outputs are rounded once from an f32 result
# (2^-8 relative), gradients are sums of such terms
TOLERANCE = {'float32': 2e-6, 'bfloat16': 2e-2}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_rms_norm_against_its_closed_form(dtype):
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype('float32')
    gain = (1 + 0.3 * rng.randn(16)).astype('float32')
    probe = rng.randn(3, 5, 16).astype('float32')

    def build(v):
        xv = layers.cast(v['x'], dtype)
        helper_out = layers.rms_norm(xv, epsilon=1e-5)
        y = layers.cast(helper_out, 'float32')
        return [y], layers.reduce_sum(layers.elementwise_mul(y, v['p']))

    def closed(x, gain):
        xl = x.astype(dtype).astype(jnp.float32)
        return (xl * jax.lax.rsqrt(jnp.mean(xl * xl, -1, keepdims=True)
                                   + 1e-5) * gain)

    # the layer creates its own gain (ones): check it, then the op
    # with a given gain through jax.grad of the closed form
    (y,), grads = _run_op(build, {'x': x, 'p': probe}, ['x'])
    want = closed(jnp.asarray(x), 1.0)
    tol = TOLERANCE[dtype]
    np.testing.assert_allclose(y, want, rtol=tol, atol=tol)
    dx = jax.grad(lambda a: jnp.sum(closed(a, 1.0) * probe))(
        jnp.asarray(x))
    np.testing.assert_allclose(grads['x'], dx, rtol=10 * tol,
                               atol=10 * tol)
    # the op itself with a gain that is not 1, forward and both grads
    from paddle_tpu.ops import registry
    op = registry.get('rms_norm').fn

    def lowered(a, g):
        return op(registry.LowerCtx(0), {'X': [a.astype(dtype)],
                                         'Scale': [g]},
                  {'epsilon': 1e-5})['Y'][0].astype(jnp.float32)

    np.testing.assert_allclose(lowered(jnp.asarray(x), gain),
                               closed(jnp.asarray(x), gain), rtol=tol,
                               atol=tol)
    got = jax.grad(lambda a, g: jnp.sum(lowered(a, g) * probe), (0, 1))(
        jnp.asarray(x), jnp.asarray(gain))
    want = jax.grad(lambda a, g: jnp.sum(closed(a, g) * probe), (0, 1))(
        jnp.asarray(x), jnp.asarray(gain))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=10 * tol,
                                   atol=10 * tol * np.abs(b).max())


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_rotary_embedding_against_its_closed_form(dtype):
    """Complex form of the rotate-half pairing: features (i, i + d/2)
    are one complex number turned by exp(i * pos * theta^(-2i/d)).  A
    rotation keeps norms, and q.k depends on the positions' difference
    only."""
    rng = np.random.RandomState(1)
    b, t, h, d = 2, 6, 2, 8
    q = rng.randn(b, t, h, d).astype('float32')
    k = rng.randn(b, t, h, d).astype('float32')
    pos = np.tile(np.arange(3, 3 + t, dtype='int32'), (b, 1))
    probe = rng.randn(b, t, h, d).astype('float32')

    def build(v):
        qv, kv = layers.cast(v['q'], dtype), layers.cast(v['k'], dtype)
        qo, ko = layers.rotary_embedding(qv, kv, v['pos'], theta=100.0)
        qo, ko = layers.cast(qo, 'float32'), layers.cast(ko, 'float32')
        return [qo, ko], layers.reduce_sum(layers.elementwise_mul(
            layers.elementwise_add(qo, ko), v['p']))

    def closed(x, positions):
        xl = np.asarray(jnp.asarray(x).astype(dtype).astype(jnp.float32))
        z = xl[..., :d // 2] + 1j * xl[..., d // 2:]
        freq = 100.0 ** (-np.arange(d // 2) / (d // 2))
        z = z * np.exp(1j * positions[:, :, None, None] * freq)
        return np.concatenate([z.real, z.imag], -1)

    (qo, ko), grads = _run_op(build, {'q': q, 'k': k, 'pos': pos,
                                      'p': probe}, ['q', 'k'])
    tol = TOLERANCE[dtype]
    np.testing.assert_allclose(qo, closed(q, pos), rtol=tol, atol=tol)
    np.testing.assert_allclose(ko, closed(k, pos), rtol=tol, atol=tol)
    # the rotation is linear and orthogonal: its gradient is the
    # inverse rotation of the cotangent
    np.testing.assert_allclose(grads['q'], closed(probe, -pos),
                               rtol=10 * tol, atol=10 * tol)
    np.testing.assert_allclose(grads['k'], closed(probe, -pos),
                               rtol=10 * tol, atol=10 * tol)
    if dtype == 'float32':
        shifted = closed(q, pos + 7), closed(k, pos + 7)
        np.testing.assert_allclose(
            np.einsum('bqhd,bkhd->bhqk', qo, ko),
            np.einsum('bqhd,bkhd->bhqk', *shifted), rtol=1e-4, atol=1e-4)


def test_tiny_trains_and_the_loss_falls():
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 9
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            fluid.optimizer.AdamW(learning_rate=3e-3, beta1=0.9,
                                  beta2=0.95,
                                  weight_decay=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = olmoe.synthetic_batch(CFG, 4, SEQ,
                                     np.random.RandomState(0))
        losses = [_scalar(exe.run(main, feed=feed, fetch_list=[loss]))
                  for _ in range(20)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] - 0.5, losses


def test_for_test_clone_gives_the_training_loss():
    """The auxiliary losses are part of the model's loss in both
    programs (the benchmark's reference check runs the clone)."""
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 4
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            params = [p.name for p in main.all_parameters()]
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = olmoe.synthetic_batch(CFG, 2, SEQ,
                                     np.random.RandomState(1))
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        got = _scalar(exe.run(test, feed=feed, fetch_list=[loss]))
    want, _ = _reference(weights, feed)
    no_aux, _ = _reference(weights, feed, aux_weight=0.0, z_weight=0.0)
    assert abs(got - float(want)) <= 2e-6 * float(want)
    assert abs(got - float(no_aux)) > 1e-3 * float(want)


def test_no_loss_scaling_leaves_the_scaling_ops_out():
    def op_types(**amp):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(CFG, SEQ)
            fluid.contrib.mixed_precision.decorate(
                fluid.optimizer.SGD(0.1), **amp).minimize(loss)
        return [op.type for op in main.global_block().ops]

    plain = op_types(use_dynamic_loss_scaling=False,
                     init_loss_scaling=1.0)
    assert 'check_finite_and_unscale' not in plain
    assert 'update_loss_scaling' not in plain
    static = op_types(use_dynamic_loss_scaling=False)
    assert 'check_finite_and_unscale' in static
    assert 'update_loss_scaling' not in static
    assert 'update_loss_scaling' in op_types()


def test_amp_places_the_new_ops():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = olmoe.build_pretrain(CFG, SEQ)
        fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.SGD(0.1)).minimize(loss)
    marks = {}
    for op in main.global_block().ops:
        marks.setdefault(op.type, set()).update(
            a for a in op.attrs if a.startswith('__amp'))
    assert marks['moe_experts'] == {'__amp__'}
    assert marks['moe_route'] == {'__amp_black_out__'}
    for kept_f32_inside in ('rms_norm', 'rotary_embedding',
                            'moe_dispatch', 'moe_combine'):
        assert marks[kept_f32_inside] == set(), kept_f32_inside


def test_dropless_under_an_expert_axis_names_the_cell_that_will_add_it():
    from paddle_tpu.parallel import mesh as pmesh
    main, startup = fluid.Program(), fluid.Program()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ('ep',))
    with pytest.raises((NotImplementedError, RuntimeError)) as info:
        with pmesh.use_trace_mesh(mesh), \
                fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            x = layers.data('x', shape=[8, 16], dtype='float32')
            layers.moe(x, num_experts=8, hidden_size=8, top_k=2,
                       capacity_factor=None)
    assert 'olmoe_1b7b_s4096_ep4' in str(info.value)


@pytest.mark.parametrize('kwargs,message', [
    (dict(top_k=3), 'capacity_factor=None'),
    (dict(top_k=0), 'Switch'),
    (dict(top_k=9, capacity_factor=None), '1..num_experts'),
    (dict(top_k=0, capacity_factor=None), '1..num_experts'),
])
def test_moe_layer_says_which_arguments_go_together(kwargs, message):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data('x', shape=[8, 16], dtype='float32')
        with pytest.raises(ValueError, match=message):
            layers.moe(x, num_experts=8, hidden_size=8, **kwargs)


def test_the_scope_table_knows_the_compiler_s_own_name_for_grouped_matmuls():
    """On the chip ``lax.ragged_dot`` comes out as Mosaic calls whose
    whole op_name is the compiler's (``ragged-dot-none``, seen in the
    first traced run of olmoe_1b7b_s4096: 37 ms a step unscoped); the
    table gives them to the one op that emits them, and nothing else
    changes."""
    from paddle_tpu.fluid import profiler
    assert profiler.fluid_scope('ragged-dot-none') == 'moe_experts'
    assert profiler.fluid_scope('ragged-dot-metadata') == 'moe_experts'
    assert profiler.fluid_scope(
        'jit(segment_x)/moe_experts/ragged_dot_general') == 'moe_experts'
    assert profiler.fluid_scope(
        'jit(segment_x)/transpose(jvp(moe_combine))/gather') == \
        'moe_combine_grad'
    assert profiler.fluid_scope('jit(segment_x)/convert') is None
