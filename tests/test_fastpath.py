"""Steady-state step fast path: argument binders, device-resident
scope bindings, batched async H2D feed staging, donation safety,
async fetch handles, and use_program_cache semantics."""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor


def _tiny_train_program(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 4, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _xs(n=4):
    return np.random.RandomState(0).randn(n, 8).astype('float32')


def test_steady_state_binder_hits_and_staged_h2d():
    """After the 2-step warmup (step 0 resolves, step 0's output
    write-back invalidates once) every step must bind through the
    cached tables, and each host feed must cross H2D exactly once per
    step through the batched async device_put."""
    main, startup, loss = _tiny_train_program()
    xs = _xs()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed={'x': xs}, fetch_list=[])
        f0 = monitor.flat()
        steps = 5
        for _ in range(steps):
            exe.run(main, feed={'x': xs}, fetch_list=[])
        f1 = monitor.flat()
    assert f1['executor/fastpath_hits'] - \
        f0['executor/fastpath_hits'] == steps
    assert f1.get('executor/scope_lookups', 0.0) == \
        f0.get('executor/scope_lookups', 0.0)
    # one async H2D batch per step, exactly the feed's bytes
    assert f1['executor/h2d_bytes_async'] - \
        f0['executor/h2d_bytes_async'] == steps * xs.nbytes
    assert f1['executor/bind_seconds/count'] > \
        f0['executor/bind_seconds/count']


def _two_segment_train_program(seed=11):
    """Device segment -> py_func host op -> device segment."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 8, act='relu')
        mid = main.current_block().create_var(
            name='fp_mid', shape=[-1, 8], dtype='float32')
        layers.py_func(lambda a: a, h, mid)
        loss = layers.reduce_mean(layers.fc(mid, 4, act='relu'))
        fluid.optimizer.SGD(0.05).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize('fetch', ['no_fetch', 'async_fetch'])
@pytest.mark.parametrize('feed', ['device_feed', 'host_feed'])
@pytest.mark.parametrize('build, segments', [
    (_tiny_train_program, 1), (_two_segment_train_program, 2)],
    ids=['one_segment', 'two_segments'])
def test_post_warmup_step_counts(build, segments, feed, fetch):
    """The per-step budgets of the steady state, as counts: after the
    warm-up of the SAME call signature no bind walks the scope, every
    segment of every step binds through its cached table, a
    device-resident feed crosses H2D never and a host feed exactly
    once a step, and an unresolved async fetch blocks nothing."""
    import jax
    main, startup, loss = build()
    xs = _xs()
    fed = {'x': jax.device_put(xs) if feed == 'device_feed' else xs}
    kw = dict(fetch_list=[loss], return_numpy='async') \
        if fetch == 'async_fetch' else dict(fetch_list=[])
    steps = 6
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(3):
            for h in exe.run(main, feed=fed, **kw) or ():
                h.as_numpy()
        f0 = monitor.flat()
        handles = [exe.run(main, feed=fed, **kw) for _ in range(steps)]
        f1 = monitor.flat()
        for hs in handles:
            for h in hs or ():
                assert np.isfinite(h.as_numpy()).all()

    def delta(key):
        return f1.get(key, 0.0) - f0.get(key, 0.0)

    assert delta('executor/scope_lookups') == 0
    assert delta('executor/fastpath_hits') == steps * segments
    assert delta('executor/h2d_bytes_async') == \
        (0 if feed == 'device_feed' else steps * xs.nbytes)
    assert delta('executor/fetch_blocked_seconds/count') == 0
    assert delta('executor/segment_cache_miss') == 0


def test_donation_safety_caller_fed_state():
    """A caller-fed jax.Array bound to a DONATED state slot must
    survive the step (the executor copies caller-owned buffers; only
    runtime-staged buffers pass by pointer)."""
    import jax
    main, startup, loss = _tiny_train_program()
    params = {p.name: p for p in main.all_parameters()}
    assert len(params) == 2  # fc weight + bias
    xs = _xs()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        fed = {n: jax.device_put(np.full(
            tuple(int(d) for d in p.shape), 0.5, 'float32'))
            for n, p in params.items()}
        outs = []
        for _ in range(3):
            feed = dict({'x': xs}, **fed)
            l, = exe.run(main, feed=feed, fetch_list=[loss])
            outs.append(float(np.asarray(l).ravel()[0]))
        # the fed buffers are still alive and unchanged after the
        # donated steps
        for v in fed.values():
            np.testing.assert_array_equal(np.asarray(v), 0.5)
        # every step restarted from the SAME fed weights -> same loss
        assert outs[0] == outs[1] == outs[2]


def test_async_fetch_matches_return_numpy():
    """FetchHandles must resolve to bit-identical values vs the
    blocking return_numpy=True path, on the same training trajectory."""
    main, startup, loss = _tiny_train_program()
    xs = _xs()

    def run(mode):
        vals = []
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            for _ in range(4):
                l, = exe.run(main, feed={'x': xs}, fetch_list=[loss],
                             return_numpy=mode)
                vals.append(l)
        return [np.asarray(v) for v in vals]

    sync = run(True)
    handles = run('async')
    for s, a in zip(sync, handles):
        np.testing.assert_array_equal(s, a)


def test_async_fetch_handle_api():
    main, startup, loss = _tiny_train_program()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        h, = exe.run(main, feed={'x': _xs()}, fetch_list=[loss],
                     return_numpy='async')
    from paddle_tpu.fluid.executor import FetchHandle
    assert isinstance(h, FetchHandle)
    first = h.as_numpy()
    assert h.as_numpy() is first          # resolution is cached
    assert np.asarray(h).shape == first.shape
    import jax
    assert isinstance(h.value, jax.Array)  # raw device value exposed


def test_device_resident_roundtrip_run_pipeline_saveload(tmp_path):
    """Device-resident state must survive the full loop: train via
    run(), save through the 'save' host op (reads the jax.Array from
    the scope), clobber, reload through 'load' (writes numpy back),
    and keep training — binders must absorb the numpy->device
    transition without wrong values."""
    import jax
    main, startup, loss = _tiny_train_program()
    pname = main.all_parameters()[0].name
    path = str(tmp_path / 'w_ckpt')
    save_p = fluid.Program()
    save_p.global_block().append_op(
        'save', inputs={'X': [pname]}, attrs={'file_path': path})
    load_p = fluid.Program()
    load_p.global_block().append_op(
        'load', outputs={'Out': [pname]}, attrs={'file_path': path})
    xs = _xs()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed={'x': xs}, fetch_list=[])
        # steady state: the param is device-resident
        assert isinstance(scope.find_var(pname), jax.Array)
        w_trained = np.asarray(scope.find_var(pname))
        exe.run(save_p)
        assert os.path.exists(path + '.npy')
        scope.set_var(pname, np.zeros((8, 4), 'float32'))
        exe.run(load_p)
        np.testing.assert_array_equal(
            np.asarray(fluid.core.as_array(scope.find_var(pname))),
            w_trained)
        l, = exe.run(main, feed={'x': xs}, fetch_list=[loss])
        assert np.isfinite(np.asarray(l)).all()

    # the same round-trip through a mid-plan host op (CompiledPipeline)
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x2 = layers.data('x', shape=[4], dtype='float32')
        y2 = layers.scale(x2, scale=2.0)
        out_v = main2.current_block().create_var(
            name='py_out', shape=[-1, 4], dtype='float32')
        layers.py_func(lambda a: a + 1.0, y2, out_v)
        z2 = layers.scale(out_v, scale=3.0)
    exe2 = fluid.Executor(fluid.XLAPlace(0))
    xv = np.ones((2, 4), 'float32')
    with fluid.scope_guard(fluid.Scope()):
        pipe = exe2.compile(main2, feed_names=('x',),
                            fetch_names=(z2.name,), allow_host=True)
        for _ in range(3):
            got, = pipe({'x': xv})
        np.testing.assert_allclose(got, (xv * 2 + 1) * 3, rtol=1e-6)
        h, = pipe({'x': xv}, return_numpy='async')
        np.testing.assert_allclose(h.as_numpy(), (xv * 2 + 1) * 3,
                                   rtol=1e-6)


def test_binder_invalidation_on_scope_and_plan_change():
    """Cached bindings must refresh when the scope layout changes (a
    child scope shadowing a param) or when the plan changes (different
    feed keyset) — stale tables would silently read the old owner."""
    main, startup, loss = _tiny_train_program()
    params = main.all_parameters()
    pname = params[0].name
    xs = np.ones((2, 8), 'float32')
    parent = fluid.Scope()
    with fluid.scope_guard(parent):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(3):
            base, = exe.run(main, feed={'x': xs}, fetch_list=[loss])
        # shadow ALL state in a child scope (a partially-shadowing
        # child would let the donated step invalidate parent buffers —
        # the long-standing sub-scope contract): the binder serving
        # the parent must re-resolve onto the child's dict
        kid = parent.new_scope()
        for p in params:
            kid.set_var(p.name, np.zeros(
                tuple(int(d) for d in p.shape), 'float32'))
        w_parent = np.asarray(
            fluid.core.as_array(parent.find_var(pname)))
        zl, = exe.run(main, feed={'x': xs}, fetch_list=[loss],
                      scope=kid)
        assert float(np.asarray(zl).ravel()[0]) == 0.0  # relu(0)=0
        # back on the parent: its buffers were untouched by the child
        # run and rebinding lands on the parent's (trained) values
        np.testing.assert_array_equal(
            np.asarray(fluid.core.as_array(parent.find_var(pname))),
            w_parent)
        again, = exe.run(main, feed={'x': xs}, fetch_list=[loss])
        assert np.isfinite(np.asarray(again)).all()
        # a NEW plan (param fed explicitly -> different feed keyset)
        # builds its own binding table and binds correctly
        import jax
        w = jax.device_put(np.full((8, 4), 0.25, 'float32'))
        fed, = exe.run(main, feed={'x': xs, pname: w},
                       fetch_list=[loss])
        assert np.isfinite(np.asarray(fed)).all()


def test_use_program_cache_false_bypasses_plan_cache():
    main, startup, loss = _tiny_train_program()
    xs = _xs()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        n_bypass0 = monitor.counter_value('executor/plan_cache_bypass')
        a, = exe.run(main, feed={'x': xs}, fetch_list=[loss],
                     use_program_cache=False)
        plan_keys = [k for k in main._exec_cache if k[0] == 'plan']
        assert not plan_keys  # nothing cached for the main program
        b, = exe.run(main, feed={'x': xs}, fetch_list=[loss],
                     use_program_cache=False)
        assert monitor.counter_value('executor/plan_cache_bypass') == \
            n_bypass0 + 2
        # same program state evolution as the cached path would give
        assert np.isfinite(np.asarray(a)).all()
        assert np.asarray(b).ravel()[0] < np.asarray(a).ravel()[0]
        c, = exe.run(main, feed={'x': xs}, fetch_list=[loss])
        assert [k for k in main._exec_cache if k[0] == 'plan']
        assert np.asarray(c).ravel()[0] < np.asarray(b).ravel()[0]


def test_check_nan_inf_device_verdict():
    """The nan/inf sweep computes its reduction on device and still
    names the poisoned var; clean programs pass."""
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            a = layers.data('a', shape=[2], dtype='float32')
            b = layers.log(a)
            out = layers.reduce_sum(b)
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            with pytest.raises(FloatingPointError,
                               match=out.name):
                exe.run(main, feed={'a': -np.ones((3, 2), 'float32')},
                        fetch_list=[out])
            got, = exe.run(main,
                           feed={'a': np.ones((3, 2), 'float32')},
                           fetch_list=[out])
            assert np.isfinite(np.asarray(got)).all()
    finally:
        fluid.set_flags({'FLAGS_check_nan_inf': False})


def test_compiled_pipeline_records_run_counters():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.scale(x, scale=2.0)
        layers.Print(y)
        z = layers.scale(y, scale=3.0)
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        pipe = exe.compile(main, feed_names=('x',),
                           fetch_names=(z.name,), allow_host=True)
        calls0 = monitor.counter_value('executor/run_calls')
        secs0 = (monitor.histogram_value('executor/run_seconds')
                 or {'count': 0})['count']
        pipe({'x': np.ones((2, 4), 'float32')})
        pipe({'x': np.ones((2, 4), 'float32')})
        assert monitor.counter_value('executor/run_calls') == calls0 + 2
        assert monitor.histogram_value(
            'executor/run_seconds')['count'] == secs0 + 2


def test_fed_state_shared_across_segments_survives_donation():
    """A fed state var consumed by TWO device segments (split by a
    host op) must not be pointer-donated to the first one: the second
    segment — and the scope, which host plans publish feeds into —
    still reference the buffer.  Regression test for the staged-feed
    ownership claim being plan-wide instead of per-consumer; the
    pre-fast-path executor's value semantics (feed precedence: each
    segment binding a fed name starts from the FED value, so the
    second increment sees 0, not segment 1's write-back) must hold."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        c = layers.data('c', shape=[4], dtype='float32')
        c.stop_gradient = True
        layers.increment(c, value=1.0)          # segment 1: c state
        probe = main.current_block().create_var(
            name='host_probe', shape=[-1, 4], dtype='float32')
        layers.py_func(lambda a: a, c, probe)   # host op cuts the plan
        layers.increment(c, value=2.0)          # segment 2: c state
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        for _ in range(2):
            out, = exe.run(main, feed={'c': np.zeros((1, 4),
                                                     'float32')},
                           fetch_list=[c])
        np.testing.assert_array_equal(np.asarray(out), 2.0)


def test_reader_batch_reuse_is_donation_safe():
    """Reader-staged batches are handed to USER code — re-feeding one
    (overfit-one-batch loops, train+eval on the same batch) must never
    hit a donated buffer: reader buffers stay caller-owned and the
    executor copies them before donating."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        c = layers.data('c', shape=[4], dtype='float32')
        c.stop_gradient = True
        layers.increment(c, value=1.0)   # fed name is segment STATE
    exe = fluid.Executor(fluid.XLAPlace(0))

    def gen():
        yield {'c': np.zeros((1, 4), 'float32')}

    loader = fluid.io.DataLoader.from_generator(
        feed_list=[c], capacity=2, use_double_buffer=True)
    loader.set_batch_generator(gen)
    with fluid.scope_guard(fluid.Scope()):
        batch = next(iter(loader))
        for _ in range(2):   # second use would read a donated buffer
            out, = exe.run(main, feed=batch, fetch_list=[c])
            np.testing.assert_array_equal(np.asarray(out), 1.0)
        np.testing.assert_array_equal(np.asarray(batch['c']), 0.0)


def test_host_only_feeds_stay_on_host():
    """A feed consumed ONLY by a host op must not be staged to the
    device (it would cross H2D and straight back every step): only the
    segment-consumed feed's bytes enter the async H2D counter."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        ids = layers.data('ids', shape=[1], dtype='int64')
        out_v = main.current_block().create_var(
            name='host_seen', shape=[-1, 1], dtype='int64')
        layers.py_func(lambda a: a, ids, out_v)
        y = layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.XLAPlace(0))
    xv = np.ones((2, 4), 'float32')
    idv = np.array([[1], [2]], 'int64')
    with fluid.scope_guard(fluid.Scope()):
        h2d0 = monitor.counter_value('executor/h2d_bytes_async')
        exe.run(main, feed={'x': xv, 'ids': idv}, fetch_list=[y])
        assert monitor.counter_value('executor/h2d_bytes_async') - \
            h2d0 == xv.nbytes
