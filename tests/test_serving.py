"""fluid.serving: multi-tenant continuous batching over CompiledStep.

Covers the serving-plane contract: the pad/mask/slice helpers are
bitwise-transparent, coalesced batches return exactly what unbatched
execution returns, tenants are scope-isolated, the warmed bucket
ladder serves every admissible shape without retracing, serving steps
are tenant-tagged in the trace plane, and the health plane gates
readiness on serving warmup and lists resident programs."""

import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import health, layers, monitor, serving
from paddle_tpu.fluid import trace as pt_trace
from paddle_tpu.fluid.reader import (bucket_for, mask_name,
                                     pow2_bucket_ladder)


def _build_mlp(width=24, seed=3, in_w=8):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[in_w], dtype='float32')
        h = layers.fc(x, width, act='relu')
        y = layers.fc(h, 6, act='softmax')
    return main_p, startup, y


@pytest.fixture
def exe():
    return fluid.Executor(fluid.XLAPlace(0))


def test_pow2_bucket_ladder():
    assert pow2_bucket_ladder(1) == [1]
    assert pow2_bucket_ladder(8) == [1, 2, 4, 8]
    assert pow2_bucket_ladder(6) == [1, 2, 4, 8]
    assert bucket_for(3, [1, 2, 4, 8]) == 4
    assert bucket_for(8, [1, 2, 4, 8]) == 8
    with pytest.raises(ValueError):
        bucket_for(9, [1, 2, 4, 8])
    assert mask_name('x') == 'x@MASK'
    assert mask_name('x', {'x': 'm'}) == 'm'


def test_pad_rows_to_bucket_and_slice():
    feed = {'x': np.arange(12, dtype='float32').reshape(3, 4),
            'side': np.float32(2.0)}   # not batch-aligned: untouched
    padded, waste = serving.pad_rows_to_bucket(
        feed, 3, 4, mask_specs=(('x@MASK', ()),))
    assert padded['x'].shape == (4, 4)
    assert np.array_equal(padded['x'][:3], feed['x'])
    assert not padded['x'][3].any()
    assert np.array_equal(padded['x@MASK'],
                          np.array([1, 1, 1, 0], 'float32'))
    assert padded['side'] == np.float32(2.0)
    assert waste == 4 * 4  # one f32 pad row
    # slice back: batch-aligned outputs slice, aggregates pass through
    out = np.arange(8, dtype='float32').reshape(4, 2)
    assert np.array_equal(serving.slice_rows(out, 1, 2, 4), out[1:3])
    assert serving.slice_rows(np.float32(7.0), 1, 2, 4) == 7.0
    # already-bucketed feed is returned as-is (no copies, no masks)
    same, waste = serving.pad_rows_to_bucket(feed, 3, 3)
    assert same is feed and waste == 0.0


def test_padded_equals_unbatched(exe):
    """The acceptance-criteria core: pad-to-bucket + slice is bitwise
    invisible."""
    main_p, startup, y = _build_mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xv = np.random.RandomState(0).randn(3, 8).astype('float32')
        direct, = exe.run(main_p, feed={'x': xv}, fetch_list=[y])
        padded, _ = serving.pad_rows_to_bucket({'x': xv}, 3, 4)
        batched, = exe.run(main_p, feed=padded, fetch_list=[y])
    assert np.array_equal(np.asarray(direct),
                          serving.slice_rows(np.asarray(batched),
                                             0, 3, 4))


def test_serving_executor_soak_bitwise_and_zero_retrace(exe):
    main_a, start_a, y_a = _build_mlp(width=16, seed=5)
    main_b, start_b, y_b = _build_mlp(width=24, seed=6)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    scopes = {}
    for name, (mp, sp, y) in (('a', (main_a, start_a, y_a)),
                              ('b', (main_b, start_b, y_b))):
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(sp)
        scopes[name] = (mp, sc, y)
        srv.add_program(name, mp, ['x'], [y], scope=sc)
    try:
        srv.warmup(wait=True)
        assert srv.ready
        lowered0 = monitor.counter_value('executor/segments_lowered')
        rng = np.random.RandomState(1)
        futs, expect = [], []
        for i in range(16):
            name = 'ab'[i % 2]
            rows = (1, 3, 2, 5)[i % 4]
            xv = rng.randn(rows, 8).astype('float32')
            futs.append(srv.submit(name, {'x': xv}))
            expect.append((name, xv))
        outs = [f.result(120) for f in futs]
        # zero retraces: every bucket came from the warmed ladder
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered0
        assert srv.resident_report()['tenants'][0]['retraces'] == 0
        # bitwise vs unbatched execution at the bucket the request
        # actually ran in: coalescing picks the bucket from the TOTAL
        # batch rows, and XLA's gemm accumulation order may differ
        # across bucket shapes — within one bucket, bytes match
        for (name, xv), res in zip(expect, outs):
            mp, sc, y = scopes[name]
            rows = xv.shape[0]
            matched = False
            for b in (bb for bb in (1, 2, 4, 8) if bb >= rows):
                padded, _ = serving.pad_rows_to_bucket(
                    {'x': xv}, rows, b)
                with fluid.scope_guard(sc):
                    direct, = exe.run(mp, feed=padded, fetch_list=[y])
                if np.array_equal(np.asarray(direct)[:rows], res[0]):
                    matched = True
                    break
            assert matched
        # SLO metrics recorded
        assert monitor.histogram_value(
            'serving/admit_to_done_seconds')['count'] >= 16
        assert monitor.histogram_value(
            'serving/batch_occupancy')['count'] >= 1
        assert monitor.gauge_value('serving/queue_depth/a', -1) >= 0
    finally:
        srv.close()


def test_tenant_scope_isolation(exe):
    """Two tenants over CONTENT-IDENTICAL programs (unique_name.guard
    makes the op/var descs byte-equal) but different parameter values
    must serve from their own scopes."""
    with fluid.unique_name.guard():
        main_a, start_a, y_a = _build_mlp(width=16, seed=7)
    with fluid.unique_name.guard():
        main_b, start_b, y_b = _build_mlp(width=16, seed=7)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    sc_a, sc_b = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(sc_a):
        exe.run(start_a)
    with fluid.scope_guard(sc_b):
        exe.run(start_b)
    # same program content, same init — perturb tenant b's weights so
    # only scope isolation can explain differing outputs
    for pname in [p.name for p in main_b.all_parameters()]:
        v = np.asarray(fluid.core.as_array(sc_b.find_var(pname)))
        sc_b.set_var(pname, v * 2.0)
    srv.add_program('a', main_a, ['x'], [y_a], scope=sc_a)
    srv.add_program('b', main_b, ['x'], [y_b], scope=sc_b)
    try:
        srv.warmup(wait=True)
        # identical program content → one fingerprint, two tenants
        rep = srv.resident_report()['tenants']
        assert rep[0]['fingerprint'] == rep[1]['fingerprint']
        xv = np.random.RandomState(2).randn(2, 8).astype('float32')
        out_a, = srv.infer('a', {'x': xv}, timeout=120)
        out_b, = srv.infer('b', {'x': xv}, timeout=120)
        assert not np.array_equal(out_a, out_b)
        with fluid.scope_guard(sc_a):
            direct_a, = exe.run(main_a, feed={'x': xv},
                                fetch_list=[y_a])
        assert np.array_equal(np.asarray(direct_a), out_a)
    finally:
        srv.close()


def test_concurrent_feeders(exe):
    main_p, startup, y = _build_mlp(width=16, seed=9)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    srv.add_program('m', main_p, ['x'], [y], scope=sc)
    try:
        srv.warmup(wait=True)
        errors = []

        def feeder(fid):
            rng = np.random.RandomState(fid)
            for i in range(8):
                xv = rng.randn((i % 3) + 1, 8).astype('float32')
                try:
                    out, = srv.infer('m', {'x': xv}, timeout=120)
                    assert out.shape[0] == xv.shape[0]
                except Exception as e:  # noqa: BLE001
                    errors.append(str(e))

        threads = [threading.Thread(target=feeder, args=(fid,))
                   for fid in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        assert srv.resident_report()['tenants'][0][
            'requests_served'] == 32
    finally:
        srv.close()


def test_submit_validation(exe):
    main_p, startup, y = _build_mlp(width=16, seed=10)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    srv.add_program('m', main_p, ['x'], [y], scope=sc)
    try:
        with pytest.raises(KeyError):
            srv.submit('nope', {'x': np.zeros((1, 8), 'float32')})
        with pytest.raises(ValueError):
            srv.submit('m', {})            # missing feed
        with pytest.raises(ValueError):    # beyond the ladder
            srv.submit('m', {'x': np.zeros((5, 8), 'float32')})
        with pytest.raises(ValueError):    # duplicate tenant
            srv.add_program('m', main_p, ['x'], [y], scope=sc)
    finally:
        srv.close()


def test_mismatched_leading_dims_rejected_at_submit(exe):
    """One malformed request must fail at submit(), not poison the
    coalesced batch it would have joined."""
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 16
    with fluid.program_guard(main_p, startup):
        a = layers.data('a', shape=[4], dtype='float32')
        b = layers.data('b', shape=[4], dtype='float32')
        y = layers.fc(layers.elementwise_add(a, b), 4)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    srv.add_program('two', main_p, ['a', 'b'], [y], scope=sc)
    try:
        with pytest.raises(ValueError, match='mismatched leading'):
            srv.submit('two', {'a': np.zeros((2, 4), 'float32'),
                               'b': np.zeros((3, 4), 'float32')})
    finally:
        srv.close()


def test_aggregate_fetch_rejected_at_registration(exe):
    """A whole-batch aggregate fetch cannot be sliced back per request
    (pad rows would contaminate it): add_program must refuse it."""
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 17
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        agg = layers.reduce_mean(layers.fc(x, 4))
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    try:
        with pytest.raises(ValueError, match='aggregate'):
            srv.add_program('agg', main_p, ['x'], [agg], scope=sc)
    finally:
        srv.close()


def test_cancelled_future_does_not_kill_dispatcher(exe):
    main_p, startup, y = _build_mlp(width=16, seed=18)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    srv.add_program('m', main_p, ['x'], [y], scope=sc)
    try:
        srv.warmup(wait=True)
        xv = np.zeros((1, 8), 'float32')
        # a burst where the middle request is cancelled while queued
        f1 = srv.submit('m', {'x': xv})
        f2 = srv.submit('m', {'x': xv})
        f2.cancel()
        f3 = srv.submit('m', {'x': xv})
        assert f1.result(120)[0].shape == (1, 6)
        assert f3.result(120)[0].shape == (1, 6)
        # the dispatcher survived: a later request still serves
        out, = srv.infer('m', {'x': xv}, timeout=120)
        assert out.shape == (1, 6)
    finally:
        srv.close()


def test_step_tags_attribution(exe):
    main_p, startup, y = _build_mlp(width=16, seed=11)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        # the tracer is process-global and this worker ran other files
        # before this one: start from an empty ring (enable() keeps
        # what an earlier test's tagged steps left behind)
        pt_trace.disable()
        pt_trace.reset()
        pt_trace.enable(buffer_steps=8)
        try:
            with pt_trace.step_tags(tenant='t1', bucket=4):
                exe.run(main_p, feed={'x': np.zeros((4, 8),
                                                    'float32')},
                        fetch_list=[y])
            exe.run(main_p, feed={'x': np.zeros((4, 8), 'float32')},
                    fetch_list=[y])
            rep = pt_trace.step_report()
            tagged = [s for s in rep['steps'] if s.get('tags')]
            assert len(tagged) == 1
            assert tagged[0]['tags'] == {'tenant': 't1', 'bucket': 4}
            # the rendered table carries the tags too
            assert 'tenant=t1' in pt_trace.format_step_report(rep)
            # and the flight-recorder dump round-trips them
            import json
            with open(pt_trace.dump()) as f:
                doc = json.load(f)
            assert any(r.get('tags') == {'tenant': 't1', 'bucket': 4}
                       for r in doc['ptSteps'])
        finally:
            pt_trace.disable()
            pt_trace.reset()


def test_health_readiness_and_statusz(exe):
    main_p, startup, y = _build_mlp(width=16, seed=12)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=2, executor=exe)
    srv.add_program('resident', main_p, ['x'], [y], scope=sc)
    try:
        st = health.status()
        assert st['ready'] is False
        assert st['serving_ready'] is False
        assert any('resident' in r for r in st['reasons'])
        srv.warmup(wait=True)
        st = health.status()
        assert st['ready'] is True and st['serving_ready'] is True
        sz = health.statusz()
        tenants = [t for rep in sz['serving'] for t in rep['tenants']]
        mine = [t for t in tenants if t['tenant'] == 'resident']
        assert mine and mine[0]['warmed']
        assert mine[0]['bucket_ladder'] == [1, 2]
        assert mine[0]['fingerprint']
    finally:
        srv.close()
    # closed executors drop out of the readiness view
    ready, _ = serving.readiness()
    assert ready in (None, True)


def test_sub_capacity_batch_closes_without_waiting(exe):
    """One queued row of a ladder that reaches 8 is a batch NOW: the
    dispatcher holds no window open for more rows."""
    from concurrent.futures import Future
    main_p, _startup, y = _build_mlp(width=16, seed=14)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    t = srv.add_program('m', main_p, ['x'], [y])
    try:
        req = serving._Request('m', {'x': np.zeros((1, 8), 'float32')},
                               1, Future())
        t.pending.append(req)      # queued, no dispatcher thread
        batch = srv._take_batch(0.0)
        assert batch is not None and batch.requests == [req]
        assert batch.rows == 1 and not t.pending
        assert srv._take_batch(0.0) is None
    finally:
        srv.close()


def test_reports_carry_the_static_keys_only(exe):
    main_p, _startup, y = _build_mlp(width=16, seed=15)
    srv = serving.ServingExecutor(max_batch=2, executor=exe)
    t = srv.add_program('m', main_p, ['x'], [y])
    try:
        assert set(t.report()) == {
            'tenant', 'fingerprint', 'bucket_ladder', 'warmed',
            'requests_served', 'batches', 'rows', 'cache_hit_batches',
            'retraces', 'pad_rows', 'errors', 'queue_depth'}
        assert set(srv.resident_report()) == {
            'ready', 'max_batch', 'tenants', 'compile_plane'}
    finally:
        srv.close()


def test_predictor_bucket_parity(exe, tmp_path):
    """Single-shot predictor run() routes through the same
    pad/mask/slice helper: padded and unpadded results bitwise-equal
    (the ISSUE's satellite acceptance)."""
    main_p, startup, y = _build_mlp(width=16, seed=13)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['x'], [y], exe,
                                      main_program=main_p)
    from paddle_tpu.inference import (AnalysisConfig,
                                      create_paddle_predictor)
    xv = np.random.RandomState(4).randn(3, 8).astype('float32')
    cfg = AnalysisConfig(str(tmp_path))
    assert cfg._serving_buckets   # bucket routing is the default
    bucketed, = create_paddle_predictor(cfg).run_dict({'x': xv})
    cfg_off = AnalysisConfig(str(tmp_path))
    cfg_off.switch_serving_buckets(False)
    plain, = create_paddle_predictor(cfg_off).run_dict({'x': xv})
    assert bucketed.shape == plain.shape == (3, 6)
    assert np.array_equal(bucketed, plain)


def test_predictor_serve_entry_point(exe, tmp_path):
    main_p, startup, y = _build_mlp(width=16, seed=14)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['x'], [y], exe,
                                      main_program=main_p)
    from paddle_tpu.inference import (AnalysisConfig,
                                      create_paddle_predictor)
    pred = create_paddle_predictor(AnalysisConfig(str(tmp_path)))
    srv = pred.serve(tenant='model', max_batch=4)
    try:
        assert srv.ready
        xv = np.random.RandomState(5).randn(2, 8).astype('float32')
        out, = srv.infer('model', {'x': xv}, timeout=120)
        plain, = pred.run_dict({'x': xv})
        assert np.array_equal(out, plain)
    finally:
        srv.close()


def test_mask_synthesis_for_declared_mask_vars(exe):
    """A program declaring '<feed>@MASK' gets a synthesized row mask:
    live rows 1.0, padding 0.0 — the bucketed-loader convention."""
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 15
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        m = layers.data('x@MASK', shape=[1], dtype='float32')
        y = layers.elementwise_mul(layers.fc(x, 4), m)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=4, executor=exe)
    t = srv.add_program('masked', main_p, ['x'], [y], scope=sc)
    try:
        assert t.mask_specs == (('x@MASK', (1,)),)
        srv.warmup(wait=True)
        xv = np.ones((3, 4), 'float32')
        out, = srv.infer('masked', {'x': xv}, timeout=120)
        assert out.shape[0] == 3
        # mask multiplied through: live rows intact
        with fluid.scope_guard(sc):
            direct, = exe.run(
                main_p, feed={'x': xv,
                              'x@MASK': np.ones((3, 1), 'float32')},
                fetch_list=[y])
        assert np.allclose(out, np.asarray(direct))
    finally:
        srv.close()
