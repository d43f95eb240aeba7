"""Runtime stats registry (fluid.monitor — platform/monitor.h
StatRegistry analog): always-on counters that observe the executor,
reader, PS and collective layers WITHOUT enabling the profiler (which
re-segments the program).

The acceptance contract: two Executor.run() calls of one program show
segment_cache_miss=N then segment_cache_hit=N, prometheus_text()
round-trips those counters in valid exposition format — all with the
profiler off."""

import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(width=32):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[width], dtype='float32')
        h = layers.fc(x, size=width, bias_attr=False)
        out = layers.reduce_mean(h)
    return main, startup, out


# ---------------------------------------------------------------- registry
def test_registry_primitives():
    monitor.reset()
    monitor.add('t/c')
    monitor.add('t/c', 2.5)
    assert monitor.counter_value('t/c') == 3.5
    monitor.set_gauge('t/g', 7)
    monitor.set_gauge('t/g', 4)
    assert monitor.gauge_value('t/g') == 4.0
    monitor.observe('t/h', 0.002, buckets=(0.001, 0.01, 0.1))
    monitor.observe('t/h', 0.5)  # later bucket args are ignored
    h = monitor.histogram_value('t/h')
    assert h['count'] == 2 and abs(h['sum'] - 0.502) < 1e-12
    assert h['buckets']['0.01'] == 1 and h['buckets']['+Inf'] == 2
    snap = monitor.snapshot()
    assert snap['t']['c'] == 3.5 and snap['t']['g'] == 4.0
    assert snap['t']['h']['count'] == 2
    flat = monitor.flat()
    assert flat['t/h/count'] == 2.0 and flat['t/c'] == 3.5
    monitor.reset()
    assert monitor.snapshot() == {}


def test_set_enabled_gates_recording():
    monitor.reset()
    prev = monitor.set_enabled(False)
    assert prev is True
    monitor.add('off/c')
    monitor.set_gauge('off/g', 1)
    monitor.observe('off/h', 1.0)
    assert monitor.snapshot() == {}
    monitor.set_enabled(True)
    monitor.add('off/c')
    assert monitor.counter_value('off/c') == 1.0
    monitor.reset()


# ------------------------------------------------- executor instrumentation
def test_segment_cache_miss_then_hit_without_profiler():
    """Acceptance: run #1 of a program misses the executable cache N
    times (N segments), run #2 hits N times — observed with the
    profiler OFF (the counters must not require re-segmentation)."""
    assert not profiler.is_enabled()
    main, startup, out = _build()
    x = np.random.RandomState(0).randn(8, 32).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        monitor.reset()
        exe.run(main, feed={'x': x}, fetch_list=[out])
        s1 = monitor.snapshot()['executor']
        n = s1['segment_cache_miss']
        assert n >= 1 and 'segment_cache_hit' not in s1
        assert s1['segments_lowered'] == n
        # compile latency histogram saw one sample per lowered segment
        assert s1['segment_compile_seconds']['count'] == n
        assert s1['segment_compile_seconds']['sum'] > 0
        exe.run(main, feed={'x': x}, fetch_list=[out])
        s2 = monitor.snapshot()['executor']
        assert s2['segment_cache_miss'] == n  # no new misses
        assert s2['segment_cache_hit'] == n
        # plan cache: one build, one reuse
        assert s2['plan_cache_miss'] == 1.0
        assert s2['plan_cache_hit'] == 1.0
        # volume + latency counters moved
        assert s2['feed_bytes'] == 2 * x.nbytes
        assert s2['fetch_bytes'] > 0
        assert s2['run_seconds']['count'] == 2
    assert not profiler.is_enabled()


def test_prometheus_text_round_trips_counters():
    main, startup, out = _build()
    x = np.zeros((4, 32), 'float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        monitor.reset()
        exe.run(main, feed={'x': x}, fetch_list=[out])
        exe.run(main, feed={'x': x}, fetch_list=[out])
        snap = monitor.snapshot()['executor']
        text = monitor.prometheus_text()
    # every line is valid text exposition format (incl. HELP metadata)
    line_re = re.compile(
        r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* '
        r'(counter|gauge|histogram)'
        r'|# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*'
        r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? -?[0-9.e+-]+'
        r'(inf)?)$')
    for line in text.strip().splitlines():
        assert line_re.match(line), line
    # and the lint-level contract holds (fluid.health.prom_lint is the
    # exhaustive check: HELP/TYPE per family, no duplicate series,
    # histogram bucket/_sum/_count consistency)
    from paddle_tpu.fluid import health
    assert health.prom_lint(text) == []
    # the cache counters round-trip by value
    parsed = {}
    for line in text.splitlines():
        if line.startswith('#') or '{' in line or not line:
            continue
        name, val = line.rsplit(' ', 1)
        parsed[name] = float(val)
    assert parsed['paddle_tpu_executor_segment_cache_hit'] == \
        snap['segment_cache_hit']
    assert parsed['paddle_tpu_executor_segment_cache_miss'] == \
        snap['segment_cache_miss']
    # histogram triplet present with consistent count
    assert parsed['paddle_tpu_executor_run_seconds_count'] == 2
    assert 'paddle_tpu_executor_run_seconds_sum' in parsed
    assert '# TYPE paddle_tpu_executor_run_seconds histogram' in text


def test_dump_jsonl_and_stat_summary_diff(tmp_path, capsys):
    main, startup, out = _build()
    x = np.zeros((4, 32), 'float32')
    p1, p2 = str(tmp_path / 'a.jsonl'), str(tmp_path / 'b.jsonl')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        monitor.reset()
        exe.run(main, feed={'x': x}, fetch_list=[out])
        monitor.dump_jsonl(p1, step=1)
        exe.run(main, feed={'x': x}, fetch_list=[out])
        monitor.dump_jsonl(p2, step=2, extra={'tag': 'second'})
    rec = json.loads(open(p2).read().splitlines()[-1])
    assert rec['step'] == 2 and rec['tag'] == 'second'
    assert rec['counters']['executor/segment_cache_hit'] >= 1
    assert rec['histograms']['executor/run_seconds']['count'] == 2
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    try:
        import stat_summary
    finally:
        sys.path.pop(0)
    assert stat_summary.main([p2]) == 0
    rendered = capsys.readouterr().out
    assert 'executor/segment_cache_hit' in rendered
    assert stat_summary.main([p1, p2]) == 0
    diffed = capsys.readouterr().out
    # between the dumps exactly one run happened: one cache hit
    m = re.search(r'executor/segment_cache_hit\s+(\S+)', diffed)
    assert m and float(m.group(1)) == \
        rec['counters']['executor/segment_cache_hit'] - \
        json.loads(open(p1).read())['counters'].get(
            'executor/segment_cache_hit', 0.0) + 0.0


# ------------------------------------------------------ reader / loader
def test_reader_pipeline_counters():
    from paddle_tpu.fluid.reader import _AsyncBatchIterator
    monitor.reset()
    batches = [{'x': np.zeros((2, 4), 'float32')} for _ in range(5)]

    def gen():
        for b in batches:
            yield b

    it = _AsyncBatchIterator(gen, capacity=2, device=None)
    got = list(it)
    assert len(got) == 5
    snap = monitor.snapshot()['reader']
    assert snap['batches_produced'] == 5.0
    assert snap['batches_consumed'] == 5.0
    assert 'queue_depth' in snap
    # the consumer blocked at least once waiting on the producer
    assert snap['consume_blocked_seconds']['count'] >= 1


def test_reader_staging_counts_bytes():
    import jax
    from paddle_tpu.fluid.reader import _AsyncBatchIterator
    monitor.reset()
    arr = np.ones((3, 4), 'float32')

    def gen():
        yield {'x': arr}

    it = _AsyncBatchIterator(gen, capacity=2, device=jax.devices()[0])
    batch = next(it)
    assert hasattr(batch['x'], 'devices')
    assert monitor.counter_value('reader/bytes_staged') == arr.nbytes


# ------------------------------------------------- PS / communicator plane
def test_communicator_counters():
    from paddle_tpu.distributed import (ParameterServerStore,
                                        AsyncCommunicator)
    monitor.reset()
    store = ParameterServerStore(lr=0.5)
    store.init_var('w', np.ones(4, 'float32'))
    comm = AsyncCommunicator(store)
    comm.start()
    g = np.full(4, 2.0, 'float32')
    comm.send('w', g)
    comm.send('w', g)
    comm.flush()
    comm.stop()
    snap = monitor.snapshot()['communicator']
    assert snap['sends'] == 2.0
    assert snap['send_bytes'] == 2.0 * g.nbytes
    assert snap['grads_merged'] == 2.0
    assert snap['server_applies'] >= 1.0


def test_collective_transpile_counters():
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.fc(x, size=1)
        loss = layers.reduce_mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    monitor.reset()
    # reference (v1.6) rewrite counters: one c_allreduce_sum per grad
    # (the planned default fuses the two small grads into ONE bucket
    # op and reports ops_inserted accordingly — test_comms_plan.py)
    prev = fluid.get_flags(['FLAGS_comms_plan'])
    fluid.set_flags({'FLAGS_comms_plan': False})
    try:
        GradAllReduce().transpile(startup, main, 0, ['127.0.0.1:6170'],
                                  '127.0.0.1:6170')
    finally:
        fluid.set_flags(prev)
    snap = monitor.snapshot()['collective']
    assert snap['transpile_calls'] == 1.0
    # fc weight + bias gradients each get one inserted c_allreduce_sum
    assert snap['allreduce_ops_inserted'] >= 2.0
    assert snap['allreduce_bytes_per_step'] >= 4 * 4  # w is [4,1] f32


# ------------------------------------------------------ profiler satellites
def test_stop_profiler_folds_table_into_monitor_and_returns_it():
    main, startup, out = _build()
    x = np.zeros((4, 32), 'float32')
    monitor.reset()
    profiler.reset_profiler()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        profiler.start_profiler('All')
        exe.run(main, feed={'x': x}, fetch_list=[out])
        table = profiler.stop_profiler(profile_path=None)
    assert isinstance(table, str) and table.startswith('Event')
    assert 'mul' in table
    prof = monitor.snapshot()['profiler']
    assert prof['mul']['calls'] == 1.0
    assert prof['mul']['total_seconds'] > 0
    # a second (defensive) stop must not re-fold the same records
    profiler.stop_profiler(profile_path=None)
    assert monitor.snapshot()['profiler']['mul']['calls'] == 1.0
    profiler.reset_profiler()


def test_stop_profiler_resets_stale_default_mode():
    """Satellite: a 'Default' capture must not leave _mode sticky —
    after stop, a bare start_profiler()/is_enabled() behaves exactly
    like a fresh process (Serial re-segmentation enabled)."""
    profiler.reset_profiler()
    # simulate the post-'Default' state without paying a jax trace
    profiler._mode = 'Default'
    profiler._enabled = True
    assert not profiler.is_enabled()  # Default never re-segments
    profiler.stop_profiler(profile_path=None)
    assert profiler._mode == 'Serial'
    profiler.start_profiler('All')
    try:
        assert profiler.is_enabled()
    finally:
        profiler.stop_profiler(profile_path=None)
        profiler.reset_profiler()


def test_start_trace_double_start_raises(tmp_path):
    profiler.start_trace(str(tmp_path / 't1'))
    try:
        with pytest.raises(RuntimeError, match='already active'):
            profiler.start_trace(str(tmp_path / 't2'))
    finally:
        profiler.stop_trace()
    # a 'Default' profiler capture owns the device tracer too
    profiler._prof_trace_dir = '/tmp/fake_prof_dir'
    try:
        with pytest.raises(RuntimeError, match='stop_profiler'):
            profiler.start_trace(str(tmp_path / 't3'))
    finally:
        profiler._prof_trace_dir = None


def test_attribute_trace_events_transform_wrapped_scopes():
    """Satellite: transform-wrapped scope components — the wpg backward
    wraps op scopes as transpose(jvp(op)), possibly nested — must
    attribute to the op, the transposed one to its ``_grad`` as in the
    scope table; kernels with no registered component land
    in per-HLO 'unattributed/…' buckets (folded keys stay one level)."""
    ev = [
        {'ph': 'X', 'name': 'fusion.9', 'dur': 50.0,
         'args': {'tf_op': 'jit_seg/transpose(jvp(relu))/max:'}},
        {'ph': 'X', 'name': 'fusion.10', 'dur': 30.0,
         'args': {'tf_op': 'jit_seg/jvp(relu)/max:'}},
        {'ph': 'X', 'name': 'convert.3', 'dur': 5.0,
         'args': {'tf_op': 'jit_seg/convert'}},
    ]
    recs = profiler.attribute_trace_events(ev, op_types={'relu'})
    assert recs['relu'][0] == 1 and recs['relu_grad'][0] == 1
    assert abs(recs['relu'][1] - 30e-6) < 1e-12
    assert abs(recs['relu_grad'][1] - 50e-6) < 1e-12
    assert recs['unattributed/convert'][0] == 1
    # fold-in keeps the unattributed bucket one level deep
    monitor.reset()
    profiler.reset_profiler()
    profiler._records.update(recs)
    profiler._fold_into_monitor()
    prof = monitor.snapshot()['profiler']
    assert prof['relu']['calls'] == 1.0
    assert prof['relu_grad']['calls'] == 1.0
    assert prof['unattributed:convert']['calls'] == 1.0
    profiler.reset_profiler()
    monitor.reset()
