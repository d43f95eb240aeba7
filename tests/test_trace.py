"""fluid.trace — span tracer, flight recorder, merged export, report.

The acceptance contract: spans nest and stay thread-attributed; the
ring buffer retains exactly FLAGS_trace_buffer_steps steps; the
DISABLED tracer costs (near) nothing per call site; the merged
host+device export loads as valid chrome-trace JSON with the device
clock aligned; and step_report() phase sums account for the step's
wall time."""

import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor, trace


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _build(width=16):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[width], dtype='float32')
        h = layers.fc(x, size=width, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


# ---------------------------------------------------------------- spans
def test_span_nesting_and_threading():
    trace.enable(buffer_steps=4)
    results = {}

    def worker():
        with trace.span('outer_w'):
            with trace.span('inner_w'):
                time.sleep(0.002)
        results['tid'] = threading.get_ident()

    with trace.step_span(1):
        with trace.span('outer', tag='a'):
            with trace.span('inner'):
                time.sleep(0.002)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    recs = trace.steps()
    assert len(recs) == 1
    spans = {s[0]: s for s in recs[0]['spans']}
    assert set(spans) == {'outer', 'inner', 'outer_w', 'inner_w'}
    main_tid = threading.get_ident()
    # thread attribution
    assert spans['outer'][3] == main_tid
    assert spans['inner'][3] == main_tid
    assert spans['outer_w'][3] == results['tid'] != main_tid
    # depth: step=0, outer=1, inner=2; worker thread starts at 0
    assert spans['outer'][4] == 1 and spans['inner'][4] == 2
    assert spans['outer_w'][4] == 0 and spans['inner_w'][4] == 1
    # nesting by interval: inner inside outer
    assert spans['outer'][1] <= spans['inner'][1]
    assert spans['inner'][2] <= spans['outer'][2]
    # args survive
    assert spans['outer'][5] == {'tag': 'a'}
    assert monitor.counter_value('trace/steps_recorded') >= 1.0


def test_record_and_decorator():
    trace.enable(buffer_steps=4)

    @trace.traced('decorated_phase')
    def work():
        return 41 + 1

    with trace.step_span(7):
        assert work() == 42
        t0 = time.perf_counter()
        trace.record('manual', t0, t0 + 0.5, {'k': 1})
    rec = trace.steps()[-1]
    names = [s[0] for s in rec['spans']]
    assert 'decorated_phase' in names and 'manual' in names
    manual = next(s for s in rec['spans'] if s[0] == 'manual')
    assert abs((manual[2] - manual[1]) - 0.5) < 1e-9


def test_ring_buffer_evicts_at_flag_capacity():
    fluid.set_flags({'FLAGS_trace_buffer_steps': 3})
    try:
        monitor.reset()
        trace.enable()
        for i in range(5):
            with trace.step_span(i):
                with trace.span('phase'):
                    pass
        recs = trace.steps()
        assert len(recs) == 3
        assert [r['step'] for r in recs] == [2, 3, 4]
        assert monitor.counter_value('trace/steps_dropped') == 2.0
        assert monitor.counter_value('trace/steps_recorded') == 5.0
    finally:
        fluid.set_flags({'FLAGS_trace_buffer_steps': 16})


def test_disabled_mode_overhead_budget():
    """Off (the default), a span site is one function call + a global
    load: 10k call pairs must stay far under a us-scale budget (50us
    per site would already be a hot-path regression)."""
    assert not trace.is_active()
    spans_before = monitor.counter_value('trace/spans_recorded')
    n = 10000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span('x'):
            pass
        with trace.span('y', nbytes=4096, vars=2):  # kwargs site shape
            pass
        trace.record('z', 0.0, 1.0)
    dt = time.perf_counter() - t0
    per_site = dt / (3 * n)
    assert per_site < 20e-6, 'disabled span site costs %.1fus' % (
        per_site * 1e6)
    # and nothing was recorded
    assert trace.steps() == []
    assert monitor.counter_value('trace/spans_recorded') == spans_before


# ------------------------------------------------------- chrome export
def test_merged_export_is_valid_chrome_trace(tmp_path):
    trace.enable(buffer_steps=4)
    with trace.step_span(1):
        with trace.span('dispatch', ops=3):
            time.sleep(0.001)
    host = trace.chrome_events()
    sync_host_us = trace.now_us()
    # synthetic jax-style device trace on a session-relative clock
    device = [
        {'ph': 'M', 'pid': 7, 'name': 'process_name',
         'args': {'name': '/device:TPU:0'}},
        {'ph': 'X', 'pid': 7, 'tid': 0, 'ts': 1000.0, 'dur': 5.0,
         'name': 'pt_clock_sync'},
        {'ph': 'X', 'pid': 7, 'tid': 0, 'ts': 1500.0, 'dur': 80.0,
         'name': 'fusion.1'},
    ]
    merged = trace.merge_device_trace(host, device,
                                      sync_host_us=sync_host_us)
    out = str(tmp_path / 'merged.json')
    trace.write_chrome(out, merged)
    doc = json.load(open(out))
    evs = doc['traceEvents']
    assert isinstance(evs, list) and evs
    # sync marker aligned exactly onto the host clock
    sync = next(e for e in evs if e['name'] == 'pt_clock_sync')
    assert abs(sync['ts'] - sync_host_us) < 1e-6
    fusion = next(e for e in evs if e['name'] == 'fusion.1')
    assert abs(fusion['ts'] - (sync_host_us + 500.0)) < 1e-6
    # host events re-homed above the device pids, schema complete
    host_evs = [e for e in evs if e.get('cat') == 'pt_host']
    assert host_evs and all(e['pid'] == 8 for e in host_evs)
    for e in evs:
        if e.get('ph') == 'X':
            assert isinstance(e['ts'], (int, float))
            assert isinstance(e['dur'], (int, float))
            assert isinstance(e['name'], str)
    names = set(e['name'] for e in host_evs if e.get('ph') == 'X')
    assert {'dispatch', 'step'} <= names


def test_merge_without_sync_aligns_on_capture_start():
    host = [{'ph': 'X', 'pid': 0, 'tid': 0, 'ts': 5_000_000.0,
             'dur': 10.0, 'name': 'bind', 'cat': 'pt_host'}]
    device = [{'ph': 'X', 'pid': 3, 'tid': 0, 'ts': 100.0, 'dur': 5.0,
               'name': 'fusion.2'}]
    merged = trace.merge_device_trace(host, device,
                                      capture_t0_us=4_999_900.0)
    fusion = next(e for e in merged if e['name'] == 'fusion.2')
    assert fusion['ts'] == pytest.approx(4_999_900.0)
    # epoch-like device clocks pass through untouched
    device_epoch = [{'ph': 'X', 'pid': 3, 'tid': 0, 'ts': 2e15,
                     'dur': 5.0, 'name': 'fusion.3'}]
    merged = trace.merge_device_trace(host, device_epoch)
    assert next(e for e in merged
                if e['name'] == 'fusion.3')['ts'] == 2e15


# ---------------------------------------------------------------- report
def test_report_sums_approximate_step_wall():
    """Synthetic step with known phases: top-level sums must account
    for the wall time and nested spans must NOT double count."""
    rec = {'step': 9, 't0': 100.0, 't1': 100.010, 'tid': 1,
           'spans': [
               ('bind', 100.0, 100.001, 1, 1, None),
               ('dispatch', 100.001, 100.008, 1, 1, None),
               ('compile', 100.002, 100.007, 1, 2, None),  # nested
               ('fetch_d2h', 100.008, 100.0095, 1, 1, None),
           ]}
    rep = trace.report_from_records([rec])
    s = rep['steps'][0]
    assert s['wall_ms'] == pytest.approx(10.0)
    # nested compile excluded from the phase sums
    assert set(s['phases_ms']) == {'bind', 'dispatch', 'fetch_d2h'}
    assert s['phases_ms']['dispatch'] == pytest.approx(7.0)
    assert s['accounted_ms'] == pytest.approx(9.5)
    assert s['coverage'] >= 0.8
    roll = rep['rollup']
    assert roll['count'] == 1
    assert roll['wall_p50_ms'] == pytest.approx(10.0)
    assert roll['slowest']['step'] == 9
    # JSON round trip (the dump() path) produces the same report
    js = json.loads(json.dumps(rec))
    rep2 = trace.report_from_records([js])
    assert rep2['steps'][0]['phases_ms'] == s['phases_ms']
    # and it renders
    table = trace.format_step_report(rep)
    assert 'dispatch' in table and 'p50' in table


def test_live_program_records_phases_and_covers_wall():
    """End-to-end: a real (tiny) program's traced steps carry the
    bind/dispatch phases and the report explains most of the wall."""
    main, startup, loss = _build()
    x = np.random.RandomState(0).randn(8, 16).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': x}, fetch_list=[loss])  # compile cold
        trace.enable(buffer_steps=8)
        for _ in range(3):
            exe.run(main, feed={'x': x}, fetch_list=[loss])
        trace.disable()
    recs = trace.steps()
    assert len(recs) == 3
    names = set(s[0] for r in recs for s in r['spans'])
    assert {'bind', 'dispatch', 'feed_h2d', 'fetch_d2h',
            'state_release'} <= names
    rep = trace.step_report(last=2)
    assert rep['rollup']['count'] == 2
    # the per-step monitor counters moved with the spans (two planes
    # stay consistent)
    assert monitor.counter_value('trace/steps_recorded') >= 3.0
    assert monitor.counter_value('trace/spans_recorded') >= 12.0


def test_dump_and_stat_summary_steps(tmp_path, capsys):
    import os
    import sys
    trace.enable(buffer_steps=4)
    with trace.step_span(3):
        with trace.span('dispatch'):
            time.sleep(0.001)
    p = str(tmp_path / 'flight.json')
    out = trace.dump(p)
    assert out == p
    doc = json.load(open(p))
    assert doc['ptSteps'] and doc['traceEvents']
    assert monitor.counter_value('trace/dumps_written') == 1.0
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, 'tools'))
    try:
        import stat_summary
    finally:
        sys.path.pop(0)
    assert stat_summary.main(['--steps', p]) == 0
    rendered = capsys.readouterr().out
    assert 'dispatch' in rendered and 'wall(ms)' in rendered


def test_dump_on_error_from_nan_check(tmp_path):
    """FLAGS_check_nan_inf failure dumps the flight recorder (the
    error notes name the path on interpreters with PEP 678)."""
    import glob
    import os
    import tempfile
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.log(x)  # log(0) -> -inf
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    trace.enable(buffer_steps=4)
    dumps_before = monitor.counter_value('trace/dumps_written')
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            with pytest.raises(FloatingPointError):
                exe.run(main, feed={'x': np.zeros((2, 4), 'float32')},
                        fetch_list=[y])
        assert monitor.counter_value('trace/dumps_written') == \
            dumps_before + 1
        paths = glob.glob(os.path.join(
            tempfile.gettempdir(),
            'pt_trace_%d_nan_*.json' % os.getpid()))
        assert paths, 'no flight-recorder dump written'
        doc = json.load(open(max(paths, key=os.path.getmtime)))
        assert doc['ptSteps']  # the failing step window is in the dump
    finally:
        fluid.set_flags({'FLAGS_check_nan_inf': False})


def test_profiler_capture_attaches_tracer(tmp_path):
    """start_trace/stop_trace auto-attach: one capture yields the
    host_trace.json sidecar and restores the tracer's prior state."""
    from paddle_tpu.fluid import profiler
    main, startup, loss = _build()
    x = np.zeros((4, 16), 'float32')
    assert not trace.is_active()
    logdir = str(tmp_path / 'cap')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': x}, fetch_list=[loss])
        profiler.start_trace(logdir)
        assert trace.is_active()
        exe.run(main, feed={'x': x}, fetch_list=[loss])
        path = profiler.stop_trace()
    assert not trace.is_active()
    host = json.load(open(str(tmp_path / 'cap' / 'host_trace.json')))
    assert path == logdir
    names = set(e['name'] for e in host['ptHostEvents']
                if e.get('ph') == 'X')
    assert {'bind', 'dispatch'} <= names
    assert host['ptSync'] is not None


def test_parallel_runner_records_the_executor_phases():
    """The data-parallel runner's step record holds the phases the
    one-chip executor records, under the same names, plus the two
    ``_to_global`` loops; the fetch is 'fetch_d2h' there too."""
    import jax
    main, startup, loss = _build()
    x = np.random.RandomState(0).randn(8, 16).astype('float32')
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ('dp',))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        target = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name).with_mesh(mesh)
        exe.run(target, feed={'x': x}, fetch_list=[loss])   # compiles
        exe.run(target, feed={'x': x}, fetch_list=[])
        trace.enable(buffer_steps=8)
        exe.run(target, feed={'x': x}, fetch_list=[])
        exe.run(target, feed={'x': x}, fetch_list=[loss])
        trace.disable()
    quiet, fetching = trace.steps()
    names = [s[0] for s in quiet['spans']]
    assert names == ['bind', 'place_state', 'place_data', 'dispatch',
                     'state_release']
    assert [s[0] for s in fetching['spans']] == names + ['fetch_d2h']
    # phases follow each other inside the step: none nests in another
    spans = sorted(fetching['spans'], key=lambda s: s[1])
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
    assert fetching['t0'] <= spans[0][1] and spans[-1][2] <= fetching['t1']
