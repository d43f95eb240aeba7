"""Device-memory observability plane (fluid.memviz): per-(program,
segment) peak attribution summing back to memory_analysis() totals,
the live-HBM census classes, OOM forensics (incident schema, rate
limit, actionable note), the budget watermark detector, the Perfetto
counter track riding the merged timeline, and the collective
planner's per-program HBM headroom."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (comms, comms_plan, health, memviz,
                              monitor, trace)

MEMVIZ_FLAGS = ('FLAGS_memviz', 'FLAGS_memviz_sample_steps',
                'FLAGS_memviz_budget_bytes', 'FLAGS_memviz_watermark',
                'FLAGS_memviz_spike_factor',
                'FLAGS_memviz_dump_interval_s',
                'FLAGS_memviz_oom_interval_s',
                'FLAGS_comms_hbm_budget_bytes')


@pytest.fixture(autouse=True)
def _clean():
    from paddle_tpu.fluid import compile_cache
    prev = fluid.get_flags(list(MEMVIZ_FLAGS))
    # warmup() marks the PROCESS-WIDE compile plane warmed (the AOT
    # run path attribution rides): isolate it both ways so this module
    # neither inherits nor leaks the plane's warmed/cached state
    compile_cache.reset_plane()
    monitor.reset()
    memviz.reset()
    comms.reset()
    trace.disable()
    trace.reset()
    yield
    fluid.set_flags(prev)
    compile_cache.reset_plane()
    monitor.reset()
    memviz.reset()
    comms.reset()
    trace.disable()
    trace.reset()


def _build_mlp(width=16):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data('x', shape=[width], dtype='float32')
        h = fluid.layers.fc(x, width, act='relu')
        loss = fluid.layers.reduce_mean(h)
        fluid.optimizer.SGD(0.05).minimize(loss)
    main_p._test_param_names = [p.name for p in main_p.all_parameters()]
    return main_p, startup, loss


def _run_steps(main_p, startup, loss, scope, steps=2, warm=True,
               width=16, batch=8):
    feed = {'x': np.ones((batch, width), 'float32')}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        if warm:
            # engage the AOT plane: attribution rides executable
            # resolution (compile / memory hit / disk hit)
            exe.warmup(main_p,
                       feed_shapes={'x': ((batch, width), 'float32')},
                       fetch_list=[loss], wait=True)
        for _ in range(steps):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        return exe, feed


# ------------------------------------------------------ peak attribution
def test_peak_decomposition_sums_to_analysis_totals():
    main_p, startup, loss = _build_mlp()
    _run_steps(main_p, startup, loss, fluid.Scope())
    rows = memviz.report()
    assert rows, 'attribution must land on the AOT path'
    r = rows[0]
    # the named classes + alignment overhead reconstruct the
    # analysis's argument arena exactly — nothing is vibes
    named = sum(r['classes'].values())
    assert named + r['arg_overhead_bytes'] == \
        pytest.approx(r['argument_bytes'])
    # the compiler's own peak where it reports one (the installed CPU
    # XLA does), else the live-set bound arg+out+temp — which bounds
    # the reported peak from above either way
    assert 0 < r['peak_bytes'] <= \
        r['argument_bytes'] + r['output_bytes'] + r['temp_bytes']
    assert r['classes']['param'] > 0      # fc weights are attributed
    assert r['classes']['feed'] > 0       # the x feed is attributed
    # largest buffers are named and sorted descending
    tops = r['top_buffers']
    assert tops and all(tops[i]['bytes'] >= tops[i + 1]['bytes']
                        for i in range(len(tops) - 1))
    top_names = {c['name'] for c in tops}
    assert top_names & set(main_p._test_param_names)
    # outputs carry their originating op desc
    assert any(c['op'] for c in r['outputs'])
    assert monitor.counter_value('memviz/segments_attributed') >= 1


def test_peak_bytes_per_program_and_top_contributors():
    class FakeCompiled(object):
        def __init__(self, arg, out, temp):
            self._f = (arg, out, temp)

        def memory_analysis(self):
            class MA(object):
                pass
            ma = MA()
            ma.argument_size_in_bytes = self._f[0]
            ma.output_size_in_bytes = self._f[1]
            ma.temp_size_in_bytes = self._f[2]
            ma.generated_code_size_in_bytes = 10
            return ma

    memviz.record_segment('small', 'seg0', FakeCompiled(100, 50, 25),
                          {'w': np.zeros(25, 'float32')},
                          {'x': np.zeros(10, 'float32')})
    memviz.record_segment('big', 'seg0', FakeCompiled(1000, 500, 250),
                          {'w2': np.zeros(250, 'float32')}, {})
    assert memviz.peak_bytes('small') == 175
    assert memviz.peak_bytes('big') == 1750
    assert memviz.peak_bytes() == 1750
    assert memviz.peak_bytes('nonexistent') is None
    tops = memviz.top_contributors(2)
    assert tops[0]['name'] == 'w2' and tops[0]['program'] == 'big'


def test_analysis_unavailable_counted_not_silent():
    class Raises(object):
        def memory_analysis(self):
            raise RuntimeError('backend has no analysis')

    class ReturnsNone(object):
        def memory_analysis(self):
            return None

    assert comms.record_memory('bad', Raises()) is None
    assert memviz.record_segment('p', 's', ReturnsNone(), {}, {}) \
        is None
    assert monitor.counter_value('memviz/analysis_unavailable') == 2


def test_record_memory_partial_fields_tolerated():
    class Partial(object):
        def memory_analysis(self):
            class MA(object):
                argument_size_in_bytes = 128
                # no output/temp/peak fields at all
            return MA()

    row = comms.record_memory('partial', Partial())
    assert row is not None
    assert row['argument_bytes'] == 128
    assert row['peak_bytes'] == 128     # arg + 0 + 0 live-set bound
    assert monitor.counter_value('memviz/analysis_unavailable') == 0


# ----------------------------------------------------------- live census
def test_live_census_classifies_scope_and_exec_bytes():
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    _run_steps(main_p, startup, loss, scope)
    with fluid.scope_guard(scope):
        census = memviz.live_census(scope)
    classes = census['classes']
    assert census['total_bytes'] > 0
    assert classes['param'] > 0          # fc weights are scope-resident
    # every class is accounted, nothing negative
    assert all(v >= 0 for v in classes.values())
    # the classes cover the resident total exactly (live arrays +
    # generated executable code) — the stacked counter track sums
    assert sum(classes.values()) == pytest.approx(
        census['total_bytes'])


def test_sampler_gated_by_flag_and_stride():
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    _run_steps(main_p, startup, loss, scope, warm=False)
    assert monitor.counter_value('memviz/samples') == 0
    assert monitor.gauge_value('memviz/live_bytes_total', None) is None
    fluid.set_flags({'FLAGS_memviz': True})
    _run_steps(main_p, startup, loss, scope, steps=3, warm=False)
    assert monitor.counter_value('memviz/samples') >= 3
    assert monitor.gauge_value('memviz/live_bytes_total') > 0
    for cls in ('param', 'state', 'feed', 'exec', 'other'):
        assert ('memviz/live_bytes/%s' % cls) in monitor._gauges


# -------------------------------------------------------- OOM forensics
def _inject_alloc_failure(exe, main_p, loss):
    plan = exe._get_plan(main_p, ('x',), (loss.name,))
    seg = [it for it in plan if hasattr(it, 'ops')][0]

    def boom(*a, **k):
        raise RuntimeError('RESOURCE_EXHAUSTED: Out of memory while '
                           'trying to allocate 12345678 bytes')
    for k in list(seg.compiled):
        seg.compiled[k] = boom


def test_oom_incident_note_dump_schema_and_rate_limit(tmp_path):
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    trace.enable()
    exe, feed = _run_steps(main_p, startup, loss, scope)
    _inject_alloc_failure(exe, main_p, loss)
    with fluid.scope_guard(scope):
        with pytest.raises(RuntimeError) as ei:
            exe.run(main_p, feed=feed, fetch_list=[loss])
    notes = getattr(ei.value, '__notes__', [])
    text = str(ei.value) + '\n'.join(notes)
    assert 'device memory exhausted' in text
    assert 'live HBM' in text
    assert 'largest buffers' in text     # top contributors are NAMED
    assert monitor.counter_value('memviz/oom_incidents') == 1
    assert monitor.counter_value('memviz/oom_dumps') == 1
    # the flight dump embeds the memory snapshot
    path = [ln for ln in text.splitlines() if 'flight dump' in ln]
    assert path
    dump_path = path[0].split()[-1]
    with open(dump_path) as f:
        doc = json.load(f)
    inc = doc['ptIncident']
    assert inc['kind'] == 'oom'
    assert 'census' in inc and 'classes' in inc['census']
    assert 'segments' in inc and 'top_buffers' in inc
    assert 'serving_tenants' in inc
    os.unlink(dump_path)
    # rate limit: a second failure counts but does not dump again
    with fluid.scope_guard(scope):
        with pytest.raises(RuntimeError):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    assert monitor.counter_value('memviz/oom_incidents') == 2
    assert monitor.counter_value('memviz/oom_dumps') == 1


def test_non_oom_failures_skip_the_memory_path():
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    exe, feed = _run_steps(main_p, startup, loss, scope, warm=False)
    plan = exe._get_plan(main_p, ('x',), (loss.name,))
    seg = [it for it in plan if hasattr(it, 'ops')][0]

    def boom(*a, **k):
        raise RuntimeError('some unrelated failure')
    for k in list(seg.compiled):
        seg.compiled[k] = boom
    with fluid.scope_guard(scope):
        with pytest.raises(RuntimeError):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    assert monitor.counter_value('memviz/oom_incidents') == 0


# ---------------------------------------------------- budget watermarks
def test_budget_watermark_trip_dumps_before_oom():
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    trace.enable()
    fluid.set_flags({'FLAGS_memviz': True,
                     'FLAGS_memviz_budget_bytes': 64})   # tiny budget
    _run_steps(main_p, startup, loss, scope, warm=False)
    assert monitor.counter_value('memviz/watermark_trips') >= 1
    assert monitor.counter_value('memviz/detector_dumps') == 1
    assert monitor.gauge_value('memviz/budget_utilization') > 1.0
    pressure = memviz.memory_pressure()
    assert pressure['degraded'] is True
    # /healthz carries the degradation without flipping liveness
    st = health.status()
    assert st['memory']['degraded'] is True
    assert st['alive'] is True
    assert any('watermark' in r for r in st['reasons'])


def test_spike_detector_over_ema():
    fluid.set_flags({'FLAGS_memviz_spike_factor': 2.0,
                     'FLAGS_memviz_dump_interval_s': 0.0})
    trace.enable()
    memviz._state['ema'] = 10.0
    memviz._check_watermarks(1, {'total_bytes': 100.0, 'classes': {},
                                 'arrays': 0, 'tenants': {}})
    assert monitor.counter_value('memviz/spike_trips') == 1
    # EMA moved toward the spike
    assert memviz._state['ema'] > 10.0


# ------------------------------------------------------- counter track
def test_counter_track_in_dump_and_merged_timeline(tmp_path):
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    fluid.set_flags({'FLAGS_memviz': True})
    trace.enable()
    _run_steps(main_p, startup, loss, scope, steps=3, warm=False)
    path = trace.dump(str(tmp_path / 'dump.json'))
    with open(path) as f:
        doc = json.load(f)
    cs = [e for e in doc['traceEvents'] if e.get('ph') == 'C']
    assert cs, 'counter track must ride the chrome export'
    for e in cs:
        assert e['name'] == 'memviz/live_bytes'
        assert isinstance(e['ts'], float)
        assert set(e['args']) == {'param', 'state', 'feed', 'exec',
                                  'other'}
        assert all(isinstance(v, (int, float))
                   for v in e['args'].values())
    assert doc['ptCounters']
    # the device-trace merger keeps counters on the re-homed host pid
    merged = trace.merge_device_trace(
        [e for e in doc['traceEvents']],
        [{'ph': 'X', 'pid': 0, 'tid': 0, 'ts': 1.0, 'dur': 1.0,
          'name': 'devkernel'}])
    mc = [e for e in merged if e.get('ph') == 'C']
    assert mc and all(e['pid'] != 0 for e in mc)
    # and collect_job passes them through with shifted clocks
    job = trace.collect_job(workers=[('0', str(path))],
                            fetch=lambda p: open(p).read())
    assert [e for e in job['traceEvents'] if e.get('ph') == 'C']


# -------------------------------------------- planner headroom (per-program)
def test_hbm_headroom_is_per_program_with_gauge_fallback():
    fluid.set_flags({'FLAGS_comms_hbm_budget_bytes': 1 << 20})

    class FakeCompiled(object):
        def __init__(self, arg):
            self.arg = arg

        def memory_analysis(self):
            class MA(object):
                pass
            ma = MA()
            ma.argument_size_in_bytes = self.arg
            ma.output_size_in_bytes = 0
            ma.temp_size_in_bytes = 0
            return ma

    memviz.record_segment('hungry', 'seg0',
                          FakeCompiled((1 << 20) - 1024), {}, {})
    memviz.record_segment('lean', 'seg0', FakeCompiled(1024), {}, {})
    monitor.set_gauge('executor/segment_peak_bytes', (1 << 20) - 1024)
    # outside any program scope: the legacy global-max gauge governs
    assert comms_plan.hbm_headroom_bytes() == 1024
    # inside the lean program's scope its OWN peak governs — the big
    # resident program no longer suppresses its planning
    with memviz.program_scope('lean'):
        assert comms_plan.hbm_headroom_bytes() == (1 << 20) - 1024
    with memviz.program_scope('hungry'):
        assert comms_plan.hbm_headroom_bytes() == 1024
    # a program with no attribution rows falls back to the gauge
    with memviz.program_scope('unknown'):
        assert comms_plan.hbm_headroom_bytes() == 1024
    # the digest folds the ambient headroom: two programs with
    # materially different headroom plan (and fingerprint) apart
    with memviz.program_scope('lean'):
        d_lean = comms_plan.digest()
    with memviz.program_scope('hungry'):
        d_hungry = comms_plan.digest()
    assert d_lean != d_hungry


def test_parallel_runner_files_estimated_attribution():
    """The shared-jit runners expose no memory_analysis(): they file
    an ESTIMATED row (args + outputs) so per-program headroom is live
    on the data-parallel/collective path too."""
    from paddle_tpu.fluid.compiler import CompiledProgram
    main_p, startup, loss = _build_mlp()
    scope = fluid.Scope()
    feed = {'x': np.ones((8, 16), 'float32')}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        cp = CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name)
        exe.run(cp, feed=feed, fetch_list=[loss])
    label = main_p._memviz_label
    rows = [r for r in memviz.report() if r['program'] == label]
    assert rows and rows[0].get('estimated') is True
    assert rows[0]['peak_bytes'] > 0
    assert rows[0]['classes']['param'] > 0
    # the headroom gate resolves this program's own peak now
    with memviz.program_scope(label):
        assert memviz.peak_bytes(memviz.current_program()) == \
            rows[0]['peak_bytes']


# ------------------------------------------------------- status surfaces
def test_statusz_memory_table_names_contributors():
    main_p, startup, loss = _build_mlp()
    fluid.set_flags({'FLAGS_memviz': True})
    _run_steps(main_p, startup, loss, fluid.Scope())
    sz = health.statusz()
    mem = sz['memory']
    assert mem['attribution'], 'top-K table replaces the four scalars'
    row = mem['attribution'][0]
    assert row['top_buffers'] and row['classes']
    assert mem['top_buffers']
    assert mem['live'] is not None and 'classes' in mem['live']


def test_stat_summary_memory_rollup(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(memviz, 'device_marks', lambda: {
        'peak_bytes_in_use': 3.0 * (1 << 30),
        'peak_bytes_reserved': 5.0 * (1 << 20)})
    main_p, startup, loss = _build_mlp()
    fluid.set_flags({'FLAGS_memviz': True})
    _run_steps(main_p, startup, loss, fluid.Scope())
    path = str(tmp_path / 'run.jsonl')
    monitor.dump_jsonl(path, step=1)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'stat_summary', os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            'tools', 'stat_summary.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(['--memory', path]) == 0
    out = capsys.readouterr().out
    assert 'live HBM' in out
    assert 'param' in out
    # the allocator's marks as a new executable's first run left them
    assert 'allocator marks      3.00GiB in use, 5.0MiB reserved' in out


# ------------------------------------------- temp_peak / high-water (PR 52)
def _fake_marks(monkeypatch, readings):
    """Stand in for the allocator's marks (the CPU backend reports
    none): each read takes the next of ``readings``."""
    it = iter(readings)
    monkeypatch.setattr(
        memviz, 'device_marks',
        lambda: dict(zip(memviz._MARKS, next(it))))


@pytest.mark.parametrize('path', ['aot', 'lazy'])
def test_build_tables_gives_the_row_its_temp_peak(path):
    main_p, startup, loss = _build_mlp(width=64)
    _run_steps(main_p, startup, loss, fluid.Scope(), warm=path == 'aot',
               width=64, batch=32)
    label = main_p._memviz_label
    before = [r for r in memviz.report() if r['program'] == label]
    # the AOT plane files its row at compile time, without the table;
    # a lazily jitted program has no row until the tables are built
    assert all('temp_peak' not in r for r in before)
    assert bool(before) == (path == 'aot')
    memviz.build_tables()
    rows = [r for r in memviz.report() if r['program'] == label]
    assert len(rows) == 1 and 'estimated' not in rows[0]
    r = rows[0]
    peak = r['temp_peak']
    assert set(peak) == {'bytes', 'point', 'op', 'by_class', 'by_op',
                         'buffers', 'top_buffers', 'elsewhere_bytes',
                         'carried_anew_bytes'}
    assert 0 < peak['bytes'] <= 2 * r['temp_bytes'] + 4096
    assert sum(peak['by_class'].values()) == peak['bytes']
    assert sum(peak['by_op'].values()) == peak['bytes']
    assert 0 < len(peak['top_buffers']) <= memviz.TOP_BUFFERS
    assert peak['top_buffers'][0]['bytes'] == max(
        b['bytes'] for b in peak['top_buffers'])
    assert {'shape', 'instruction', 'op', 'class'} <= set(
        peak['top_buffers'][0])
    # what the row had, it keeps: the classes still sum to the arena
    assert sum(r['classes'].values()) + r['arg_overhead_bytes'] == \
        pytest.approx(r['argument_bytes'])
    assert r['classes']['param'] > 0 and r['classes']['feed'] > 0
    assert r['alias_bytes'] <= r['output_bytes']
    # /statusz shows it
    shown = [a for a in health.statusz()['memory']['attribution']
             if a['program'] == label]
    assert shown and shown[0]['temp_peak']['point'] == peak['point']
    # built once: a second call parses nothing again and changes nothing
    memviz.build_tables()
    again = [r for r in memviz.report() if r['program'] == label]
    assert again[0]['temp_peak'] == peak


def test_the_mesh_row_loses_estimated_once_tables_are_built():
    from paddle_tpu.fluid.compiler import CompiledProgram
    main_p, startup, loss = _build_mlp(width=64)
    feed = {'x': np.ones((32, 64), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        cp = CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name)
        exe.run(cp, feed=feed, fetch_list=[loss])
        label = main_p._memviz_label
        first = [r for r in memviz.report() if r['program'] == label]
        # the first-run bound the headroom gate reads
        assert len(first) == 1 and first[0]['estimated'] is True
        assert first[0]['temp_bytes'] == 0.0
        memviz.build_tables()
    rows = [r for r in memviz.report() if r['program'] == label]
    assert len(rows) == 1 and rows[0]['segment'] == first[0]['segment']
    r = rows[0]
    assert 'estimated' not in r
    assert r['temp_bytes'] > 0 and r['generated_code_bytes'] >= 0
    assert r['temp_peak']['bytes'] > 0
    # one device's shard of the batch, as memory_analysis() counts
    import jax
    # (and the learning rate's four bytes)
    assert r['classes']['feed'] == pytest.approx(
        32 * 64 * 4 / jax.device_count(), abs=16)
    assert r['classes']['param'] > 0
    shown = [a for a in health.statusz()['memory']['attribution']
             if a['program'] == label]
    assert 'estimated' not in shown[0] and 'temp_peak' in shown[0]
    with memviz.program_scope(label):
        assert memviz.peak_bytes(label) == r['peak_bytes']


def test_the_high_water_list_is_in_run_order(monkeypatch):
    """Start-up program, the for_test clone, the step that fetches, the
    quiet step: each new executable's first run is filed once, with
    the marks before and after it, and a steady step reads nothing."""
    gb = 1e9
    _fake_marks(monkeypatch, [
        (0.0, 0.0), (1 * gb, 0.0),              # start-up
        (1 * gb, 0.0), (3 * gb, 2 * gb),        # the clone
        (3 * gb, 2 * gb), (3 * gb, 6 * gb),     # the step that fetches
        (3 * gb, 6 * gb), (3 * gb, 6 * gb)])    # the quiet step
    main_p, startup, loss = _build_mlp()
    test_p = main_p.clone(for_test=True)
    feed = {'x': np.ones((8, 16), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(test_p, feed=feed, fetch_list=[loss])
        exe.run(main_p, feed=feed, fetch_list=[loss])
        exe.run(main_p, feed=feed, fetch_list=[])
        for _ in range(3):          # steady: the marks are not read
            exe.run(main_p, feed=feed, fetch_list=[loss])
            exe.run(main_p, feed=feed, fetch_list=[])
    water = memviz.high_water()
    runs = water['first_runs']
    assert [r['order'] for r in runs] == [0, 1, 2, 3]
    assert [r['program'] for r in runs] == [
        startup._memviz_label, test_p._memviz_label,
        main_p._memviz_label, main_p._memviz_label]
    # the same ops planned for another fetch list are another executable
    assert runs[2]['segment'] != runs[3]['segment']
    assert runs[1]['before'] == runs[0]['after']
    assert runs[3]['after'] == {'peak_bytes_in_use': 3 * gb,
                                'peak_bytes_reserved': 6 * gb}
    raised = water['raised_by']
    assert (raised['peak_bytes_in_use']['program'],
            raised['peak_bytes_in_use']['order'],
            raised['peak_bytes_in_use']['bytes']) == (
                test_p._memviz_label, 1, 3 * gb)
    assert (raised['peak_bytes_reserved']['program'],
            raised['peak_bytes_reserved']['segment']) == (
                main_p._memviz_label, runs[2]['segment'])
    assert monitor.gauge_value('memviz/hwm_in_use_bytes') == 3 * gb
    assert monitor.gauge_value('memviz/hwm_reserved_bytes') == 6 * gb
    # the rows carry their place in the list, and /statusz the list
    memviz.build_tables()
    orders = {r['segment']: r['first_run']['order']
              for r in memviz.report()
              if r['program'] == main_p._memviz_label}
    assert sorted(orders.values()) == [2, 3]
    shown = health.statusz()['memory']['high_water']
    assert [r['order'] for r in shown['first_runs']] == [0, 1, 2, 3]
    assert shown['raised_by']['peak_bytes_reserved']['order'] == 2


def test_a_rise_between_first_runs_is_the_callers(monkeypatch):
    """What raises a mark between two first runs is none of the
    program's executables (a caller's own jit, a batch put on the
    device): it is filed with no program, before the run it precedes."""
    _fake_marks(monkeypatch, [(0.0, 0.0), (1.0, 0.0),
                              (4.0, 0.0), (4.0, 7.0)])
    main_p, startup, loss = _build_mlp()
    _run_steps(main_p, startup, loss, fluid.Scope(), steps=1, warm=False)
    raised = memviz.high_water()['raised_by']
    assert raised['peak_bytes_in_use']['program'] is None
    assert raised['peak_bytes_in_use']['bytes'] == 4.0
    assert raised['peak_bytes_in_use']['segment'].startswith(
        "outside the program's executables, before %s/"
        % main_p._memviz_label)
    assert raised['peak_bytes_reserved']['program'] == main_p._memviz_label


def test_the_aot_planes_new_entry_files_its_first_run(monkeypatch):
    _fake_marks(monkeypatch, [(0.0, 0.0), (1.0, 0.0), (1.0, 0.0),
                              (2.0, 5.0)] + [(9.0, 9.0)] * 8)
    main_p, startup, loss = _build_mlp()
    _run_steps(main_p, startup, loss, fluid.Scope(), steps=3)
    runs = memviz.high_water()['first_runs']
    # start-up (a lazy first run), then the warmed step's ONE new entry
    assert [r['program'] for r in runs] == [
        startup._memviz_label, main_p._memviz_label]
    rows = [r for r in memviz.report()
            if r['program'] == main_p._memviz_label]
    assert rows[0]['segment'] == runs[1]['segment']
    assert rows[0]['first_run']['after'] == {
        'peak_bytes_in_use': 2.0, 'peak_bytes_reserved': 5.0}


def test_no_marks_where_the_backend_reports_none():
    """The CPU backend's ``memory_stats()`` is None: the list still
    holds the run order, with no marks and nobody raising any."""
    main_p, startup, loss = _build_mlp()
    _run_steps(main_p, startup, loss, fluid.Scope(), warm=False)
    water = memviz.high_water()
    assert len(water['first_runs']) == 2
    assert all(r['before'] is None and r['after'] is None
               for r in water['first_runs'])
    assert water['raised_by'] == {}
    assert monitor.gauge_value('memviz/hwm_in_use_bytes', None) is None
