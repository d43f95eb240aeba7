"""The seam the three runners share: `Executor._step_scope` (what a step
boundary is) and `Executor._dispatch_segment` (what dispatching one
compiled segment is), driven through each of the four entry points:
one chip, `with_data_parallel` on the 8-device CPU mesh, a
`_collective_dp` program under shard_map, and `CompiledPipeline`.

The first two cases hold the seam itself; every case after them holds
one difference between the runners that was an accident before the
seam existed and is closed by it (CHANGES.md, PR 28).
"""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import faultinject, layers, monitor, trace
from paddle_tpu.fluid import supervisor
from paddle_tpu.fluid.executor import _Segment
from paddle_tpu.fluid.transpiler.collective import GradAllReduce

ENTRIES = ['one_chip', 'data_parallel', 'collective', 'pipeline']
GOOD = np.ones((8, 16), 'float32')


class _Entry(object):
    """One way into the executor: `run(feed)` is one step and returns
    the loss; `program` is the fluid Program it steps."""

    def __init__(self, kind):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[16], dtype='float32')
            # log(0) is the NaN the sweep must catch; ones are clean
            h = layers.fc(layers.log(x), 16, act='relu')
            if kind == 'pipeline':
                mid = main.current_block().create_var(
                    name='seam_mid', shape=[-1, 16], dtype='float32')
                layers.py_func(lambda a: a, h, mid)   # cuts 2 segments
                h = mid
            loss = layers.reduce_mean(layers.fc(h, 8))
            fluid.optimizer.SGD(0.05).minimize(loss)
        if kind == 'collective':
            GradAllReduce().transpile(startup, main, 0, ['127.0.0.1:0'],
                                      '127.0.0.1:0')
        self.kind = kind
        self.program = main
        self.loss = loss
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(self.scope):
            self.exe.run(startup)
        self._target = main
        if kind == 'data_parallel':
            self._target = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        elif kind == 'compiled_plain':
            self._target = fluid.CompiledProgram(main)
        elif kind == 'pipeline':
            self._target = self.exe.compile(
                main, feed_names=['x'], fetch_names=[loss],
                allow_host=True)

    def run(self, x=GOOD):
        if self.kind == 'pipeline':
            out, = self._target({'x': x}, scope=self.scope)
        else:
            out, = self.exe.run(self._target, feed={'x': x},
                                fetch_list=[self.loss], scope=self.scope)
        return out

    def segments(self):
        caches = [self.program._exec_cache]
        if self.kind == 'data_parallel':
            caches.append(self._target._exec_cache)
        if self.kind == 'pipeline':
            return [it for it in self._target._plan
                    if isinstance(it, _Segment)]
        return [it for c in caches for plan in c.values()
                for it in plan if isinstance(it, _Segment)]

    def params(self):
        return {p.name: np.array(self.scope.find_var(p.name))
                for p in self.program.all_parameters()}


@pytest.fixture
def clean_planes():
    yield
    faultinject.reset()
    trace.disable()
    trace.reset()
    fluid.set_flags({'FLAGS_check_nan_inf': False,
                     'FLAGS_health_summaries': False})


# what one steady step records, in order, per entry point
PHASES = {
    'one_chip': ['feed_h2d', 'bind', 'dispatch', 'state_release',
                 'fetch_d2h'],
    'data_parallel': ['bind', 'place_state', 'place_data', 'dispatch',
                      'state_release', 'fetch_d2h'],
    'collective': ['bind', 'dispatch', 'state_release', 'fetch_d2h'],
    'pipeline': ['feed_h2d', 'bind', 'dispatch', 'state_release',
                 'host_op', 'bind', 'dispatch', 'state_release',
                 'fetch_d2h'],
}


@pytest.mark.parametrize('kind', ENTRIES)
def test_one_run_is_one_step(kind, clean_planes):
    e = _Entry(kind)
    e.run()                      # compile outside the step looked at
    trace.enable(buffer_steps=4)
    trace.reset()        # whatever an earlier test of this worker left
    step0 = e.exe._step
    calls0 = monitor.counter_value('executor/run_calls')
    ts0 = monitor.gauge_value('executor/last_step_unix_ts')
    e.run()
    assert e.exe._step == step0 + 1
    assert monitor.counter_value('executor/run_calls') == calls0 + 1
    assert monitor.gauge_value('executor/last_step_unix_ts') >= ts0
    records = trace.steps()
    assert [r['step'] for r in records] == [step0 + 1]
    top = sorted((s for s in records[0]['spans'] if s[4] == 1),
                 key=lambda s: s[1])
    assert [s[0] for s in top] == PHASES[kind]


@pytest.mark.parametrize('kind', ENTRIES)
def test_failed_dispatch_one_dump_scope_unchanged(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    before = e.params()

    def boom(*a, **k):
        raise RuntimeError('executable refused')
    for seg in e.segments():
        for key in list(seg.compiled.keys()):
            seg.compiled[key] = boom
    trace.enable(buffer_steps=4)
    trace.reset()        # whatever an earlier test of this worker left
    dumps0 = monitor.counter_value('trace/dumps_written')
    calls0 = monitor.counter_value('executor/run_calls')
    with pytest.raises(RuntimeError, match='executable refused') as ei:
        e.run()
    assert monitor.counter_value('trace/dumps_written') == dumps0 + 1
    named = [n for n in getattr(ei.value, '__notes__', [])
             if 'dumped to ' in n]
    assert len(named) == 1
    assert os.path.exists(named[0].rsplit('dumped to ', 1)[1].strip())
    # a failed step is not a completed one, and it published nothing
    assert monitor.counter_value('executor/run_calls') == calls0
    after = e.params()
    assert sorted(after) == sorted(before)
    for n in before:
        np.testing.assert_array_equal(after[n], before[n])


# ------------------------------------------------------ drift, closed
@pytest.mark.parametrize('kind', ENTRIES)
def test_nan_sweep_runs_in_every_runner(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    trips0 = monitor.counter_value('health/nan_trips')
    with pytest.raises(FloatingPointError, match='nan/inf detected'):
        e.run(np.zeros((8, 16), 'float32'))
    assert monitor.counter_value('health/nan_trips') == trips0 + 1


@pytest.mark.parametrize('kind', ENTRIES)
def test_health_summaries_run_in_every_runner(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    fluid.set_flags({'FLAGS_health_summaries': True})
    n0 = monitor.counter_value('health/summary_steps')
    e.run()
    assert monitor.counter_value('health/summary_steps') > n0


@pytest.mark.parametrize('kind', ENTRIES)
def test_post_step_runs_in_every_runner(kind, clean_planes, monkeypatch):
    e = _Entry(kind)
    e.run()
    params = [p.name for p in e.program.all_parameters()]
    e.program._local_sgd = {'period': 1, 'params': params}
    synced = []
    monkeypatch.setattr(e.exe, '_local_sgd_sync',
                        lambda scope, names: synced.append(list(names)))
    e.run()
    assert synced == [params]


@pytest.mark.parametrize('kind', ENTRIES)
def test_step_chaos_site_fires_in_every_runner(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    faultinject.configure('executor.step:raise@1')
    with pytest.raises(faultinject.FaultInjected):
        e.run()
    assert faultinject.fired('executor.step') == 1


@pytest.mark.parametrize('kind', ENTRIES)
def test_dispatch_chaos_site_needs_no_watchdog(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    site = 'executor.dispatch' if kind in ('one_chip', 'pipeline') \
        else 'collective.dispatch'
    faultinject.configure(site + ':raise@1')
    with pytest.raises(faultinject.FaultInjected):
        e.run()
    assert faultinject.fired(site) == 1


@pytest.mark.parametrize('kind', ENTRIES + ['compiled_plain'])
def test_supervisor_sees_each_step_once(kind, clean_planes, monkeypatch):
    e = _Entry(kind)
    e.run()
    seen = []
    monkeypatch.setattr(supervisor, 'active', lambda: True)
    monkeypatch.setattr(supervisor, 'on_step_begin',
                        lambda exe: seen.append(('begin', exe._step)))
    monkeypatch.setattr(supervisor, 'on_step_end',
                        lambda exe: seen.append(('end', exe._step)))
    step0 = e.exe._step
    e.run()
    assert seen == [('begin', step0), ('end', step0 + 1)]


@pytest.mark.parametrize('kind', ENTRIES)
def test_feed_mismatch_note_in_every_runner(kind, clean_planes):
    e = _Entry(kind)
    e.run()
    with pytest.raises(Exception) as ei:
        e.run(np.ones((8, 24), 'float32'))
    notes = '\n'.join(getattr(ei.value, '__notes__', []))
    assert "feed 'x': shape (8, 24), declared (-1, 16)" in notes


def test_host_op_reads_the_feed_under_data_parallel(clean_planes):
    """Host ops read their inputs through the scope, so the plan walk
    makes the feeds visible there for every runner."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[16], dtype='float32')
        seen = main.current_block().create_var(
            name='seam_seen', shape=[-1, 16], dtype='float32')
        layers.py_func(lambda a: a, x, seen)
        loss = layers.reduce_mean(layers.fc(seen, 8))
        fluid.optimizer.SGD(0.05).minimize(loss)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        n0 = monitor.counter_value('executor/host_ops_run')
        out, = exe.run(compiled, feed={'x': GOOD}, fetch_list=[loss])
        assert np.isfinite(out).all()
        assert monitor.counter_value('executor/host_ops_run') == n0 + 1
