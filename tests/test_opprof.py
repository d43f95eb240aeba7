"""Op-level cost attribution plane (fluid.opprof): stable instance
scope naming, capture attribution that sums honestly (remainder under
unattributed/, fused-kernel time split across constituents, malformed
rows counted not eaten), eager-replay parity with the step report's
dispatch wall, deterministic worklist ranking with pallas coverage
cross-references, the JSON-able /statusz op_costs section, and zero
fingerprint drift when the flag flips mid-run."""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (health, monitor, opprof, profiler,
                              trace)

OPPROF_FLAGS = ('FLAGS_opprof', 'FLAGS_opprof_snapshot_steps')


@pytest.fixture(autouse=True)
def _clean():
    from paddle_tpu.fluid import compile_cache
    prev = fluid.get_flags(list(OPPROF_FLAGS))
    compile_cache.reset_plane()
    monitor.reset()
    opprof.reset()
    trace.disable()
    trace.reset()
    yield
    fluid.set_flags(prev)
    compile_cache.reset_plane()
    monitor.reset()
    opprof.reset()
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
    return main_p, startup, loss


# ------------------------------------------------- instance provenance
def test_instance_scopes_unique_and_stable():
    main_p, _startup, _loss = _build_mlp()
    ops = list(main_p.global_block().ops)
    names = [opprof.op_scope(op) for op in ops]
    assert len(set(names)) == len(names), 'instance names must be ' \
        'unique within a block'
    for op, name in zip(ops, names):
        typ, idx = opprof.split_instance(name)
        assert typ == op.type and idx is not None
    # a retrace walks the SAME block again — and a cleared memo (fresh
    # process, new trace) must rebuild the identical names, because
    # the suffix is the op's position in its block, not visit order
    again = [opprof.op_scope(op) for op in ops]
    assert again == names
    opprof.reset()
    assert [opprof.op_scope(op) for op in ops] == names


def test_want_snapshot_gate():
    fluid.set_flags({'FLAGS_opprof': False})
    assert not any(opprof.want_snapshot(s) for s in range(50))
    fluid.set_flags({'FLAGS_opprof': True,
                     'FLAGS_opprof_snapshot_steps': 8})
    hits = [s for s in range(33) if opprof.want_snapshot(s)]
    assert hits == [0, 8, 16, 24, 32]
    # a zero cadence clamps to every step instead of dividing by zero
    fluid.set_flags({'FLAGS_opprof_snapshot_steps': 0})
    assert all(opprof.want_snapshot(s) for s in range(3))


# ------------------------------------------------ capture attribution
def test_capture_sums_with_honest_unattributed_remainder():
    events = [
        {'ph': 'X', 'name': 'fusion.1', 'dur': 100,
         'args': {'tf_op': 'jit_seg/relu#2'}},
        {'ph': 'X', 'name': 'copy.3', 'dur': 50,
         'args': {'tf_op': 'jit_seg/grad_glue'}},
        {'ph': 'C', 'name': 'counter', 'args': {}},       # filtered
        {'ph': 'X', 'name': 'nometa.0', 'dur': 7,
         'args': {'tf_op': None}},                        # dropped
        'not even a dict',                                # dropped
    ]
    res = opprof.record_capture(events, program='cap', steps=2)
    assert res['dropped'] == 2
    rep = opprof.report()
    # attributed + unattributed reconstruct the capture total (the
    # X-event dur sum / steps) — nothing silently vanishes
    attributed = sum(c['ms_per_step'] for c in rep['top'])
    assert attributed == pytest.approx(100e-3 / 2)
    assert rep['unattributed_ms'] == pytest.approx(50e-3 / 2)
    assert attributed + rep['unattributed_ms'] <= \
        (100 + 50) * 1e-3 / 2 + 1e-9
    assert rep['top'][0]['instance'] == 'relu#2'
    assert monitor.counter_value('opprof/capture_events') == 4.0
    assert monitor.counter_value('opprof/dropped_events') == 2.0
    assert monitor.gauge_value('opprof/attributed_ms_total') == \
        pytest.approx(attributed)


def test_fused_kernel_time_splits_across_constituents():
    # one fusion event carrying three source paths: two resolve to
    # instances, the third's share lands in unattributed — equal split
    events = [{'ph': 'X', 'name': 'fusion.9', 'dur': 90,
               'args': {'tf_op': 'jit_s/relu#1;jit_s/tanh#4;'
                                 'jit_s/opaque_glue'}}]
    recs, stats = profiler.attribute_trace_events(
        events, per_instance=True, with_stats=True)
    assert stats == {'events': 1, 'attributed': 1, 'dropped': 0}
    assert recs['relu#1'][1] == pytest.approx(30e-6)
    assert recs['tanh#4'][1] == pytest.approx(30e-6)
    assert recs['unattributed/fusion'][1] == pytest.approx(30e-6)
    # transform wrappers strip; without per_instance the '#' names
    # stay unresolved (type-only mode is the legacy profiler table)
    recs2 = profiler.attribute_trace_events(
        [{'ph': 'X', 'name': 'k', 'dur': 5,
          'args': {'tf_op': 'jit_s/transpose(jvp(relu))/max'}}])
    assert recs2['relu'][1] == pytest.approx(5e-6)


def test_negative_lookup_cache_and_dropped_accounting():
    # a capture repeats each unattributable scope every step: the
    # negative cache folds the repeats without re-splitting, and the
    # stats count malformed rows instead of eating them
    events = [{'ph': 'X', 'name': 'copy.1', 'dur': 2,
               'args': {'tf_op': 'jit_s/not_an_op/really_not'}}] * 500
    events += [{'ph': 'X', 'name': 'bad', 'dur': 1, 'args': {}},
               {'ph': 'X', 'name': 'bad2', 'dur': 1,
                'args': {'tf_op': 123}}]
    recs, stats = profiler.attribute_trace_events(
        events, per_instance=True, with_stats=True)
    assert recs['unattributed/copy'][0] == 500
    assert stats['events'] == 502 and stats['dropped'] == 2
    assert stats['attributed'] == 0


# ------------------------------------------------------- eager replay
@pytest.mark.filterwarnings('ignore::UserWarning')
def test_replay_parity_with_step_report_on_lenet():
    from paddle_tpu import models
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        _feeds, _pred, loss, _acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(16, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (16, 1)).astype('int64')}
    fluid.set_flags({'FLAGS_opprof': True,
                     'FLAGS_opprof_snapshot_steps': 1})
    trace.enable()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.warmup(main_p,
                   feed_shapes={'img': ((16, 1, 28, 28), 'float32'),
                                'label': ((16, 1), 'int64')},
                   fetch_list=[loss], wait=True)
        for _ in range(2):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        assert monitor.counter_value('opprof/snapshots') >= 1
        done = opprof.replay_all()
    assert done and all(isinstance(v, int) for v in done.values()), \
        'replay must walk every stashed segment: %r' % done
    rep = opprof.report()
    replay_segs = [s for s in rep['segments']
                   if s['source'] == 'replay']
    assert replay_segs
    for seg in replay_segs:
        # normalization contract: instance costs sum to the measured
        # synchronous wall of the snapshot step, exactly
        assert seg['measured_ms'] is not None
        assert seg['attributed_ms'] == pytest.approx(
            seg['measured_ms'], rel=1e-3)
    # ...and that measured wall is the SAME number the step report's
    # dispatch phase carries for the snapshot step (the sync is parked
    # inside the dispatch span) — 10% band for clock-read skew
    sr = trace.step_report()
    last = sr['steps'][-1]
    disp_ms = last['phases_ms'].get('dispatch', 0.0)
    total_measured = sum(s['measured_ms'] for s in replay_segs)
    assert disp_ms > 0
    assert total_measured == pytest.approx(disp_ms, rel=0.10)
    # the replay measured real work: bytes and layers resolve
    top = rep['top']
    assert any(c['bytes_per_step'] > 0 for c in top)
    assert any(c.get('layer') for c in top)
    assert monitor.counter_value('opprof/replays') >= 1


# ---------------------------------------------------------- worklist
def _adam_run_capture():
    events = [
        {'ph': 'X', 'name': 'f.0', 'dur': 40,
         'args': {'tf_op': 'jit_s/adam#5'}},
        {'ph': 'X', 'name': 'f.1', 'dur': 35,
         'args': {'tf_op': 'jit_s/adam#6'}},
        {'ph': 'X', 'name': 'f.2', 'dur': 30,
         'args': {'tf_op': 'jit_s/adam#7'}},
        {'ph': 'X', 'name': 'f.3', 'dur': 20,
         'args': {'tf_op': 'jit_s/softmax#0'}},
        # same type but NOT block-contiguous: its own run
        {'ph': 'X', 'name': 'f.4', 'dur': 10,
         'args': {'tf_op': 'jit_s/adam#9'}},
    ]
    opprof.record_capture(events, program='cap', steps=1)


def test_worklist_ranks_contiguous_runs_deterministically(tmp_path):
    _adam_run_capture()
    wl1 = opprof.kernel_worklist()
    wl2 = opprof.kernel_worklist()
    assert wl1 == wl2, 'ranking must be deterministic'
    assert [r['rank'] for r in wl1] == list(range(1, len(wl1) + 1))
    top = wl1[0]
    # the three contiguous adam instances coalesce into ONE run ranked
    # by summed cost; adam#9 stays a separate (non-contiguous) run
    assert top['op_type'] == 'adam'
    assert top['ops'] == ['adam#5', 'adam#6', 'adam#7']
    assert top['span'] == [5, 7]
    assert top['ms_per_step'] == pytest.approx((40 + 35 + 30) * 1e-3)
    assert ['adam#9'] in [r['ops'] for r in wl1]
    # coverage cross-reference: no kernel serves adam runs (XLA's own
    # per-parameter fusions run at the memory system's pace); the
    # registry does declare one for the softmax
    assert top['covered_by'] is None
    assert {r['op_type']: r['covered_by'] for r in wl1}[
        'softmax'] == 'flash_attention'
    assert monitor.gauge_value('opprof/worklist_candidates') == \
        float(len(wl1))
    # the artifact round-trips as schema-stable JSON
    path = str(tmp_path / 'op_worklist.json')
    assert opprof.write_worklist(path) == path
    with open(path) as f:
        doc = json.load(f)
    assert doc['version'] == 1 and doc['generated_by'] == 'fluid.opprof'
    assert doc['candidates'][0]['ops'] == ['adam#5', 'adam#6', 'adam#7']
    assert set(doc) >= {'candidates', 'by_type', 'by_layer',
                        'segments'}


# ------------------------------------------------------ statusz / json
def test_report_and_statusz_json_able():
    _adam_run_capture()
    fluid.set_flags({'FLAGS_opprof': True})
    rep = opprof.report()
    json.dumps(rep)   # must never raise
    assert rep['enabled'] and rep['top']
    assert rep['by_type']['adam']['ms_per_step'] > 0
    sz = health.statusz()
    assert sz.get('op_costs'), '/statusz must carry the op_costs ' \
        'section once the registry has rows'
    json.dumps(sz['op_costs'])
    assert sz['op_costs']['top'][0]['instance'] == 'adam#5'


# ---------------------------------------------- fingerprint neutrality
def test_zero_fingerprint_drift_under_flag_flips():
    main_p, startup, loss = _build_mlp()
    feed = {'x': np.ones((8, 16), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(2):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        misses = monitor.counter_value('segment_cache_miss')
        # flipping the flag mid-run keys NO cache: zero new compiles
        fluid.set_flags({'FLAGS_opprof': True,
                         'FLAGS_opprof_snapshot_steps': 1})
        for _ in range(2):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        assert monitor.counter_value('opprof/snapshots') >= 1
        fluid.set_flags({'FLAGS_opprof': False})
        for _ in range(2):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        assert monitor.counter_value('segment_cache_miss') == misses
