"""Job-wide observability: fluid.comms collective telemetry, the
cross-worker trace collection (trace.collect_job + epoch anchors),
straggler/skew detection, per-segment XLA memory accounting, and the
comms cost model.

The two-subprocess test at the bottom is the acceptance path: a REAL
two-worker job (each a GradAllReduce program with a live status plane)
must collect into ONE schema-valid merged timeline with both ranks'
spans on a shared clock."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import comms, layers, monitor, trace
from paddle_tpu.fluid import health
from paddle_tpu.fluid.transpiler.collective import GradAllReduce


@pytest.fixture(autouse=True)
def _clean_registries():
    monitor.reset()
    comms.reset()
    trace.reset()
    trace.disable()
    yield
    monitor.reset()
    comms.reset()
    trace.reset()
    trace.disable()


# ------------------------------------------------------------ unit: comms
def test_wire_bytes_formulas():
    # ring allreduce moves 2(n-1)/n, allgather receives n-1 shards,
    # reduce-scatter (n-1)/n; n=1 moves nothing
    assert comms.wire_bytes('allreduce', 800, 8) == \
        pytest.approx(2 * 7 / 8 * 800)
    assert comms.wire_bytes('allgather', 800, 8) == \
        pytest.approx(7 * 800)
    assert comms.wire_bytes('reducescatter', 800, 8) == \
        pytest.approx(7 / 8 * 800)
    assert comms.wire_bytes('allreduce', 800, 1) == 0.0


def test_size_bucket_labels():
    assert comms.size_bucket(1024) == 'le4KiB'
    assert comms.size_bucket(5 << 10) == 'le64KiB'
    assert comms.size_bucket(2 << 20) == 'le16MiB'
    assert comms.size_bucket(1 << 30) == 'gt256MiB'


def test_record_trace_collecting_registry():
    # no ambient context: record_trace is a no-op
    assert comms.record_trace('allreduce', 100, participants=4) is None
    with comms.collecting('fp1'):
        rec = comms.record_trace('allreduce', 100, dtype='float32',
                                 axis='dp', participants=4)
        assert rec['wire_bytes'] == pytest.approx(2 * 3 / 4 * 100)
    recs = comms.records_for('fp1')
    assert len(recs) == 1 and recs[0]['axis'] == 'dp'
    # a re-entered context whose call skipped tracing (executable
    # reused) must not blank the registered profile
    with comms.collecting('fp1'):
        pass
    assert len(comms.records_for('fp1')) == 1
    assert comms.records_for(None) == ()


def test_account_dispatch_points_and_histograms():
    with comms.collecting('fp2'):
        comms.record_trace('allreduce', 1 << 20, dtype='float32',
                           axis='dp', participants=8)
    recs = comms.records_for('fp2')
    # compile run: bytes count, no bandwidth sample
    comms.account_dispatch(recs, 0.5, compile_run=True)
    assert monitor.counter_value('comms/bytes_on_wire') > 0
    assert comms.bw_samples() == {}
    # steady run: bandwidth histogram + raw samples
    comms.account_dispatch(recs, 0.01)
    key = 'comms/bw_gbps/allreduce/le1MiB'
    hist = monitor.histogram_value(key)
    assert hist and hist['count'] == 1
    samples = comms.bw_samples()[('allreduce', 'le1MiB')]
    expect = comms.wire_bytes('allreduce', 1 << 20, 8) / 0.01 / 1e9
    assert samples[0] == pytest.approx(expect)
    assert monitor.counter_value('comms/collective_calls') == 2.0


def test_summarize_for_span_annotation():
    with comms.collecting('fp3'):
        comms.record_trace('allreduce', 100, axis='dp', participants=8)
        comms.record_trace('allgather', 50, axis='sp', participants=2)
    s = comms.summarize(comms.records_for('fp3'))
    assert s['collectives'] == 'allgather:1 allreduce:1'
    assert s['axes'] == 'dp,sp'
    assert s['participants'] == 8
    assert s['payload_bytes'] == 150.0


def test_cost_model_fit_and_predict():
    alpha, beta = 2e-4, 1e-9   # 200us latency, 1 GB/s
    rng = np.random.RandomState(0)
    pts = [(b, (alpha + beta * b) * rng.uniform(0.95, 1.05))
           for b in (1e4, 1e5, 1e6, 1e7, 1e8)]
    a, bta = comms.fit_linear(pts)
    entry = {'latency_s': a, 'inv_bw_s_per_byte': bta}
    for b, t in pts:
        pred = comms.model_predict(entry, b)
        assert max(pred / t, t / pred) < 2.0
    assert a == pytest.approx(alpha, rel=0.5)
    assert bta == pytest.approx(beta, rel=0.5)
    # degenerate inputs stay finite
    a, bta = comms.fit_linear([])
    assert bta > 0
    a, bta = comms.fit_linear([(1e6, 0.001)])
    assert bta > 0 and a == 0.0


@pytest.mark.parametrize('points,want', [
    ([], (0.0, 1e-12)),
    ([(1e6, 1e-3)], (0.0, 1e-9)),       # one point: through the origin
    ([(1e6, 0.0), (1e6, -1.0)], (0.0, 1e-12)),   # no positive wall
    ([(float(b), 2e-4 + 2e-9 * b)
      for b in (1 << 20, 4 << 20, 16 << 20)], (2e-4, 2e-9)),
], ids=['empty', 'one_point', 'no_positive_wall', 'exact_line'])
def test_fit_linear_cases(points, want):
    """What tools/comms_calibrate.py's sweeps rest on: degenerate
    inputs give a finite model, an exact line gives itself."""
    a, b = comms.fit_linear(points)
    assert a == pytest.approx(want[0], rel=1e-6)
    assert b == pytest.approx(want[1], rel=1e-6)


# --------------------------------------------- real collective telemetry
def _allreduce_program(width=16):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 3
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[width], dtype='float32')
        h = layers.fc(x, width, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    return main_p, startup, loss


def test_collective_runner_records_comms():
    import jax
    ndev = len(jax.devices())
    main_p, startup, loss = _allreduce_program()
    exe = fluid.Executor(fluid.XLAPlace(0))
    feed = {'x': np.ones((8, 16), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        trace.enable()
        for _ in range(3):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    assert monitor.counter_value('comms/bytes_on_wire') > 0
    assert monitor.counter_value('comms/allreduce_calls') > 0
    # the traced records carry dtype/axis/participants
    seen = [r for recs in comms._BY_KEY.values() for r in recs]
    assert seen and all(r['participants'] == ndev for r in seen)
    assert all(r['axis'] == 'dp' for r in seen)
    # steady dispatches observed achieved bandwidth
    hists = [n for n in monitor._hists
             if n.startswith('comms/bw_gbps/allreduce/')]
    assert hists
    # the dispatch span is annotated with the collective profile
    annotated = [s for rec in trace.steps() for s in rec['spans']
                 if s[0] == 'dispatch' and s[5]
                 and 'wire_bytes' in s[5]]
    assert annotated
    args = annotated[-1][5]
    assert args['participants'] == ndev and args['axes'] == 'dp'


_PLAN_ARMS = {
    'dense_flat': {'FLAGS_comms_plan': False,
                   'FLAGS_comms_quantize': False},
    'fused_dense': {'FLAGS_comms_plan': True,
                    'FLAGS_comms_quantize': False},
    'quant': {'FLAGS_comms_plan': True, 'FLAGS_comms_quantize': True,
              'FLAGS_comms_quantize_min_bytes': 4096},
}


@pytest.mark.parametrize('width', [64, 96])
@pytest.mark.parametrize('arm', sorted(_PLAN_ARMS))
def test_bytes_on_wire_per_step_is_the_arms_formula(arm, width):
    """What the planner A/B's record was worth, as counts: every step
    of a GradAllReduce program, the first included, puts on the wire
    exactly the ring formula over its gradients' bytes: 2(n-1)/n of
    them dense, whether reduced one by one or fused into a bucket,
    and the int8 payload plus its block scales where the bucket is
    quantized."""
    import jax
    from paddle_tpu.fluid import comms_plan
    ndev = len(jax.devices())
    flags = dict(_PLAN_ARMS[arm], FLAGS_comms_model_path=os.devnull)
    prev = fluid.get_flags(sorted(set(flags) |
                                  {'FLAGS_comms_quantize_min_bytes'}))
    fluid.set_flags(flags)
    comms_plan.reset()
    try:
        main_p, startup, loss = _allreduce_program(width)
        payload = sum(4 * int(np.prod(p.shape))
                      for p in main_p.all_parameters())
        exe = fluid.Executor(fluid.XLAPlace(0))
        feed = {'x': np.ones((16, width), 'float32')}
        per_step = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(4):
                w0 = monitor.counter_value('comms/bytes_on_wire')
                exe.run(main_p, feed=feed, fetch_list=[loss])
                per_step.append(
                    monitor.counter_value('comms/bytes_on_wire') - w0)
    finally:
        fluid.set_flags(prev)
        comms_plan.reset()
    want = comms_plan.quant_wire_bytes(payload, 4, ndev) \
        if arm == 'quant' else comms.wire_bytes('allreduce', payload,
                                                ndev)
    assert per_step == [want] * 4
    assert monitor.counter_value('comms/plan_arm/' + (
        'quant' if arm == 'quant' else 'dense')) == \
        (0 if arm == 'dense_flat' else 4)


def test_ring_attention_op_records_ppermute():
    import jax
    from paddle_tpu.parallel import mesh as pmesh
    from paddle_tpu.ops.parallel_ops import ring_attention_op
    if len(jax.devices()) < 2:
        pytest.skip('needs a multi-device mesh')
    ndev = len(jax.devices())
    mesh = pmesh.create_mesh(dp=ndev // 2, sp=2)
    rng = np.random.RandomState(0)
    q = rng.rand(1, 8, 2, 4).astype('float32')
    with pmesh.use_trace_mesh(mesh):
        with comms.collecting('ring_fp'):
            out = ring_attention_op(None, {'Q': [q], 'K': [q],
                                           'V': [q]}, {'axis': 'sp'})
    assert out['Out'][0].shape == q.shape
    recs = comms.records_for('ring_fp')
    assert len(recs) == 1 and recs[0]['kind'] == 'ppermute'
    assert recs[0]['participants'] == 2
    # one rotation (sp-1) of both K and V block shards
    hop = 2 * q.nbytes / 2
    assert recs[0]['wire_bytes'] == pytest.approx(hop)


# -------------------------------------------------------- skew detection
def _rollup(count, p50, p99, phases):
    return {'count': count, 'wall_p50_ms': p50, 'wall_p99_ms': p99,
            'wall_max_ms': p99, 'phases_ms': phases}


def test_job_skew_report_math():
    rep = trace.job_skew_report({
        '0': _rollup(10, 10.0, 12.0, {'dispatch': 80.0, 'bind': 10.0}),
        '1': _rollup(10, 30.0, 60.0, {'dispatch': 280.0, 'bind': 9.0}),
        '2': _rollup(10, 10.0, 11.0, {'dispatch': 82.0, 'bind': 11.0}),
    })
    assert rep['wall']['slowest_rank'] == '1'
    assert rep['wall']['skew_ratio'] == pytest.approx(3.0)
    assert rep['ranks']['1']['p99_over_p50'] == pytest.approx(2.0)
    ph = rep['phases']['dispatch']
    assert ph['slowest_rank'] == '1'
    assert ph['max_ms'] == pytest.approx(28.0)   # per step
    # reference is the median of the OTHER ranks' per-step phase time
    assert ph['ratio'] == pytest.approx(28.0 / 8.1)
    # empty / step-less rollups degrade to None
    assert trace.job_skew_report({}) is None
    assert trace.job_skew_report({'0': _rollup(0, 0, 0, {})}) is None
    # a zero reference with a nonzero straggler is UNBOUNDED skew (a
    # finite sentinel that trips any factor and stays JSON-safe), not
    # a masked 1.0 — e.g. a phase only the straggler runs
    rep = trace.job_skew_report({
        '0': _rollup(10, 10.0, 12.0, {'reader_wait': 50.0}),
        '1': _rollup(10, 0.0, 0.0, {}),
    })
    assert rep['wall']['skew_ratio'] == trace._SKEW_UNBOUNDED
    assert rep['phases']['reader_wait']['ratio'] == \
        trace._SKEW_UNBOUNDED
    json.dumps(rep)


def test_straggler_detector_autodump(tmp_path):
    fluid.set_flags({'FLAGS_straggler_factor': 2.0})
    try:
        agg = health._Aggregator('0', [('0', 'local')], 1000.0)
        agg.stop()
        trace.enable()
        with trace.step_span(1):
            pass
        # inject a straggling peer rollup and run one detector pass
        agg._peers['1'] = {
            'endpoint': 'x', 'up': True, 'ready': True, 'state': None,
            'status': None, 'error': None, 'ts': time.time(),
            'rollup': _rollup(5, 3000.0, 3600.0,
                              {'dispatch': 12000.0})}
        agg.workers = [('1', 'x')]
        rep = agg.check_skew()
        assert rep is not None and rep['wall']['slowest_rank'] == '1'
        assert monitor.gauge_value('comms/skew_ratio') >= 2.0
        assert monitor.counter_value('comms/straggler_trips') == 1.0
        assert monitor.counter_value('health/detector_dumps') == 1.0
        # rate limit: an immediate second trip must not dump again
        agg.check_skew()
        assert monitor.counter_value('comms/straggler_trips') == 2.0
        assert monitor.counter_value('health/detector_dumps') == 1.0
    finally:
        fluid.set_flags({'FLAGS_straggler_factor': 2.0})


# ------------------------------------------------------ memory accounting
def test_memory_gauges_from_real_executable():
    import jax
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((64, 64), 'float32')).compile()
    row = comms.record_memory('test_seg', compiled)
    assert row is not None and row['argument_bytes'] > 0
    assert monitor.gauge_value('executor/segment_argument_bytes') > 0
    assert monitor.gauge_value('executor/segment_peak_bytes') >= \
        row['argument_bytes']
    rows = comms.memory_report()
    assert rows and rows[0]['segment'] == 'test_seg'
    # a backend without the analysis degrades to None, no gauges harmed
    class NoMa:
        def memory_analysis(self):
            raise NotImplementedError
    assert comms.record_memory('bad', NoMa()) is None


def test_executor_populates_memory_and_statusz_section(tmp_path):
    # the AOT compile plane is where memory_analysis runs: point it at
    # a scratch dir (the plane is off by default in the test env)
    prev = fluid.flags.get_flag('FLAGS_compile_cache_dir')
    fluid.set_flags({'FLAGS_compile_cache_dir': str(tmp_path)})
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        loss = layers.reduce_mean(layers.fc(x, 8))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.XLAPlace(0))
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main_p, feed={'x': np.ones((4, 8), 'float32')},
                    fetch_list=[loss])
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': prev})
    doc = health.statusz()
    mem = doc['memory']
    assert mem is not None and mem['segments']
    assert mem['segment_peak_bytes'] > 0
    json.dumps(doc, default=str)   # /statusz stays JSON-able


# ------------------------------------------------- collect_job (in-proc)
def _fake_dump(shift_us=0.0, rank='0'):
    trace.reset()
    trace.enable()
    for step in range(3):
        with trace.step_span(step):
            with trace.span('dispatch'):
                time.sleep(0.001)
    payload = json.loads(json.dumps(trace.dump_payload()))
    payload['ptRank'] = rank
    if shift_us:
        payload['ptClock']['export_us'] -= shift_us
        for e in payload['traceEvents']:
            if isinstance(e.get('ts'), (int, float)):
                e['ts'] -= shift_us
    trace.disable()
    trace.reset()
    return payload


def test_dump_carries_epoch_anchor():
    payload = _fake_dump()
    clock = payload['ptClock']
    assert abs(clock['unix_us'] - time.time() * 1e6) < 60e6
    assert abs(clock['unix_us'] - clock['export_us']) < 60e6
    assert payload['ptRank'] == '0'


def test_collect_job_rehomes_clocks_and_tracks():
    d0 = _fake_dump(rank='0')
    d1 = _fake_dump(shift_us=7e6, rank='1')   # 7s of NTP drift
    payloads = {'h0:1': json.dumps(d0), 'h1:2': json.dumps(d1)}
    doc = trace.collect_job(workers=[('0', 'h0:1'), ('1', 'h1:2')],
                            fetch=lambda ep: payloads[ep])
    assert not doc['ptJob']['skipped']
    meta = doc['ptJob']['workers']
    assert meta['0']['clock'] == 'anchored'
    # per-rank process tracks
    bands = {e['pid'] // 100 for e in doc['traceEvents']
             if e.get('ph') == 'X'}
    assert bands == {0, 1}
    # re-homed onto one clock: the 7s drift is gone
    t0 = [e['ts'] for e in doc['traceEvents']
          if e.get('ph') == 'X' and e['pid'] < 100]
    t1 = [e['ts'] for e in doc['traceEvents']
          if e.get('ph') == 'X' and e['pid'] >= 100]
    assert abs(min(t0) - min(t1)) < 5e6
    # rank-tagged steps + per-rank skew report computed
    assert {r['rank'] for r in doc['ptSteps']} == {'0', '1'}
    assert doc['ptJob']['skew']['wall']['skew_ratio'] >= 1.0
    # process names carry the rank
    names = [e['args']['name'] for e in doc['traceEvents']
             if e.get('ph') == 'M' and e.get('name') == 'process_name']
    assert any(n.startswith('rank 0 ') for n in names)
    assert any(n.startswith('rank 1 ') for n in names)


def test_collect_job_tolerates_bad_workers():
    d0 = _fake_dump(rank='0')
    payloads = {'good:1': json.dumps(d0),
                'trunc:2': json.dumps(d0)[:40],      # truncated JSON
                'empty:3': '{}'}                      # no traceEvents

    def fetch(ep):
        if ep == 'dead:4':
            raise OSError('connection refused')
        return payloads[ep]

    before = monitor.counter_value('trace/collect_skipped')
    doc = trace.collect_job(
        workers=[('0', 'good:1'), ('1', 'trunc:2'), ('2', 'empty:3'),
                 ('3', 'dead:4')], fetch=fetch)
    assert sorted(doc['ptJob']['skipped']) == ['1', '2', '3']
    assert monitor.counter_value('trace/collect_skipped') == before + 3
    # the healthy rank still collected
    assert doc['ptJob']['workers']['0']['events'] > 0


def test_collect_job_unanchored_fallback():
    d0 = _fake_dump(rank='0')
    d1 = _fake_dump(shift_us=3e6, rank='1')
    del d1['ptClock']   # pre-anchor dump
    payloads = {'a:1': json.dumps(d0), 'b:2': json.dumps(d1)}
    doc = trace.collect_job(workers=[('0', 'a:1'), ('1', 'b:2')],
                            fetch=lambda ep: payloads[ep])
    assert doc['ptJob']['workers']['1']['clock'] == 'aligned'
    assert monitor.counter_value('trace/collect_unanchored') == 1.0
    t0 = [e['ts'] for e in doc['traceEvents']
          if e.get('ph') == 'X' and e['pid'] < 100]
    t1 = [e['ts'] for e in doc['traceEvents']
          if e.get('ph') == 'X' and e['pid'] >= 100]
    # capture-start alignment: earliest events coincide
    assert abs(min(t0) - min(t1)) < 1e3


# ------------------------------------------------------- tools integration
def test_stat_summary_rank_filter(tmp_path, capsys):
    d0 = _fake_dump(rank='0')
    d1 = _fake_dump(rank='1')
    payloads = {'a:1': json.dumps(d0), 'b:2': json.dumps(d1)}
    doc = trace.collect_job(workers=[('0', 'a:1'), ('1', 'b:2')],
                            fetch=lambda ep: payloads[ep],
                            out_path=str(tmp_path / 'job.json'))
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import importlib
    import stat_summary
    importlib.reload(stat_summary)
    rc = stat_summary.main(['--steps', str(tmp_path / 'job.json'),
                            '--rank', '1'])
    out = capsys.readouterr().out
    assert rc == 0 and out.startswith('rank 1:')
    assert 'steps: 3' in out
    rc = stat_summary.main(['--steps', str(tmp_path / 'job.json'),
                            '--rank', '9'])
    assert rc == 1


def test_metrics_json_carries_step_rollup():
    trace.enable()
    with trace.step_span(1):
        with trace.span('dispatch'):
            time.sleep(0.001)
    roll = trace.step_rollup()
    assert roll['count'] == 1 and 'dispatch' in roll['phases_ms']
    # the aggregator-facing scrape shape is json-able and compact
    json.dumps(roll)


# ---------------------------------------------- two-subprocess acceptance
def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _wait_ready(proc, url, deadline):
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError('worker died: rc=%d' % proc.returncode)
        try:
            code, _body = _get(url + '/healthz/local', timeout=2)
            if code == 200:
                return
        except Exception:
            pass
        time.sleep(0.25)
    raise RuntimeError('worker at %s never became ready' % url)


def _wait_steps(proc, url, deadline, steps):
    """``/healthz/local`` answers as soon as the worker's status server
    is up, before its first train step has compiled: wait until its own
    counters say it has stepped ``steps`` times (a fixed sleep is too
    short on a loaded machine)."""
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError('worker died: rc=%d' % proc.returncode)
        _code, body = _get(url + '/metrics.json')
        counters = json.loads(body)['state']['counters']
        # run_calls counts the startup program's run too
        if counters.get('executor/run_calls', 0.0) > steps:
            return
        time.sleep(0.25)
    raise RuntimeError('worker at %s never ran %d steps' % (url, steps))


def test_two_process_collect_job_merged_timeline():
    """Acceptance: a real two-worker collective job collects into ONE
    schema-valid merged trace with both ranks' spans on a shared
    clock, plus nonzero comms telemetry on every rank."""
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, 'comms_worker.py')
    p0, p1 = _free_port(), _free_port()
    spec = '0=127.0.0.1:%d,1=127.0.0.1:%d' % (p0, p1)
    base_env = dict(os.environ)
    base_env.update({'JAX_PLATFORMS': 'cpu',
                     'PADDLE_TPU_STATUS_WORKERS': spec,
                     'FLAGS_health_heartbeat_seconds': '0.5',
                     # each rank's dump is the window its flight
                     # recorder retains, and the two are pulled one
                     # after the other: keep every step since READY,
                     # or on a loaded machine the default 16 steps
                     # (~0.4 s) of one rank end before the other's
                     # begin and "stepping concurrently" cannot show
                     'FLAGS_trace_buffer_steps': '4096',
                     'FLAGS_trace': '1'})
    env0 = dict(base_env, PADDLE_TRAINER_ID='0',
                PADDLE_TPU_STATUS_AGGREGATE='1')
    env1 = dict(base_env, PADDLE_TRAINER_ID='1',
                PADDLE_TPU_STATUS_AGGREGATE='0')
    procs = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(p1), '120'], env=env1,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(p0), '120'], env=env0,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        deadline = time.time() + 180
        agg = 'http://127.0.0.1:%d' % p0
        wrk = 'http://127.0.0.1:%d' % p1
        _wait_ready(procs[0], wrk, deadline)
        _wait_ready(procs[1], agg, deadline)
        _wait_steps(procs[0], wrk, deadline, 3)
        _wait_steps(procs[1], agg, deadline, 3)

        doc = trace.collect_job(workers=spec)
        assert not doc['ptJob']['skipped']
        assert sorted(doc['ptJob']['workers']) == ['0', '1']
        assert all(m['clock'] == 'anchored'
                   for m in doc['ptJob']['workers'].values())
        # schema: every span event complete, rank bands distinct
        bands = set()
        for e in doc['traceEvents']:
            assert isinstance(e, dict)
            if e.get('ph') == 'X':
                assert {'ts', 'dur', 'pid', 'name'} <= set(e)
                bands.add(e['pid'] // 100)
        assert bands == {0, 1}
        # shared clock: both ranks' windows overlap (they step
        # concurrently)
        w = {}
        for e in doc['traceEvents']:
            if e.get('ph') == 'X':
                band = w.setdefault(e['pid'] // 100, [1e30, 0])
                band[0] = min(band[0], e['ts'])
                band[1] = max(band[1], e['ts'] + e['dur'])
        assert w[0][0] < w[1][1] and w[1][0] < w[0][1]
        # rank-tagged step records feed the per-rank report
        assert {r['rank'] for r in doc['ptSteps']} == {'0', '1'}
        assert doc['ptJob']['skew'] is not None
        # comms telemetry populated on both ranks
        for url in (agg, wrk):
            code, body = _get(url + '/metrics.json')
            counters = json.loads(body)['state']['counters']
            assert counters.get('comms/bytes_on_wire', 0.0) > 0
        # aggregator /statusz carries per-rank liveness + skew
        code, body = _get(agg + '/statusz')
        job = json.loads(body)['job']
        assert sorted(job['workers']) == ['0', '1']
        assert all(v['up'] for v in job['workers'].values())
        assert job['skew'] is None or \
            job['skew']['wall']['skew_ratio'] >= 1.0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
