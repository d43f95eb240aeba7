"""fluid.health — status plane, Prometheus correctness, NaN
provenance, tensor-health summaries, and the flight-recorder dump
paths of every runner.

The acceptance contract: /metrics lints clean and /healthz//statusz
are schema-stable JSON; a tripped NaN check names the exact OP (type +
output var) that first produced the non-finite value, reports EVERY
bad var of the step, and embeds the provenance in the flight-recorder
dump; health summaries record norms/ratios and their detectors
auto-dump; dispatch failures dump from the CompiledPipeline and the
parallel/collective runners — not just the plain executor; and a real
two-process job aggregates into one scrape target whose readiness
flips when a worker dies."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import health, layers, monitor, trace


@pytest.fixture(autouse=True)
def _clean_health():
    yield
    fluid.set_flags({'FLAGS_check_nan_inf': False,
                     'FLAGS_health_summaries': False,
                     'FLAGS_health_zero_update_steps': 3,
                     'FLAGS_health_spike_factor': 10.0})
    health.reset_state()
    health.stop()
    trace.disable()
    trace.reset()


def _build(lr=0.01, seed=1):
    # square loss: gradients stay nonzero over the whole test window
    # (a relu head can die in two SGD steps and zero them)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 8)
        loss = layers.reduce_mean(layers.square(h))
        fluid.optimizer.SGD(lr).minimize(loss)
    return main, startup, loss


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


# ------------------------------------------------- prometheus lint
def test_prometheus_text_lints_clean():
    monitor.add('executor/some_counter', 3)
    monitor.set_gauge('reader/queue_depth', 4)
    monitor.observe('executor/run_seconds', 0.01)
    text = monitor.prometheus_text()
    assert health.prom_lint(text) == []
    # HELP + TYPE metadata present for a counter family
    assert '# HELP paddle_tpu_executor_some_counter' in text
    assert '# TYPE paddle_tpu_executor_some_counter counter' in text


def test_prom_lint_catches_scrape_breakers():
    bad = '\n'.join([
        '# TYPE m counter',
        'm 1',
        'm 2',                      # duplicate series
        'orphan 5',                 # no TYPE/HELP
        '# TYPE h histogram',
        '# HELP h h',
        'h_bucket{le="1"} 5',
        'h_bucket{le="+Inf"} 3',    # not cumulative, != _count
        'h_sum 1.0',
        'h_count 4',
    ]) + '\n'
    problems = health.prom_lint(bad)
    text = '\n'.join(problems)
    assert 'duplicate series' in text
    assert 'no TYPE metadata' in text
    assert 'not cumulative' in text
    assert '+Inf bucket' in text
    assert any('HELP' in p for p in problems)


def test_prom_escaping_label_and_help():
    assert monitor.prom_escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert monitor.prom_escape_help('x\\y\nz') == 'x\\\\y\\nz'
    line = monitor.prom_sample('m', [('worker', 'a"b')], 1.0)
    assert line == 'm{worker="a\\"b"} 1'


def test_render_merged_sums_counters_and_labels_gauges():
    s1 = {'counters': {'executor/run_calls': 3.0},
          'gauges': {'reader/queue_depth': 2.0},
          'hists': {'executor/run_seconds': {
              'edges': [0.1, 1.0], 'counts': [2, 1, 0],
              'sum': 0.5, 'count': 3}}}
    s2 = {'counters': {'executor/run_calls': 4.0,
                       'rpc/calls': 1.0},
          'gauges': {'reader/queue_depth': 7.0},
          'hists': {'executor/run_seconds': {
              'edges': [0.1, 1.0], 'counts': [1, 0, 1],
              'sum': 1.5, 'count': 2}}}
    text = health.render_merged([('0', s1), ('1', s2)])
    assert health.prom_lint(text) == []
    assert 'paddle_tpu_executor_run_calls 7' in text
    assert 'paddle_tpu_rpc_calls 1' in text
    # gauges keep worker identity instead of summing
    assert 'paddle_tpu_reader_queue_depth{worker="0"} 2' in text
    assert 'paddle_tpu_reader_queue_depth{worker="1"} 7' in text
    # histogram merged: counts sum, +Inf == _count
    assert 'paddle_tpu_executor_run_seconds_bucket{le="+Inf"} 5' in text
    assert 'paddle_tpu_executor_run_seconds_count 5' in text


# ------------------------------------------------- status endpoints
def test_status_endpoints_serve_and_validate():
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((4, 8), 'float32')},
                fetch_list=[loss])
    srv = health.serve(port=0)
    assert srv.port > 0
    try:
        code, text = _get(srv.url + '/metrics')
        assert code == 200
        assert health.prom_lint(text) == []
        assert 'paddle_tpu_executor_run_calls' in text

        code, body = _get(srv.url + '/healthz')
        doc = json.loads(body)
        assert code == 200 and doc['ready'] is True
        assert doc['alive'] and doc['steps'] >= 1
        assert doc['last_step_age_s'] is not None

        code, body = _get(srv.url + '/statusz')
        doc = json.loads(body)
        assert code == 200
        assert 'rollup' in doc['step_report']
        assert 'segment_cache_hit' in doc['caches']
        assert 'FLAGS_status_port' in doc['flags']
        assert doc['versions'].get('jax')

        code, body = _get(srv.url + '/metrics.json')
        doc = json.loads(body)
        assert code == 200
        assert 'counters' in doc['state'] and 'hists' in doc['state']

        trace.enable(buffer_steps=4)
        with trace.step_span(1):
            with trace.span('dispatch'):
                pass
        code, body = _get(srv.url + '/trace/dump')
        doc = json.loads(body)
        assert code == 200
        assert doc['ptSteps'] and os.path.exists(doc['ptDumpPath'])

        code, body = _get(srv.url + '/nope')
        assert code == 404 and 'paths' in json.loads(body)
    finally:
        srv.stop()
    assert health.server() is None


# README "Health & status endpoints": every section is always present
# (None until its plane has something to say)
STATUSZ_KEYS = {
    'status', 'step_report', 'caches', 'serving', 'memory',
    'comms_plan', 'auto_shard', 'elastic', 'verify', 'supervisor',
    'timeseries', 'pallas', 'job', 'flags', 'versions', 'trace_active',
    'monitor',
}


def test_statusz_keys_are_the_documented_list_and_alertz_is_gone():
    srv = health.serve(port=0)
    try:
        code, body = _get(srv.url + '/statusz')
        assert code == 200 and set(json.loads(body)) == STATUSZ_KEYS
        code, body = _get(srv.url + '/alertz')
        doc = json.loads(body)
        assert code == 404 and '/alertz' not in doc['paths']
        assert '/timeseries' in doc['paths']
    finally:
        srv.stop()


def test_healthz_not_ready_before_first_step():
    monitor.reset()
    from paddle_tpu.fluid import compile_cache
    compile_cache.reset_plane()
    st = health.status()
    assert st['ready'] is False and st['reasons']
    monitor.add('executor/run_calls')
    assert health.status()['ready'] is True


def test_aggregator_marks_unreachable_worker_down():
    # no process listens on this endpoint: one probe flips it down
    agg = health._Aggregator('0', [('1', '127.0.0.1:9')], 0.2)
    try:
        agg.probe_once()
        doc = agg.healthz()
        assert doc['aggregated'] is True
        assert doc['workers']['1']['up'] is False
        assert doc['ready'] is False
        assert monitor.gauge_value('health/worker_up/1') == 0.0
        # merged text still renders (self only) and lints clean
        assert health.prom_lint(agg.metrics_text()) == []
    finally:
        agg.stop()


# ------------------------------------------------- NaN provenance
def test_nan_error_names_op_and_dumps_provenance():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        h = layers.scale(x, scale=2.0)
        y = layers.log(h)          # log(0) -> -inf: the culprit op
        z = layers.scale(y, scale=3.0)
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    trace.enable(buffer_steps=4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        with pytest.raises(FloatingPointError) as ei:
            exe.run(main, feed={'x': np.zeros((2, 4), 'float32')},
                    fetch_list=[z])
    msg = str(ei.value)
    assert 'op [log]' in msg                   # exact op type
    assert y.name in msg                       # its output var
    assert 'nonfinite=100.0%' in msg           # output stats
    assert 'min=0.0' in msg                    # input stats
    assert 'dumped to' in msg                  # flight recorder path
    path = msg.rsplit('dumped to ', 1)[1].strip()
    doc = json.load(open(path))
    inc = doc['ptIncident']
    assert inc['kind'] == 'nan_check'
    assert inc['provenance']['op_type'] == 'log'
    assert inc['provenance']['outputs'] == [y.name]
    assert monitor.counter_value('health/nan_trips') >= 1.0


def test_nan_check_reports_every_bad_var():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y1 = layers.log(x)                     # -inf
        y2 = layers.scale(y1, scale=2.0)       # still -inf
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        with pytest.raises(FloatingPointError) as ei:
            exe.run(main, feed={'x': np.zeros((2, 4), 'float32')},
                    fetch_list=[y1, y2])
    first = str(ei.value).splitlines()[0]
    assert '2 var(s)' in first
    assert y1.name in first and y2.name in first


def test_nan_replay_flag_off_still_reports_vars():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.log(x)
    fluid.set_flags({'FLAGS_check_nan_inf': True,
                     'FLAGS_nan_replay': False})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            with pytest.raises(FloatingPointError) as ei:
                exe.run(main,
                        feed={'x': np.zeros((2, 4), 'float32')},
                        fetch_list=[y])
        assert y.name in str(ei.value)
        assert 'produced by op' not in str(ei.value)
    finally:
        fluid.set_flags({'FLAGS_nan_replay': True})


# ------------------------------------------------- tensor health
def test_health_summaries_record_norms_and_ratios():
    fluid.set_flags({'FLAGS_health_summaries': True})
    monitor.reset()
    health.reset_state()
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(4):
            exe.run(main, feed={'x': np.ones((4, 8), 'float32')},
                    fetch_list=[loss])
    assert monitor.counter_value('health/summary_steps') >= 4.0
    assert monitor.counter_value('health/summary_errors') == 0.0
    gh = monitor.histogram_value('health/grad_norm')
    assert gh and gh['count'] >= 4      # param grads surfaced
    uh = monitor.histogram_value('health/update_ratio')
    assert uh and uh['count'] >= 4
    assert monitor.histogram_value('health/global_grad_norm')['count'] \
        >= 4
    assert monitor.gauge_value('health/last_global_grad_norm') > 0.0
    # an SGD step with lr>0 and nonzero grads must NOT look dead
    assert monitor.counter_value('health/zero_update_trips') == 0.0
    # and a healthy run must not false-positive the spike detector
    # (the grad-free startup program must not seed the EMA at zero)
    assert monitor.counter_value('health/grad_spikes') == 0.0


def test_zero_update_detector_dumps_flight_recorder():
    fluid.set_flags({'FLAGS_health_summaries': True,
                     'FLAGS_health_zero_update_steps': 2})
    monitor.reset()
    health.reset_state()
    trace.enable(buffer_steps=4)
    main, startup, loss = _build(lr=0.0)   # frozen optimizer
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(4):
            exe.run(main, feed={'x': np.ones((4, 8), 'float32')},
                    fetch_list=[loss])
    assert monitor.counter_value('health/zero_update_trips') == 1.0
    assert monitor.counter_value('health/detector_dumps') >= 1.0


def test_grad_spike_detector():
    fluid.set_flags({'FLAGS_health_summaries': True,
                     'FLAGS_health_spike_factor': 5.0})
    monitor.reset()
    health.reset_state()
    trace.enable(buffer_steps=4)
    main, startup, loss = _build(lr=1e-4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        small = {'x': np.ones((4, 8), 'float32') * 0.01}
        for _ in range(3):
            exe.run(main, feed=small, fetch_list=[loss])
        huge = {'x': np.ones((4, 8), 'float32') * 1e6}
        exe.run(main, feed=huge, fetch_list=[loss])
    assert monitor.counter_value('health/grad_spikes') >= 1.0
    assert monitor.counter_value('health/detector_dumps') >= 1.0


def test_summaries_off_costs_nothing():
    assert not fluid.flags.get_flag('FLAGS_health_summaries')
    monitor.reset()
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={'x': np.ones((4, 8), 'float32')},
                    fetch_list=[loss])
    assert monitor.counter_value('health/summary_steps') == 0.0
    assert monitor.histogram_value('health/grad_norm') is None


# ---------------------------------------- dispatch-failure dump paths
def test_pipeline_dispatch_failure_dumps_flight_recorder():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 8, act='relu')
        mid = main.current_block().create_var(
            name='hmid', shape=[-1, 8], dtype='float32')
        layers.py_func(lambda a: a, h, mid)   # host op: pipeline plan
        h2 = layers.fc(mid, 4)
        loss = layers.reduce_mean(h2)
    trace.enable(buffer_steps=4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        pipe = exe.compile(main, feed_names=['x'],
                           fetch_names=[loss.name], allow_host=True)
        d0 = monitor.counter_value('trace/dumps_written')
        with pytest.raises(Exception):
            # inner dim 7 violates the fc weights: segment fails
            pipe(feed={'x': np.ones((4, 7), 'float32')})
        assert monitor.counter_value('trace/dumps_written') == d0 + 1


def test_parallel_runner_dispatch_failure_dumps():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 2
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 8)
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    trace.enable(buffer_steps=4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        d0 = monitor.counter_value('trace/dumps_written')
        with pytest.raises(Exception):
            exe.run(cp, feed={'x': np.ones((8, 7), 'float32')},
                    fetch_list=[loss])
        assert monitor.counter_value('trace/dumps_written') == d0 + 1


def test_collective_runner_dispatch_failure_dumps():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 2
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        h = layers.fc(x, 8)
        loss = layers.reduce_mean(h)
    main._collective_dp = True    # fleet GradAllReduce posture
    trace.enable(buffer_steps=4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        d0 = monitor.counter_value('trace/dumps_written')
        with pytest.raises(Exception):
            exe.run(main, feed={'x': np.ones((8, 7), 'float32')},
                    fetch_list=[loss])
        assert monitor.counter_value('trace/dumps_written') == d0 + 1


# ------------------------------------------------- two-process job
def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


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


def test_two_process_aggregated_metrics_and_failover():
    """Acceptance: rank 0's aggregated /metrics carries both workers'
    counters; killing one worker flips aggregated /healthz readiness
    within one heartbeat interval."""
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, 'health_worker.py')
    p0, p1 = _free_port(), _free_port()
    spec = '0=127.0.0.1:%d,1=127.0.0.1:%d' % (p0, p1)
    base_env = dict(os.environ)
    base_env.update({'JAX_PLATFORMS': 'cpu',
                     'PADDLE_TPU_STATUS_WORKERS': spec,
                     'FLAGS_health_heartbeat_seconds': '0.5'})
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

        # aggregated readiness: both workers up within a heartbeat
        doc = None
        for _ in range(40):
            code, body = _get(agg + '/healthz')
            doc = json.loads(body)
            if code == 200:
                break
            time.sleep(0.25)
        assert doc['aggregated'] is True
        assert doc['workers']['0']['ready'] is True
        assert doc['workers']['1']['up'] is True

        # merged /metrics: BOTH workers' marker counters in one blob
        code, text = _get(agg + '/metrics')
        assert code == 200
        assert health.prom_lint(text) == []
        assert 'paddle_tpu_health_test_marker_rank0 1' in text
        assert 'paddle_tpu_health_test_marker_rank1 1' in text
        # run_calls merged = sum of both workers (> either alone).
        # Both keep stepping and the aggregator scrapes on its
        # heartbeat, so read worker 1's own count FIRST and give the
        # merged view a few heartbeats to hold a scrape newer than
        # that reading: from then on it is that count plus rank 0's.
        code, body = _get(wrk + '/metrics.json')
        w1_calls = json.loads(body)['state']['counters'][
            'executor/run_calls']
        for _ in range(40):
            code, text = _get(agg + '/metrics')
            merged = dict(
                line.rsplit(' ', 1)
                for line in text.splitlines()
                if line and not line.startswith('#') and '{' not in line)
            if float(merged['paddle_tpu_executor_run_calls']) > w1_calls:
                break
            time.sleep(0.25)
        assert float(merged['paddle_tpu_executor_run_calls']) > \
            w1_calls
        assert 'paddle_tpu_health_agg_worker_up{worker="1"' in text

        # kill worker 1: readiness flips within one heartbeat interval
        procs[0].kill()
        procs[0].wait(timeout=10)
        flipped = False
        for _ in range(20):        # 0.5s heartbeat + slack
            time.sleep(0.25)
            code, body = _get(agg + '/healthz')
            if code == 503:
                doc = json.loads(body)
                assert doc['workers']['1']['up'] is False
                flipped = True
                break
        assert flipped, 'aggregated readiness never flipped after kill'
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
