"""Telemetry-plane gate: the windowed time-series history and its
HTTP surfaces must work against REAL executors and REAL processes —
and cost nothing when off.

Three postures:

  1. in-process live run: FLAGS_timeseries on, a real executor
     stepping a real program with the status plane on an ephemeral
     port.  /timeseries must serve a schema-valid directory listing,
     a counter window (executor/run_calls with derived reset-aware
     rate), a histogram window (executor/run_seconds with windowed
     p50/p95/p99), a `point` query, a 404-with-directory on an
     unknown name and a 400 on a malformed number; /statusz must
     carry the sparkline rollup section;
  2. two-process job (tests/comms_worker.py x2, rank 0 aggregating
     with FLAGS_timeseries on): the aggregator's /timeseries must
     list both ranks in the job history, serve a per-worker
     (`?rank=1`) counter window built from scraped heartbeats, and
     serve its own local series — per-worker AND aggregated history
     from one endpoint;
  3. disabled-path cost: with FLAGS_timeseries off (the default),
     tools/check_hot_path.py's steady-state budgets must still hold —
     the step boundary pays one flag read for the whole plane.

Run from `make check` (CPU: JAX_PLATFORMS=cpu).
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:   # 4xx bodies are part of
        return e.code, e.read()           # the surface under test


def _get_json(url, timeout=10):
    code, body = _get(url, timeout=timeout)
    return code, json.loads(body)


def _wait_ready(proc, url, deadline):
    while time.time() < deadline:
        if proc.poll() is not None:
            out = proc.stdout.read().decode('utf-8', 'replace') \
                if proc.stdout else ''
            raise RuntimeError('worker died rc=%d: %s'
                               % (proc.returncode, out[-800:]))
        try:
            code, _ = _get(url + '/healthz/local', timeout=2)
            if code == 200:
                return
        except Exception:
            pass
        time.sleep(0.25)
    raise RuntimeError('worker at %s never became ready' % url)


def check_local_plane(failures):
    """Posture 1: live in-process run against the real status plane."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, timeseries

    port = _free_port()
    fluid.set_flags({'FLAGS_timeseries': True,
                     'FLAGS_status_port': port})
    timeseries.reset()

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 11
    with fluid.program_guard(prog, startup):
        x = layers.data('x', shape=[16], dtype='float32')
        h = layers.fc(x, 16, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {'x': np.ones((4, 16), 'float32')}
    base = 'http://127.0.0.1:%d' % port
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            for _ in range(20):
                exe.run(prog, feed=feed, fetch_list=[loss])

            # directory listing
            code, doc = _get_json(base + '/timeseries')
            if code != 200 or not doc.get('enabled') \
                    or 'executor/run_seconds' not in doc.get(
                        'series', []):
                failures.append('/timeseries listing broken: code=%d '
                                'enabled=%r series~%d'
                                % (code, doc.get('enabled'),
                                   len(doc.get('series', []))))

            # counter window: derived reset-aware rate over real steps
            code, doc = _get_json(
                base + '/timeseries?name=executor/run_calls&points=16')
            if code != 200 or doc.get('kind') != 'counter':
                failures.append('counter window broken: %d %r'
                                % (code, doc.get('kind')))
            else:
                d = doc['derived']
                if not (doc['n'] >= 2 and d['rate_per_s'] and
                        d['rate_per_s'] > 0 and
                        d['total_delta'] > 0 and d['resets'] == 0):
                    failures.append('counter derived math wrong: %r'
                                    % d)
                if len(doc['points'][0]) != 3:
                    failures.append('counter point is not '
                                    '(ts, step, value): %r'
                                    % doc['points'][0])

            # histogram window: windowed percentiles from cumulative
            # bucket subtraction
            code, doc = _get_json(
                base + '/timeseries?name=executor/run_seconds'
                       '&points=16')
            if code != 200 or doc.get('kind') != 'hist':
                failures.append('hist window broken: %d %r'
                                % (code, doc.get('kind')))
            else:
                d = doc['derived']
                pcts = d.get('percentiles', {})
                if not (d['count'] > 0 and d['sum'] > 0 and
                        pcts.get('p50') is not None and
                        pcts.get('p99') is not None and
                        pcts['p50'] <= pcts['p99']):
                    failures.append('hist window percentiles wrong: '
                                    '%r' % d)
                if not doc.get('edges'):
                    failures.append('hist window lost its edges')

            # point query + error surfaces
            code, doc = _get_json(
                base + '/timeseries?name=executor/run_calls&point=1')
            if code != 200 or len(doc.get('point', [])) != 3:
                failures.append('point query broken: %d %r'
                                % (code, doc.get('point')))
            code, doc = _get_json(base + '/timeseries?name=no/such')
            if code != 404 or not doc.get('series'):
                failures.append('unknown series should 404 with the '
                                'directory, got %d' % code)
            code, doc = _get_json(
                base + '/timeseries?name=executor/run_calls'
                       '&points=banana')
            if code != 400:
                failures.append('malformed points= should 400, got '
                                '%d' % code)

            # /statusz sparkline rollup
            code, body = _get(base + '/statusz')
            ts_sec = json.loads(body).get('timeseries')
            if not ts_sec or not ts_sec.get('series'):
                failures.append('/statusz timeseries section missing '
                                'or empty')
            elif not any(r.get('spark') for r in ts_sec['series']):
                failures.append('/statusz timeseries rows carry no '
                                'sparklines: %r' % ts_sec['series'][:2])
    finally:
        fluid.set_flags({'FLAGS_timeseries': False})
        timeseries.reset()


def check_job_plane(failures):
    """Posture 2: two real processes, rank 0 aggregating per-worker
    history from scraped heartbeats."""
    worker = os.path.join(ROOT, 'tests', 'comms_worker.py')
    p0, p1 = _free_port(), _free_port()
    spec = '0=127.0.0.1:%d,1=127.0.0.1:%d' % (p0, p1)
    base_env = dict(os.environ)
    base_env.update({'PADDLE_TPU_STATUS_WORKERS': spec,
                     'FLAGS_health_heartbeat_seconds': '0.5',
                     'FLAGS_timeseries': '1'})
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
        deadline = time.time() + 240
        agg = 'http://127.0.0.1:%d' % p0
        wrk = 'http://127.0.0.1:%d' % p1
        _wait_ready(procs[0], wrk, deadline)
        _wait_ready(procs[1], agg, deadline)
        # let a few heartbeats land so per-rank series have >= 2
        # points (rates need pairs)
        time.sleep(2.5)

        code, doc = _get_json(agg + '/timeseries')
        ranks = doc.get('ranks', [])
        if code != 200 or not ('0' in ranks and '1' in ranks):
            failures.append('aggregator job history covers ranks %r, '
                            'wanted 0 and 1' % ranks)
        if doc.get('job_samples', 0) < 4:
            failures.append('aggregator retained only %r job samples '
                            'after 2.5s of 0.5s heartbeats'
                            % doc.get('job_samples'))

        # a per-worker series scraped over heartbeats, windowed
        code, doc = _get_json(
            agg + '/timeseries?rank=1&name=executor/run_calls'
                  '&points=32')
        if code != 200 or doc.get('kind') != 'counter' or \
                doc.get('rank') != '1':
            failures.append('per-worker window broken: %d kind=%r '
                            'rank=%r' % (code, doc.get('kind'),
                                         doc.get('rank')))
        elif not (doc['n'] >= 2 and
                  doc['derived']['total_delta'] > 0):
            failures.append('rank-1 run_calls never advanced across '
                            'heartbeats: %r' % doc['derived'])

        # the aggregator's own local history serves from the same
        # endpoint (no rank param)
        code, doc = _get_json(
            agg + '/timeseries?name=executor/run_calls&points=32')
        if code != 200 or doc.get('rank') is not None or doc['n'] < 2:
            failures.append('aggregator local series broken: %d '
                            'rank=%r n=%r' % (code, doc.get('rank'),
                                              doc.get('n')))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass


def main():
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()
    sys.path.insert(0, ROOT)
    failures = []

    check_local_plane(failures)
    check_job_plane(failures)

    # ---- 3: disabled-path hot-loop budgets ------------------------------
    env = dict(os.environ)
    env.pop('FLAGS_timeseries', None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'tools',
                                      'check_hot_path.py')],
        env=env, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        failures.append('check_hot_path budgets broke with the '
                        'timeseries hook in the step loop:\n%s'
                        % (r.stdout + r.stderr)[-800:])

    if failures:
        print('check_timeseries: FAIL')
        for f in failures:
            print('  - %s' % f)
        return 1
    print('check_timeseries: /timeseries windows schema-valid '
          '(counter rate, hist percentiles, point/404/400), /statusz '
          'sparklines render, 2-rank job history serves '
          'per-worker and aggregated series, hot-path budgets hold')
    return 0


if __name__ == '__main__':
    sys.exit(main())
