"""Chaos soak gate: a REAL multi-process training job driven through
scripted ``faultinject`` clauses must reach its target step with ZERO
human intervention, bounded lost work, a bitwise-reproducible
post-recovery trajectory, and EVERY injected fault matched to a named
supervisor decision in /statusz.

Injected fault kinds (>= 4 distinct, all in one soak):

  worker kill       peer worker armed with 'executor.step:die@N' — a
                    real kill -9 mid-step; the rank-0 supervisor must
                    confirm the death through the aggregator's
                    consecutive-miss signal and degrade to the
                    survivors ('death' -> 'recovery' decisions)
  torn shard write  'elastic.shard_write:torn@K' tears one shard of a
                    periodic checkpoint; the supervisor's post-save
                    digest verification must catch it and resave
                    ('checkpoint_torn' decision) so lost work stays
                    bounded by ONE cadence
  RPC stall/fault   'rpc.call:fail@N' injects a transport failure
                    into the live PS heartbeat; the rpc_ps
                    bounded-backoff machinery absorbs it and the
                    supervisor logs the tolerated degradation
                    ('rpc_backoff' decision)
  heartbeat flap    a peer's status endpoint goes unreachable for
                    less than FLAGS_heartbeat_misses scrapes and
                    recovers — a real network-level drop-and-recover;
                    must be tolerated ('heartbeat_flap'), NEVER
                    resharded
  collective stall  'executor.dispatch:stall:S@N' parks a segment
                    dispatch past FLAGS_step_timeout_s; the hung-step
                    watchdog converts it into a named timeout + flight
                    dump and the supervisor recovers from last-good
                    ('hung_step' -> 'recovery')

Topology note: same cluster-in-a-box posture as check_elastic /
check_supervisor (cross-process jax collectives are unavailable on the
CPU backend) — every kill, scrape, RPC frame and restart crosses a
real OS process boundary, which is what the controller gates.

Run from `make check` (CPU: JAX_PLATFORMS=cpu).
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TARGET_STEP = 24
CADENCE = 4
HEARTBEAT_S = 0.25
MISSES = 3
STEP_TIMEOUT_S = 0.7
REJOIN_WAIT_S = 8.0
STALL_HIT = 12          # executor.dispatch hit of the injected stall
RPC_FAIL_HIT = 6        # rpc.call hit of the injected transport fault
FLAP_START_S = 6.0      # flapper outage window, relative to its start
FLAP_LEN_S = 0.4        # < MISSES * HEARTBEAT_S: a flap, not a death


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def build_model():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 23
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            y = fluid.layers.data('y', shape=[1], dtype='float32')
            h = fluid.layers.fc(x, 16, act='relu')
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.reduce_mean(fluid.layers.square(
                fluid.layers.elementwise_sub(pred, y)))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def batch_for(step, n=8):
    import numpy as np
    rng = np.random.RandomState(4000 + step)
    x = rng.randn(n, 8).astype('float32')
    return x, (x.sum(1, keepdims=True) * 0.5).astype('float32')


def _hex(v):
    import numpy as np
    return np.float32(np.asarray(v).ravel()[0]).tobytes().hex()


# -------------------------------------------------------------- workers
def victim_main():
    """Peer worker 1: dies by a REAL kill -9 mid-step (faultinject
    'executor.step:die' in its env)."""
    import paddle_tpu.fluid as fluid
    main, startup, loss = build_model()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        time.sleep(1.0)      # visibly UP for the aggregator first
        for s in range(1000):
            x, y = batch_for(s)
            exe.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
            time.sleep(0.1)
    print('VICTIM_SURVIVED')


def flapper_main(port):
    """Peer worker 2: a status endpoint that goes dark for
    FLAP_LEN_S (< the miss tolerance) and recovers — the
    heartbeat-drop-and-recover fault, at the real network level."""
    import http.server
    t0 = time.time()
    body = json.dumps({
        'rank': '2',
        'state': {'counters': {}, 'gauges': {}, 'hists': {}},
        'status': {'ready': True, 'steps': 1},
        'step_rollup': None}).encode()

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            dt = time.time() - t0
            if FLAP_START_S <= dt < FLAP_START_S + FLAP_LEN_S:
                time.sleep(3.0)    # outlives the scrape timeout
            try:
                self.send_response(200)
                self.send_header('Content-Type', 'application/json')
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except Exception:
                pass

    httpd = http.server.ThreadingHTTPServer(('127.0.0.1', int(port)),
                                            H)
    httpd.daemon_threads = True
    httpd.serve_forever()


def soak_main(store):
    """Rank 0: the supervised trainer every fault lands on."""
    import urllib.request
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import faultinject, monitor, supervisor
    from paddle_tpu.fluid.io import _persistable_vars
    main, startup, loss = build_model()
    nshards = len(_persistable_vars(main))
    # deterministic chaos plan, computed against THIS model: tear the
    # first shard of checkpoint #2, fail one heartbeat RPC, stall one
    # steady-state dispatch past the watchdog deadline
    clauses = ['elastic.shard_write:torn@%d' % (nshards + 1),
               'executor.dispatch:stall:2@%d' % STALL_HIT]
    rpc_ok = False
    ps = hb = None
    try:
        from paddle_tpu.distributed.rpc_ps import PsServer
        ps = PsServer()
        rpc_ok = True
        clauses.append('rpc.call:fail@%d' % RPC_FAIL_HIT)
    except Exception:
        ps = None      # native runtime unavailable: 4 kinds remain
    faultinject.configure(';'.join(clauses))

    losses = {}
    recoveries = []
    timeouts = 0
    required = {'death', 'recovery', 'checkpoint_torn',
                'heartbeat_flap', 'hung_step'}
    if rpc_ok:
        required.add('rpc_backoff')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        if rpc_ok:
            from paddle_tpu.distributed.rpc_ps import TrainerHeartbeat
            hb = TrainerHeartbeat(ps.endpoint, 0, timeout=30.0,
                                  interval=0.1)
        x0, y0 = batch_for(0)
        supervisor.attach(store, program=main, executor=exe,
                          checkpoint_steps=CADENCE,
                          rejoin_wait_s=REJOIN_WAIT_S,
                          feed_shapes={'x': x0, 'y': y0},
                          fetch_list=[loss])
        deadline = time.time() + 120
        target = TARGET_STEP
        try:
            while time.time() < deadline:
                s = int(exe._step)
                seen = {d['kind'] for d in supervisor.decisions()}
                if s >= target and required <= seen:
                    break
                x, y = batch_for(s)
                try:
                    l, = exe.run(main, feed={'x': x, 'y': y},
                                 fetch_list=[loss])
                    losses[int(exe._step)] = _hex(l)
                except supervisor.Recovered as e:
                    recoveries.append({
                        'generation': e.generation, 'step': e.step,
                        'lost_steps': e.lost_steps,
                        'wall': time.time()})
                    target = max(TARGET_STEP, e.step + 6)
                    continue
                except supervisor.StepTimeoutError:
                    timeouts += 1
                    continue   # next run() executes the recovery
                time.sleep(0.1)
            decs = supervisor.decisions()
            # the /statusz proof: every fault's decision is scrapeable
            port = int(fluid.get_flags('FLAGS_status_port')
                       ['FLAGS_status_port'])
            with urllib.request.urlopen(
                    'http://127.0.0.1:%d/statusz' % port,
                    timeout=10) as resp:
                doc = json.loads(resp.read().decode())
            section = doc.get('supervisor') or {}
            statusz_kinds = sorted({d['kind'] for d in
                                    section.get('decisions', [])})
        finally:
            sup = supervisor.current()
            t = sup._save_thread if sup else None
            supervisor.detach()
            if t is not None:
                t.join(timeout=10)
            if hb is not None:
                hb.stop()
            if ps is not None:
                ps.stop()
    out = {
        'losses': losses,
        'recoveries': recoveries,
        'timeouts': timeouts,
        'final_step': int(exe._step),
        'rpc_ok': rpc_ok,
        'decisions': [{k: d.get(k) for k in
                       ('kind', 'choice', 'acted', 'fault',
                        'wall_unix', 'info')} for d in decs],
        'statusz_kinds': statusz_kinds,
        'faultinject': faultinject.report(),
        'counters': {k: monitor.counter_value(k) for k in (
            'supervisor/checkpoints_taken', 'supervisor/checkpoint_torn',
            'supervisor/recoveries', 'supervisor/deaths_confirmed',
            'supervisor/lost_steps', 'supervisor/hung_steps',
            'executor/step_timeouts', 'elastic/heartbeat_flaps',
            'elastic/refused_generations', 'rpc/retries')},
    }
    print('CHECK_JSON ' + json.dumps(out))


def verify_main(store, generation, target):
    """Bitwise-reproducibility reference: a fresh process resumes the
    LAST recovery's generation and replays to the same step."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import elastic
    main, startup, loss = build_model()
    losses = {}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        elastic.load_checkpoint(store, main, executor=exe,
                                generation=int(generation))
        while exe._step < int(target):
            s = int(exe._step)
            x, y = batch_for(s)
            l, = exe.run(main, feed={'x': x, 'y': y},
                         fetch_list=[loss])
            losses[int(exe._step)] = _hex(l)
    print('CHECK_JSON ' + json.dumps({'losses': losses}))


# -------------------------------------------------------------- driver
def _spawn(mode, args, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), '--child', mode]
        + [str(a) for a in args],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def _child_json(stdout, tag=''):
    for line in stdout.splitlines():
        if line.startswith('CHECK_JSON '):
            return json.loads(line[len('CHECK_JSON '):])
    raise RuntimeError('%s produced no CHECK_JSON\n%s'
                       % (tag, stdout[-2000:]))


def run_soak():
    """The whole soak; returns (failures, stats)."""
    work = tempfile.mkdtemp(prefix='pt_chaos_')
    store = os.path.join(work, 'store')
    p0, p1, p2 = _free_port(), _free_port(), _free_port()
    spec = ('0=127.0.0.1:%d,1=127.0.0.1:%d,2=127.0.0.1:%d'
            % (p0, p1, p2))
    common = {
        'PADDLE_TPU_STATUS_WORKERS': spec,
        'FLAGS_health_heartbeat_seconds': str(HEARTBEAT_S),
        'FLAGS_heartbeat_misses': str(MISSES),
        'FLAGS_trace': '1',
        'FLAGS_elastic_keep_generations': '64',
    }
    failures = []
    stats = {}
    procs = []
    try:
        flapper = _spawn('flapper', [p2])
        procs.append(flapper)
        victim = _spawn('victim', [], dict(
            common, PADDLE_TRAINER_ID='1', FLAGS_status_port=str(p1),
            FLAGS_faultinject='executor.step:die@6'))
        procs.append(victim)
        t_start = time.time()
        soak = _spawn('soak', [store], dict(
            common, PADDLE_TRAINER_ID='0', FLAGS_status_port=str(p0),
            FLAGS_step_timeout_s=str(STEP_TIMEOUT_S)))
        procs.append(soak)
        s_out, s_err = soak.communicate(timeout=300)
        soak_wall = time.time() - t_start
        v_rc = victim.wait(timeout=60)
        if v_rc != 9:
            failures.append('victim exited %r, wanted kill -9 code 9'
                            % v_rc)
        if soak.returncode != 0:
            failures.append('soak child exited %d (manual '
                            'intervention would have been needed)\n%s'
                            % (soak.returncode, s_err[-3000:]))
            return failures, stats
        res = _child_json(s_out, tag='soak')
        kinds = sorted({d['kind'] for d in res['decisions']})
        fired = res['faultinject'].get('fired', {})
        print('soak: final step %d, %d recoveries, %d checkpoints '
              '(%d torn->resaved), decisions %s, fired %s'
              % (res['final_step'], len(res['recoveries']),
                 res['counters']['supervisor/checkpoints_taken'],
                 res['counters']['supervisor/checkpoint_torn'],
                 kinds, fired))

        # 1. zero-intervention completion
        if res['final_step'] < TARGET_STEP:
            failures.append('soak stopped at step %d < target %d'
                            % (res['final_step'], TARGET_STEP))

        # 2. every injected fault matched to a NAMED decision, both in
        #    the child's log and in the scraped /statusz section
        matches = [('worker kill (kill -9 rc=9)', True, 'death'),
                   ('worker kill recovery', True, 'recovery'),
                   ('torn shard write',
                    fired.get('elastic.shard_write', 0) >= 1,
                    'checkpoint_torn'),
                   ('heartbeat flap', res['counters'][
                       'elastic/heartbeat_flaps'] >= 1,
                    'heartbeat_flap'),
                   ('collective stall',
                    fired.get('executor.dispatch', 0) >= 1,
                    'hung_step'),
                   ('rpc fault', res['rpc_ok'] and
                    fired.get('rpc.call', 0) >= 1, 'rpc_backoff')]
        injected_kinds = 0
        for label, injected, kind in matches:
            if not injected:
                if kind in ('checkpoint_torn', 'hung_step',
                            'heartbeat_flap'):
                    failures.append('%s was never injected' % label)
                continue
            injected_kinds += 1
            if kind not in kinds:
                failures.append('injected fault %r has no %r '
                                'decision in the log' % (label, kind))
            if kind not in res['statusz_kinds']:
                failures.append('injected fault %r has no %r '
                                'decision in /statusz' % (label, kind))
        distinct = len({k for _l, inj, k in matches
                        if inj and k not in ('recovery',)})
        if distinct < 4:
            failures.append('only %d distinct fault kinds injected, '
                            'need >= 4' % distinct)

        # 3. bounded lost work: <= one checkpoint cadence per recovery
        for r in res['recoveries']:
            if r['lost_steps'] > CADENCE:
                failures.append('recovery from gen %s lost %d steps '
                                '> cadence %d'
                                % (r['generation'], r['lost_steps'],
                                   CADENCE))

        # 4. bitwise-reproducible post-recovery trajectory
        compared = 0
        if res['recoveries']:
            last = res['recoveries'][-1]
            target = max(int(s) for s in res['losses'])
            verify = _spawn('verify',
                            [store, last['generation'], target])
            vout, verr = verify.communicate(timeout=240)
            if verify.returncode != 0:
                failures.append('verifier exited %d\n%s'
                                % (verify.returncode, verr[-2000:]))
            else:
                ref = _child_json(vout, tag='verify')['losses']
                for s, hx in ref.items():
                    if int(s) <= last['step']:
                        continue
                    got = res['losses'].get(s)
                    if got is None:
                        continue
                    compared += 1
                    if got != hx:
                        failures.append(
                            'post-recovery step %s not bitwise-'
                            'reproducible: %s vs %s' % (s, got, hx))
                if compared < 3:
                    failures.append('only %d post-recovery steps '
                                    'compared bitwise' % compared)
        else:
            failures.append('no recovery ever happened')

        stats = {
            'soak_wall_s': round(soak_wall, 2),
            'final_step': res['final_step'],
            'target_step': TARGET_STEP,
            'checkpoint_cadence_steps': CADENCE,
            'fault_kinds_injected': distinct,
            'recoveries': len(res['recoveries']),
            'lost_steps': [r['lost_steps'] for r in res['recoveries']],
            'step_timeouts': res['counters']['executor/step_timeouts'],
            'checkpoints_taken': res['counters'][
                'supervisor/checkpoints_taken'],
            'checkpoints_torn_resaved': res['counters'][
                'supervisor/checkpoint_torn'],
            'heartbeat_flaps_tolerated': res['counters'][
                'elastic/heartbeat_flaps'],
            'rpc_retries': res['counters']['rpc/retries'],
            'decision_kinds': kinds,
            'bitwise_steps_verified': compared,
            'rpc_ok': res['rpc_ok'],
        }
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGKILL)
                except OSError:
                    pass
        shutil.rmtree(work, ignore_errors=True)
    return failures, stats


def main():
    if '--child' in sys.argv:
        i = sys.argv.index('--child')
        sys.path.insert(0, REPO)
        mode = sys.argv[i + 1]
        if mode == 'victim':
            return victim_main()
        if mode == 'flapper':
            return flapper_main(sys.argv[i + 2])
        if mode == 'soak':
            return soak_main(sys.argv[i + 2])
        if mode == 'verify':
            return verify_main(sys.argv[i + 2], sys.argv[i + 3],
                               sys.argv[i + 4])
        raise SystemExit('unknown child mode %r' % mode)

    failures, stats = run_soak()
    if stats:
        print('CHAOS_STATS ' + json.dumps(stats, sort_keys=True))
    if failures:
        print('\ncheck_chaos FAILURES:')
        for f in failures:
            print('  - ' + f)
        return 1
    print('\ncheck_chaos OK: %d distinct fault kinds (worker kill, '
          'torn shard, %sheartbeat flap, collective stall) survived '
          'with zero intervention — %d recoveries, lost work %r '
          '(cadence %d), %d post-recovery steps bitwise-reproducible, '
          'every fault matched to a named supervisor decision in '
          '/statusz'
          % (stats['fault_kinds_injected'],
             'rpc fault, ' if stats['rpc_ok'] else '',
             stats['recoveries'], stats['lost_steps'], CADENCE,
             stats['bitwise_steps_verified']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
