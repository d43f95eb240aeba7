"""Op-cost plane gate: the attribution plane must name where a REAL
run's milliseconds go per op instance, agree with the step report, and
cost nothing when off (the fluid.opprof analog of check_memviz.py's
contract).

Runs a real LeNet training job (through Executor.warmup so the replay
snapshots ride warmed segments) with FLAGS_opprof on at snapshot
cadence 1 and the tracer live, then checks:

  1. replay: every stashed segment replays eagerly into per-instance
     rows with nonzero ms/step and output bytes, layers resolved;
  2. agreement: each segment's normalized instance costs sum to its
     measured synchronous wall, and the summed measured walls agree
     with trace.step_report()'s dispatch phase for the snapshot step
     within 10% (the acceptance band — both read the same interval);
  3. worklist: op_worklist.json is schema-valid and names >= 3 ranked
     candidates with per-instance ms/step (LeNet holds no op a pallas
     kernel serves; tests/test_opprof.py checks the cross-reference);
  4. /statusz + /opprof: the op_costs section and the replay endpoint
     serve the same registry over a live status server;
  5. disabled: with FLAGS_opprof off (the default), zero snapshots are
     taken and the steady-state hot-path budgets of
     tools/check_hot_path.py must still hold.

Run from `make check` (CPU: JAX_PLATFORMS=cpu).
"""

import json
import os
import sys


def main():
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tempfile
    import urllib.request
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import health, monitor, opprof, trace
    from paddle_tpu import models

    failures = []
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        feeds, pred, loss, acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(64, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (64, 1)).astype('int64')}

    fluid.set_flags({'FLAGS_opprof': True,
                     'FLAGS_opprof_snapshot_steps': 1})
    trace.enable()
    srv = health.serve(port=0)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            exe.warmup(main_p,
                       feed_shapes={'img': ((64, 1, 28, 28), 'float32'),
                                    'label': ((64, 1), 'int64')},
                       fetch_list=[loss], wait=True)
            for _ in range(3):
                exe.run(main_p, feed=feed, fetch_list=[loss])

            # 1. eager replay into per-instance rows
            done = opprof.replay_all()
            bad = {k: v for k, v in done.items()
                   if not isinstance(v, int)}
            if not done:
                failures.append('no snapshots stashed on a warmed run '
                                'with FLAGS_opprof on')
            if bad:
                failures.append('replay errors: %r' % bad)
            rep = opprof.report()
            replay_segs = [s for s in rep['segments']
                           if s['source'] == 'replay']
            if not replay_segs:
                failures.append('replay produced no registry rows')
            if not any(c['bytes_per_step'] > 0 for c in rep['top']):
                failures.append('no instance recorded output bytes')
            if not any(c.get('layer') for c in rep['top']):
                failures.append('no instance resolved a layer label '
                                '(plan-rule reuse broken)')

            # 2. normalization + step-report agreement (10% band)
            for seg in replay_segs:
                if seg['measured_ms'] is None:
                    failures.append('segment %s has no measured wall'
                                    % seg['segment'])
                    continue
                if abs(seg['attributed_ms'] - seg['measured_ms']) > \
                        1e-3 * max(seg['measured_ms'], 1e-9):
                    failures.append(
                        'segment %s instance sum %.4f != measured '
                        '%.4f ms' % (seg['segment'],
                                     seg['attributed_ms'],
                                     seg['measured_ms']))
            sr = trace.step_report()
            disp_ms = sr['steps'][-1]['phases_ms'].get('dispatch', 0.0) \
                if sr['steps'] else 0.0
            total_measured = sum(s['measured_ms'] or 0.0
                                 for s in replay_segs)
            if disp_ms <= 0:
                failures.append('step report carries no dispatch '
                                'phase on the snapshot step')
            elif abs(total_measured - disp_ms) > 0.10 * disp_ms:
                failures.append(
                    'replay walls %.4f ms vs step-report dispatch '
                    '%.4f ms: outside the 10%% agreement band'
                    % (total_measured, disp_ms))

            # 3. the worklist artifact
            wl_path = os.path.join(
                tempfile.mkdtemp(prefix='pt_opprof_'),
                'op_worklist.json')
            opprof.write_worklist(wl_path)
            with open(wl_path) as f:
                doc = json.load(f)
            cands = doc.get('candidates') or []
            if len(cands) < 3:
                failures.append('worklist names %d candidates, need '
                                '>= 3' % len(cands))
            for c in cands:
                if not (c.get('ms_per_step', 0) > 0 and c.get('ops')
                        and c.get('rank')):
                    failures.append('underspecified candidate %r' % c)
                    break

            # 4. /statusz op_costs + /opprof off the live server
            with urllib.request.urlopen('%s/statusz' % srv.url,
                                        timeout=10) as resp:
                sz = json.loads(resp.read().decode('utf-8'))
            oc = sz.get('op_costs') or {}
            if not oc.get('top'):
                failures.append('/statusz op_costs has no top-K table')
            with urllib.request.urlopen('%s/opprof' % srv.url,
                                        timeout=60) as resp:
                op_doc = json.loads(resp.read().decode('utf-8'))
            if not (op_doc.get('report', {}).get('top') and
                    'worklist' in op_doc):
                failures.append('/opprof endpoint serves no '
                                'report/worklist')

        print('opprof: %d replayed segments, %d instances, dispatch '
              'agreement %.4f vs %.4f ms, %d worklist candidates'
              % (len(replay_segs), len(rep['top']), total_measured,
                 disp_ms, len(cands)))
    finally:
        health.stop()
        trace.disable()
        trace.reset()
        fluid.set_flags({'FLAGS_opprof': False,
                         'FLAGS_opprof_snapshot_steps': 16})
        opprof.reset()
        monitor.reset()

    # 5. disabled-path budgets: FLAGS_opprof off must keep the PR-2
    # hot path byte-identical (one flag read per step) and take zero
    # snapshots
    import check_hot_path
    rc = check_hot_path.main()
    if rc != 0:
        failures.append('check_hot_path budgets violated with opprof '
                        'disabled (rc=%d)' % rc)
    if monitor.counter_value('opprof/snapshots'):
        failures.append('snapshots taken with FLAGS_opprof off')

    if failures:
        for f in failures:
            print('OPPROF GATE  ' + f)
        return 1
    print('opprof: replay + agreement + worklist + statusz + disabled '
          'budgets all hold')
    return 0


if __name__ == '__main__':
    sys.exit(main())
