"""Render or diff fluid.monitor JSONL dumps, or a fluid.trace step
report.

Usage:
  python tools/stat_summary.py run.jsonl            # render last line
  python tools/stat_summary.py before.jsonl after.jsonl   # diff
  python tools/stat_summary.py --live               # snapshot of THIS
                                                    # process's registry
  python tools/stat_summary.py --steps dump.json    # per-step phase
                                                    # report from a
                                                    # trace.dump() file
  python tools/stat_summary.py --steps job.json --rank 1
                                  # one rank's steps out of a merged
                                  # job dump (trace.collect_job /
                                  # tools/timeline.py --job output)
  python tools/stat_summary.py --plan run.jsonl     # collective-
                                  # planner rollup: arm mix, wire vs
                                  # dense-equivalent bytes, cost-model
                                  # predicted vs measured
  python tools/stat_summary.py --memory run.jsonl   # device-memory
                                  # rollup: live HBM by class, high
                                  # watermark, budget utilization,
                                  # per-program peaks, OOM/watermark
                                  # incident counts (fluid.memviz)
  python tools/stat_summary.py --autoshard run.jsonl
                                  # auto-sharding planner rollup:
                                  # chosen dp/fsdp/tp layout, plan
                                  # builds/reuse, candidates priced,
                                  # HBM-gate rejections, unpriced
                                  # terms (parallel/plan.py)
  python tools/stat_summary.py --verify run.jsonl
                                  # static-verifier rollup: programs
                                  # checked/clean, diagnostics by
                                  # class, seeded chaos mutations,
                                  # verify wall time
                                  # (fluid.progcheck)
  python tools/stat_summary.py --watch 2 http://host:port/metrics.json
  python tools/stat_summary.py --watch 2 run.jsonl [--iterations K]
                                  # LIVE mode: re-poll the source
                                  # every N seconds and render each
                                  # series' trend — reset-aware rates
                                  # for counters, levels for gauges,
                                  # windowed mean for histograms,
                                  # sparklines — via the
                                  # fluid.timeseries window math

One-file mode prints the last record as a sorted table (counters,
gauges, histogram sum/count).  Two-file mode prints after-minus-before
for counters and histograms — the per-interval rates a trajectory of
dump_jsonl() lines is for (e.g. diffing two BENCH rounds' monitor
sections).  --steps reads the flight-recorder dump fluid.trace.dump()
writes (its 'ptSteps' records) and prints the bind / feed_h2d /
dispatch / fetch_d2h breakdown per step with p50/p99/slowest rollups.
Companion of tools/timeline.py (traces) and the profiler table: this
one reads the ALWAYS-ON stats.
"""

import json
import os
import sys


def load_last(path):
    """Last JSONL record of `path` (one dump_jsonl line per step)."""
    last = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                last = json.loads(line)
    if last is None:
        raise ValueError('no records in %s' % path)
    return last


def _rows(rec):
    rows = []
    for n, v in sorted(rec.get('counters', {}).items()):
        rows.append((n, 'counter', v))
    for n, v in sorted(rec.get('gauges', {}).items()):
        rows.append((n, 'gauge', v))
    for n, h in sorted(rec.get('histograms', {}).items()):
        rows.append((n + '/count', 'histogram', float(h['count'])))
        rows.append((n + '/sum', 'histogram', h['sum']))
    return rows


def _fmt(v):
    if v == int(v) and abs(v) < 1e15:
        return '%d' % int(v)
    return '%.6g' % v


def render(rec, out=None):
    out = out if out is not None else sys.stdout
    out.write('%-52s %-10s %14s\n' % ('stat', 'kind', 'value'))
    for n, kind, v in _rows(rec):
        out.write('%-52s %-10s %14s\n' % (n, kind, _fmt(v)))


def diff(before, after, out=None):
    """after − before for cumulative stats; gauges show both levels."""
    out = out if out is not None else sys.stdout
    b = dict((n, v) for n, k, v in _rows(before) if k != 'gauge')
    out.write('%-52s %14s\n' % ('stat', 'delta'))
    for n, kind, v in _rows(after):
        if kind == 'gauge':
            continue
        out.write('%-52s %14s\n' % (n, _fmt(v - b.get(n, 0.0))))
    ga = after.get('gauges', {})
    gb = before.get('gauges', {})
    for n in sorted(set(ga) | set(gb)):
        out.write('%-52s %14s -> %s\n'
                  % (n + ' (gauge)', _fmt(gb.get(n, 0.0)),
                     _fmt(ga.get(n, 0.0))))


def steps_report(path, out=None, rank=None):
    """Per-step phase table from a fluid.trace.dump() file; `rank`
    filters a merged job dump (trace.collect_job tags each record with
    its worker rank) down to one worker's steps."""
    # resolve stdout at CALL time: the module may be imported while a
    # test harness has stdout captured, and a def-time default would
    # pin that (soon-closed) stream
    out = out if out is not None else sys.stdout
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.fluid import trace as pt_trace
    with open(path) as f:
        recs = json.load(f).get('ptSteps', [])
    if rank is not None:
        ranks = sorted({str(r.get('rank')) for r in recs
                        if r.get('rank') is not None})
        recs = [r for r in recs if str(r.get('rank')) == str(rank)]
        if not recs:
            out.write('no step records for rank %s in %s (ranks '
                      'present: %s)\n'
                      % (rank, path, ', '.join(ranks) or 'none'))
            return 1
        out.write('rank %s:\n' % rank)
    if not recs:
        out.write('no step records in %s (was the tracer enabled?)\n'
                  % path)
        return 1
    rep = pt_trace.report_from_records(recs)
    out.write(pt_trace.format_step_report(rep) + '\n')
    return 0


def plan_report(rec, out=None):
    """Collective-planner rollup from one monitor record: which arms
    ran (comms/plan_arm/*), the wire bytes the plan moved vs what flat
    dense would have (the measured saving), and the cost model's
    predicted-vs-measured seconds.  The same numbers /statusz's
    comms_plan section serves live."""
    out = out if out is not None else sys.stdout
    c = rec.get('counters', {})
    arms = {n.rsplit('/', 1)[1]: v for n, v in c.items()
            if n.startswith('comms/plan_arm/')}
    if not arms:
        out.write('no comms/plan_arm/* counters: the collective '
                  'planner never ran in this record\n')
        return 1
    total = sum(arms.values())
    out.write('collective planner rollup\n')
    for arm in sorted(arms):
        out.write('  arm %-8s %10d dispatches (%.0f%%)\n'
                  % (arm, arms[arm], 100.0 * arms[arm] / total))
    wire = c.get('comms/plan_wire_bytes', 0.0)
    dense = c.get('comms/plan_dense_equiv_bytes', 0.0)
    if dense > 0:
        out.write('  wire bytes      %14s vs dense-equiv %s '
                  '(%.2fx reduction)\n'
                  % (_fmt(wire), _fmt(dense),
                     dense / wire if wire > 0 else float('inf')))
    fused = c.get('comms/plan_fused_grads', 0.0)
    if fused:
        out.write('  fused grads     %14s\n' % _fmt(fused))
    pred = c.get('comms/plan_predicted_seconds', 0.0)
    meas = c.get('comms/plan_measured_seconds', 0.0)
    if meas > 0:
        out.write('  cost model      predicted %.6gs vs measured '
                  '%.6gs (ratio %.2f)\n' % (pred, meas, pred / meas))
    return 0


def autoshard_report(rec, out=None):
    """Auto-sharding planner rollup from one monitor record: the
    chosen (dp, fsdp, tp) layout gauges, plan build/reuse volume, the
    candidate table size, HBM-gate rejections and the unpriced-term
    honesty counter — the offline form of /statusz's auto_shard
    section."""
    out = out if out is not None else sys.stdout
    c = rec.get('counters', {})
    g = rec.get('gauges', {})
    builds = c.get('parallel/plan_builds', 0.0)
    if not builds:
        out.write('no parallel/plan_* counters: the auto-sharding '
                  'planner never ran in this record '
                  '(FLAGS_auto_shard)\n')
        return 1
    out.write('auto-sharding planner rollup\n')
    out.write('  layout          dp=%d fsdp=%d tp=%d\n'
              % (g.get('parallel/plan_layout_dp', 0),
                 g.get('parallel/plan_layout_fsdp', 0),
                 g.get('parallel/plan_layout_tp', 0)))
    out.write('  plan builds     %10d (reused %d)\n'
              % (builds, c.get('parallel/plan_reused', 0.0)))
    out.write('  candidates      %10d priced\n'
              % c.get('parallel/plan_candidates', 0.0))
    rej = c.get('parallel/plan_hbm_rejected', 0.0)
    if rej:
        out.write('  HBM gate        %10d layouts rejected before '
                  'compile\n' % rej)
    unpriced = c.get('parallel/plan_unpriced', 0.0)
    if unpriced:
        out.write('  unpriced terms  %10d (no comms_model.json '
                  'entry: heuristic byte pricing)\n' % unpriced)
    out.write('  params          %10d sharded, %d replicated\n'
              % (c.get('parallel/plan_params_sharded', 0.0),
                 c.get('parallel/plan_params_replicated', 0.0)))
    return 0


def _fmt_bytes(b):
    b = float(b)
    if b >= 1 << 30:
        return '%.2fGiB' % (b / (1 << 30))
    if b >= 1 << 20:
        return '%.1fMiB' % (b / (1 << 20))
    if b >= 1024:
        return '%.1fKiB' % (b / 1024.0)
    return '%dB' % int(b)


def memory_report(rec, out=None):
    """Device-memory rollup from one monitor record: the memviz
    live-HBM classes, high watermark, budget utilization, per-program
    attributed peaks and incident counters — the offline form of the
    /statusz memory section."""
    out = out if out is not None else sys.stdout
    g = rec.get('gauges', {})
    c = rec.get('counters', {})
    total = g.get('memviz/live_bytes_total')
    if total is None and not any(n.startswith('memviz/')
                                 for n in list(g) + list(c)):
        out.write('no memviz/* stats in this record: enable '
                  'FLAGS_memviz for the live-HBM sampler\n')
        return 1
    out.write('device-memory rollup (fluid.memviz)\n')
    if total is not None:
        classes = {n.rsplit('/', 1)[1]: v for n, v in g.items()
                   if n.startswith('memviz/live_bytes/')}
        out.write('  live HBM        %12s across %d arrays (%s)\n'
                  % (_fmt_bytes(total),
                     int(g.get('memviz/live_arrays', 0)),
                     ', '.join('%s=%s' % (k, _fmt_bytes(classes[k]))
                               for k in sorted(classes))))
        hwm = g.get('memviz/live_bytes_hwm')
        if hwm is not None:
            out.write('  high watermark  %12s\n' % _fmt_bytes(hwm))
        util = g.get('memviz/budget_utilization')
        if util is not None:
            out.write('  budget          %11.1f%% utilized\n'
                      % (100.0 * util))
    peaks = sorted(((n.rsplit('/', 1)[1], v) for n, v in g.items()
                    if n.startswith('memviz/program_peak_bytes/')),
                   key=lambda kv: -kv[1])
    for prog, peak in peaks[:8]:
        out.write('  program %-12s peak %12s\n'
                  % (prog, _fmt_bytes(peak)))
    in_use = g.get('memviz/hwm_in_use_bytes')
    if in_use is not None:
        # the allocator's own marks as last read around a new
        # executable's first run (memviz.high_water() names who
        # raised each); no FLAGS_memviz needed
        out.write('  allocator marks %12s in use, %s reserved\n'
                  % (_fmt_bytes(in_use), _fmt_bytes(
                      g.get('memviz/hwm_reserved_bytes', 0.0))))
    for name, label in (('memviz/samples', 'census samples'),
                        ('memviz/segments_attributed',
                         'segments attributed'),
                        ('memviz/watermark_trips', 'watermark trips'),
                        ('memviz/spike_trips', 'spike trips'),
                        ('memviz/oom_incidents', 'OOM incidents'),
                        ('memviz/oom_dumps', 'OOM dumps'),
                        ('memviz/analysis_unavailable',
                         'analysis unavailable')):
        v = c.get(name)
        if v:
            out.write('  %-22s %10d\n' % (label, v))
    return 0


def verify_report(rec, out=None):
    """Static-verifier rollup from one monitor record: programs
    checked vs clean, error/warning volume, the per-diagnostic-class
    breakdown (sorted loudest first), seeded chaos mutations, and the
    verification wall-time histogram — the offline form of /statusz's
    verify section (fluid.progcheck)."""
    out = out if out is not None else sys.stdout
    c = rec.get('counters', {})
    h = rec.get('histograms', {})
    programs = c.get('verify/programs', 0.0)
    if not programs:
        out.write('no verify/* counters: the static verifier never '
                  'ran in this record (FLAGS_program_verify, '
                  'Executor.warmup, or a transpiler output)\n')
        return 1
    out.write('program-verifier rollup\n')
    out.write('  programs checked %9d (%d fully clean)\n'
              % (programs, c.get('verify/clean', 0.0)))
    out.write('  errors           %9d\n' % c.get('verify/errors', 0.0))
    out.write('  warnings         %9d\n'
              % c.get('verify/warnings', 0.0))
    prefix = 'verify/diagnostics/'
    by_class = sorted(((k[len(prefix):], v) for k, v in c.items()
                       if k.startswith(prefix)),
                      key=lambda kv: -kv[1])
    for cls, n in by_class:
        out.write('    %-22s %8d\n' % (cls, n))
    mut = c.get('verify/mutations', 0.0)
    if mut:
        out.write('  seeded mutations %9d (faultinject '
                  'progcheck.mutate)\n' % mut)
    vs = h.get('verify/seconds')
    if vs and vs.get('count'):
        out.write('  verify wall      %9.1f ms mean over %d runs\n'
                  % (1e3 * vs['sum'] / vs['count'], vs['count']))
    return 0


def _poll_source(source):
    """One sample of `source` -> (now, counters, gauges, hists) where
    hists is {name: (count, sum, edges, counts)} (edges/counts None
    when the source only records the count/sum rollup).  The source is
    a /metrics.json URL (live scrape) or a dump_jsonl trajectory file
    (newest line of a growing file)."""
    import time
    if source.startswith('http://') or source.startswith('https://'):
        import urllib.request
        with urllib.request.urlopen(source, timeout=10) as resp:
            doc = json.loads(resp.read())
        state = doc.get('state', doc)
        hists = {n: (h.get('count', 0), h.get('sum', 0.0),
                     h.get('edges'), h.get('counts'))
                 for n, h in (state.get('hists') or {}).items()}
        return (time.time(), dict(state.get('counters') or {}),
                dict(state.get('gauges') or {}), hists)
    rec = load_last(source)
    hists = {n: (h.get('count', 0), h.get('sum', 0.0), None, None)
             for n, h in (rec.get('histograms') or {}).items()}
    return (rec.get('ts', time.time()),
            dict(rec.get('counters') or {}),
            dict(rec.get('gauges') or {}), hists)


def watch(interval, source, iterations=None, out=None):
    """Live trend view: poll `source` every `interval` seconds,
    accumulate (ts, step, value) points per series, and render rates /
    levels / windowed means with sparklines — all derived through
    fluid.timeseries' window math on plain point lists, the same code
    the /timeseries endpoint runs on the in-process rings."""
    out = out if out is not None else sys.stdout
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import time
    from paddle_tpu.fluid import timeseries as ts
    keep = 256
    series = {}   # name -> {'kind': ..., 'points': [...], 'edges': e}
    tick = 0
    while iterations is None or tick < iterations:
        if tick:
            time.sleep(interval)
        tick += 1
        try:
            now, counters, gauges, hists = _poll_source(source)
        except Exception as e:
            out.write('watch: poll of %s failed: %s\n' % (source, e))
            continue
        for n, v in counters.items():
            s = series.setdefault(n, {'kind': 'counter', 'points': []})
            s['points'] = (s['points'] + [(now, None, float(v))])[-keep:]
        for n, v in gauges.items():
            s = series.setdefault(n, {'kind': 'gauge', 'points': []})
            s['points'] = (s['points'] + [(now, None, float(v))])[-keep:]
        for n, (cnt, total, edges, counts) in hists.items():
            s = series.setdefault(n, {'kind': 'hist', 'points': [],
                                      'edges': edges})
            s['edges'] = edges or s.get('edges')
            s['points'] = (s['points'] +
                           [(now, None, int(cnt), float(total),
                             tuple(counts or ()))])[-keep:]
        out.write('\n-- watch tick %d  %s  (%d series, %gs interval)\n'
                  % (tick, time.strftime('%H:%M:%S',
                                         time.localtime(now)),
                     len(series), interval))
        out.write('%-46s %-8s %12s %12s  %s\n'
                  % ('stat', 'kind', 'last', 'per_sec', 'trend'))
        for n in sorted(series):
            s = series[n]
            pts = s['points']
            if s['kind'] == 'counter':
                deltas = [d for _t, _s, d in ts.counter_deltas(pts)]
                rate = ts.rate_per_s(pts)
                if not deltas or not any(deltas):
                    continue    # idle counters only add noise live
                out.write('%-46s %-8s %12s %12s  %s\n'
                          % (n, 'counter', _fmt(pts[-1][2]),
                             '-' if rate is None else '%.4g' % rate,
                             ts.spark(deltas)))
            elif s['kind'] == 'gauge':
                st = ts.gauge_stats(pts)
                vals = [p[2] for p in pts if p[2] is not None]
                out.write('%-46s %-8s %12s %12s  %s\n'
                          % (n, 'gauge', _fmt(st['last']), '-',
                             ts.spark(vals)))
            else:
                hw = ts.hist_window(s.get('edges') or (), pts)
                rate = hw.get('count', 0)
                elapsed = pts[-1][0] - pts[0][0] if len(pts) > 1 else 0
                per_s = (rate / elapsed) if elapsed > 0 else None
                means = [(b[3] - a[3]) / (b[2] - a[2])
                         for a, b in zip(pts, pts[1:])
                         if b[2] > a[2]]
                if not means:
                    continue
                mean_s = hw['mean']
                out.write('%-46s %-8s %12s %12s  %s\n'
                          % (n, 'hist',
                             '-' if mean_s is None
                             else '%.4g' % mean_s,
                             '-' if per_s is None else '%.4g' % per_s,
                             ts.spark(means)))
        out.flush()
    return 0


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == '--watch':
        iters = None
        if '--iterations' in argv:
            i = argv.index('--iterations')
            if i + 1 >= len(argv):
                sys.stderr.write(__doc__)
                return 2
            iters = int(argv[i + 1])
            del argv[i:i + 2]
        if len(argv) != 3:
            sys.stderr.write(__doc__)
            return 2
        return watch(float(argv[1]), argv[2], iterations=iters)
    if argv and argv[0] == '--verify':
        if len(argv) != 2:
            sys.stderr.write(__doc__)
            return 2
        return verify_report(load_last(argv[1]))
    if argv and argv[0] == '--memory':
        if len(argv) != 2:
            sys.stderr.write(__doc__)
            return 2
        return memory_report(load_last(argv[1]))
    if argv and argv[0] == '--autoshard':
        if len(argv) != 2:
            sys.stderr.write(__doc__)
            return 2
        return autoshard_report(load_last(argv[1]))
    if argv and argv[0] == '--plan':
        if len(argv) != 2:
            sys.stderr.write(__doc__)
            return 2
        return plan_report(load_last(argv[1]))
    if argv and argv[0] == '--steps':
        rank = None
        if '--rank' in argv:
            i = argv.index('--rank')
            if i + 1 >= len(argv):
                sys.stderr.write(__doc__)
                return 2
            rank = argv[i + 1]
            del argv[i:i + 2]
        if len(argv) != 2:
            sys.stderr.write(__doc__)
            return 2
        return steps_report(argv[1], rank=rank)
    if argv == ['--live']:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        os.environ.setdefault('JAX_PLATFORMS', 'cpu')
        from paddle_tpu.fluid import monitor
        rec = {'counters': monitor._counters, 'gauges': monitor._gauges,
               'histograms': {n: {'count': h[3], 'sum': h[2]}
                              for n, h in monitor._hists.items()}}
        render(rec)
        return 0
    if len(argv) == 1:
        render(load_last(argv[0]))
        return 0
    if len(argv) == 2:
        diff(load_last(argv[0]), load_last(argv[1]))
        return 0
    sys.stderr.write(__doc__)
    return 2


if __name__ == '__main__':
    try:
        sys.exit(main())
    except BrokenPipeError:  # e.g. `stat_summary.py x.jsonl | head`
        sys.exit(0)
