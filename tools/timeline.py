"""Export a chrome://tracing file from a captured profile, merging the
fluid.trace host spans with the device trace when both exist.

Reference: tools/timeline.py converts the profiler's protobuf dump into
chrome-trace JSON.  The jax profiler (fluid.profiler wraps it) already
emits a gzipped chrome trace inside its plugin directory; this tool
locates it and writes a plain .json chrome://tracing / Perfetto can
open directly.  Since the fluid.trace PR, `fluid.profiler.start_trace`
also rides the span tracer along and `stop_trace` drops the host spans
as `<logdir>/host_trace.json` — when that file is present (or passed
via --host_trace), the output is ONE merged timeline: device kernels
on their original pids, host phase spans (bind / feed_h2d / dispatch /
compile / reader_wait / fetch_d2h) on a 'paddle_tpu host' process,
aligned on the pt_clock_sync annotation the capture emitted.

Usage: python tools/timeline.py --profile_path /tmp/profile \
           --timeline_path /tmp/timeline.json [--host_trace host.json]

`--scope NAME [NAME ...]` prints, instead, every instruction the capture
ran under those fluid scopes (`moe_dispatch moe_dispatch_grad`; the
first device's), longest first, with its pass, the opcode it holds, its
shapes, calls, ms, one call's MB and GB/s: what `--xla_dump_to` was
needed for.  `'(unscoped)'` is a name too: the instructions the scope
table gives no fluid op (the compiler's own copies and broadcasts), by
opcode and shape, no owner guessed.  It reads the
`<logdir>/device.trace.json` that `stop_trace` wrote.

Usage: python tools/timeline.py --profile_path /tmp/profile \
           --scope moe_dispatch moe_dispatch_grad '(unscoped)'

Job mode (`--job`) merges a whole MULTI-WORKER job instead: it pulls
every worker's /trace/dump over HTTP (--workers 'rank=host:port,...',
default $PADDLE_TPU_STATUS_WORKERS — the launcher's wire format) or
reads already-saved dump files (--dumps a.json b.json ...), re-homes
each rank's clock onto the shared unix-epoch anchor its dump carries,
and writes ONE Perfetto timeline with per-rank process tracks plus the
cross-rank skew report (fluid.trace.collect_job).

Usage: python tools/timeline.py --job --workers 0=h:9184,1=h:9185 \
           --timeline_path /tmp/job_timeline.json
       python tools/timeline.py --job --dumps w0.json w1.json \
           --timeline_path /tmp/job_timeline.json
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import sys


def find_trace(profile_path):
    pats = [os.path.join(profile_path, '**', '*.trace.json.gz'),
            os.path.join(profile_path, '**', '*.trace.json')]
    hits = []
    for p in pats:
        hits.extend(h for h in glob.glob(p, recursive=True)
                    if not h.endswith('host_trace.json'))
    if not hits:
        raise SystemExit(
            'no trace found under %s — capture one with '
            'fluid.profiler.start_trace(logdir)/stop_trace() around '
            'the steps to convert' % profile_path)
    return max(hits, key=os.path.getmtime)


def find_host_trace(profile_path):
    hits = glob.glob(os.path.join(profile_path, '**', 'host_trace.json'),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_device_events(src):
    opener = gzip.open if src.endswith('.gz') else open
    with opener(src, 'rt') as f:
        return json.load(f).get('traceEvents', [])


def merge(src, host_path, out_path):
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from paddle_tpu.fluid import trace as pt_trace
    with open(host_path) as f:
        host = json.load(f)
    merged = pt_trace.merge_device_trace(
        host.get('ptHostEvents', []), load_device_events(src),
        sync_host_us=host.get('ptSync'),
        capture_t0_us=host.get('ptCaptureT0'))
    pt_trace.write_chrome(out_path, merged)
    n_host = sum(1 for e in host.get('ptHostEvents', [])
                 if e.get('ph') == 'X')
    # counter tracks (memviz live-HBM classes) ride the host events
    # as 'C' samples; surface their presence so a silently-dark
    # memory axis is visible at merge time
    n_counters = sum(1 for e in host.get('ptHostEvents', [])
                     if e.get('ph') == 'C')
    return n_host, n_counters


def print_scope(profile_path, scopes, top):
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from paddle_tpu.fluid import profiler
    hits = glob.glob(os.path.join(profile_path, '**', 'device.trace.json'),
                     recursive=True)
    if not hits:
        raise SystemExit(
            'no device.trace.json under %s: capture with fluid.profiler.'
            'start_trace(logdir)/stop_trace()' % profile_path)
    rows = profiler.instructions_under(
        load_device_events(max(hits, key=os.path.getmtime)), scopes)
    print('%d instructions under %s, %.3f ms in all (ms: an '
          "instruction's own time over all its calls in the capture)"
          % (len(rows), ' + '.join(scopes), sum(r['ms'] for r in rows)))
    print('%-28s %-24s %-10s %-12s %6s %10s %9s %8s  %s'
          % ('instruction', 'scope', 'pass', 'holds', 'calls', 'ms', 'MB',
             'GB/s', 'shapes'))

    def cell(value, form):
        return '-' if value is None else form % value

    for r in rows[:top]:
        print('%-28s %-24s %-10s %-12s %6d %10.3f %9s %8s  %s'
              % (r['name'], r['tf_op'], r['pass'] or '-', r['kind'] or '-',
                 r['calls'], r['ms'], cell(r['mb'], '%.2f'),
                 cell(r['gbps'], '%.1f'), r['shapes'] or ''))
    if len(rows) > top:
        print('(%d more, %.3f ms)' % (len(rows) - top,
                                     sum(r['ms'] for r in rows[top:])))
    return 0


def collect_job_cli(args):
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from paddle_tpu.fluid import trace as pt_trace
    if args.dumps:
        workers = [(str(i), p) for i, p in enumerate(args.dumps)]

        def fetch(path):
            with open(path) as f:
                return f.read()
    else:
        spec = args.workers or os.environ.get(
            'PADDLE_TPU_STATUS_WORKERS', '')
        if not spec:
            raise SystemExit(
                '--job needs --workers rank=host:port,... (or '
                'PADDLE_TPU_STATUS_WORKERS) or --dumps file.json ...')
        workers = spec
        fetch = None
    doc = pt_trace.collect_job(workers=workers, fetch=fetch,
                               out_path=args.timeline_path)
    job = doc.get('ptJob', {})
    n = sum(1 for e in doc['traceEvents'] if e.get('ph') == 'X')
    print('merged job timeline written to %s (%d ranks, %d span '
          'events; open in https://ui.perfetto.dev)'
          % (args.timeline_path, len(job.get('workers', {})), n))
    for rank, err in sorted(job.get('skipped', {}).items()):
        print('  SKIPPED rank %s: %s' % (rank, err))
    skew = job.get('skew')
    if skew:
        wall = skew['wall']
        print('  skew: slowest rank %s at p50 %.3f ms, %.2fx the '
              'cross-rank median (%.3f ms)'
              % (wall['slowest_rank'], wall['max_p50_ms'],
                 wall['skew_ratio'], wall['median_p50_ms']))
        worst = sorted(skew['phases'].items(),
                       key=lambda kv: -kv[1]['ratio'])[:3]
        for name, ph in worst:
            print('    phase %-14s rank %s %.3f ms/step '
                  '(%.2fx median)' % (name, ph['slowest_rank'],
                                      ph['max_ms'], ph['ratio']))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--profile_path', default='/tmp/profile')
    ap.add_argument('--timeline_path', default='/tmp/timeline.json')
    ap.add_argument('--host_trace', default=None,
                    help='host_trace.json written by fluid.profiler.'
                         'stop_trace (default: auto-discover under '
                         'profile_path)')
    ap.add_argument('--job', action='store_true',
                    help='merge a multi-worker job from /trace/dump '
                         'scrapes (--workers) or saved dump files '
                         '(--dumps) into one per-rank timeline')
    ap.add_argument('--workers', default=None,
                    help="job worker spec 'rank=host:port,...' "
                         '(default: $PADDLE_TPU_STATUS_WORKERS)')
    ap.add_argument('--dumps', nargs='*', default=None,
                    help='merge saved /trace/dump files instead of '
                         'scraping (each dump\'s own ptRank labels '
                         'it; argument order is the fallback)')
    ap.add_argument('--scope', nargs='+', default=None,
                    help='print the instructions the capture ran under '
                         "these fluid scopes ('(unscoped)': under "
                         'none) instead of writing a timeline')
    ap.add_argument('--top', type=int, default=60,
                    help='rows --scope prints')
    args = ap.parse_args()
    if args.job:
        return collect_job_cli(args)
    if args.scope:
        return print_scope(args.profile_path, args.scope, args.top)
    src = find_trace(args.profile_path)
    host_path = args.host_trace or find_host_trace(args.profile_path)
    if host_path:
        n_host, n_counters = merge(src, host_path, args.timeline_path)
        print('merged chrome trace written to %s (%d host spans + '
              '%d counter samples + device events; open in '
              'chrome://tracing or https://ui.perfetto.dev)'
              % (args.timeline_path, n_host, n_counters))
        return 0
    # device-only capture: passthrough, byte-identical to the source
    if src.endswith('.gz'):
        with gzip.open(src, 'rb') as f_in, \
                open(args.timeline_path, 'wb') as f_out:
            shutil.copyfileobj(f_in, f_out)
    else:
        shutil.copy(src, args.timeline_path)
    print('chrome trace written to %s (open in chrome://tracing or '
          'https://ui.perfetto.dev)' % args.timeline_path)
    return 0


if __name__ == '__main__':
    sys.exit(main())
