"""Measure one cell as the driver does, in one call on the machine that
holds the chips:

    chiprun [--chips 4] --timeout 2400 -- python3 \
        benchmark/tools/measure.py --workload <cell> [--sets 2] [--runs 6] \
        [--first-seed 100] [--traced 1] [--keep-trace DIR]

Runs ``BENCHMARK.json``'s command ``--traced`` times with ``--trace 1``
and then ``sets x runs`` times with ``--trace 0``, every run a new
process with another ``--seed`` (this parent never touches JAX, so each
child gets the chip).  Prints per set and metric the
median and the spread (distance between the quartiles over the
median), the wider of the sets' spreads, and how far the second set's
median lies from the first's; writes every result line to
``chiprun_out/measure/<cell>.jsonl``.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_once(manifest, cell, seed, trace, log, extra=()):
    command = manifest['command'] + [
        '--workload', cell, '--seed', str(seed),
        '--seconds', str(manifest['run_seconds']), '--trace', str(trace)]
    command += list(extra)
    t0 = time.time()
    done = subprocess.run(command, cwd=ROOT, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True)
    log.write(done.stdout + done.stderr)
    lines = done.stdout.strip().splitlines()
    if done.returncode != 0 or not lines:
        raise SystemExit('run failed (exit %d):\n%s' % (
            done.returncode, (done.stdout + done.stderr)[-3000:]))
    result = json.loads(lines[-1])
    result.update(seed=seed, trace=trace, process_seconds=time.time() - t0)
    return result


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4, method='inclusive')
    return (q3 - q1) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--sets', type=int, default=2)
    ap.add_argument('--runs', type=int, default=6)
    ap.add_argument('--first-seed', type=int, default=100)
    ap.add_argument('--traced', type=int, default=1)
    ap.add_argument('--keep-trace', metavar='DIR',
                    help='handed to the traced runs')
    args = ap.parse_args()
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    out_dir = os.path.join(ROOT, 'chiprun_out', 'measure')
    os.makedirs(out_dir, exist_ok=True)
    seed = args.first_seed
    sets = []
    with open(os.path.join(out_dir, args.workload + '.jsonl'), 'w') as out, \
            open(os.path.join(out_dir, args.workload + '.log'), 'w') as log:
        def record(result, **tags):
            result.update(tags)
            out.write(json.dumps(result) + '\n')
            out.flush()
            print(json.dumps({k: result[k] for k in (
                'set', 'seed', 'trace', 'correct', 'process_seconds',
                'metrics')}), flush=True)

        keep = ['--keep-trace', args.keep_trace] if args.keep_trace else []
        for _ in range(args.traced):
            record(run_once(manifest, args.workload, seed, 1, log, keep),
                   set='traced')
            seed += 1
        for number in range(args.sets):
            results = []
            for _ in range(args.runs):
                results.append(run_once(manifest, args.workload, seed, 0,
                                        log))
                record(results[-1], set=number)
                seed += 1
            sets.append(results)

    print('\n%s: %d sets of %d runs' % (args.workload, args.sets, args.runs))
    for metric in sets[0][0]['metrics']:
        columns = [[r['metrics'][metric]['value'] for r in results]
                   for results in sets]
        if metric == 'setup_s' and not args.traced:
            columns[0] = columns[0][1:]     # the first run compiles
        medians = [statistics.median(c) for c in columns]
        spreads = [spread(c) for c in columns]
        print('%-12s medians %s; spreads %s; widest %.4f%%; last set '
              'against first %+.4f%%'
              % (metric, ' '.join('%.6g' % m for m in medians),
                 ' '.join('%.4f%%' % (100 * s) for s in spreads),
                 100 * max(spreads),
                 100 * (medians[-1] - medians[0]) / medians[0]))
    if not all(r['correct'] for results in sets for r in results):
        sys.exit('a run reported correct: false')


if __name__ == '__main__':
    main()
