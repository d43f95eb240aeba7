"""One cell of the benchmark, in one process, on the machine it is
started on:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It trains the cell's configuration through the normal entry points
(``fluid.Program`` -> ``fluid.Executor.run``, or a ``CompiledProgram``
over a mesh) on one fixed, seeded, device-resident batch, and prints as
its LAST stdout line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  Earlier
lines carry losses, block times, cache hits and versions.

Everything that belongs to one cell, configuration, model family,
layout or per-layer metric is a file found by the name ``BENCHMARK.json``
gives; this file holds no such name.  It needs a TPU with as many chips
as the cell asks for and exits non-zero, with no result line, without
them: nothing here falls back to the CPU.

A *step* is one ``Executor.run`` of the train program.  A *block* is
``steps_per_block - 1`` steps dispatched with ``fetch_list=[]`` and one
that fetches the loss (users print the loss every N steps; a fetch per
step would serialise host and device).  ``--trace 0`` runs whole blocks
until ``--seconds`` have passed and reports the end-to-end metrics;
``--trace 1`` records the profiler over one block and reports the
per-layer metrics.
"""

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import math                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import statistics           # noqa: E402
import sys                  # noqa: E402
import traceback            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the platforms a measurement may run on; the CPU rehearsal under
# benchmark/tests widens this in the test and nowhere else
ACCELERATORS = ('tpu',)
# dropout's stream is a constant of the train program, not an input:
# op seeds are baked into the compiled step, so a seed of the run in
# their place would compile every run anew.  --seed selects the startup
# values and the batch.
TRAIN_PROGRAM_SEED = 42
SPAN = 'bench/'
# where a traced run keeps its profile: inside the checkout, ignored by git
OUT_DIR = os.path.join(ROOT, '.bench_out')


def say(msg):
    print('[%7.1fs] %s' % (time.time() - T_START, msg), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(directory, name):
    """``<HERE>/<directory>/<name>.py`` as a module, by path: a later
    PR adds a file and edits nothing."""
    path = os.path.join(HERE, directory, name + '.py')
    spec = importlib.util.spec_from_file_location(
        'benchmark_%s_%s' % (directory, name.replace('-', '_')), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell(object):
    """One entry of ``workloads`` with the files its names point at."""

    def __init__(self, manifest, name):
        cells = {w['name']: w for w in manifest['workloads']}
        if name not in cells:
            raise SystemExit('no workload %r in BENCHMARK.json (have: %s)'
                             % (name, ', '.join(sorted(cells))))
        entry = cells[name]
        configs = {c['name']: c for c in manifest['configs']}
        self.name = name
        self.chips = entry['chips']
        self.config = load_json(
            os.path.join(ROOT, configs[entry['config']]['file']))
        self.traffic = load_json(os.path.join(
            HERE, 'workloads', entry['traffic'] + '.json'))
        self.family = load_module('families', self.config['family'])
        self.layout = load_module('layouts', self.traffic['layout'])
        self.metrics = {
            group: [m for m in manifest[group]
                    if name in m.get('workloads', [name])]
            for group in ('end_to_end', 'per_layer')}

    @property
    def batch(self):
        return self.traffic['batch_per_chip'] * self.chips

    @property
    def items_per_step(self):
        return self.batch * self.family.items_per_sample(
            self.config, self.traffic)


def require_devices(chips):
    """The cell's chips, or exit non-zero with no result line."""
    import jax
    devices = jax.devices()
    if devices[0].platform not in ACCELERATORS:
        raise SystemExit(
            'benchmark/run.py needs a TPU and found none: '
            'jax.devices()[0].platform is %r' % devices[0].platform)
    if len(devices) < chips:
        raise SystemExit('this cell needs %d chips; %d attached'
                         % (chips, len(devices)))
    return devices[:chips]


def build_programs(cell, seed):
    """(main, startup, for_test clone taken before minimize, loss,
    parameter names in creation order)."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = TRAIN_PROGRAM_SEED
    startup.random_seed = 1 + seed          # 0 would mean "unseeded"
    settings = dict(cell.config['optimizer'])
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = cell.family.build(cell.config, cell.traffic)
        params = [p.name for p in main.all_parameters()]
        test = main.clone(for_test=True)
        optimizer = getattr(fluid.optimizer, settings.pop('type'))(
            **settings)
        if 'amp' in cell.config:
            optimizer = fluid.contrib.mixed_precision.decorate(
                optimizer, **cell.config['amp'])
        optimizer.minimize(loss)
    return main, startup, test, loss, params


def scalar(fetched):
    import numpy as np
    return float(np.asarray(fetched[0]).ravel()[0])


def reference_check(cell, exe, test, loss, params, host_batch):
    """The for_test program against the family's plain reference, on
    the seeded weights as the startup program left them and on the
    first ``reference_samples`` samples of the batch."""
    import jax
    import paddle_tpu.fluid as fluid
    n = cell.traffic['reference_samples']
    small = {k: jax.device_put(v[:n]) for k, v in host_batch.items()}
    got = scalar(exe.run(test, feed=small, fetch_list=[loss]))
    scope = fluid.global_scope()
    weights = [fluid.core.as_array(scope.find_var(p)) for p in params]

    def reference(weights, feed):
        return cell.family.reference_loss(cell.config, cell.traffic,
                                          weights, feed)

    want = float(jax.jit(reference)(weights, small))
    rtol = cell.family.REFERENCE_RTOL
    ok = math.isfinite(got) and abs(got - want) <= rtol * abs(want)
    say('reference check on %d samples: program %.6f, plain f32 '
        'reference %.6f, relative difference %.2e (tolerance %g): %s'
        % (n, got, want, abs(got - want) / abs(want), rtol,
           'ok' if ok else 'FAILED'))
    return ok


class Runner(object):
    """The train loop of one cell: blocks of steps on the fixed batch."""

    def __init__(self, cell, exe, target, feed, loss):
        self.exe, self.target, self.feed, self.loss = exe, target, feed, loss
        self.quiet_steps = cell.traffic['steps_per_block'] - 1

    def step(self):
        self.exe.run(self.target, feed=self.feed, fetch_list=[])

    def fetch_step(self):
        return scalar(self.exe.run(self.target, feed=self.feed,
                                   fetch_list=[self.loss]))

    def block(self):
        for _ in range(self.quiet_steps):
            self.step()
        return self.fetch_step()

    def traced_block(self):
        """One block with every run inside a profiler annotation and
        the host's clock around it -> (loss, [ms per quiet run],
        [(annotation name, perf_counter at its start)])."""
        import jax.profiler as jp
        marks = []

        def annotated(kind, i, call):
            name = '%s%s#%d' % (SPAN, kind, i)
            marks.append((name, time.perf_counter()))
            with jp.TraceAnnotation(name):
                t = time.perf_counter()
                out = call()
                return out, (time.perf_counter() - t) * 1e3

        host_ms = [annotated('run', i, self.step)[1]
                   for i in range(self.quiet_steps)]
        loss, _ = annotated('fetch_run', self.quiet_steps, self.fetch_step)
        return loss, host_ms, marks


def measure_window(runner, seconds):
    """Whole blocks until ``seconds`` have passed -> (losses, seconds
    from the window's start to each block's fetched loss, failure)."""
    losses, ends, failure = [], [], None
    t0 = time.perf_counter()
    while not ends or ends[-1] < seconds:
        try:
            losses.append(runner.block())
        except Exception:                        # the run itself raised
            failure = traceback.format_exc()
            break
        ends.append(time.perf_counter() - t0)
    return losses, ends, failure


def trace_block(runner, cell, keep_trace):
    """Profile one block -> (loss, reduced trace or None, host ms per
    quiet run)."""
    import jax.profiler as jp
    from paddle_tpu.fluid import trace as fluid_trace
    from benchmark.lib import trace_reduce
    logdir = os.path.join(OUT_DIR, 'trace', cell.name)
    shutil.rmtree(logdir, ignore_errors=True)
    options = jp.ProfileOptions()
    options.python_tracer_level = 0     # Python frames: size, no use here
    steps = cell.traffic['steps_per_block']
    fluid_trace.enable(buffer_steps=steps)
    jp.start_trace(logdir, profiler_options=options)
    try:
        loss, host_ms, marks = runner.traced_block()
    finally:
        jp.stop_trace()
        records = fluid_trace.steps()[-steps:]
        fluid_trace.disable()
        fluid_trace.reset()
    path = trace_reduce.newest_xplane(logdir)
    say('trace: %s (%.1f MB)' % (os.path.relpath(path, ROOT),
                                 os.path.getsize(path) / 1e6))
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(path, os.path.join(keep_trace,
                                       cell.name + '.xplane.pb'))
    reduced = trace_reduce.reduce_profile(trace_reduce.load(path), steps,
                                          SPAN)
    if reduced is not None:
        reduced.spans = reduced.spans + executor_phases(
            reduced.spans, marks, records)
    return loss, reduced, host_ms


def executor_phases(spans, marks, records):
    """``fluid/trace.py``'s per-step phases (bind, dispatch, fetch_d2h,
    ...; perf_counter seconds) moved onto the profiler's clock through
    the benchmark's own annotations, which are on both."""
    from benchmark.lib.trace_reduce import Span
    by_name = {s.name: s for s in spans}
    offsets = [by_name[name].start - t * 1e9 for name, t in marks
               if name in by_name]
    if not offsets:
        return []
    offset = statistics.median(offsets)
    return [Span('executor/' + s[0], s[1] * 1e9 + offset,
                 s[2] * 1e9 + offset)
            for rec in records for s in rec['spans']]


# where the reader files of each group of BENCHMARK.json's metrics live
READERS = {'end_to_end': 'end_to_end', 'per_layer': 'layer_metrics'}


def read_metrics(cell, group, *measured):
    """{name: {value, unit}} of the cell's metrics of that group, from
    one reader file per metric, each handed what the harness measured;
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for entry in cell.metrics[group]:
        reader = load_module(READERS[group], entry['name'])
        value = reader.read(*measured)
        if value is not None:
            out[entry['name']] = {'value': value, 'unit': reader.UNIT}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--keep-trace', metavar='DIR',
                    help='copy the traced run\'s .xplane.pb here')
    args = ap.parse_args(argv)
    cell = Cell(load_json(os.path.join(ROOT, 'BENCHMARK.json')),
                args.workload)

    import jax
    import jaxlib
    devices = require_devices(cell.chips)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache
    from benchmark.lib import listener
    events = listener.CompileListener()
    cache_dir = compile_cache.place_jax_cache()
    say('jax %s jaxlib %s; %d x %s; jax cache %s'
        % (jax.__version__, jaxlib.__version__, len(devices),
           devices[0].device_kind, cache_dir))
    say('cell %s: batch %d (%d per chip), traffic %s, changed %s'
        % (cell.name, cell.batch, cell.traffic['batch_per_chip'],
           json.dumps({k: v for k, v in cell.traffic.items()
                       if k != 'changed'}, sort_keys=True),
           json.dumps(cell.traffic.get('changed', {}))))

    # a scope of this run's own: parameters never leak between cells
    with fluid.scope_guard(fluid.Scope()):
        result = run_cell(cell, args, devices, events)
    print(json.dumps(result))
    return 0


def run_cell(cell, args, devices, events):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    main_prog, startup, test, loss, params = build_programs(cell,
                                                            args.seed)
    host_batch = cell.family.batch(cell.config, cell.traffic, cell.batch,
                                   args.seed)
    exe = fluid.Executor(fluid.XLAPlace(0))
    exe.run(startup)
    say('startup done: %d parameters' % len(params))
    reference_ok = reference_check(cell, exe, test, loss, params,
                                   host_batch)

    target, feed = cell.layout.place(main_prog, loss, devices, host_batch)
    runner = Runner(cell, exe, target, feed, loss)

    def fused_calls():
        return sum(v for k, v in monitor.flat().items()
                   if k.startswith('pallas/') and
                   k.endswith('/dispatch_fused'))

    # warm-up: the loss before any update, then one whole block, which
    # covers both call signatures of the window (the quiet and the
    # loss-fetching run compile to different XLA programs)
    fused_before = fused_calls()
    first_loss = runner.fetch_step()
    warm_loss = runner.block()
    setup = events.snapshot()
    setup_seconds = time.time() - T_START
    say('warm-up: loss %.4f -> %.4f; set-up %.1f s; jax obtained %d '
        'programs (seconds each: %s): %d built, %d from the persistent '
        'cache (%d misses)'
        % (first_loss, warm_loss, setup_seconds, setup['compiles'],
           ' '.join('%.2f' % c for c in setup['compile_seconds']),
           setup['built'], setup['cache_hits'], setup['cache_misses']))

    # what the harness itself measured, for the metric readers
    run = {'cell': cell, 'device_kind': devices[0].device_kind,
           'setup_seconds': setup_seconds,
           'built_in_setup': setup['built'],
           'fused_dispatches': fused_calls() - fused_before}
    steps = cell.traffic['steps_per_block']
    reduced = None
    if args.trace:
        t0 = time.perf_counter()
        last_loss, reduced, run['quiet_run_host_ms'] = trace_block(
            runner, cell, args.keep_trace)
        losses, failure = [last_loss], None
        say('traced block: %d steps in %.3f s, loss %.4f'
            % (steps, time.perf_counter() - t0, last_loss))
    else:
        losses, ends, failure = measure_window(runner, args.seconds)
        say('window: %d blocks; seconds at each fetched loss: %s'
            % (len(ends), ' '.join('%.3f' % e for e in ends)))
        say('window losses: %s' % ' '.join('%.4f' % v for v in losses))
        if ends:
            run['items_done'] = len(ends) * steps * cell.items_per_step
            run['window_seconds'] = ends[-1]
    if failure:
        say('a run raised:\n' + failure)
    compiled_in_window = events.compiles - setup['compiles']

    stats = [d.memory_stats() or {} for d in devices]
    say('memory_stats of the first chip: %s' % json.dumps(stats[0]))
    # on the TPU runtime a running program's temporaries are a
    # reservation beside the allocator's own buffers (parameters,
    # optimizer state, the batch): the peak is both together
    peak_bytes = max(s.get('peak_bytes_in_use', 0) +
                     s.get('peak_bytes_reserved', 0) for s in stats)
    run['memory_peak_bytes'] = peak_bytes
    bad = [v for v in losses if not math.isfinite(v)]
    attempted = (len(losses) + bool(failure)) * steps
    failed = (len(bad) + bool(failure)) * steps
    checks = {
        'reference check': reference_ok,
        'no compile inside the window (%d)' % compiled_in_window:
            compiled_in_window == 0,
        'every run completed': not failure,
        'losses finite': not bad and math.isfinite(first_loss),
        'loss falls on the fixed batch (%.4f -> %.4f)'
        % (first_loss, losses[-1] if losses else float('nan')):
            bool(losses) and losses[-1] < first_loss,
    }
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices),
              'memory_peak_bytes': peak_bytes}
    result = {'attempted': attempted, 'failed': failed, 'device': device}
    if args.trace:
        from benchmark.lib import trace_reduce
        checks['an operation ran on the device in the trace'] = \
            reduced is not None
        result['metrics'] = read_metrics(cell, 'per_layer', reduced, run)
        for what, note in run.get('notes', {}).items():
            say('%s: %s' % (what, note))
        if reduced is not None:
            device['busy_s'] = reduced.busy_ns / 1e9
            device['window_s'] = reduced.window_ns / 1e9
            result['breakdown'] = {
                'device_ops': trace_reduce.top_ops(reduced.first, 10),
                'idle_gaps': trace_reduce.idle_gaps(
                    reduced.first, reduced.spans, 5)}
    else:
        result['metrics'] = read_metrics(cell, 'end_to_end', run)
    for what, ok in checks.items():
        say('%s: %s' % ('ok' if ok else 'FAILED', what))
    result['correct'] = all(checks.values())
    return result


if __name__ == '__main__':
    sys.exit(main())
