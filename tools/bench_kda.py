"""Delta-rule bench, on the attached TPU (without one it exits non-zero
and prints no row: a time from anywhere else is not a reading; the
op's CPU twin is tests/test_kda_kernel.py).

``ops/kda_ops.py`` ``gated_delta_rule`` alone, forward and forward +
backward, at one layer's shapes (q, k, v, a [B, T, H, d], beta [B, T,
H]; the Solar cell's by default), beside the hand count's least time
(``benchmark/lib/solar_flops.py`` ``kda_train_cost``).
--impl NAME=PATH (repeatable) times another copy of ``kda_ops.py``
(the parent's, a variant) beside the tree's own in one process, on the
same operands; every row after the first says how far its output and
gradients lie from the first's, as a share of the largest entry:

  git show HEAD~1:paddle_tpu/ops/kda_ops.py > .bench_archive/kda_parent.py
  python tools/bench_kda.py --impl parent=.bench_archive/kda_parent.py \\
      --impl tree --ops chiprun_out/kda_trace
  python tools/bench_kda.py --tokens 8192 --heads 32 ...   # Kimi Linear's

(A copy loads beside the TREE's ``ops/pallas``: one from before a PR
that changed the kernels' entry points, as PR 62 did, is timed from
its own checkout with this file copied in, ``--impl tree`` there.)

``--ops`` traces one forward + backward call a row and gives the row
its device time BY PART, ms (``parts_ms``): the preparation's two
kernels by the names ``kda_chunk`` gives its calls (``chunk_forward``,
twice a call of the op, and ``chunk_backward``; a copy whose Mosaic
calls carry no name reads ``scores_kernels``, all of them together),
the inverse of the unit-triangular system and its products (what lowers
under the ``inverse`` scope, or a ``triangular_solve``), the running
decay's sums (a ``cumsum`` of XLA's), the forward walk and the reverse
walk over the chunks (the ``kda_walk`` kernels' two calls by their
name, or where the copy holds none the program's two ``while`` loops in
the order they run, with ``trip_us``: a walk's time over its chunks),
``layout`` (every ``copy`` and ``transpose`` instruction and every
fusion the compiler named for one: an operand re-laid for a call shows
here, without an ``--xla_dump_to``), and everything else of the op
(exponentials, casts, the chunks' padding); and the five longest
operations outside the walks by name, whose instructions are in
``program.txt`` beside the trace.

Rows go to stdout and to ``--out`` (a .jsonl under chiprun_out/).
"""

import argparse
import importlib
import importlib.util
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, peaks, solar_flops, trace_reduce

PACKAGE = 'paddle_tpu.ops'
OUTPUTS = ('o', 'dq', 'dk', 'dv', 'da', 'dbeta')
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?(\S+) = .*op_name="([^"]*)"')
# what a part's instructions carry in the op_name of their metadata:
# the scope ``_prepare`` lowers it under, or the primitive it calls (a
# copy of the module from before PR 59 solves by ``triangular_solve``)
PARTS = (('inverse', ('inverse', 'triangular_solve')),
         ('sums', ('cumsum',)))
WALKS = ('forward_walk', 'reverse_walk')
# the names ``ops/pallas/kda_walk.py`` gives its two calls
WALK_CALLS = ('kda_walk_forward', 'kda_walk_reverse')
# and ``ops/pallas/kda_chunk.py`` its two, by part
CHUNK_CALLS = (('kda_chunk_forward', 'chunk_forward'),
               ('kda_chunk_backward', 'chunk_backward'))
_LAYOUT = re.compile(r'(?:^|[_\-.])(?:copy|transpose)(?:$|[_\-.])')


def load_impl(spec):
    """'name=path/to/kda_ops.py' -> (name, module loaded as a sibling
    of the tree's own inside paddle_tpu.ops, so its relative imports
    resolve); 'tree' is the tree's own module."""
    importlib.import_module(PACKAGE)
    if spec == 'tree':
        return spec, importlib.import_module(PACKAGE + '.kda_ops')
    name, path = spec.split('=', 1)
    modname = '%s._bench_kda_%s' % (PACKAGE, name)
    mspec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(mspec)
    sys.modules[modname] = mod
    mspec.loader.exec_module(mod)
    return name, mod


def timed(fn, *args, runs=10):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return statistics.median(times)


def part_of(name, op_name):
    """The part of an instruction of XLA's, by the scope or primitive
    in its ``op_name`` and else by what the compiler called it."""
    for part, marks in PARTS:
        if any(mark in op_name for mark in marks):
            return part
    return 'layout' if _LAYOUT.search(name) else 'other'


def kernel_part(op_name):
    """The part of a Mosaic call that is no walk."""
    return next((part for call, part in CHUNK_CALLS if call in op_name),
                'scores_kernels')


def by_part(ops, op_names, trips):
    """One traced call's device ops (``trace_reduce.plane_ops``) ->
    ({part: ms}, {walk: us a trip}, the five longest operations outside
    the walks).  Every instant goes to the innermost op running then;
    inside a ``while`` it goes to that loop, whatever the body's
    instruction is called, and a Mosaic call that ``kda_walk`` named
    is that walk."""
    loops = sorted((op for op in ops
                    if op.name.split('.')[0] == 'while'),
                   key=lambda op: op.start)
    ms, outside = {}, {}
    for a, b, op in trace_reduce.innermost_segments(ops):
        part = next((name for name, loop in zip(WALKS, loops)
                     if loop.start <= a and b <= loop.end), None)
        if part is None and op.kind == trace_reduce.MOSAIC:
            part = next((name for name, call in zip(WALKS, WALK_CALLS)
                         if call in op_names.get(op.name, '')), None)
        if part is None:
            scope = op_names.get(op.name, '')
            part = kernel_part(scope) if op.kind == trace_reduce.MOSAIC \
                else part_of(op.name, scope)
            outside[op.name] = outside.get(op.name, 0.0) + (b - a) / 1e6
        ms[part] = ms.get(part, 0.0) + (b - a) / 1e6
    longest = sorted(outside.items(), key=lambda kv: -kv[1])[:5]
    return ({part: round(v, 3) for part, v in sorted(ms.items())},
            {name: round(1e3 * ms[name] / trips, 2)
             for name in WALKS if name in ms},
            {name: round(v, 3) for name, v in longest})


def traced_parts(compiled, operands, logdir, trips):
    """Trace one call of the compiled forward + backward and split its
    device time by part."""
    text = compiled.as_text()
    op_names = dict(m.groups() for m in map(
        _OP_NAME.match, text.splitlines()) if m)
    os.makedirs(logdir, exist_ok=True)
    with open(os.path.join(logdir, 'program.txt'), 'w') as f:
        f.write(text)       # the names the trace uses, with their scopes
    jax.profiler.start_trace(logdir)
    jax.block_until_ready(compiled(*operands))
    jax.profiler.stop_trace()
    plane = trace_reduce.device_planes(trace_reduce.load(
        trace_reduce.newest_xplane(logdir)))[0]
    return by_part(trace_reduce.plane_ops(plane), op_names, trips)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--tokens', type=int, default=4096)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--dims', type=int, default=128)
    ap.add_argument('--dtype', default='bfloat16',
                    help='of q, k, v and beta (the log decays are '
                    'float32, as the layer hands them over)')
    ap.add_argument('--impl', action='append',
                    help="'tree' or NAME=PATH of a kda_ops.py; "
                    'repeatable, the first is what the others are '
                    'compared with (default: tree)')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default='')
    ap.add_argument('--ops', default='', help='a directory: trace one '
                    'forward + backward call of every row into it and '
                    'give the row its device time by part, ms')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('bench_kda: no TPU attached (%s)' % device.platform)
    rng = np.random.RandomState(args.seed)
    b, t, h, d = args.batch, args.tokens, args.heads, args.dims
    dtype = jnp.dtype(args.dtype)
    q, k = (rng.randn(b, t, h, d) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, t, h, d)
    # the startup draws' range: A in (1, 16), dt in (0.001, 0.1)
    a = -rng.uniform(1, 16, (1, 1, h, 1)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(1e-1), (b, t, h, d)))
    beta = 2 / (1 + np.exp(-rng.randn(b, t, h)))
    operands = [jnp.asarray(x, jnp.float32 if x is a else dtype)
                for x in (q, k, v, a, beta)]
    probe = jnp.asarray(rng.randn(b, t, h, d), dtype)
    cost = solar_flops.kda_train_cost(b, t, h, d, itemsize=dtype.itemsize)
    least_ms = 1e3 * flops.roofline_seconds(
        *cost, *peaks.chip_peak(device.device_kind))[0]
    rows, first = [], None
    for name, mod in [load_impl(s) for s in (args.impl or ['tree'])]:
        row = {'impl': name, 'batch': b, 'tokens': t, 'heads': h,
               'dims': d, 'dtype': args.dtype,
               'device': device.device_kind,
               'least_fwd_bwd_ms': round(least_ms, 3)}

        def run(*x, rule=mod.gated_delta_rule):
            out, pull = jax.vjp(rule, *x)
            return (out,) + pull(probe)

        try:
            forward = jax.jit(mod.gated_delta_rule)
            both = jax.jit(run).lower(*operands).compile()
            row['fwd_ms'] = round(1e3 * timed(forward, *operands), 3)
            row['fwd_bwd_ms'] = round(1e3 * timed(both, *operands), 3)
            row['roofline'] = round(100 * least_ms / row['fwd_bwd_ms'], 2)
            if args.ops:
                chunk, trips = mod._layout(t, mod.CHUNK)
                row['chunk'], row['trips'] = chunk, trips
                row['parts_ms'], row['trip_us'], row['longest_ms'] = \
                    traced_parts(both, operands,
                                 os.path.join(args.ops, name), trips)
            got = [np.asarray(x, np.float64) for x in both(*operands)]
            if first is None:
                first = got
            else:
                row['from_first'] = dict(zip(OUTPUTS, (
                    float('%.3g' % (np.abs(g - w).max() / np.abs(w).max()))
                    for g, w in zip(got, first))))
        except Exception as e:          # a form the compiler refuses
            row['error'] = '%s: %s' % (type(e).__name__, str(e)[:300])
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)
        with open(args.out, 'a') as f:
            for row in rows:
                f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
