"""Selective-scan bench, on the attached TPU (without one it exits
non-zero and prints no row: a time from anywhere else is not a reading;
the op's CPU twin is tests/test_phi4flash.py).

``ops/ssm_ops.py`` ``selective_scan`` alone, forward and forward +
backward, at one layer's shapes (x, delta [B, T, D]; B, C [B, T, N]), a
row a path at the same operands: ``dense`` (the two ``lax.scan``s, over
the unroll factors given: the module's ``UNROLL``, set by the bench
between rounds) and ``fused`` (the ``ssm_scan`` kernels,
``ops/pallas/ssm_scan.py``), over the chunk sizes given, beside the
hand count's least time
(``benchmark/lib/phi4flash_flops.py`` ``scan_train_cost``).  A fused
row also says how far its output and gradients lie from the first dense
row's at the same chunk, as a share of the largest entry:

  python tools/bench_selective_scan.py --tokens 8192 --channels 5120 \
      --chunks 256 --path dense fused --ops /tmp/ssm_trace

``--ops`` traces one forward + backward call a row and gives the row
its ten longest device operations by name (the two Mosaic calls apart
from the copies around them).

Rows go to stdout and to ``--out`` (a .jsonl under chiprun_out/).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, peaks, phi4flash_flops, trace_reduce
from paddle_tpu.ops import ssm_ops


def timed(fn, *args, runs=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return statistics.median(times)


def device_ops(fn, operands, logdir):
    """{instruction: ms} of one traced call, the ten longest."""
    jax.profiler.start_trace(logdir)
    jax.block_until_ready(fn(*operands))
    jax.profiler.stop_trace()
    plane = trace_reduce.device_planes(trace_reduce.load(
        trace_reduce.newest_xplane(logdir)))[0]
    ms = {}
    for op in trace_reduce.plane_ops(plane):
        ms[op.name] = ms.get(op.name, 0.0) + (op.end - op.start) / 1e6
    longest = sorted(ms.items(), key=lambda kv: -kv[1])[:10]
    return {name: round(v, 3) for name, v in longest}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--tokens', type=int, default=8192)
    ap.add_argument('--channels', type=int, default=5120)
    ap.add_argument('--states', type=int, default=16)
    ap.add_argument('--chunks', type=int, nargs='+', default=[256])
    ap.add_argument('--unrolls', type=int, nargs='+', default=[8])
    ap.add_argument('--path', nargs='+', default=['dense'],
                    choices=['dense', 'fused'])
    ap.add_argument('--dtype', default='bfloat16')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default='')
    ap.add_argument('--ops', default='', help='a directory: trace one '
                    'forward + backward call of every row into it and '
                    'give the row its device operations by name, ms')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('bench_selective_scan: no TPU attached (%s)'
                 % device.platform)
    rng = np.random.RandomState(args.seed)
    b, t, d, n = args.batch, args.tokens, args.channels, args.states
    dtype = jnp.dtype(args.dtype)
    x = jnp.asarray(rng.randn(b, t, d), dtype)
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                           (b, t, d))), jnp.float32)
    a = -jnp.asarray(np.tile(np.arange(1, n + 1, dtype='float32'), (d, 1)))
    bm, cm = (jnp.asarray(rng.randn(b, t, n), dtype) for _ in range(2))
    skip = jnp.ones((d,), jnp.float32)
    weight = jnp.asarray(rng.randn(b, t, d), dtype)
    cost = phi4flash_flops.scan_train_cost(b, t, d, n, dtype.itemsize)
    least_ms = 1e3 * flops.roofline_seconds(
        *cost, *peaks.chip_peak(device.device_kind))[0]
    rows = []
    operands = (x, delta, a, bm, cm, skip)
    rounds = [('dense', unroll) for unroll in args.unrolls
              if 'dense' in args.path] + \
        [('fused', None)] * ('fused' in args.path)
    for chunk in args.chunks:
        dense = None
        for path, unroll in rounds:
            row = {'path': path, 'chunk': chunk, 'tokens': t,
                   'channels': d, 'states': n, 'dtype': args.dtype,
                   'device': device.device_kind,
                   'least_fwd_bwd_ms': round(least_ms, 3)}
            if unroll:
                ssm_ops.UNROLL = row['unroll'] = unroll

            def forward(*operands):
                return ssm_ops._scan(*operands, chunk, path)

            def backward(*operands):
                return jax.grad(lambda *p: jnp.sum(
                    (forward(*p) * weight).astype(jnp.float32)),
                    argnums=range(6))(*operands)

            forward, backward = jax.jit(forward), jax.jit(backward)
            try:
                row['fwd_ms'] = round(1e3 * timed(forward, *operands), 3)
                row['fwd_bwd_ms'] = round(
                    1e3 * timed(backward, *operands), 3)
                if args.ops:
                    row['ops_ms'] = device_ops(
                        backward, operands, os.path.join(
                            args.ops, '%s_%s_%d' % (path, unroll, chunk)))
                got = [np.asarray(v, np.float64) for v in
                       (forward(*operands),) + backward(*operands)]
                if path == 'dense':
                    dense = dense or got
                elif dense:
                    row['from_dense'] = [
                        float('%.3g' % (np.abs(g - w).max() /
                                        np.abs(w).max()))
                        for g, w in zip(got, dense)]
            except Exception as e:      # a shape the compiler refuses
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
