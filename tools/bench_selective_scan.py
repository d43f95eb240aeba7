"""Selective-scan bench, on the attached TPU (without one it exits
non-zero and prints no row: a time from anywhere else is not a reading;
the op's CPU twin is tests/test_phi4flash.py).

``ops/ssm_ops.py`` ``selective_scan`` alone, forward and forward +
backward, at one layer's shapes (x, delta [B, T, D]; B, C [B, T, N]),
over the chunk sizes and unroll factors given (the module's ``CHUNK``
and ``UNROLL``, set by the bench between rounds), beside the hand
count's least time (``benchmark/lib/phi4flash_flops.py``
``scan_train_cost``):

  python tools/bench_selective_scan.py --tokens 8192 --channels 5120 \
      --chunks 256 --unrolls 4 8 16

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

from benchmark.lib import flops, peaks, phi4flash_flops
from paddle_tpu.ops import ssm_ops


def timed(fn, *args, runs=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--tokens', type=int, default=8192)
    ap.add_argument('--channels', type=int, default=5120)
    ap.add_argument('--states', type=int, default=16)
    ap.add_argument('--chunks', type=int, nargs='+', default=[256])
    ap.add_argument('--unrolls', type=int, nargs='+', default=[8])
    ap.add_argument('--dtype', default='bfloat16')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default='')
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
    for chunk in args.chunks:
        for unroll in args.unrolls:
            ssm_ops.UNROLL = unroll

            def forward(*operands):
                return ssm_ops.selective_scan(*operands, chunk=chunk)

            def backward(*operands):
                return jax.grad(lambda *p: jnp.sum(
                    (forward(*p) * weight).astype(jnp.float32)),
                    argnums=range(6))(*operands)

            row = {'chunk': chunk, 'unroll': unroll, 'tokens': t,
                   'channels': d, 'states': n, 'dtype': args.dtype,
                   'device': device.device_kind,
                   'least_fwd_bwd_ms': round(least_ms, 3)}
            try:
                operands = (x, delta, a, bm, cm, skip)
                row['fwd_ms'] = round(
                    1e3 * timed(jax.jit(forward), *operands), 3)
                row['fwd_bwd_ms'] = round(
                    1e3 * timed(jax.jit(backward), *operands), 3)
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
