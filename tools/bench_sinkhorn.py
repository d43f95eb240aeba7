"""Sinkhorn kernel bench, on the attached TPU (without one it exits
non-zero and prints no row: a time from anywhere else is not a reading;
the kernel bodies' CPU rehearsal is tests/test_sinkhorn_kernel.py).

The two calls of ops/pallas/sinkhorn.py (all ``--iters`` trips forward,
and the exact backward) beside the dense form they replace
(``hyper_connection_ops.sinkhorn``, one ``lax.scan`` under a
``jax.checkpoint``) on an [n, n, tokens] float32 matrix, over the row
tiles given (a tile is the module's ``ROW_TILE``, set by the bench
between rounds):

  python tools/bench_sinkhorn.py --tokens 4096 --tiles 8 16 32

A call's time is ``--inner`` calls chained in ONE program (each call's
operand hangs on the call before), so no launch gap between CALLS is in
it; the scan's own trips are launches inside its program and stay.
Every row also says how far the kernels' result and gradient lie from
the scan's.  Rows go to stdout and to ``--out`` (a .jsonl under
chiprun_out/).
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

from paddle_tpu.ops import hyper_connection_ops as hc_ops
from paddle_tpu.ops.pallas import sinkhorn as kernel


def timed(fn, *args, runs=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return statistics.median(times)


def chained(project, weight, inner, backward):
    """``inner`` calls of ``project`` (or of its gradient under
    ``weight``) in one program, each on what the last one gave."""
    def step(_, m):
        if not backward:
            return project(m) + 0.5
        grad = jax.grad(lambda x: jnp.sum(weight * project(x)))(m)
        return jnp.abs(grad) + 0.5

    return jax.jit(lambda m: jax.lax.fori_loop(0, inner, step, m))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--tokens', type=int, nargs='+', default=[4096])
    ap.add_argument('--streams', type=int, default=4)
    ap.add_argument('--iters', type=int, default=20)
    ap.add_argument('--tiles', type=int, nargs='+', default=[8])
    ap.add_argument('--inner', type=int, default=50)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default='')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('bench_sinkhorn: no TPU attached (%s)' % device.platform)
    rng = np.random.RandomState(args.seed)
    n, eps = args.streams, 1e-6

    def scan(m):
        return jax.checkpoint(
            lambda x: hc_ops.sinkhorn(x, args.iters, eps))(m)

    def fused(m):
        return kernel.sinkhorn(m, args.iters, eps, False)

    rows = []
    for tokens in args.tokens:
        m0 = jnp.asarray(np.exp(np.clip(3 * rng.randn(n, n, tokens),
                                        -30, 30)), jnp.float32)
        weight = jnp.asarray(rng.randn(n, n, tokens), jnp.float32)
        want = jax.jit(jax.value_and_grad(
            lambda m: jnp.sum(weight * scan(m))))(m0)
        sides = [('scan', 0, scan)] + [('kernel', t, fused)
                                       for t in args.tiles]
        for side, tile, project in sides:
            if tile:
                kernel.ROW_TILE = tile
                kernel._call.clear_cache()
            row = {'side': side, 'row_tile': tile, 'tokens': tokens,
                   'streams': n, 'iters': args.iters,
                   'device': device.device_kind}
            try:
                got = jax.jit(jax.value_and_grad(
                    lambda m: jnp.sum(weight * project(m))))(m0)
                row['value_off'] = float(abs(got[0] - want[0]) /
                                         abs(want[0]))
                row['grad_off'] = float(jnp.abs(got[1] - want[1]).max() /
                                        jnp.abs(want[1]).max())
                for name, backward in (('fwd_us', False),
                                       ('fwd_bwd_us', True)):
                    row[name] = round(timed(chained(
                        project, weight, args.inner, backward), m0) /
                        args.inner * 1e6, 2)
            except Exception as e:      # a tile the compiler refuses
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
