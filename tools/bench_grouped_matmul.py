"""Grouped-matmul kernel bench, on the attached TPU (without one it
exits non-zero and prints no row: a time from anywhere else is not a
reading; the kernel bodies' CPU rehearsal is
tests/test_grouped_matmul_kernel.py).

Each of the three forms of ops/pallas/grouped_matmul.py (rows x w,
rows x w^T, rows^T x cot per group) at the five routed cells' (M, E,
K, N) and live-row counts, the compiler's ``jax.lax.ragged_dot`` (and
its two transposes, as parallel/moe.py's dense side calls them) beside
ours: ms a call, TFLOP/s by the LIVE rows' FLOPs and GB/s by the bytes
a call has to move (the live rows in and out, the weights of every
group once), over the row tiles given:

  python tools/bench_grouped_matmul.py --tiles 128 256 512 \
      --cells moonlight lfm2 solar laguna olmoe --loads 1 1.9

``--loads``: 1 is even groups; another figure draws groups whose
largest holds that many times the mean (``moe_load_max``), the others
sharing the rest unevenly (0.7 to 1.3 of their mean, from ``--seed``).  A call's time is
``--inner`` calls chained in ONE program (each call's group sizes hang
on the call before), so no launch gap is in it.  Every row also says
how far our result lies from the compiler's, in units of the last
bfloat16 place of the result's largest entry, over the rows inside
the groups.  Rows go to stdout and to ``--out`` (a .jsonl under
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

from chip_smoke import GROUPED_SHAPES as CELLS, bf16_units
from paddle_tpu.parallel import moe

# CELLS: (buffer rows M, groups E, K, N, live rows a step) of
# BENCHMARK.json's routed cells, PERF.md section 4; the live rows are
# the ledger's (PR 47: the even share times ``moe_held_share`` over the
# even one)
PEAK_TFLOPS, PEAK_GBS = 197.0, 819.0      # one v5e (benchmark/lib/peaks.py)


def group_sizes(e, live, load, rng):
    """[E] int32 summing to ``live``: even, or the largest ``load``
    times the mean, the others 0.7 to 1.3 of what is left to each."""
    if load == 1:
        sizes = np.full(e, live // e)
    else:
        most = min(int(load * live / e), live)
        share = rng.uniform(0.7, 1.3, e - 1)
        sizes = np.floor((live - most) * share / share.sum()).astype(int)
        sizes = np.insert(sizes, rng.randint(0, e), most)
    sizes[-1] += live - sizes.sum()
    return sizes.astype(np.int32)


def time_call(fn, args, steps, repeats):
    """ms per call of fn: ``steps`` calls queued back to back, one
    sync on the last; (min, median) over ``repeats`` such rounds."""
    jax.block_until_ready(fn(*args))    # compile + warm
    rounds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / steps * 1e3)
    return min(rounds), statistics.median(rounds)


def chained(product, inner):
    """``inner`` calls of product(a, b, sizes) in one program, each
    one's sizes hanging on an entry of the result before."""
    def run(a, b, sizes):
        def body(_, sizes):
            probe = product(a, b, sizes).reshape(-1)[0]
            return sizes + (probe.astype(jnp.float32) ==
                            12345.678).astype(jnp.int32)
        return jax.lax.fori_loop(0, inner, body, sizes)
    return jax.jit(run)


def forms(m, dense):
    """{form: product(a, b, sizes)} of one side, through the object
    parallel/moe.py's ``_operands`` hands its MLPs for that side."""
    def side(sizes):
        if dense:
            return moe._RaggedDot(sizes, None, 'flag_off')
        return moe._GroupedMatmul(sizes, m, 'tpu', False)

    return {
        'forward': lambda rows, w, sizes: side(sizes)(rows, w),
        'transposed': lambda cot, w, sizes:
            side(sizes).transposed(cot, w),
        'weight_gradient': lambda rows, cot, sizes:
            side(sizes).weight_gradient(rows, cot)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--cells', nargs='+', default=list(CELLS))
    ap.add_argument('--loads', nargs='+', type=float, default=[1.0])
    ap.add_argument('--tiles', nargs='+', type=int, default=[])
    ap.add_argument('--forms', nargs='+', default=[
        'forward', 'transposed', 'weight_gradient'])
    ap.add_argument('--inner', type=int, default=8)
    ap.add_argument('--steps', type=int, default=4)
    ap.add_argument('--repeats', type=int, default=5)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default='chiprun_out/pr48/bench.jsonl')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('no TPU attached: %s' % (device,))
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    tiles = args.tiles or [gm.ROW_TILE]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    sink = open(args.out, 'a')
    rng = np.random.RandomState(args.seed)
    for cell in args.cells:
        m, e, k, n, live = CELLS[cell]
        rows = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        cot = jnp.asarray(rng.randn(m, n), jnp.bfloat16)
        w = jnp.asarray(rng.randn(e, k, n) / 32, jnp.bfloat16)
        operands = {'forward': (rows, w), 'transposed': (cot, w),
                    'weight_gradient': (rows, cot)}
        flops = 2.0 * live * k * n
        moved = 2.0 * (e * k * n + live * (k + n))
        for load in args.loads:
            sizes = jnp.asarray(group_sizes(e, live, load, rng))
            sides = [('ragged_dot', None)] + [('ours', t) for t in tiles]
            want = {}
            for side, tile in sides:
                if tile:
                    gm.ROW_TILE = tile
                    jax.clear_caches()
                if tile and m % tile:
                    continue
                for form in args.forms:
                    row = {'cell': cell, 'm': m, 'e': e, 'k': k, 'n': n,
                           'live': live, 'load': load,
                           'largest_group': int(sizes.max()),
                           'form': form, 'side': side, 'row_tile': tile,
                           'device': device.device_kind}
                    product = forms(m, tile is None)[form]
                    a, b = operands[form]
                    try:
                        got = np.asarray(jax.jit(product)(
                            a, b, sizes).astype(jnp.float32))
                        if form != 'weight_gradient':
                            got = got[:live]
                        if tile is None:
                            want[form] = got
                        else:
                            row['off_bf16_units'] = bf16_units(
                                got, want[form])
                            row['finite'] = bool(np.isfinite(got).all())
                        low, mid = time_call(
                            chained(product, args.inner), (a, b, sizes),
                            args.steps, args.repeats)
                        row['ms_min'] = low / args.inner
                        row['ms'] = ms = mid / args.inner
                        row['tflops'] = flops / ms / 1e9
                        row['share_of_peak'] = row['tflops'] / PEAK_TFLOPS
                        row['gbs'] = moved / ms / 1e6
                        row['share_of_hbm'] = row['gbs'] / PEAK_GBS
                    except Exception as ex:     # a refused compile
                        row['error'] = str(ex)[:400]
                    line = json.dumps(row)
                    print(line, flush=True)
                    sink.write(line + '\n')
                    sink.flush()
    return 0


if __name__ == '__main__':
    sys.exit(main())
