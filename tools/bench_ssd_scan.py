"""Mamba-2's chunked scan alone, on the attached TPU (without one it
exits non-zero and prints no row: a time from anywhere else is not a
reading; the op's CPU twins are tests/test_nemotron_h.py and
tests/test_ssd_kernel.py).

``ops/ssd_ops.py`` ``ssd_scan``, forward and forward + backward, at one
layer's shapes (x [B, T, H, P]; delta [B, T, H]; B, C [B, T, G, N]), a
row a path at the same operands: ``dense`` (XLA's lowering of every
chunk at once) and ``fused`` (the ``ssd_scan`` kernels,
``ops/pallas/ssd_scan.py``), beside the hand count's least time
(``benchmark/lib/nemotron_h_flops.py`` ``ssd_train_cost``).  A fused
row also says how far its output and six gradients lie from the dense
row's, as a share of the largest entry:

  python tools/bench_ssd_scan.py --path dense fused --ops /tmp/ssd_trace

``--ops`` traces one forward + backward call a row and gives the row
its ten longest device operations by name (the two Mosaic calls apart
from what XLA runs around them).  Rows go to stdout and to ``--out`` (a
.jsonl under chiprun_out/).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, nemotron_h_flops, peaks
from paddle_tpu.ops import ssd_ops
from tools.bench_selective_scan import device_ops, timed


def operands(seed, b, t, h, p, g, n, dtype):
    """The op's six operands and a cotangent, AS THE COMPILED STEP HOLDS
    THEM: what is [B, T, .] arrives token-minor ([B, ., T]; ``as_fed``
    takes it back inside the jit, a bitcast where the consumer wants
    that order).  Steps from 0.001 to 0.1 and decay rates from 1 to 16
    a head, what Mamba-2's initialisers give."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, h * p, t), dtype)
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                           (b, h, t))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    bm, cm = (jnp.asarray(rng.randn(b, g * n, t), dtype) for _ in range(2))
    skip = jnp.ones((h,), jnp.float32)
    return (x, delta, a, bm, cm, skip), \
        jnp.asarray(rng.randn(b, h * p, t), dtype)


def as_fed(v, heads):
    """[B, heads x ., T] -> [B, T, heads, .] ([B, heads, T] -> [B, T,
    heads])."""
    v = jnp.swapaxes(v, 1, 2)
    return v if v.shape[2] == heads else \
        v.reshape(v.shape[:2] + (heads, -1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--tokens', type=int, default=8192)
    ap.add_argument('--heads', type=int, default=64)
    ap.add_argument('--head-dim', type=int, default=64)
    ap.add_argument('--groups', type=int, default=8)
    ap.add_argument('--states', type=int, default=128)
    ap.add_argument('--chunk', type=int, default=128)
    ap.add_argument('--path', nargs='+', default=['dense', 'fused'],
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
        sys.exit('bench_ssd_scan: no TPU attached (%s)' % device.platform)
    dtype = jnp.dtype(args.dtype)
    shape = (args.batch, args.tokens, args.heads, args.head_dim,
             args.groups, args.states)
    ins, weight = operands(args.seed, *shape, dtype)
    least_ms = 1e3 * flops.roofline_seconds(
        *nemotron_h_flops.ssd_train_cost(*shape, args.chunk,
                                         dtype.itemsize),
        *peaks.chip_peak(device.device_kind))[0]
    rows, dense = [], None
    for path in args.path:
        row = {'path': path, 'shape': list(shape), 'chunk': args.chunk,
               'dtype': args.dtype, 'device': device.device_kind,
               'least_fwd_bwd_ms': round(least_ms, 3)}

        def forward(x, delta, a, bm, cm, skip, path=path):
            with jax.named_scope('ssd_scan'):
                return jnp.swapaxes(ssd_ops._scan(
                    as_fed(x, args.heads), as_fed(delta, args.heads), a,
                    as_fed(bm, args.groups), as_fed(cm, args.groups), skip,
                    args.chunk, path).reshape(x.shape[0], x.shape[2], -1),
                    1, 2)

        def backward(*ins, forward=forward):
            out, pull = jax.vjp(forward, *ins)
            return (out,) + pull(weight)

        forward, backward = jax.jit(forward), jax.jit(backward)
        try:
            row['fwd_ms'] = round(1e3 * timed(forward, *ins), 3)
            row['fwd_bwd_ms'] = round(1e3 * timed(backward, *ins), 3)
            if args.ops:
                row['ops_ms'] = device_ops(
                    backward, ins, os.path.join(args.ops, path))
            got = [np.asarray(v, np.float64) for v in backward(*ins)]
            if path == 'dense':
                dense = got
            elif dense:
                row['from_dense'] = [
                    float('%.3g' % (np.abs(g - w).max() / np.abs(w).max()))
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
