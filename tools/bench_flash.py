"""Flash-vs-naive attention crossover bench.

Measures fwd+bwd wall time of the Pallas flash kernels against the
naive XLA chain at several sequence lengths on the attached TPU, for
BERT-base (h12 d64) and GPT/large shapes (d128) — round-4 VERDICT
item 7 widened the sweep beyond d=64.

Three columns per shape:
  naive    — the dense XLA chain
  flash    — the Pallas kernels, FORCED (min_seq=0)
  shipped  — the public flash_attention() auto-dispatch, which picks
             the dense path below FLASH_MIN_SEQ: this column must
             never lose to naive beyond noise.

Usage: python tools/bench_flash.py [--steps 30] [--block-sweep]
       [--dims 64 128] [--heads-for 64=12 128=16]
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def naive_attention(q, k, v, causal=False):
    b, t, h, d = q.shape
    s = jnp.einsum('bthd,bshd->bhts', q, k,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum('bhts,bshd->bthd', p, v)


def timed(fn, args, steps):
    """Chained steps (each consumes the previous grads) + one host
    readback: serialize on-device and sync via np.asarray (bench.py's
    convention)."""
    q, k, v = args

    def step(q, k, v):
        dq, dk, dv = fn(q, k, v)
        eps = jnp.bfloat16(1e-3)
        return (q + eps * dq.astype(q.dtype),
                k + eps * dk.astype(k.dtype),
                v + eps * dv.astype(v.dtype))

    step = jax.jit(step)
    q, k, v = step(q, k, v)
    np.asarray(q[0, 0, 0, 0].astype(jnp.float32))  # warm + sync
    t0 = time.perf_counter()
    for _ in range(steps):
        q, k, v = step(q, k, v)
    np.asarray(q[0, 0, 0, 0].astype(jnp.float32))
    return (time.perf_counter() - t0) / steps * 1e3


def loss_of(att):
    def f(q, k, v):
        return jnp.sum(att(q, k, v).astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(f, argnums=(0, 1, 2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--batch', type=int, default=32)
    ap.add_argument('--heads', type=int, default=None,
                    help='override heads for every dim')
    ap.add_argument('--dims', type=int, nargs='+', default=[64, 128])
    ap.add_argument('--seqs', type=int, nargs='+',
                    default=[128, 256, 512, 1024, 2048])
    ap.add_argument('--causal', action='store_true')
    ap.add_argument('--block-sweep', action='store_true')
    args = ap.parse_args()

    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(0)
    # keep per-step FLOPs roughly comparable across dims: h12 for the
    # BERT shape, h16 d128 for the GPT/large shape at half the batch
    default_heads = {64: 12, 128: 16}
    default_batch = {64: args.batch, 128: max(1, args.batch // 2)}
    for dim in args.dims:
        heads = args.heads or default_heads.get(dim, 12)
        batch = default_batch.get(dim, args.batch)
        print('--- d=%d h=%d b=%d %s' % (dim, heads, batch,
              'causal' if args.causal else 'bidirectional'), flush=True)
        for t in args.seqs:
            shape = (batch, t, heads, dim)
            q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
            k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
            v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)

            g_naive = loss_of(functools.partial(naive_attention,
                                                causal=args.causal))
            ms_naive = timed(g_naive, (q, k, v), args.steps)

            g_flash = loss_of(functools.partial(
                fa.flash_attention, causal=args.causal, min_seq=0))
            ms_flash = timed(g_flash, (q, k, v), args.steps)

            g_ship = loss_of(functools.partial(fa.flash_attention,
                                               causal=args.causal))
            ms_ship = timed(g_ship, (q, k, v), args.steps)
            best = min(ms_naive, ms_flash)
            verdict = 'OK' if ms_ship <= best * 1.10 else \
                'SHIPPED LOSES'
            print('seq %5d  naive %7.2f  flash %7.2f  shipped %7.2f '
                  'ms  [%s]' % (t, ms_naive, ms_flash, ms_ship,
                                verdict), flush=True)

            if args.block_sweep:
                shipped = (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
                seen = set()
                for bq in (128, 256, 512, 1024, 2048):
                    for bk in (128, 256, 512, 1024, 2048):
                        if bq > t or bk > t:
                            continue
                        # the VMEM clamp rewrites oversized configs;
                        # label (and dedupe) by what actually RUNS
                        ebq, ebk = fa._block_sizes(t, bq, bk, dim, 2)
                        if (ebq, ebk) in seen:
                            continue
                        seen.add((ebq, ebk))
                        fa.DEFAULT_BLOCK_Q = bq
                        fa.DEFAULT_BLOCK_K = bk
                        gf = loss_of(functools.partial(
                            fa.flash_attention, causal=args.causal,
                            min_seq=0))
                        ms = timed(gf, (q, k, v), args.steps)
                        print('    bq=%4d bk=%4d  %7.2f ms'
                              % (ebq, ebk, ms), flush=True)
                # restore SHIPPED defaults so later seqs measure them
                fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = shipped


if __name__ == '__main__':
    main()
