"""Flash attention kernel bench, on the attached TPU (without one it
exits non-zero and prints no row: a time from anywhere else is not a
reading, and the kernel bodies' CPU rehearsal is tests/'s).

Default: the forward and the backward kernel call timed APART at the
benchmark cells' shapes (bert_base_s2048: --shapes 12x2048,
bert_base_s512_b48: 48x512; h12 d64 bf16), for every combination of
--rate and --key-bias given — the ablation that says what the
in-kernel dropout draw and the key bias cost:

  python tools/bench_flash.py --shapes 12x2048 48x512 \
      --rate 0.1 0 --key-bias 1 0

--impl NAME=PATH (repeatable) times another copy of
ops/pallas/flash_attention.py beside the tree's own (the parent's file
beside the change's, in one process on one chip) and says whether its
outputs are bit-equal to the first implementation's.  Two readings a
row.  ``fwd_ms`` / ``bwd_ms``: the module's _flash_fwd / _flash_bwd on
[B*H, T, D] operands, the Mosaic kernels plus, in the backward, the
one XLA reduce that makes delta; the [B, T, H, D] transposes around
them are not in the times.  ``entry_fwd_ms`` / ``entry_fwd_bwd_ms``:
the public flash_attention() and its vjp on [B, T, H, D] views of
[B, T, H*D] arrays, as a model's projections leave them: the calls
AND what the entry puts around them (the transposes of a [B*H, T, D]
call; nothing but delta around a d64 pair's, whose kernels only this
reading runs), so ``entry_fwd_bwd_ms - fwd_ms - bwd_ms`` is what
surrounds a layer's two calls (``around_ms``; ``entry_differ`` holds
the entry's outputs to the first implementation's).
A shape the chip's compiler refuses is a row with ``error`` and no
time.  --kv-heads, --v-dim and --window reach the grouped, latent and
banded calls (Laguna: --heads 48 --kv-heads 8 --dims 128 --causal
[--window 512]; Moonlight: --heads 16 --dims 192 --v-dim 128
--causal), and every row says what the one-pass backward's instance
counts in VMEM and what the call asks Mosaic for (``bwd_vmem_mb``,
``bwd_asked_mb``: 0 where it asks for nothing; the dq + dkv calls of
--two-pass ask by rules of their own).  --dense-parity adds, per
row, how far each output lies from
the module's own dense chain (_dense_path: same operands, same mask)
as a share of that output's largest entry.

--crossover: fwd+bwd of the kernels against the naive XLA chain at
several sequence lengths (the FLASH_MIN_SEQ question), three columns
per shape:
  naive    — the dense XLA chain
  flash    — the Pallas kernels, FORCED (min_seq=0)
  shipped  — the public flash_attention() auto-dispatch, which picks
             the dense path below FLASH_MIN_SEQ: this column must
             never lose to naive beyond noise.
  python tools/bench_flash.py --crossover [--block-sweep]
      [--dims 64 128] [--seqs 128 256 512]
"""

import argparse
import functools
import importlib
import importlib.util
import itertools
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

PACKAGE = 'paddle_tpu.ops.pallas'


def load_impl(spec):
    """'name=path/to/flash_attention.py' -> (name, module loaded as a
    sibling of the tree's own inside paddle_tpu.ops.pallas, so its
    relative imports resolve); 'tree' is the tree's own module."""
    importlib.import_module(PACKAGE)
    if spec == 'tree':
        return spec, importlib.import_module(PACKAGE + '.flash_attention')
    name, path = spec.split('=', 1)
    modname = '%s._bench_flash_%s' % (PACKAGE, name)
    mspec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(mspec)
    sys.modules[modname] = mod
    mspec.loader.exec_module(mod)
    return name, mod


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


OUTPUTS = ('o', 'lse', 'dq', 'dk', 'dv', 'dbias')


def measure(fa, args, h, operands, bias, rate):
    """One implementation at one shape: (forward ms, backward ms, each
    (min, median) a call, and the outputs of one forward + backward as
    float32 numpy arrays, in OUTPUTS' order)."""
    q, k, v, do = operands
    seed = fa._pack_seed(jnp.uint32(args.seed + 1), (3, 5), 7) \
        if rate else None
    static = dict(
        h=h, causal=args.causal, block_q=fa.DEFAULT_BLOCK_Q,
        block_k=fa.DEFAULT_BLOCK_K, rate=rate, interpret=False)
    if args.window:     # a parent from before the banded calls has none
        static['window'] = args.window
    fwd = jax.jit(functools.partial(fa._flash_fwd, **static))
    bwd = jax.jit(functools.partial(fa._flash_bwd, g_lse=None, **static))
    o, lse = fwd(q, k, v, bias, seed)
    grads = bwd(q, k, v, bias, seed, o, lse, do)
    outs = [np.asarray(x.astype(jnp.float32))
            for x in (o, lse) + tuple(grads) if x is not None]
    # --inner calls chained inside ONE program (each call's output is
    # the next one's q, or q / k / v), so no launch gap between programs
    # is in the time; the kernels' time does not depend on the values
    n = args.inner

    def fwd_n(q, k, v, bias, seed):
        def chain(_, q):
            o = fwd(q, k, v, bias, seed)[0]
            # values of another width than q: the next q hangs on a
            # column of o (one more pass over q in the time)
            return o if o.shape == q.shape else \
                q + (o[..., :1] * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, n, chain, q)

    def bwd_n(q, k, v, bias, seed, o, lse, do):
        return jax.lax.fori_loop(
            0, n, lambda _, c: bwd(*c, bias, seed, o, lse, do)[:3],
            (q, k, v))

    f_ms = [x / n for x in time_call(
        jax.jit(fwd_n), (q, k, v, bias, seed), args.steps, args.repeats)]
    b_ms = [x / n for x in time_call(
        jax.jit(bwd_n), (q, k, v, bias, seed, o, lse, do), args.steps,
        args.repeats)]
    return f_ms, b_ms, outs


def to_model_layout(x, b):
    """[B*H, T, D] -> the [B, T, H*D] a projection writes."""
    n, t, d = x.shape
    return x.reshape(b, n // b, t, d).transpose(0, 2, 1, 3).reshape(
        b, t, -1)


def measure_entry(fa, args, b, heads, operands, bias, rate):
    """flash_attention() on [B, T, H, D] views of [B, T, H*D] arrays:
    ((min, median) ms of a forward, of a forward + backward, and the
    outputs o, dq, dk, dv (and dbias) as float32 numpy arrays)."""
    q, k, v, do = (to_model_layout(x, b) for x in operands)

    def entry(q, k, v, bias):
        split = [x.reshape(b, x.shape[1], n, -1)
                 for x, n in zip((q, k, v), heads)]
        o = fa.flash_attention(
            *split, causal=args.causal, key_bias=bias, dropout_rate=rate,
            dropout_seed=jnp.uint32(args.seed + 1) if rate else None,
            dropout_offsets=(3, 5), dropout_g_offset=7,
            **({'window': args.window} if args.window else {}))
        return o.reshape(b, o.shape[1], -1)

    def both(q, k, v, bias, do):
        o, vjp = jax.vjp(entry, q, k, v, bias)
        return (o,) + vjp(do)

    outs = [np.asarray(x.astype(jnp.float32))
            for x in jax.jit(both)(q, k, v, bias, do) if x is not None]
    n = args.inner

    def fwd_n(q, k, v, bias):
        def chain(_, q):
            o = entry(q, k, v, bias)
            return o if o.shape == q.shape else \
                q + (o[..., :1] * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, n, chain, q)

    def both_n(q, k, v, bias, do):
        return jax.lax.fori_loop(
            0, n, lambda _, c: both(*c, bias, do)[1:4], (q, k, v))

    f_ms = [x / n for x in time_call(
        jax.jit(fwd_n), (q, k, v, bias), args.steps, args.repeats)]
    fb_ms = [x / n for x in time_call(
        jax.jit(both_n), (q, k, v, bias, do), args.steps, args.repeats)]
    return f_ms, fb_ms, outs


def dense_outputs(fa, args, b, h, operands, bias, rate):
    """o, dq, dk, dv (and dbias) of the module's dense chain on the
    same operands and the same mask, as [B*H, T, D] float32 numpy
    arrays; two samples at a time, so the [2, H, T, T] scores fit."""
    q, k, v, do = ([x.reshape(b, -1, *x.shape[1:]).transpose(0, 2, 1, 3)
                    for x in operands])

    @jax.jit
    def chunk(q, k, v, do, bias, g_off):
        def f(q, k, v, bias):
            return fa._dense_path(
                q, k, v, args.causal, bias, rate,
                jnp.uint32(args.seed + 1), (3, 5), g_off,
                **({'window': args.window} if args.window else {}))
        o, vjp = jax.vjp(f, q, k, v, bias)
        return (o,) + vjp(do)

    step = 2 if b % 2 == 0 else 1
    parts = []
    for b0 in range(0, b, step):
        sl = slice(b0, b0 + step)
        parts.append(chunk(q[sl], k[sl], v[sl], do[sl],
                           None if bias is None else bias[sl],
                           jnp.uint32(7 + b0 * h)))
    outs = []
    for i, xs in enumerate(zip(*parts)):
        if xs[0] is None:       # no bias, no dbias
            continue
        x = np.concatenate([np.asarray(x.astype(jnp.float32))
                            for x in xs])
        outs.append(x if i == 4 else
                    x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1],
                                                    x.shape[3]))
    return outs


def one_pass_vmem(fa, args, t, d, dv, group, has_bias):
    """What an instance of the one-pass backward counts and what its
    call asks Mosaic for, in MB, by the implementation's own rule
    (none in a copy from before PR 42); 0 asked: nothing, the
    compiler's default."""
    if not hasattr(fa, '_one_pass_vmem'):
        return {}
    item = jnp.dtype(args.dtype).itemsize
    blocks = fa._one_pass_blocks(t, t, *fa._window_blocks(
        fa._block_sizes(t, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K, d,
                        item, dv), args.window))
    count = fa._one_pass_vmem(t, t, d, dv, *blocks, item, group,
                              has_bias, False)
    admitted, limit = fa._common.one_pass_backward_limit(count)
    return dict(bwd_vmem_mb=round(count / 2 ** 20, 2),
                bwd_asked_mb=round((limit or 0) / 2 ** 20, 2),
                one_pass_admitted=admitted)


def bench_calls(args):
    from paddle_tpu.fluid import monitor
    impls = [load_impl(s) for s in (args.impl or ['tree'])]
    h, d = args.heads or 12, args.dims[0]
    hkv, dv = args.kv_heads or h, args.v_dim or d
    device = jax.devices()[0].device_kind
    rows = []
    first_entry = {}
    for shape in args.shapes:
        b, t = (int(x) for x in shape.split('x'))
        rng = np.random.RandomState(args.seed)
        # q, k, v, dO as the kernels take them: [B*H | B*Hkv, T, D | Dv]
        operands = [jnp.asarray(rng.randn(b * n, t, w),
                                jnp.dtype(args.dtype))
                    for n, w in ((h, d), (hkv, d), (hkv, dv), (h, dv))]
        # a padding mask as models/bert.py builds it: 0 / -10000
        bias_full = jnp.asarray(
            np.where(rng.rand(b, t) < 0.1, -10000.0, 0.0), jnp.float32)
        for has_bias, rate in itertools.product(args.key_bias, args.rate):
            first = None
            for name, fa in impls:
                fa.FUSED_BWD = not args.two_pass
                if args.fused_blocks:
                    fa.FUSED_BLOCK_Q, fa.FUSED_BLOCK_K = args.fused_blocks
                row = dict(device=device, impl=name, b=b, t=t, h=h, d=d,
                           hkv=hkv, dv=dv, window=args.window,
                           dtype=args.dtype, bias=int(has_bias),
                           rate=rate, causal=int(args.causal),
                           fused_bwd=int(not args.two_pass),
                           fused_blocks=[fa.FUSED_BLOCK_Q,
                                         fa.FUSED_BLOCK_K])
                row.update(one_pass_vmem(fa, args, t, d, dv, h // hkv,
                                         has_bias))
                bias = bias_full if has_bias else None
                try:
                    f_ms, b_ms, outs = measure(fa, args, h, operands,
                                               bias, rate)
                except Exception as e:   # refused by the chip's compiler
                    m = str(e)
                    row['error'] = m[max(m.find('Scoped allocation'), 0):][
                        :200]
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    continue
                first = first or outs
                # which outputs differ from the first impl's, and by
                # how much at most
                differ = {n: float(np.nanmax(np.abs(x - y)))
                          for n, x, y in zip(OUTPUTS, outs, first)
                          if not np.array_equal(x, y, equal_nan=True)}
                row.update(fwd_ms=round(f_ms[0], 4),
                           fwd_ms_median=round(f_ms[1], 4),
                           bwd_ms=round(b_ms[0], 4),
                           bwd_ms_median=round(b_ms[1], 4),
                           bit_equal_to_first=not differ, differ=differ)
                if not args.two_pass:   # FUSED_BWD off is no entry's
                    paired = monitor.counter_value(
                        'pallas/flash_attention/layout_paired') or 0
                    ef_ms, efb_ms, e_outs = measure_entry(
                        fa, args, b, (h, hkv, hkv), operands, bias, rate)
                    row['entry_layout'] = 'paired' if (
                        monitor.counter_value(
                            'pallas/flash_attention/layout_paired') or 0
                    ) > paired else 'transposed'
                    mine = first_entry.setdefault(
                        (shape, has_bias, rate), e_outs)
                    row.update(
                        entry_fwd_ms=round(ef_ms[0], 4),
                        entry_fwd_bwd_ms=round(efb_ms[0], 4),
                        entry_fwd_bwd_ms_median=round(efb_ms[1], 4),
                        around_ms=round(efb_ms[0] - f_ms[0] - b_ms[0], 4),
                        entry_differ={
                            n: float(np.nanmax(np.abs(x - y)))
                            for n, x, y in zip(
                                ('o', 'dq', 'dk', 'dv', 'dbias'),
                                e_outs, mine)
                            if not np.array_equal(x, y, equal_nan=True)})
                if args.dense_parity:
                    # lse is not an output of the dense chain
                    mine = [x for n, x in zip(OUTPUTS, outs) if n != 'lse']
                    names = [n for n in OUTPUTS if n != 'lse']
                    row['off_dense'] = {
                        n: float(np.abs(x - y).max() / np.abs(y).max())
                        for n, x, y in zip(names, mine, dense_outputs(
                            fa, args, b, h, operands, bias, rate))}
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)
        with open(args.out, 'w') as f:
            json.dump({'device': device, 'rows': rows}, f, indent=1)


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
    readback: serialize on-device and sync via np.asarray."""
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


def bench_crossover(args):
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
            ms_naive = timed(g_naive, (q, k, v), args.steps * args.inner)

            g_flash = loss_of(functools.partial(
                fa.flash_attention, causal=args.causal, min_seq=0))
            ms_flash = timed(g_flash, (q, k, v), args.steps * args.inner)

            g_ship = loss_of(functools.partial(fa.flash_attention,
                                               causal=args.causal))
            ms_ship = timed(g_ship, (q, k, v), args.steps * args.inner)
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
                        ms = timed(gf, (q, k, v), args.steps * args.inner)
                        print('    bq=%4d bk=%4d  %7.2f ms'
                              % (ebq, ebk, ms), flush=True)
                # restore SHIPPED defaults so later seqs measure them
                fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = shipped


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=5)
    ap.add_argument('--inner', type=int, default=10,
                    help='kernel calls chained inside one program')
    ap.add_argument('--heads', type=int, default=None,
                    help='override heads (default 12; crossover: per dim)')
    ap.add_argument('--dims', type=int, nargs='+', default=[64, 128],
                    help='head dims (the call bench takes the first)')
    ap.add_argument('--causal', action='store_true')
    ap.add_argument('--kv-heads', type=int, default=None,
                    help='K/V heads (default: as many as --heads)')
    ap.add_argument('--v-dim', type=int, default=None,
                    help="the values' width (default: the first --dims)")
    ap.add_argument('--window', type=int, default=0,
                    help='band of the causal mask (with --causal)')
    ap.add_argument('--fused-blocks', type=int, nargs=2, default=None,
                    metavar=('Q', 'K'),
                    help='sweep: FUSED_BLOCK_Q / FUSED_BLOCK_K')
    # the call bench
    ap.add_argument('--shapes', nargs='+', default=['12x2048', '48x512'],
                    help='BATCHxSEQ of each call')
    ap.add_argument('--rate', type=float, nargs='+', default=[0.1])
    ap.add_argument('--key-bias', type=int, nargs='+', default=[1])
    ap.add_argument('--impl', action='append',
                    help="'tree' or NAME=PATH of a flash_attention.py")
    ap.add_argument('--two-pass', action='store_true',
                    help='FUSED_BWD off: the dq + dkv kernels')
    ap.add_argument('--repeats', type=int, default=3)
    ap.add_argument('--dtype', default='bfloat16',
                    choices=['bfloat16', 'float32'])
    ap.add_argument('--dense-parity', action='store_true',
                    help="each output's distance from the dense chain")
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--out', default=None, help='also write JSON here')
    # the crossover bench
    ap.add_argument('--crossover', action='store_true')
    ap.add_argument('--batch', type=int, default=32)
    ap.add_argument('--seqs', type=int, nargs='+',
                    default=[128, 256, 512, 1024, 2048])
    ap.add_argument('--block-sweep', action='store_true')
    args = ap.parse_args()
    if jax.default_backend() != 'tpu':
        sys.exit('bench_flash.py times kernels on a TPU; this process '
                 'has %r' % jax.default_backend())
    if args.crossover:
        bench_crossover(args)
    else:
        bench_calls(args)


if __name__ == '__main__':
    main()
