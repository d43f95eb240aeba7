"""chip_smoke.py — the quickest proof that the system still starts on
the chip: BERT-base pretraining through the normal entry points
(fluid.Program -> fluid.Executor(fluid.XLAPlace(0)).run) on one TPU.

    python chip_smoke.py            # one chip, every phase below
    python chip_smoke.py --chips 4  # the data-parallel / sharded path
                                    # across four chips and the single-
                                    # device run it is compared with
    python chip_smoke.py --phase olmoe   # OLMoE-1B-7B's train program
                                    # at published widths (one layer,
                                    # one 4096-token sequence, f32)
                                    # against jax.grad of its reference
    python chip_smoke.py --phase moonlight   # Moonlight-16B-A3B's, one
                                    # 8192-token sequence, before and
                                    # after its routers' bias has moved
    python chip_smoke.py --phase lfm2    # LFM2-8B-A1B's: short
                                    # convolutions, grouped causal flash
                                    # at d64, the tied table's gradient
    python chip_smoke.py --phase evabyte # EvaByte's: one EVA attention
                                    # call at the published 32768
                                    # positions, then every gradient
                                    # of one layer at 4096
    python chip_smoke.py --phase solar   # Solar-Open2-250B's: the gated
                                    # delta rule alone at 32768
                                    # positions, then gradients of
                                    # the cell's four layers
    python chip_smoke.py --phase ouro    # Ouro-2.6B's: the looped stack
                                    # as ONE While, gradients of the
                                    # shared layers summed over trips
    python chip_smoke.py --phase xing4   # Xing4.0-29B-A4B's: the hyper-
                                         # connection ops alone, the train
                                         # step's gradients, the cell's loss
    python chip_smoke.py --phase phi4flash   # Phi-4-mini-flash's: the
                                    # selective scan, differential
                                    # attention, the cross-decoder
    python chip_smoke.py --phase kimi    # Kimi-Linear-48B-A3B's: the
                                    # delta rule at all 32 heads in
                                    # recompute groups, NoPE latent
                                    # attention, the cell's loss
    python chip_smoke.py --phase sdar    # SDAR-30B-A3B-Chat's: block
                                    # diffusion's two copies under the
                                    # block-relation flash mask,
                                    # gradients, the cell's loss
    python chip_smoke.py --phase nemotron_h  # Nemotron-3-Nano-30B-A3B's:
                                    # the chunked ssd_scan in recompute
                                    # groups, relu2 experts, GQA 32/2,
                                    # gradients, the cell's loss
    python chip_smoke.py --phase grouped # the experts' grouped-matmul
                                    # kernels against ragged_dot at
                                    # the five routed cells' shapes

One process, no children.  It fails (non-zero, no result line) unless
jax.devices()[0].platform == 'tpu'; nothing here falls back to the CPU.
Weights and batches are random, from fixed seeds; every check is the
repo's own means (loss falls, dispatch counters, eval determinism,
save/load round trip, fused-vs-dense agreement).  Earlier lines carry
what is worth keeping (step times, compile seconds, peak HBM, the
dispatch table, versions) — smoke readings, not a benchmark.  The LAST
stdout line is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# first-step losses of one program on the same seeded weights on one
# device and under a mesh: both run bf16 matmuls (8 mantissa bits, eps
# 2^-8 = 3.9e-3) and average ~8k token losses, so they agree far inside
# 1e-2; a wrong gather row or a dropped gradient moves the loss by
# whole units
BF16_LOSS_RTOL = 1e-2
# kernel-vs-dense elementwise on bf16 attention outputs / f32 grads
BF16_ELEM_TOL = 3e-2
# the kernels a BERT + Adam train step reaches (Adam and the embedding
# lookups are no kernels: their own lowerings, fused by XLA)
KERNELS = ('flash_attention',)

_T0 = time.time()


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    say('ok: %s' % what)


def say(msg):
    print('[%7.1fs] %s' % (time.time() - _T0, msg), flush=True)


# what JAX itself reports while the phases run: every backend compile
# (the executor's own segment_cache_miss cannot see a jit that quietly
# re-specialises) and the persistent cache's hits and misses
_JAX_EVENTS = {'compiles': 0, 'cache_hits': 0, 'cache_misses': 0}
_listening = []


def _listen():
    if _listening:
        return
    import jax

    def on_duration(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            _JAX_EVENTS['compiles'] += 1

    def on_event(event, **_):
        for k in ('cache_hits', 'cache_misses'):
            if event == '/jax/compilation_cache/' + k:
                _JAX_EVENTS[k] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _listening.append(True)


def _ints32(batch):
    return {k: v.astype('int32') if v.dtype == np.int64 else v
            for k, v in batch.items()}


def build_bert(cfg, seq):
    """BERT pretrain, bf16 AMP with dynamic loss scaling around Adam.
    Also returns the for_test clone taken before minimize (forward
    only, dropout off)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        _, _, loss = models.bert.build_pretrain(cfg, seq)
        test = main.clone(for_test=True)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(1e-4), use_dynamic_loss_scaling=True)
        opt.minimize(loss)
    return main, startup, test, loss


def host_batch(cfg, batch, seq):
    """The one fixed synthetic batch (seed 0), ints already int32."""
    from paddle_tpu import models
    return _ints32(models.bert.synthetic_batch(
        cfg, batch, seq, np.random.RandomState(0)))


def train_fresh(cfg, batch, seq, steps):
    """Build the program, run startup in a fresh scope and train
    `steps` steps on the device-resident fixed batch (uncommitted, as
    the benchmark feeds).  Returns train_steps()'s triple."""
    import jax
    import paddle_tpu.fluid as fluid
    main, startup, _, loss = build_bert(cfg, seq)
    feed = {k: jax.device_put(v)
            for k, v in host_batch(cfg, batch, seq).items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        return train_steps(exe, main, feed, loss, steps)


def _scalar(fetched):
    return float(np.asarray(fetched[0]).ravel()[0])


def train_steps(exe, target, feed, loss, steps):
    """`steps` runs of one fixed batch; each fetch is a host numpy
    value, so every step has finished on the device when its time is
    taken.  Returns (losses, seconds per step, and per step the
    compiles it caused: (executor/segment_cache_miss, JAX backend
    compiles))."""
    from paddle_tpu.fluid import monitor
    _listen()

    def compiles():
        return (int(monitor.counter_value('executor/segment_cache_miss')),
                _JAX_EVENTS['compiles'])

    losses, secs, compiled = [], [], []
    for _ in range(steps):
        t, c0 = time.time(), compiles()
        losses.append(_scalar(exe.run(target, feed=feed,
                                      fetch_list=[loss])))
        secs.append(time.time() - t)
        compiled.append(tuple(b - a for a, b in zip(c0, compiles())))
    return losses, secs, compiled


def check_trained(tag, losses, secs, compiled, warm=1, falls=True):
    say('%s losses %s' % (tag, ' '.join('%.4f' % v for v in losses)))
    say('%s first step (compile + run) %.1f s; later steps ms: %s'
        % (tag, secs[0], ' '.join('%.1f' % (s * 1e3) for s in secs[1:])))
    check(all(np.isfinite(losses)), '%s: losses finite' % tag)
    if falls:
        check(losses[-1] < losses[0],
              '%s: loss falls (%.4f -> %.4f)'
              % (tag, losses[0], losses[-1]))
    check(not any(n for c in compiled[warm:] for n in c),
          '%s: no compile after warm-up; per step (segment_cache_miss, '
          'jax backend compiles) = %s' % (tag, compiled))


def check_dispatch(kernels):
    """From the counters, not the flags: each kernel dispatched fused,
    its last decision was not for the interpreter (the platform is one
    per process, so then none was), and nothing fell back for want of
    a TPU."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops.pallas import common
    table = common.report().get('kernels', {})
    say('dispatch table: %s' % json.dumps(table, sort_keys=True))
    for k in kernels:
        n = monitor.counter_value('pallas/%s/dispatch_fused' % k)
        check(n > 0, 'pallas/%s/dispatch_fused = %d > 0' % (k, n))
        check(table[k]['last']['interpret'] is False,
              '%s: interpret False (last decision %s)'
              % (k, table[k]['last']))
    off = {k: v for k, v in monitor.flat().items()
           if k.startswith('pallas/') and k.endswith('/fallback/off_tpu')
           and v}
    check(not off, 'no pallas/*/fallback/off_tpu counted %s' % (off or ''))


def check_dropout_draws(drawn, ops, elements):
    """The dropout op draws from the flash kernels' counter hash
    (ops/keep_hash.py): `dropout/counter_draws` rose by one a lowering
    of each of the program's `ops` dropout ops, a whole number of
    times (once a trace of the one-chip runner's whole-program
    gradient), and `dropout/elements` is what the last traced program
    draws a step."""
    from paddle_tpu.fluid import monitor
    seen = monitor.gauge_value('dropout/elements')
    check(drawn > 0 and drawn % ops == 0 and seen % (ops * elements) == 0
          and seen > 0,
          'dropout/counter_draws rose by %d = %d x %d dropout ops; '
          'dropout/elements %d = %d x %d ops x %d elements'
          % (drawn, drawn // ops, ops, seen, seen // (ops * elements),
             ops, elements))


def phase_train_eval_roundtrip(cfg, batch, seq, steps):
    """Train on one fixed batch, then flows 2 and 3 of the verify
    skill: for_test clone evaluated twice is the same loss, and
    save_persistables -> fresh scope -> load_persistables reproduces
    it."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    main, startup, test, loss = build_bert(cfg, seq)
    feed = {k: jax.device_put(v)
            for k, v in host_batch(cfg, batch, seq).items()}
    tag = 'bert b%d s%d L%d' % (batch, seq, cfg.layers)
    # taken after the build, which infers shapes through the lowerings
    drawn = monitor.counter_value('dropout/counter_draws')
    exe = fluid.Executor(fluid.XLAPlace(0))
    ckpt = tempfile.mkdtemp(prefix='chip_smoke_ckpt_')
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            losses, secs, compiled_at = train_steps(exe, main, feed,
                                                    loss, steps)
            check_trained(tag, losses, secs, compiled_at)
            check_dispatch(KERNELS)
            check_dropout_draws(
                monitor.counter_value('dropout/counter_draws') - drawn,
                2 * cfg.layers + 1, batch * seq * cfg.hidden)
            e1 = _scalar(exe.run(test, feed=feed, fetch_list=[loss]))
            e2 = _scalar(exe.run(test, feed=feed, fetch_list=[loss]))
            check(np.isfinite(e1) and e1 == e2,
                  'for_test clone evaluated twice: %.6f == %.6f'
                  % (e1, e2))
            fluid.io.save_persistables(exe, ckpt, main)
        with fluid.scope_guard(fluid.Scope()):
            fluid.io.load_persistables(exe, ckpt, main)
            e3 = _scalar(exe.run(test, feed=feed, fetch_list=[loss]))
            check(e3 == e1, 'save_persistables -> fresh scope -> '
                  'load_persistables reproduces the eval loss: %.6f == '
                  '%.6f' % (e3, e1))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def phase_attn_dropout(cfg, batch, seq):
    """The reference-default attn_dropout=0.1: the in-kernel
    counter-hash mask path, two steps."""
    losses, secs, _ = train_fresh(cfg, batch, seq, 2)
    say('attn_dropout=%.1f losses %s; first step %.1f s, second %.1f ms'
        % (cfg.attn_dropout, ' '.join('%.4f' % v for v in losses),
           secs[0], secs[1] * 1e3))
    check(all(np.isfinite(losses)),
          'attn_dropout=%.1f: two steps, losses finite' % cfg.attn_dropout)


def phase_dropout_bits(shape=(16, 512, 768), rate=0.1, steps=2):
    """The dropout op through the executor on the chip, two steps:
    `Mask` is the counter hash of ops/keep_hash.py computed here in
    numpy (the chip's integer multiplies, shifts and the signed
    compare agree with it bit for bit), the gradient is the same
    mask times the scale, and each step draws anew."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=list(shape[1:]), dtype='float32')
        x.stop_gradient = False
        out = fluid.layers.dropout(
            x, rate, dropout_implementation='upscale_in_train')
        loss = fluid.layers.reduce_sum(out)
        fluid.backward.append_backward(loss)
    op = [o for o in main.global_block().ops if o.type == 'dropout'][0]
    op_seed = op.attrs['__op_seed__'] * 2654435761 % (1 << 32)
    rows = np.arange(int(np.prod(shape[:-1])), dtype=np.uint32)[:, None]
    cols = np.arange(shape[-1], dtype=np.uint32)[None, :]
    u = np.uint32

    def want(step):
        seed = u(op_seed) ^ u(step * 0x9E3779B9 % (1 << 32))
        h = (rows * u(0x9E3779B1)) ^ seed ^ (cols * u(0x85EBCA77))
        h ^= h >> u(16)
        h *= u(0x7FEB352D)
        h ^= h >> u(15)
        h *= u(0x846CA68B)
        h ^= h >> u(16)
        return ((h >> u(8)) < u(round((1 - rate) * (1 << 24)))
                ).reshape(shape)

    feed = {'x': np.ones(shape, 'float32')}
    masks = []
    with fluid.scope_guard(fluid.Scope()), np.errstate(over='ignore'):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(steps):
            got, dx = exe.run(main, feed=feed, fetch_list=[
                out, main._grad_name_map[x.name]])
            masks.append(np.asarray(got) != 0)
            hits = [s for s in range(4)
                    if np.array_equal(masks[-1], want(s))]
            check(len(hits) == 1 and
                  np.array_equal(np.asarray(dx), np.asarray(got)),
                  'dropout op on the chip, run %d: Mask is the numpy '
                  'counter hash of step %s, keep share %.4f, the '
                  'gradient is Out' % (len(masks), hits, masks[-1].mean()))
    check((masks[0] != masks[1]).mean() > rate,
          'dropout op: two steps draw two masks')


def phase_flash_vs_dense(b=2, t=1024, h=12, d=64, rate=0.1):
    """Flash attention has no flag to turn off, so hold the kernels to
    the dense chain directly: forward and all four gradients, with a
    key bias and in-kernel dropout (both arms draw the same counter-
    hash mask)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
               for kk in ks[:3])
    bias = jax.random.normal(ks[3], (b, t), jnp.float32)

    def run(min_seq):
        def loss(q, k, v, bias):
            o = fa.flash_attention(
                q, k, v, key_bias=bias, min_seq=min_seq,
                dropout_rate=rate, dropout_seed=jnp.uint32(11))
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2, 3), has_aux=True))(q, k, v, bias)
        return (o,) + g

    fused, dense = run(0), run(10 ** 9)
    for name, a, r in zip(('out', 'dq', 'dk', 'dv', 'dbias'), fused,
                          dense):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        err = float(np.max(np.abs(a - r)) / (np.max(np.abs(r)) + 1e-9))
        check(np.isfinite(a).all() and err <= BF16_ELEM_TOL,
              'flash %s vs dense chain: max err / max |ref| = %.2e <= %g'
              % (name, err, BF16_ELEM_TOL))


def phase_lenet(batch=512):
    """One line of record: does the LeNet b512 f32 conv weight-gradient
    at FLAGS_conv_precision='highest' compile on this chip's compiler,
    and how long does it take."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        _, _, loss, _ = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(batch, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        losses, secs, _ = train_steps(exe, main, feed, loss, 3)
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          'lenet b%d conv_precision=%s compiled and trains: first step '
          '(compile + run) %.1f s, loss %.4f -> %.4f'
          % (batch, fluid.flags.get_flag('FLAGS_conv_precision'),
             secs[0], losses[0], losses[-1]))


def _peak_bytes(devs):
    return [d.memory_stats()['peak_bytes_in_use'] for d in devs]


_COUNTED_EXECUTABLES = set()


def _mosaic_calls_a_step():
    """The most Mosaic calls in one executable this process has come
    to hold since the last time this was asked (the train step's;
    every chip runs the same program, and none of the calls sits in a
    loop): which arm of the dispatch ran, read from the optimised HLO
    without a trace."""
    from paddle_tpu.fluid import compile_cache
    new = compile_cache.plane().held_hlo(skip=_COUNTED_EXECUTABLES)
    _COUNTED_EXECUTABLES.update(key for key, _ in new)
    return max([text.count('custom_call_target="tpu_custom_call"')
                for _, text in new] or [0])


def phase_four_chips(cfg, global_batch, seq, steps, n=4, mp=2, falls=True):
    """The data-parallel and the sharded path, in this one process over
    `n` devices, against the single-device run of the same seeded
    program: (a) with_data_parallel on a dp mesh, (b) with ``mp``,
    dp x mp with __graft_entry__'s column-parallel rule.  First-step
    losses agree (attention dropout included, where cfg has it: every
    shard hashes its global batch x head index) and losses fall;
    parameters and the batch really lie on every device.  The step's
    kernel dispatches fused in the single-device run, and under either
    mesh inside a shard_map over the batch axis, on each chip's share
    of the batch (`dispatch_sharded`; ops/pallas/flash_attention.py
    mesh_flash_attention): nothing answers dense for the mesh's sake.
    Every run prints its dispatch counters and the Mosaic calls a
    step.  ``falls``: whether `steps` steps have to lower the loss."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    devs = jax.devices()[:n]
    host = host_batch(cfg, global_batch, seq)

    def column_parallel(name, shape):
        # fc weight matrices [in, out] -> column-parallel on 'mp'
        if len(shape) == 2 and min(shape) >= 8 and '.w' in name:
            return P(None, 'mp')
        return None

    counters = ('dispatch_fused', 'dispatch_sharded', 'dispatch_dense',
                'fallback/auto_partitioned', 'fallback/batch_not_split')

    def kernel_counts():
        return np.array([[int(monitor.counter_value(
            'pallas/%s/%s' % (k, c)) or 0) for k in KERNELS]
            for c in counters])

    def run(tag, mesh, rule=None):
        main, startup, _, loss = build_bert(cfg, seq)
        target, feed = main, host
        if mesh is not None:
            target = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name).with_mesh(mesh)
            if rule is not None:
                target = target.with_param_shardings(rule)
            feed = {k: jax.device_put(v, NamedSharding(mesh, P('dp')))
                    for k, v in host.items()}
            spans = {k: len(v.sharding.device_set)
                     for k, v in feed.items()}
            check(set(spans.values()) == {n},
                  '%s: every feed of the batch lies on %d devices'
                  % (tag, n))
        else:
            feed = {k: jax.device_put(v) for k, v in host.items()}
        scope = fluid.Scope()
        # taken after the build, which infers shapes through dispatch()
        before = kernel_counts()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            losses, secs, compiled_at = train_steps(exe, target, feed,
                                                    loss, steps)
            check_trained(tag, losses, secs, compiled_at, falls=falls)
            fused, sharded, dense, unwrapped, unsplit = \
                kernel_counts() - before
            counted = '%s for %s; %d Mosaic calls a step' % (
                ', '.join('%s %s' % (c, v) for c, v in zip(
                    counters, (fused, sharded, dense, unwrapped,
                               unsplit))),
                ' / '.join(KERNELS), _mosaic_calls_a_step())
            if mesh is None:
                check((fused > 0).all() and not dense.any() and
                      not sharded.any(),
                      '%s: every kernel dispatched fused: %s'
                      % (tag, counted))
            else:
                check((fused > 0).all() and (sharded > 0).all() and
                      not dense.any(),
                      '%s: every kernel dispatched fused inside a '
                      'shard_map over the batch axis: %s'
                      % (tag, counted))
            if mesh is not None:
                params = {
                    p.name: fluid.core.as_array(scope.find_var(p.name))
                    for p in main.all_parameters()}
                on = {k: len(v.sharding.device_set)
                      for k, v in params.items()}
                check(set(on.values()) == {n},
                      '%s: all %d parameters lie on %d devices'
                      % (tag, len(on), n))
                if rule is not None:
                    split = [k for k, v in params.items()
                             if not v.sharding.is_fully_replicated]
                    check(split, '%s: %d parameters are split over '
                          "'mp' (e.g. %s %s)"
                          % (tag, len(split), split[0],
                             params[split[0]].sharding.spec))
        return losses

    single = run('single device b%d s%d' % (global_batch, seq), None)
    check_dispatch(KERNELS)     # while the last decisions are its own
    base = _peak_bytes(devs)
    meshes = [('dp%d' % n, Mesh(np.array(devs), ('dp',)), None)]
    if mp:
        meshes.append((
            'dp%dxmp%d' % (n // mp, mp),
            Mesh(np.array(devs).reshape(n // mp, mp), ('dp', 'mp')),
            column_parallel))
    for tag, mesh, rule in meshes:
        losses = run(tag, mesh, rule)
        check(abs(losses[0] - single[0]) <=
              BF16_LOSS_RTOL * abs(single[0]),
              '%s first-step loss %.4f vs single device %.4f within '
              'rtol %g' % (tag, losses[0], single[0], BF16_LOSS_RTOL))
    peaks = _peak_bytes(devs)
    say('peak HBM per device, GB: after single-device run %s; after '
        'mesh runs %s'
        % (' '.join('%.2f' % (p / 1e9) for p in base),
           ' '.join('%.2f' % (p / 1e9) for p in peaks)))
    # the single-device run leaves devices 1.. untouched; a mesh run
    # that quietly put everything on device 0 would leave them so
    check(min(peaks[1:]) > 0.25 * max(peaks),
          'every device held a real share of a mesh run')


# OLMoE's f32 train program against jax.grad of the plain reference at
# published widths, per sampled gradient tensor (my chip run, PR 25).
# Every product on both sides is full f32, with attention as the dense
# matmul + softmax chain and with the flash kernels alike (they
# multiply f32 operands at full precision), and what is left is
# summation order: the loss equal to the last digit, entries within
# 3.0e-5 (dense) and 3.2e-5 (flash) of their tensor's largest; the
# bounds are the benchmark family's on the loss (which explains it)
# and 6x the reading on the entries.  A wrong permutation, gate or rotary pairing moves a
# gradient by its own size, one bfloat16 product in the kernels moved
# single entries by up to 9.7e-2.
OLMOE_LOSS_RTOL = 1e-5
OLMOE_LOSS_BATCHES = 96
OLMOE_ENTRY_RTOL = 2e-4
# embedding, g_in, Wq, Wk, Wv, g_q, g_k, Wo, g_post, router, gate, up,
# down, g_final, head: build_pretrain's creation order at one layer
OLMOE_SAMPLED = {'embedding': 0, 'q_norm_gain': 5, 'router': 9,
                 'gate': 10, 'up': 11, 'down': 12}


def _olmoe_train_grads(cfg, seq, seed, feed, sample):
    """One f32 train step (SGD at lr 0: the step is the gradients) of
    the zoo's program -> (loss, {what: sampled gradient on the host});
    with ``sample`` None only the startup program runs -> the seeded
    weights, on the host.  Leaves nothing on the chip."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import olmoe
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(cfg, seq)
            params = [p.name for p in main.all_parameters()]
            pairs = dict((p.name, g.name) for p, g in
                         fluid.optimizer.SGD(0.0).minimize(loss)[1])
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        if sample is None:
            # on the host: the chip has no room for a second copy
            # beside the dense-attention step
            return [np.asarray(fluid.core.as_array(scope.find_var(p)))
                    for p in params]
        dropped0 = monitor.counter_value('moe/dropped_tokens')
        t0 = time.time()
        got = exe.run(main, feed=feed, return_numpy=False, fetch_list=[
            loss] + [pairs[params[i]] for i in OLMOE_SAMPLED.values()])
        got_loss = _scalar(got[:1])
        say('olmoe f32 train program (%s attention), 1 x %d tokens: loss '
            '%.6f in %.1f s (with compile)'
            % ('flash' if cfg.use_flash else 'dense', seq, got_loss,
               time.time() - t0))
        # a run that fetches and blocks reads the routers' loads
        exe.run(main, feed=feed, fetch_list=[loss])
        check(monitor.counter_value('moe/dropped_tokens') == dropped0,
              'moe/dropped_tokens stayed 0 (%d pairs routed so far, '
              'moe/load_max_over_mean %.3f)'
              % (monitor.counter_value('moe/tokens_routed'),
                 monitor.gauge_value('moe/load_max_over_mean')))
        grads = {what: np.asarray(x)
                 for name, g in zip(OLMOE_SAMPLED, got[1:])
                 for what, x in sample(name, g).items()}
        del got
        for name in scope.local_var_names():
            scope.erase(name)
    return got_loss, grads


def _olmoe_test_losses(cfg, seq, seed, feeds):
    """The f32 for_test program's loss on each feed, on the weights
    the seeded startup program gives (the same as the train runs')."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import olmoe
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = olmoe.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        got = [_scalar(exe.run(test, feed=feed, fetch_list=[loss]))
               for feed in feeds]
        scope = fluid.global_scope()
        for name in scope.local_var_names():
            scope.erase(name)
    return got


def phase_olmoe_gradients(seq=4096, seed=0, rows=64):
    """models.olmoe.BASE cut to one layer: loss and sampled gradients
    of the f32 TRAIN program against the reference's, on one seeded
    sequence, with dense attention and with the flash kernels; then
    the reference in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import olmoe
    from paddle_tpu.models.reference import olmoe as reference
    dense = olmoe.OlmoeConfig(layers=1, use_flash=False)
    sizes = dict(layers=1, heads=dense.heads, top_k=dense.top_k)
    feed = _ints32(olmoe.synthetic_batch(
        dense, 1, seq, np.random.RandomState(seed)))
    ids, pos, labels = (jnp.asarray(feed[k])
                        for k in ('ids', 'pos_ids', 'labels'))
    picked_rows = np.unique(feed['ids'])[:rows]
    experts = {}

    def sample(name, array):
        if name == 'embedding':
            return {'embedding rows': array[picked_rows]}
        if name in ('gate', 'up', 'down'):
            return {'%s, %s loaded expert' % (name, which): array[e]
                    for which, e in experts.items()}
        return {name: array}

    # the loads come from the reference's forward on the same seeded
    # weights, which the startup program alone gives
    weights = _olmoe_train_grads(dense, seq, seed, feed, None)
    load = np.asarray(jax.jit(lambda w: reference.forward(
        w, ids, pos, **sizes)[3][0])(weights))
    experts.update(most=int(load.argmax()), least=int(load.argmin()))
    runs = {'dense': _olmoe_train_grads(dense, seq, seed, feed, sample),
            'flash': _olmoe_train_grads(olmoe.OlmoeConfig(layers=1), seq,
                                        seed, feed, sample)}

    weights = [jnp.asarray(w) for w in weights]

    def ref_loss(some, dtype=jnp.float32):
        full = list(weights)
        for name, w in some.items():
            full[OLMOE_SAMPLED[name]] = w
        return reference.loss(full, ids, pos, labels, dtype=dtype,
                              **sizes)

    some = {name: weights[i] for name, i in OLMOE_SAMPLED.items()}
    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(some)
    want_loss = float(want_loss)
    say('reference: loss %.6f; expert loads max %d (expert %d) min %d '
        '(expert %d) mean %.0f' % (want_loss, load.max(), experts['most'],
                                   load.min(), experts['least'],
                                   load.mean()))
    want = {what: np.asarray(y) for name in OLMOE_SAMPLED
            for what, y in sample(name, want_grads[name]).items()}
    del want_grads
    worst = {}
    for kind, (got_loss, grads) in runs.items():
        rel = abs(got_loss - want_loss) / want_loss
        say('%s attention: loss %.6f, relative difference %.2e'
            % (kind, got_loss, rel))
        check(rel <= OLMOE_LOSS_RTOL, 'olmoe f32 train loss (%s '
              'attention) within %g of the reference'
              % (kind, OLMOE_LOSS_RTOL))
        entry = 0.0
        for what, y in want.items():
            x = grads[what]
            e = float(np.abs(x - y).max() / np.abs(y).max())
            d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
            entry = max(entry, e)
            say('%s attention, gradient of %s %s: largest entry '
                'difference %.3e of the largest entry (%.3e), relative '
                'L2 distance %.3e' % (kind, what, x.shape, e,
                                      np.abs(y).max(), d))
        worst[kind] = entry
    # what the benchmark's limit on the loss rests on, over many
    # batches on the same weights: how far the f32 for_test program is
    # from the reference (a token whose 8th and 9th router
    # probabilities nearly tie may pick the other expert: the one
    # source of a difference above the last place), and how far the
    # reference one precision lower is
    both = jax.jit(lambda w, i, p, l: [reference.loss(
        w, i, p, l, dtype=dt, **sizes)
        for dt in (jnp.float32, jnp.bfloat16)])
    feeds = [_ints32(olmoe.synthetic_batch(
        dense, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + OLMOE_LOSS_BATCHES)]
    program = _olmoe_test_losses(olmoe.OlmoeConfig(layers=1), seq, seed,
                                 feeds)
    off, low = [], []
    for n, (other, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(weights, *(
            jnp.asarray(other[k]) for k in ('ids', 'pos_ids', 'labels'))))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        if off[-1] > 2e-7 or low[-1] <= OLMOE_LOSS_RTOL:
            say('batch seed %d: program %.6f, reference %.6f (relative '
                'difference %.2e), reference in bfloat16 throughout '
                '%.6f (%.2e)' % (seed + n, got, full, off[-1], half,
                                 low[-1]))
    say('over %d batches: f32 for_test program against the reference, '
        'relative: median %.2e, %d above 2e-7, largest %.2e; reference '
        'in bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e '
        '%.2e, largest %.2e, %d within %g'
        % ((len(off), np.median(off), sum(x > 2e-7 for x in off), max(off),
            min(low)) + tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= OLMOE_LOSS_RTOL for x in low),
            OLMOE_LOSS_RTOL)))
    check(max(off) <= OLMOE_LOSS_RTOL, 'olmoe f32 for_test loss within '
          '%g of the reference on every batch' % OLMOE_LOSS_RTOL)
    for kind, entry in worst.items():
        check(entry <= OLMOE_ENTRY_RTOL,
              'olmoe gradients, %s attention: every sampled entry within '
              '%g of its tensor\'s largest (worst %.3e)'
              % (kind, OLMOE_ENTRY_RTOL, entry))


# Laguna-S-2.1's cut (5 layers, experts 0-7 of 256, 12544 vocabulary
# rows) at published widths: the f32 train program against jax.grad of
# the plain reference, per sampled gradient tensor, and the loss over
# many batches in f32 and with the reference in bfloat16 throughout
# (my chip runs, PR 30: PERF.md section 6 has the readings).  Every
# product on both sides is full f32 (the flash kernels, the grouped
# matmuls, the reference), so what is left is summation order through
# five layers, and the tokens whose 10th and 11th router probabilities
# nearly tie: such a token may pick the other expert in the program
# than in the reference (the loads of two experts of a layer then
# differ by one, which this phase counts), its own loss and gradient
# move by their own size, and the mean loss by up to 3.9e-6 (5 of 24
# batches read over 3e-7; the limit on the loss is the benchmark
# family's, 1e-5, which the reference in bfloat16 throughout misses on
# 20 of the 24: 2.9e-6 to 1.6e-4).  Gradients: relative L2 distance
# 2.8e-5 to 3.8e-4 (largest where every token's error adds up: the
# shared expert, the projections of the early layers), single entries
# up to 2.2e-3 of their tensor's largest where one flipped token's
# rows land; the bound is on the L2 distance, 5x the worst reading.  A
# wrong K/V group, band edge, rotated width, gate or held range, or an
# unmasked row past the held groups (the first chip run of this phase:
# 1e5) moves a gradient by its own size or more.
LAGUNA_LOSS_RTOL = 1e-5
LAGUNA_LOSS_BATCHES = 24
LAGUNA_L2_RTOL = 2e-3
# creation order: embedding 0; layer 0 (full, dense) g_in 1 Wq Wk Wv
# Wg Wo g_post gate up down 10; layer 1 (sliding, sparse) g_in 11 Wq
# Wk Wv Wg Wo g_post router 18 gate up down 21 shared 22-24; layers 2,
# 3 the same from 25 and 39; layer 4 (full, sparse) from 53; g_final
# 67; head 68
LAGUNA_SAMPLED = {'embedding': 0, 'full Wk (layer 0)': 3,
                  'full head gate (layer 0)': 5,
                  'sliding Wk (layer 1)': 13,
                  'sliding head gate (layer 1)': 15,
                  'router (layer 1)': 18, 'gate': 19, 'up': 20,
                  'down': 21, 'shared down (layer 1)': 24,
                  'full Wk (layer 4)': 55, 'router (layer 4)': 60}


def _laguna_cut():
    from paddle_tpu.models import laguna
    return laguna.LagunaConfig(vocab_size=12544, layers=5,
                               experts_held=(0, 8))


def _laguna_programs(seq, seed):
    """(main with SGD at lr 0, startup, f32 for_test clone, loss,
    params in creation order, {param: its gradient's name})."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import laguna
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = laguna.build_pretrain(_laguna_cut(), seq)
        params = [p.name for p in main.all_parameters()]
        test = main.clone(for_test=True)
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    return main, startup, test, loss, params, pairs


def phase_laguna_gradients(seq=4096, seed=0, rows=64):
    """models.laguna.BASE cut as the benchmark cuts it: loss and
    sampled gradients of the f32 TRAIN program (flash kernels, banded
    and full, grouped K/V; the held experts' grouped matmuls) against
    the reference's, on one seeded sequence; then the f32 for_test
    program's loss against the reference in float32 and in bfloat16
    throughout over LAGUNA_LOSS_BATCHES batches."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import laguna
    from paddle_tpu.models.reference import laguna as reference
    cfg = _laguna_cut()
    sizes = reference.sizes_of(cfg)
    feeds = [_ints32(laguna.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + LAGUNA_LOSS_BATCHES)]
    feed = feeds[0]
    picked_rows = np.unique(feed['ids'])[:rows]
    experts = {}

    def sample(name, array):
        if name == 'embedding':
            return {'embedding rows': array[picked_rows]}
        if name in ('gate', 'up', 'down'):
            return {'%s, %s loaded held expert (layer 1)' % (name, which):
                    array[e] for which, e in experts.items()}
        return {name: array}

    main, startup, test, loss, params, pairs = _laguna_programs(seq, seed)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        program_losses = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                          for f in feeds]
        say('laguna f32 for_test program: %d batches of 1 x %d tokens'
            % (len(feeds), seq))
        t0 = time.time()
        got = exe.run(main, feed=feed, return_numpy=False, fetch_list=[
            loss] + [pairs[params[i]] for i in LAGUNA_SAMPLED.values()])
        got_loss = _scalar(got[:1])
        # a run that fetches and blocks reads the routers' loads
        program_loads = exe.run(main, feed=feed, fetch_list=[loss] + [
            op.output('Load')[0] for op in main.global_block().ops
            if op.type == 'moe_route'])[1:]
        say('laguna f32 train program, 1 x %d tokens: loss %.6f in %.1f s '
            '(with compile); moe/held_share %.4f, moe/rows_held %d of '
            '%d routed in four layers, moe/held_rows_max %d, '
            'moe/walked_share %.4f, moe/dropped_tokens %d'
            % (seq, got_loss, time.time() - t0,
               monitor.gauge_value('moe/held_share'),
               monitor.counter_value('moe/rows_held'),
               monitor.counter_value('moe/tokens_routed'),
               monitor.gauge_value('moe/held_rows_max'),
               monitor.gauge_value('moe/walked_share'),
               monitor.counter_value('moe/dropped_tokens')))
        check(monitor.counter_value('moe/dropped_tokens') == 0 and
              monitor.counter_value('moe/rows_held') > 0,
              'rows were held and moe/dropped_tokens stayed 0')
        # the held experts' loads of layer 1, from the reference below
        held_grads = got[1:]
        del got
        ids, pos, labels = (jnp.asarray(feed[k])
                            for k in ('ids', 'pos_ids', 'labels'))
        device_weights = [jnp.asarray(w) for w in weights]
        loads = [np.asarray(x) for x in jax.jit(
            lambda w: reference.forward(w, ids, pos, sizes=sizes)[1])(
                device_weights)]
        say('routed layers whose experts\' loads differ between program '
            'and reference (a near-tie token changes two by one): %s'
            % [int(np.sum(np.asarray(a) != b))
               for a, b in zip(program_loads, loads)])
        load = loads[0][:8]
        experts.update(most=int(load.argmax()), least=int(load.argmin()))
        grads = {what: np.asarray(x)
                 for name, g in zip(LAGUNA_SAMPLED, held_grads)
                 for what, x in sample(name, g).items()}
        del held_grads
        for name in scope.local_var_names():
            scope.erase(name)

    # the weights go in as an argument: closed over, they would be
    # constants of the program (3.2 GB a compile on the host)
    def ref_loss(some, full, dtype=jnp.float32, remat=True, batch=None):
        full = list(full)
        for name, w in some.items():
            full[LAGUNA_SAMPLED[name]] = w
        i, p, l = batch or (ids, pos, labels)
        return reference.loss(full, i, p, l, sizes=sizes, dtype=dtype,
                              remat=remat)

    some = {name: device_weights[i] for name, i in LAGUNA_SAMPLED.items()}
    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(
        some, device_weights)
    want_loss = float(want_loss)
    say('reference: loss %.6f; held experts\' loads in layer 1: max %d '
        '(expert %d) min %d (expert %d), %d of %d rows'
        % (want_loss, load.max(), experts['most'], load.min(),
           experts['least'], load.sum(), seq * cfg.top_k))
    rel = abs(got_loss - want_loss) / want_loss
    say('f32 train program: loss %.6f, relative difference %.2e'
        % (got_loss, rel))
    check(rel <= LAGUNA_LOSS_RTOL, 'laguna f32 train loss within %g of '
          'the reference' % LAGUNA_LOSS_RTOL)
    worst = 0.0
    for name in LAGUNA_SAMPLED:
        for what, y in sample(name, np.asarray(want_grads[name])).items():
            x = grads[what]
            e = float(np.abs(x - y).max() / np.abs(y).max())
            d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
            worst = max(worst, d)
            say('gradient of %s %s: largest entry difference %.3e of the '
                'largest entry (%.3e), relative L2 distance %.3e'
                % (what, x.shape, e, np.abs(y).max(), d))
    del want_grads
    both = jax.jit(lambda full, i, p, l: [
        ref_loss({}, full, dtype=dt, remat=False, batch=(i, p, l))
        for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (other, got) in enumerate(zip(feeds, program_losses)):
        full, half = (float(x) for x in both(device_weights, *(
            jnp.asarray(other[k]) for k in ('ids', 'pos_ids', 'labels'))))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        if off[-1] > 3e-7 or low[-1] <= LAGUNA_LOSS_RTOL:
            say('batch seed %d: program %.6f, reference %.6f (relative '
                'difference %.2e), reference in bfloat16 throughout '
                '%.6f (%.2e)' % (seed + n, got, full, off[-1], half,
                                 low[-1]))
    say('over %d batches: f32 for_test program against the reference, '
        'relative: median %.2e, %d above 3e-7, largest %.2e; reference '
        'in bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e '
        '%.2e, largest %.2e, %d within %g'
        % ((len(off), np.median(off), sum(x > 3e-7 for x in off), max(off),
            min(low)) + tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= LAGUNA_LOSS_RTOL for x in low),
            LAGUNA_LOSS_RTOL)))
    check(max(off) <= LAGUNA_LOSS_RTOL, 'laguna f32 for_test loss within '
          '%g of the reference on every batch' % LAGUNA_LOSS_RTOL)
    check(worst <= LAGUNA_L2_RTOL,
          'laguna gradients: every sampled tensor within %g of the '
          'reference\'s, relative L2 distance (worst %.3e)'
          % (LAGUNA_L2_RTOL, worst))


# Moonlight-16B-A3B at published widths, one 8192-token sequence (the
# cell's), the dense layer and MOONLIGHT_LAYERS - 1 sparse ones with
# experts 0-7 of 64 held and 20480 vocabulary rows: the f32 train
# program (the flash kernels at 192-wide queries and keys over 128-wide
# values, whose f32 rows ask Mosaic for more scoped VMEM than its
# default; the held experts' grouped matmuls; the sigmoid router and
# its choice bias) against jax.grad of the plain reference, per sampled
# gradient tensor: once on the startup weights and once after
# MOONLIGHT_STEPS train steps have moved the bias (SGD at lr 0: the
# bias is the only state that moves, so what the second loss differs
# by is the routing following it), weights and bias read back from the
# scope.  Fewer layers than the cell's six: six layers' f32
# activations at 8192 tokens do not fit beside the weights.
#
# Of 8192 tokens a layer, three to seven have a 6th and 7th BIASED
# score so near a tie (64 sigmoid scores lie 6e-3 apart on average, and
# a float32 logit is good to 1e-6) that the program picks the other
# expert than the reference; each moves its own rows' gradients by
# their own size, which a router's or a lightly loaded expert's
# gradient (a sum over a few hundred rows) shows as 0.7-1.4e-2 of its
# L2 norm (my chip run, PR 32: PERF.md section 6).  So the gradients
# are compared with the reference routed by the PROGRAM'S OWN CHOICE
# (``chosen=``, the op's TopKIdx), which leaves summation order alone,
# and the choice itself is held to the reference's through the loads
# (the experts whose load differs are printed and bounded).  The loss
# is compared with both.  The limit on it lies between the program's
# reading against the free reference (1.8e-7 and 4.6e-7 on the two
# states) and the bfloat16-throughout reference's (1.48e-5 and
# 5.07e-6), which has to miss it; the family's 1e-5 for the cell's six
# layers is a limit on any seed's batch, this one is for seed 0.
MOONLIGHT_LAYERS = 3
MOONLIGHT_STEPS = 5
MOONLIGHT_LOSS_RTOL = 2e-6
MOONLIGHT_L2_RTOL = 2e-3
# experts of a layer whose load may differ from the reference's (two
# a near-tie token)
MOONLIGHT_LOADS_OFF = 24
# the cell's own cut, forward only: benchmark/families/moonlight.py's
# REFERENCE_RTOL and the readings it lies between
MOONLIGHT_CELL_LAYERS = 6
MOONLIGHT_CELL_RTOL = 2e-5
MOONLIGHT_LOSS_BATCHES = 12
# creation order, trainable parameters only: embedding 0; layer 0
# (dense) g_in 1 Wq 2 Wkva 3 g_latent 4 Wkvb 5 Wo 6 g_post 7 gate up
# down 10; layer 1 (sparse) g_in 11 Wq Wkva 13 g_latent 14 Wkvb 15 Wo
# g_post 17 router 18 gate 19 up 20 down 21 shared gate up down 24;
# layer 2 the same from 25
MOONLIGHT_SAMPLED = {'embedding': 0, 'Wq (layer 0)': 2,
                     'Wkva (layer 0)': 3,
                     'latent norm gain (layer 0)': 4, 'Wkvb (layer 0)': 5,
                     'Wkva (layer 1)': 13, 'Wkvb (layer 1)': 15,
                     'router Wg (layer 1)': 18, 'gate': 19, 'up': 20,
                     'down': 21, 'shared gate (layer 1)': 22,
                     'shared down (layer 1)': 24,
                     'router Wg (layer 2)': 32}


def _moonlight_cut(layers=None):
    from paddle_tpu.models import moonlight
    return moonlight.MoonlightConfig(
        vocab_size=20480, layers=layers or MOONLIGHT_LAYERS,
        experts_held=(0, 8), bias_init_std=0.005)


def _moonlight_cell_losses(seq, seed):
    """The benchmark cell's own cut (MOONLIGHT_CELL_LAYERS layers),
    forward only: the f32 for_test program's loss on
    MOONLIGHT_LOSS_BATCHES batches, on the state the seeded startup
    program gives, beside the reference's in float32 and in bfloat16
    throughout: the two readings the family's REFERENCE_RTOL lies
    between."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import moonlight
    from paddle_tpu.models.reference import moonlight as reference
    cfg = _moonlight_cut(MOONLIGHT_CELL_LAYERS)
    sizes = reference.sizes_of(cfg)
    feeds = [_ints32(moonlight.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + MOONLIGHT_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = moonlight.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        # host copies first: a run donates the state it may write
        weights, biases = ([np.asarray(fluid.core.as_array(
            scope.find_var(p.name))) for p in main.all_parameters()
            if p.trainable == kind] for kind in (True, False))
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        weights, biases = ([jnp.asarray(x) for x in part]
                           for part in (weights, biases))
        both = jax.jit(lambda w, b, i, p, l: [reference.loss(
            w, b, i, p, l, sizes=sizes, dtype=dt)
            for dt in (jnp.float32, jnp.bfloat16)])
        off, low = [], []
        for n, (feed, got) in enumerate(zip(feeds, program)):
            full, half = (float(x) for x in both(weights, biases, *(
                jnp.asarray(feed[k])
                for k in ('ids', 'pos_ids', 'labels'))))
            off.append(abs(got - full) / full)
            low.append(abs(half - full) / full)
            say('%d layers, batch seed %d: program %.6f, reference %.6f '
                '(relative difference %.2e), reference in bfloat16 '
                'throughout %.6f (%.2e)'
                % (cfg.layers, seed + n, got, full, off[-1], half,
                   low[-1]))
        for name in scope.local_var_names():
            scope.erase(name)
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e %.2e, '
        'largest %.2e, %d within %g'
        % ((len(off), cfg.layers, np.median(off), max(off), min(low)) +
           tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= MOONLIGHT_CELL_RTOL for x in low),
            MOONLIGHT_CELL_RTOL)))
    check(max(off) <= MOONLIGHT_CELL_RTOL, 'moonlight f32 for_test loss '
          'at the cell\'s cut within %g of the reference on every batch'
          % MOONLIGHT_CELL_RTOL)


def _moonlight_programs(seq, seed):
    """(main with SGD at lr 0, startup, loss, trainable parameters in
    creation order, the choice biases in layer order, {param: its
    gradient's name})."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import moonlight
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = moonlight.build_pretrain(_moonlight_cut(), seq)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    return main, startup, loss, params, biases, pairs


def phase_moonlight_gradients(seq=8192, seed=0, rows=64):
    """models.moonlight.BASE cut as above: loss and sampled gradients
    of the f32 TRAIN program against the reference's on one seeded
    sequence, on the startup state and again after the bias has moved;
    beside each the reference in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import moonlight
    from paddle_tpu.models.reference import moonlight as reference
    cfg = _moonlight_cut()
    sizes = reference.sizes_of(cfg)
    feed = _ints32(moonlight.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    ids, pos, labels = (jnp.asarray(feed[k])
                        for k in ('ids', 'pos_ids', 'labels'))
    picked_rows = np.unique(feed['ids'])[:rows]
    experts = {}

    def sample(name, array):
        if name == 'embedding':
            return {'embedding rows': array[picked_rows]}
        if name in ('gate', 'up', 'down'):
            return {'%s, %s loaded held expert (layer 1)' % (name, which):
                    array[e] for which, e in experts.items()}
        return {name: array}

    # the weights go in as arguments: closed over, they would be
    # constants of the program
    def ref_loss(some, full, biases, chosen=None, dtype=jnp.float32):
        full = list(full)
        for name, w in some.items():
            full[MOONLIGHT_SAMPLED[name]] = w
        return reference.loss(full, biases, ids, pos, labels, sizes=sizes,
                              dtype=dtype, remat=True, chosen=chosen)

    ref_grads = jax.jit(jax.value_and_grad(ref_loss))
    ref_free = jax.jit(lambda full, biases: [
        ref_loss({}, full, biases, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    ref_loads = jax.jit(lambda full, biases: reference.forward(
        full, biases, ids, pos, sizes=sizes)[1])

    main, startup, loss, params, biases, pairs = _moonlight_programs(
        seq, seed)
    fetches = [loss] + [pairs[params[i]]
                        for i in MOONLIGHT_SAMPLED.values()]
    routers = [op for op in main.global_block().ops
               if op.type == 'moe_route']
    load_names = [op.output('Load')[0] for op in routers]
    choice_names = [op.output('TopKIdx')[0] for op in routers]
    worst = {}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()

        def state():
            """Host copies: the run below donates the scope's own."""
            return tuple([np.asarray(fluid.core.as_array(
                scope.find_var(n))) for n in names]
                for names in (params, biases))

        def compare(tag):
            """One fetching run of the train program on the state the
            scope holds now, against the reference on that state."""
            weights, bias_values = state()
            t0 = time.time()
            # a run that fetches and blocks also reads what the layers
            # have the program watch (the loads, the biases)
            got = exe.run(main, feed=feed, fetch_list=fetches +
                          load_names + choice_names)
            got_loss = _scalar(got[:1])
            chosen = [jnp.asarray(x) for x in got[-len(routers):]]
            weights, bias_values = ([jnp.asarray(x) for x in part]
                                    for part in (weights, bias_values))
            program_loads = [np.asarray(x) for x in got[
                len(fetches):len(fetches) + len(routers)]]
            say('moonlight f32 train program, %s, 1 x %d tokens: loss '
                '%.6f in %.1f s; largest |bias| %.4f; '
                'moe/held_share %.4f, moe/held_rows_max %d, '
                'moe/walked_share %.4f, moe/dropped_tokens %d, '
                'moe/bias_updates %d, moe/score_bias_abs_max %.4f'
                % (tag, seq, got_loss, time.time() - t0,
                   max(float(jnp.abs(b).max()) for b in bias_values),
                   monitor.gauge_value('moe/held_share'),
                   monitor.gauge_value('moe/held_rows_max'),
                   monitor.gauge_value('moe/walked_share'),
                   monitor.counter_value('moe/dropped_tokens'),
                   monitor.counter_value('moe/bias_updates'),
                   monitor.gauge_value('moe/score_bias_abs_max')))
            loads = [np.asarray(x)
                     for x in ref_loads(weights, bias_values)]
            loads_off = [int(np.sum(a != b))
                         for a, b in zip(program_loads, loads)]
            say('%s: experts a routed layer whose load differs between '
                'program and reference (a near-tie token changes two by '
                'one): %s; held rows a layer %s'
                % (tag, loads_off, [int(x[:8].sum()) for x in loads]))
            check(max(loads_off) <= MOONLIGHT_LOADS_OFF,
                  'the program\'s choice, %s, is the reference\'s but '
                  'for near-ties (at most %d experts\' loads a layer '
                  'differ)' % (tag, MOONLIGHT_LOADS_OFF))
            load = loads[0][:8]
            experts.update(most=int(load.argmax()),
                           least=int(load.argmin()))
            grads = {what: np.asarray(x)
                     for name, g in zip(MOONLIGHT_SAMPLED,
                                        got[1:len(fetches)])
                     for what, x in sample(name, g).items()}
            del got
            some = {name: weights[i]
                    for name, i in MOONLIGHT_SAMPLED.items()}
            pinned, want_grads = ref_grads(some, weights, bias_values,
                                           chosen)
            want_loss, low = (float(x)
                              for x in ref_free(weights, bias_values))
            rel = abs(got_loss - want_loss) / want_loss
            low_rel = abs(low - want_loss) / want_loss
            say('%s: reference loss %.6f, program %.6f (relative '
                'difference %.2e; %.2e from the reference routed by '
                'the program\'s choice); reference in bfloat16 '
                'throughout %.6f (%.2e)'
                % (tag, want_loss, got_loss, rel,
                   abs(got_loss - float(pinned)) / float(pinned), low,
                   low_rel))
            check(rel <= MOONLIGHT_LOSS_RTOL, 'moonlight f32 train loss, '
                  '%s, within %g of the reference'
                  % (tag, MOONLIGHT_LOSS_RTOL))
            check(low_rel > MOONLIGHT_LOSS_RTOL, 'the reference in '
                  'bfloat16 throughout, %s, misses that tolerance' % tag)
            far = 0.0
            for name in MOONLIGHT_SAMPLED:
                for what, y in sample(
                        name, np.asarray(want_grads[name])).items():
                    x = grads[what]
                    e = float(np.abs(x - y).max() / np.abs(y).max())
                    d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
                    far = max(far, d)
                    say('%s, gradient of %s %s: largest entry '
                        'difference %.3e of the largest entry (%.3e), '
                        'relative L2 distance %.3e'
                        % (tag, what, x.shape, e, np.abs(y).max(), d))
            worst[tag] = far
            return got_loss, bias_values

        first_loss, before = compare('startup state')
        check(monitor.counter_value('moe/dropped_tokens') == 0 and
              monitor.counter_value('moe/rows_held') > 0,
              'rows were held and moe/dropped_tokens stayed 0')
        for _ in range(MOONLIGHT_STEPS - 1):
            exe.run(main, feed=feed, fetch_list=[])
        moved_loss, after = compare(
            'after %d train steps' % MOONLIGHT_STEPS)
        moved = max(float(jnp.abs(a - b).max())
                    for a, b in zip(after, before))
        say('the bias moved by at most %.4f an expert in %d steps of '
            'gamma %g; the loss on the same weights %.6f -> %.6f'
            % (moved, MOONLIGHT_STEPS, cfg.bias_update_rate, first_loss,
               moved_loss))
        check(abs(moved - MOONLIGHT_STEPS * cfg.bias_update_rate) <= 1e-6,
              'some expert\'s bias moved by gamma on every step')
        check(moved_loss != first_loss,
              'the routing followed the bias (the loss moved)')
        check(monitor.counter_value('moe/dropped_tokens') == 0,
              'moe/dropped_tokens stayed 0')
        for name in scope.local_var_names():
            scope.erase(name)
    _moonlight_cell_losses(seq, seed)
    for tag, far in worst.items():
        check(far <= MOONLIGHT_L2_RTOL,
              'moonlight gradients, %s: every sampled tensor within %g '
              'of the reference\'s routed by the program\'s choice, '
              'relative L2 distance (worst %.3e)'
              % (tag, MOONLIGHT_L2_RTOL, far))


# --- LFM2-8B-A1B ------------------------------------------------------
# Published widths (models.lfm2.BASE) as the benchmark cuts the rest:
# experts 0-7 of 32 held, 16384 vocabulary rows, one 8192-token
# sequence.  The f32 TRAIN program runs the model's layers 1 to 4 (the
# dense conv layer, the sparse attention layer, two sparse conv layers:
# every kind of operator and MLP; five f32 layers and the reference's
# gradients were not tried beside each other), the forward-only cell
# check the cell's own five.  Tolerances from the chip runs of this
# phase (my chip runs, PR 36), each with room over its reading: the
# loss against the reference routed by the program's own choice 9.43e-8
# and 0.00 (bfloat16 throughout: 6.14e-5 and 4.53e-6), against the
# reference's own choice 3.87e-6 after the bias moved (six experts'
# loads off by near-tie tokens); gradients at most 1.8e-4 relative L2
# (the attention layer's, through the f32 flash kernels; the short
# convolution's 1.06e-5); the cell's cut over 12 batches at most
# 1.61e-6, bfloat16 throughout 1.51e-6 to 1.02e-4, median 1.05e-5.
LFM2_LAYERS = 4
LFM2_STEPS = 5
LFM2_LOSS_RTOL = 2e-6
LFM2_L2_RTOL = 2e-3
LFM2_LOADS_OFF = 24
LFM2_CELL_LAYERS = 5
LFM2_CELL_RTOL = 1e-5
LFM2_LOSS_BATCHES = 12
# creation order, trainable parameters only: embedding 0; layer 1
# (dense, conv) g_op 1 W_in 2 filter 3 W_out 4 g_ffn 5 gate up down 8;
# layer 2 (sparse, attention) g_op 9 Wq 10 Wk 11 Wv 12 g_q 13 g_k 14
# Wo 15 g_ffn 16 router 17 gate 18 up 19 down 20; layer 3 (sparse,
# conv) g_op 21 W_in 22 filter 23 W_out 24 g_ffn 25 router 26 ...
LFM2_SAMPLED = {'embedding': 0, 'W_in (layer 1)': 2,
                'filter (layer 1)': 3, 'W_out (layer 1)': 4,
                'Wq (layer 2)': 10, 'Wk (layer 2)': 11,
                'q gain (layer 2)': 13, 'k gain (layer 2)': 14,
                'router Wg (layer 2)': 17, 'gate': 18, 'up': 19,
                'down': 20, 'filter (layer 3)': 23,
                'router Wg (layer 3)': 26}


def _lfm2_cut(layers=None):
    from paddle_tpu.models import lfm2
    return lfm2.Lfm2Config(
        vocab_size=16384, layers=layers or LFM2_LAYERS, first_layer=1,
        experts_held=(0, 8), bias_init_std=0.005)


def _lfm2_cell_losses(seq, seed):
    """The benchmark cell's own cut (LFM2_CELL_LAYERS layers), forward
    only: the f32 for_test program's loss on LFM2_LOSS_BATCHES batches,
    on the state the seeded startup program gives, beside the
    reference's in float32 and in bfloat16 throughout: the two readings
    the family's REFERENCE_RTOL lies between."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import lfm2
    from paddle_tpu.models.reference import lfm2 as reference
    cfg = _lfm2_cut(LFM2_CELL_LAYERS)
    sizes = reference.sizes_of(cfg)
    feeds = [_ints32(lfm2.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + LFM2_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = lfm2.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        # host copies first: a run donates the state it may write
        weights, biases = ([np.asarray(fluid.core.as_array(
            scope.find_var(p.name))) for p in main.all_parameters()
            if p.trainable == kind] for kind in (True, False))
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        for name in scope.local_var_names():
            scope.erase(name)
    weights, biases = ([jnp.asarray(x) for x in part]
                       for part in (weights, biases))
    both = jax.jit(lambda w, b, i, p, l: [reference.loss(
        w, b, i, p, l, sizes=sizes, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(weights, biases, *(
            jnp.asarray(feed[k]) for k in ('ids', 'pos_ids', 'labels'))))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers, batch seed %d: program %.6f, reference %.6f '
            '(relative difference %.2e), reference in bfloat16 '
            'throughout %.6f (%.2e)'
            % (cfg.layers, seed + n, got, full, off[-1], half, low[-1]))
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e %.2e, '
        'largest %.2e, %d within %g'
        % ((len(off), cfg.layers, np.median(off), max(off), min(low)) +
           tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= LFM2_CELL_RTOL for x in low),
            LFM2_CELL_RTOL)))
    check(max(off) <= LFM2_CELL_RTOL, 'lfm2 f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % LFM2_CELL_RTOL)


def phase_lfm2_gradients(seq=8192, seed=0, rows=64):
    """models.lfm2.BASE cut as above: loss and sampled gradients of the
    f32 TRAIN program (short_conv and its gradient, the grouped causal
    flash kernels at width 64, the held experts' grouped matmuls, the
    tied table's two gradients) against the reference's on one seeded
    sequence, on the startup state and again after the bias has moved;
    beside each the reference in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import lfm2
    from paddle_tpu.models.reference import lfm2 as reference
    from paddle_tpu.ops.pallas import common
    cfg = _lfm2_cut()
    sizes = reference.sizes_of(cfg)
    feed = _ints32(lfm2.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    ids, pos, labels = (jnp.asarray(feed[k])
                        for k in ('ids', 'pos_ids', 'labels'))
    picked_rows = np.unique(feed['ids'])[:rows]
    experts = {}

    def sample(name, array):
        if name == 'embedding':
            return {'embedding rows (lookup + head)': array[picked_rows]}
        if name in ('gate', 'up', 'down'):
            return {'%s, %s loaded held expert (layer 2)' % (name, which):
                    array[e] for which, e in experts.items()}
        return {name: array}

    # the weights go in as arguments: closed over, they would be
    # constants of the program
    def ref_loss(some, full, biases, chosen=None, dtype=jnp.float32):
        full = list(full)
        for name, w in some.items():
            full[LFM2_SAMPLED[name]] = w
        return reference.loss(full, biases, ids, pos, labels, sizes=sizes,
                              dtype=dtype, remat=True, chosen=chosen)

    ref_grads = jax.jit(jax.value_and_grad(ref_loss))
    ref_free = jax.jit(lambda full, biases: [
        ref_loss({}, full, biases, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    ref_loads = jax.jit(lambda full, biases: reference.forward(
        full, biases, ids, pos, sizes=sizes)[1])

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = lfm2.build_pretrain(cfg, seq)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    fetches = [loss] + [pairs[params[i]] for i in LFM2_SAMPLED.values()]
    routers = [op for op in main.global_block().ops
               if op.type == 'moe_route']
    load_names = [op.output('Load')[0] for op in routers]
    choice_names = [op.output('TopKIdx')[0] for op in routers]
    worst = {}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()

        def state():
            """Host copies: the run below donates the scope's own."""
            return tuple([np.asarray(fluid.core.as_array(
                scope.find_var(n))) for n in names]
                for names in (params, biases))

        def compare(tag):
            """One fetching run of the train program on the state the
            scope holds now, against the reference on that state."""
            weights, bias_values = state()
            t0 = time.time()
            got = exe.run(main, feed=feed, fetch_list=fetches +
                          load_names + choice_names)
            got_loss = _scalar(got[:1])
            chosen = [jnp.asarray(x) for x in got[-len(routers):]]
            weights, bias_values = ([jnp.asarray(x) for x in part]
                                    for part in (weights, bias_values))
            program_loads = [np.asarray(x) for x in got[
                len(fetches):len(fetches) + len(routers)]]
            say('lfm2 f32 train program, %s, 1 x %d tokens: loss %.6f '
                'in %.1f s; largest |bias| %.4f; moe/held_share %.4f, '
                'moe/held_rows_max %d, moe/walked_share %.4f, '
                'moe/dropped_tokens %d, moe/bias_updates %d, '
                'moe/score_bias_abs_max %.4f; short_conv/calls %d; '
                'flash_attention last dispatch %s'
                % (tag, seq, got_loss, time.time() - t0,
                   max(float(jnp.abs(b).max()) for b in bias_values),
                   monitor.gauge_value('moe/held_share'),
                   monitor.gauge_value('moe/held_rows_max'),
                   monitor.gauge_value('moe/walked_share'),
                   monitor.counter_value('moe/dropped_tokens'),
                   monitor.counter_value('moe/bias_updates'),
                   monitor.gauge_value('moe/score_bias_abs_max'),
                   monitor.counter_value('short_conv/calls'),
                   common._LAST.get('flash_attention')))
            loads = [np.asarray(x)
                     for x in ref_loads(weights, bias_values)]
            loads_off = [int(np.sum(a != b))
                         for a, b in zip(program_loads, loads)]
            say('%s: experts a routed layer whose load differs between '
                'program and reference (a near-tie token changes two by '
                'one): %s; held rows a layer %s'
                % (tag, loads_off, [int(x[:8].sum()) for x in loads]))
            check(max(loads_off) <= LFM2_LOADS_OFF,
                  'the program\'s choice, %s, is the reference\'s but '
                  'for near-ties (at most %d experts\' loads a layer '
                  'differ)' % (tag, LFM2_LOADS_OFF))
            load = loads[0][:8]
            experts.update(most=int(load.argmax()),
                           least=int(load.argmin()))
            grads = {what: np.asarray(x)
                     for name, g in zip(LFM2_SAMPLED, got[1:len(fetches)])
                     for what, x in sample(name, g).items()}
            del got
            some = {name: weights[i] for name, i in LFM2_SAMPLED.items()}
            pinned, want_grads = ref_grads(some, weights, bias_values,
                                           chosen)
            want_loss, low = (float(x)
                              for x in ref_free(weights, bias_values))
            rel = abs(got_loss - want_loss) / want_loss
            low_rel = abs(low - want_loss) / want_loss
            say('%s: reference loss %.6f, program %.6f (relative '
                'difference %.2e; %.2e from the reference routed by '
                'the program\'s choice); reference in bfloat16 '
                'throughout %.6f (%.2e)'
                % (tag, want_loss, got_loss, rel,
                   abs(got_loss - float(pinned)) / float(pinned), low,
                   low_rel))
            # a token whose 4th and 5th biased scores nearly tie picks
            # the other expert in the program than in the reference and
            # moves the mean over 8191 targets by about 1e-6 (my chip
            # run, PR 36: none on the startup state, 9.43e-8; six
            # experts' loads off after five steps, 3.87e-6): the tight
            # limit holds against the reference routed by the program's
            # own choice, the cell's against the reference's own
            check(abs(got_loss - float(pinned)) <=
                  LFM2_LOSS_RTOL * float(pinned),
                  'lfm2 f32 train loss, %s, within %g of the reference '
                  'routed by the program\'s choice'
                  % (tag, LFM2_LOSS_RTOL))
            check(rel <= LFM2_CELL_RTOL, 'lfm2 f32 train loss, %s, '
                  'within %g of the reference by its own choice'
                  % (tag, LFM2_CELL_RTOL))
            check(low_rel > LFM2_LOSS_RTOL, 'the reference in bfloat16 '
                  'throughout, %s, misses the tight tolerance' % tag)
            far = 0.0
            for name in LFM2_SAMPLED:
                for what, y in sample(
                        name, np.asarray(want_grads[name])).items():
                    x = grads[what]
                    e = float(np.abs(x - y).max() / np.abs(y).max())
                    d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
                    far = max(far, d)
                    say('%s, gradient of %s %s: largest entry '
                        'difference %.3e of the largest entry (%.3e), '
                        'relative L2 distance %.3e'
                        % (tag, what, x.shape, e, np.abs(y).max(), d))
            worst[tag] = far
            return got_loss, bias_values

        first_loss, before = compare('startup state')
        check(common._LAST.get('flash_attention', {}).get('path') ==
              'fused', 'the grouped causal calls at width 64 ran the '
              'flash kernels')
        check(monitor.counter_value('short_conv/calls') > 0,
              'short_conv/calls counted the op\'s lowerings')
        check(monitor.counter_value('moe/dropped_tokens') == 0 and
              monitor.counter_value('moe/rows_held') > 0,
              'rows were held and moe/dropped_tokens stayed 0')
        for _ in range(LFM2_STEPS - 1):
            exe.run(main, feed=feed, fetch_list=[])
        moved_loss, after = compare('after %d train steps' % LFM2_STEPS)
        moved = max(float(jnp.abs(a - b).max())
                    for a, b in zip(after, before))
        say('the bias moved by at most %.4f an expert in %d steps of '
            'gamma %g; the loss on the same weights %.6f -> %.6f'
            % (moved, LFM2_STEPS, cfg.bias_update_rate, first_loss,
               moved_loss))
        check(abs(moved - LFM2_STEPS * cfg.bias_update_rate) <= 1e-6,
              'some expert\'s bias moved by gamma on every step')
        check(moved_loss != first_loss,
              'the routing followed the bias (the loss moved)')
        for name in scope.local_var_names():
            scope.erase(name)
    _lfm2_cell_losses(seq, seed)
    for tag, far in worst.items():
        check(far <= LFM2_L2_RTOL,
              'lfm2 gradients, %s: every sampled tensor within %g of '
              'the reference\'s routed by the program\'s choice, '
              'relative L2 distance (worst %.3e)'
              % (tag, LFM2_L2_RTOL, far))


# EvaByte at published widths (hidden 4096, 32 heads of 128, MLP
# 11008, 320 rows, 8 heads of prediction, windows of 2048 over chunks
# of 16).  Tolerances from the chip runs of this phase (my chip runs,
# PR 38; PERF.md section 6 has the readings), each with room over its
# reading.  Every product on both sides of the f32 comparisons is full
# float32 (the flash kernels' too) and nothing is chosen by a top-k,
# so what is left is the order of float32 sums: two streams merged by
# their log-sum-exps against one softmax over both kinds of keys.
# The single op at 32768: float32 9.9e-6 to 3.4e-5 of a tensor's
# largest entry (limit 1e-4), bfloat16 2.2e-3 to 3.1e-3 (limit 3e-2).
# One layer's gradients at 4096, relative L2: 2.3e-5 to 1.3e-4 for
# every matrix and gain, 2.0e-4 for phi and 5.8e-4 for mu, whose
# gradients are sums that all but cancel (a row's dS sums to zero over
# ALL its keys; mu's gradient is the part over the summaries alone, its
# largest entry 3.3e-5 where Wq's is 4.1e-4): limit 2e-3.  The loss:
# 7.8e-8 (train program) and 0 to 2.3e-7 over six batches at the
# cell's four layers, the reference in bfloat16 throughout 4.8e-6 to
# 1.9e-5 on the same batches: the limit 1e-6 refuses every one.
EVA_T = 32768               # the published max_position_embeddings
EVA_LAYER_SEQ = 4096        # the cell's: two windows
EVA_F32_OUT_TOL = 1e-4      # f32 single op, of the tensor's largest
EVA_BF16_OUT_TOL = 3e-2     # bf16 single op, of the tensor's largest
EVA_LOSS_RTOL = 1e-6        # = benchmark/families/evabyte.py's
EVA_L2_RTOL = 2e-3          # a gradient tensor's relative L2 distance
EVA_CELL_LAYERS = 4
EVA_LOSS_BATCHES = 6
EVA_NAMES = ('embedding', 'g1', 'Wq', 'Wk', 'Wv', 'phi', 'mu', 'Wo',
             'g2', 'Wg', 'Wu', 'Wd', 'g_last') + tuple(
                 'W_%d' % i for i in range(8))


def _timed(fn, *args, runs=3):
    """Seconds a call of a jitted ``fn`` takes on the chip, the best of
    ``runs`` after one that compiles."""
    import jax
    jax.block_until_ready(fn(*args))
    best = float('inf')
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _eva_single_op(t, dtype, seed, heads=32, d=128, window=2048,
                   chunk=16):
    """ONE ``layers.eva_attention`` forward + backward at ``t``
    positions through a fluid program (the normal path), against the
    plain reference in float32 on the same (rounded) inputs: agreement
    of the output and of the five gradients, the device's peak memory,
    and each part's time and share of its roofline from the same
    functions the ops lower to, timed apart."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import evabyte
    from paddle_tpu.models.reference import evabyte as reference
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import flash_attention as fa
    from benchmark.lib import evabyte_flops, flops, peaks
    name = jnp.dtype(dtype).name
    rng = np.random.RandomState(seed)
    host = {n: np.asarray(jnp.asarray(
        rng.randn(1, t, heads, d), dtype).astype(jnp.float32))
        for n in ('q', 'k', 'v', 'cot')}
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            ins = {n: layers.data(n, shape=[t, heads, d], dtype='float32')
                   for n in host}
            for x in ins.values():
                x.stop_gradient = False
            q, k, v = (layers.cast(ins[n], name) for n in ('q', 'k', 'v'))
            phi, mu = (layers.create_parameter(
                [heads, d], 'float32',
                default_initializer=evabyte.ClippedNormal(d ** -0.5))
                for _ in range(2))
            out = layers.eva_attention(q, k, v, window, chunk, phi, mu)
            loss = layers.reduce_sum(layers.elementwise_mul(
                layers.cast(out, 'float32'), ins['cot']))
            grads = fluid.backward.gradients(
                [loss], [ins['q'], ins['k'], ins['v'], phi, mu])
        fetch = [out] + list(grads)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        phi_v, mu_v = (np.asarray(fluid.core.as_array(
            scope.find_var(x.name))) for x in (phi, mu))
        fused0 = _fused_dispatches()
        t0 = time.time()
        got = [np.asarray(x, np.float32)
               for x in exe.run(main, feed=host, fetch_list=fetch)]
        say('eva_attention %s at T = %d (%d windows, %d summaries), %d '
            'heads of %d, forward + backward through fluid: %.1f s with '
            'compile; %d flash dispatches fused'
            % (name, t, t // window, t // chunk, heads, d,
               time.time() - t0, _fused_dispatches() - fused0))
        check(_fused_dispatches() - fused0 >= 2,
              'both streams dispatched to the flash kernels')
        for n in scope.local_var_names():
            scope.erase(n)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get('peak_bytes_in_use', 0) + \
        stats.get('peak_bytes_reserved', 0)
    scores = 4 * heads * t * (t // chunk)
    say('device peak so far %.2f GB (in use %.2f + reserved %.2f); one '
        '[32, T, T / 16] float32 tensor would be %.2f GB, [32, T, 2048] '
        '%.2f GB' % (peak / 1e9, stats.get('peak_bytes_in_use', 0) / 1e9,
                     stats.get('peak_bytes_reserved', 0) / 1e9,
                     scores / 1e9, 4 * heads * t * window / 1e9))

    # the plain reference, float32 'highest', a block of queries at a
    # time, on the inputs as the program rounded them
    dev = {n: jnp.asarray(x) for n, x in host.items()}

    def plain(q, k, v, phi, mu):
        o = reference.eva_attention(q, k, v, phi, mu, window, chunk,
                                    block=512, remat=True)
        return jnp.vdot(o, dev['cot']), o

    with jax.default_matmul_precision('highest'):
        t0 = time.time()
        (_, want_o), want_g = jax.jit(jax.value_and_grad(
            plain, (0, 1, 2, 3, 4), has_aux=True))(
            dev['q'], dev['k'], dev['v'], jnp.asarray(phi_v),
            jnp.asarray(mu_v))
        want = [np.asarray(want_o)] + [np.asarray(g) for g in want_g]
        say('reference (one softmax over [T + T / 16] keys, blocks of '
            '512 queries, float32 highest), forward + jax.grad: %.1f s'
            % (time.time() - t0))
    tol = EVA_F32_OUT_TOL if name == 'float32' else EVA_BF16_OUT_TOL
    worst = 0.0
    for what, x, y in zip(('o', 'dq', 'dk', 'dv', 'dphi', 'dmu'), got,
                          want):
        e = float(np.abs(x - y).max() / np.abs(y).max())
        l2 = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        worst = max(worst, e)
        say('%s %s %s: largest entry difference %.3e of the largest '
            'entry (%.3e), relative L2 distance %.3e'
            % (name, what, x.shape, e, np.abs(y).max(), l2))
    check(worst <= tol, 'eva_attention %s at T = %d within %g of the '
          'reference, output and five gradients (worst %.3e)'
          % (name, t, tol, worst))
    if name == 'float32':
        return
    # each part alone, forward + backward, the functions the ops lower to
    del got, want, want_o, want_g
    q, k, v, cot = (dev[n].astype(dtype) for n in ('q', 'k', 'v', 'cot'))
    phi_d, mu_d = jnp.asarray(phi_v), jnp.asarray(mu_v)
    cot_lse = jnp.ones((1, heads, t), jnp.float32)

    def fold(x):
        return x.reshape(-1, window, heads, d)

    def pool(k, v, phi, mu):
        out = registry.get('eva_chunk_summary').fn(
            registry.LowerCtx(0), {'K': [k], 'V': [v], 'Phi': [phi],
                                   'Mu': [mu]}, {'chunk_size': chunk})
        return out['KS'][0], out['VS'][0]

    def with_cotangents(stream):
        def loss(*args):
            o, lse = stream(*args)
            return jnp.vdot(o.astype(jnp.float32), cot.reshape(o.shape)) \
                + jnp.sum(jnp.where(jnp.isfinite(lse), lse, 0.0))
        return jax.jit(jax.grad(loss, tuple(range(3))))

    local = with_cotangents(lambda q, k, v: fa.flash_attention(
        fold(q), fold(k), fold(v), causal=True, with_lse=True))
    remote = with_cotangents(lambda q, ks, vs: fa.flash_attention(
        q, ks, vs, coarse=(window, chunk), with_lse=True))
    pooling = jax.jit(jax.grad(
        lambda k, v, phi, mu: sum(jnp.sum(x.astype(jnp.float32))
                                  for x in pool(k, v, phi, mu)),
        (0, 1, 2, 3)))
    ks, vs = jax.jit(pool)(k, v, phi_d, mu_d)
    peak_flops, peak_bytes = peaks.chip_peak(
        jax.devices()[0].device_kind)
    for what, seconds, cost in (
            ('local stream', _timed(local, q, k, v),
             evabyte_flops.local_train_cost(1, heads, t, d, window)),
            ('remote stream', _timed(remote, q, ks, vs),
             evabyte_flops.remote_train_cost(1, heads, t, d, window,
                                             chunk)),
            ('pooling', _timed(pooling, k, v, phi_d, mu_d),
             evabyte_flops.chunk_summary_train_cost(1, heads, t, d,
                                                    chunk))):
        least, bound = flops.roofline_seconds(cost[0], cost[1],
                                              peak_flops, peak_bytes)
        say('T = %d %s, forward + backward with its cotangents\' dot '
            'products in the same program: %.2f ms; %.2f GFLOP, %.1f MB, '
            '%s-bound, least %.2f ms: %.1f%% of its roofline'
            % (t, what, seconds * 1e3, cost[0] / 1e9, cost[1] / 1e6,
               bound, least * 1e3, 100.0 * least / seconds))
    say('T = %d: remote pairs %.2f of local (%d / %d a head)'
        % (t, evabyte_flops.remote_pairs(t, window, chunk) /
           evabyte_flops.local_pairs(t, window),
           evabyte_flops.remote_pairs(t, window, chunk),
           evabyte_flops.local_pairs(t, window)))


def _fused_dispatches():
    from paddle_tpu.fluid import monitor
    return monitor.counter_value('pallas/flash_attention/dispatch_fused') \
        or 0


def _evabyte_layer_gradients(seq, seed):
    """One layer of the published model, f32 TRAIN program (SGD at lr
    0: the step is the gradients) on one seeded sequence: the loss and
    EVERY parameter's gradient, phi and mu by name, against jax.grad
    of the plain reference."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import evabyte
    from paddle_tpu.models.reference import evabyte as reference
    cfg = evabyte.EvaByteConfig(layers=1)
    feed = _ints32(evabyte.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = evabyte.build_pretrain(cfg, seq)
            params = [p.name for p in main.all_parameters()]
            pairs = dict((p.name, g.name) for p, g in
                         fluid.optimizer.SGD(0.0).minimize(loss)[1])
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        # host copies first: a run donates the state it may write
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        fused0 = _fused_dispatches()
        t0 = time.time()
        got = exe.run(main, feed=feed,
                      fetch_list=[loss] + [pairs[p] for p in params])
        got_loss = _scalar(got[:1])
        grads = [np.asarray(g) for g in got[1:]]
        say('evabyte f32 train program, one layer, 1 x %d bytes: loss '
            '%.6f in %.1f s (with compile); %d flash dispatches fused'
            % (seq, got_loss, time.time() - t0,
               _fused_dispatches() - fused0))
        check(_fused_dispatches() - fused0 >= 2,
              'both streams of the f32 train step dispatched to the '
              'flash kernels')
        del got
        for n in scope.local_var_names():
            scope.erase(n)
    jfeed = {k: jnp.asarray(v) for k, v in feed.items()}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference.loss(w, jfeed, cfg, remat=True)))(
        [jnp.asarray(w) for w in weights])
    want_loss = float(want_loss)
    rel = abs(got_loss - want_loss) / want_loss
    say('reference: loss %.6f; relative difference %.2e'
        % (want_loss, rel))
    check(rel <= EVA_LOSS_RTOL, 'evabyte f32 train loss within %g of '
          'the reference' % EVA_LOSS_RTOL)
    worst = 0.0
    for what, x, y in zip(EVA_NAMES, grads, want):
        y = np.asarray(y)
        e = float(np.abs(x - y).max() / np.abs(y).max())
        l2 = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        worst = max(worst, l2)
        say('gradient of %s %s: largest entry difference %.3e of the '
            'largest entry (%.3e), relative L2 distance %.3e'
            % (what, x.shape, e, np.abs(y).max(), l2))
    check(worst <= EVA_L2_RTOL, 'evabyte gradients: every parameter '
          'within %g relative L2 of the reference\'s (worst %.3e)'
          % (EVA_L2_RTOL, worst))


def _evabyte_cell_losses(seq, seed):
    """The benchmark cell's own cut (four layers), forward only: the
    f32 for_test program's loss on EVA_LOSS_BATCHES batches beside the
    reference's in float32 and in bfloat16 throughout: the two readings
    the family's REFERENCE_RTOL lies between."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import evabyte
    from paddle_tpu.models.reference import evabyte as reference
    cfg = evabyte.EvaByteConfig(layers=EVA_CELL_LAYERS)
    feeds = [_ints32(evabyte.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + EVA_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = evabyte.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p.name)))
                   for p in main.all_parameters()]
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        for n in scope.local_var_names():
            scope.erase(n)
    weights = [jnp.asarray(w) for w in weights]
    both = jax.jit(lambda w, f: [reference.loss(w, f, cfg, dtype=dt)
                                 for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(
            weights, {k: jnp.asarray(v) for k, v in feed.items()}))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers, batch seed %d: program %.6f, reference %.6f '
            '(relative difference %.2e), reference in bfloat16 '
            'throughout %.6f (%.2e)'
            % (cfg.layers, seed + n, got, full, off[-1], half, low[-1]))
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, median %.2e, largest %.2e, '
        '%d within %g'
        % (len(off), cfg.layers, np.median(off), max(off), min(low),
           np.median(low), max(low),
           sum(x <= EVA_LOSS_RTOL for x in low), EVA_LOSS_RTOL))
    check(max(off) <= EVA_LOSS_RTOL, 'evabyte f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % EVA_LOSS_RTOL)
    check(min(low) > EVA_LOSS_RTOL, 'the reference in bfloat16 '
          'throughout misses %g on every batch' % EVA_LOSS_RTOL)


def phase_evabyte(seed=0):
    """EvaByte at published widths: (d) one eva_attention call at the
    published 32768 positions, where the remote stream is half of
    attention, in bfloat16 (timed, each stream against its roofline)
    and in float32 (tight agreement); (c) every gradient of one layer's
    f32 train program at the cell's 4096 bytes; the cell's own forward
    check over several batches, f32 and bfloat16."""
    _eva_single_op(EVA_T, 'bfloat16', seed)
    _eva_single_op(EVA_T, 'float32', seed)
    _evabyte_layer_gradients(EVA_LAYER_SEQ, seed)
    _evabyte_cell_losses(EVA_LAYER_SEQ, seed)


# --- Solar-Open2-250B ---------------------------------------------------
# Published widths (models.solar_open2.BASE) as the benchmark cuts the
# rest: the model's layers 0 to 3 (softmax, then three delta-rule
# layers), 8 of 64 heads of each kind with one K/V head, experts 0-7 of
# 320 held, 24576 vocabulary rows, one 4096-token sequence.  First the
# ``kda_attention`` op ALONE at the 32768 positions the cell cannot
# hold, forward and backward, against the token scan stepped in
# checkpointed blocks; then sampled gradients of the f32 TRAIN program
# against jax.grad of the reference, and the f32 for_test loss over
# SOLAR_LOSS_BATCHES batches against the reference in f32 and in
# bfloat16 throughout: the two readings the family's REFERENCE_RTOL
# lies between.
SOLAR_LAYERS = 4
SOLAR_OP_TOKENS = 32768
SOLAR_OP_TOL = 2e-4         # op alone, f32, relative L2 of a tensor
SOLAR_LOSS_RTOL = 2e-6
SOLAR_L2_RTOL = 2e-3
SOLAR_CELL_RTOL = 5e-6      # = benchmark/families/solar_open2.py's
SOLAR_LOSS_BATCHES = 12
SOLAR_LOADS_OFF = 24
# creation order, trainable parameters only: embedding 0; layer 0
# (softmax) g_op 1 Wq 2 Wk 3 Wv 4 Wgate 5 Wo 6 g_ffn 7 router 8 gate 9
# up 10 down 11 shared gate 12 up 13 down 14; layer 1 (delta rule) g_op
# 15 Wq 16 fq 17 Wk 18 fk 19 Wv 20 fv 21 Wf_down 22 Wf_up 23 A_log 24
# dt_bias 25 Wb 26 g_o 27 Wg_down 28 Wg_up 29 Wo 30 g_ffn 31 router 32
# gate 33 up 34 down 35 shared 36 37 38; layer 2 from 39
# (the embedding's and the head's [24576, 4096] gradients and a second
# expert tensor are left out: fetched beside 12 GB of f32 temporaries
# they do not fit)
SOLAR_SAMPLED = {'Wq (layer 0)': 2, 'Wk (layer 0)': 3,
                 'Wgate (layer 0)': 5, 'router (layer 0)': 8,
                 'gate': 9, 'shared gate (layer 0)': 12,
                 'Wq (layer 1)': 16, 'filter q (layer 1)': 17,
                 'Wk (layer 1)': 18, 'filter v (layer 1)': 21,
                 'Wf_down (layer 1)': 22, 'Wf_up (layer 1)': 23,
                 'A_log (layer 1)': 24, 'dt_bias (layer 1)': 25,
                 'Wb (layer 1)': 26, 'o gain (layer 1)': 27,
                 'Wg_up (layer 1)': 29, 'Wo (layer 1)': 30,
                 'A_log (layer 2)': 48, 'Wb (layer 2)': 50}


def _solar_cut(layers=SOLAR_LAYERS):
    from paddle_tpu.models import solar_open2
    base = solar_open2.BASE
    return solar_open2.SolarOpen2Config(
        vocab_size=24576, layers=layers, heads=base.heads // 8,
        kv_heads=base.kv_heads // 8, kda_heads=base.kda_heads // 8,
        experts_held=(0, 8), bias_init_std=0.005)


def _solar_single_op(t=SOLAR_OP_TOKENS, heads=8, d=128, seed=0):
    """The ``kda_attention`` op alone, float32, at ``t`` positions:
    forward and all five gradients against the recurrence stepped a
    token at a time (in checkpointed blocks of 128, or its gradient
    would keep t states of [128, 128] a head), with the device's peak
    memory and each direction's time beside the hand count's
    roofline."""
    import jax
    import jax.numpy as jnp
    from benchmark.lib import flops, peaks, solar_flops
    from paddle_tpu.models.reference import solar_open2 as reference
    from paddle_tpu.ops import kda_ops
    from paddle_tpu.ops.pallas import common
    rng = np.random.RandomState(seed)
    q, k = (rng.randn(1, t, heads, d) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(1, t, heads, d)
    # the startup draws' range: A in (1, 16), dt in (0.001, 0.1)
    a = -rng.uniform(1, 16, (1, 1, heads, 1)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(1e-1), (1, t, heads, d)))
    beta = 2 / (1 + np.exp(-rng.randn(1, t, heads)))
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, a, beta)]
    probe = jnp.asarray(rng.randn(1, t, heads, d), jnp.float32)

    def both(f):
        def run(*x):
            out, pull = jax.vjp(f, *x)
            return (out,) + pull(probe)
        return jax.jit(run)

    op = both(kda_ops.gated_delta_rule)
    with jax.default_matmul_precision('highest'):
        scan = both(lambda *x: reference.kda_recurrence(*x, block=128))
        want = [np.asarray(x) for x in scan(*args)]
    got = [np.asarray(x) for x in op(*args)]
    worst = 0.0
    for name, x, y in zip(('o', 'dq', 'dk', 'dv', 'da', 'dbeta'), got,
                          want):
        l2 = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        worst = max(worst, l2)
        say('kda_attention alone, f32, 1 x %d x %d x %d: %s relative L2 '
            'distance from the token scan %.3e (largest entry '
            'difference %.3e of %.3e)'
            % (t, heads, d, name, l2, np.abs(x - y).max(),
               np.abs(y).max()))
    check(np.isfinite(got[0]).all() and worst <= SOLAR_OP_TOL,
          'the op at %d positions, forward and five gradients, within '
          '%g of the token scan (worst %.3e; PR 46, the scores dense: '
          '1.1e-5)' % (t, SOLAR_OP_TOL, worst))
    last = common._LAST.get('kda_chunk', {})
    check(last.get('path') == 'fused' and not last.get('interpret'),
          'the op\'s in-chunk scores ran the kda_chunk kernels (%s)' % last)
    last = common._LAST.get('kda_walk', {})
    check(last.get('path') == 'fused' and not last.get('interpret'),
          'the op\'s chunks were walked by the kda_walk kernels (%s)' % last)
    forward = jax.jit(kda_ops.gated_delta_rule)
    fwd_s, both_s = _timed(forward, *args), _timed(op, *args)
    cost = solar_flops.kda_train_cost(1, t, heads, d, itemsize=4)
    least, side = flops.roofline_seconds(
        *cost, *peaks.chip_peak(jax.devices()[0].device_kind))
    say('kda_attention alone at %d positions, the scores by the '
        'kda_chunk kernels: forward %.2f ms, forward + backward %.2f ms '
        '(PR 46, the scores dense: 23.87 / 77.63 ms); the hand count '
        '(%.1f GFLOP, %.1f MB at 4 bytes an element) is %s-bound at '
        '%.2f ms: %.1f%% of its roofline (PR 46: 3.8%%); peak memory '
        '%.2f GB'
        % (t, fwd_s * 1e3, both_s * 1e3, cost[0] / 1e9, cost[1] / 1e6,
           side, least * 1e3, 100 * least / both_s,
           _peak_bytes(jax.devices()[:1])[0] / 1e9))


def _solar_cell_losses(seq, seed):
    """The cell's own cut, forward only: the f32 for_test program's
    loss on SOLAR_LOSS_BATCHES batches beside the reference's in
    float32 and in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import solar_open2
    from paddle_tpu.models.reference import solar_open2 as reference
    cfg = _solar_cut()
    sizes = reference.sizes_of(cfg)
    feeds = [_ints32(solar_open2.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + SOLAR_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = solar_open2.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights, biases = ([np.asarray(fluid.core.as_array(
            scope.find_var(p.name))) for p in main.all_parameters()
            if p.trainable == kind] for kind in (True, False))
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        for name in scope.local_var_names():
            scope.erase(name)
    weights, biases = ([jnp.asarray(x) for x in part]
                       for part in (weights, biases))
    both = jax.jit(lambda w, b, i, l: [reference.loss(
        w, b, i, l, sizes=sizes, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(weights, biases, *(
            jnp.asarray(feed[k]) for k in ('ids', 'labels'))))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers, batch seed %d: program %.6f, reference %.6f '
            '(relative difference %.2e), reference in bfloat16 '
            'throughout %.6f (%.2e)'
            % (cfg.layers, seed + n, got, full, off[-1], half, low[-1]))
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e %.2e, '
        'largest %.2e, %d within %g'
        % ((len(off), cfg.layers, np.median(off), max(off), min(low)) +
           tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= SOLAR_CELL_RTOL for x in low),
            SOLAR_CELL_RTOL)))
    check(max(off) <= SOLAR_CELL_RTOL, 'solar f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % SOLAR_CELL_RTOL)
    check(min(low) > SOLAR_CELL_RTOL, 'the reference in bfloat16 '
          'throughout misses %g on every batch' % SOLAR_CELL_RTOL)


def phase_solar(seq=4096, seed=0):
    """The op alone at 32768 positions; then models.solar_open2.BASE
    cut as above: loss and sampled gradients of the f32 TRAIN program
    (the chunked delta rule and its reverse walk, the three ungated
    filters, the float32 decay chain, the grouped causal flash kernels
    at a group of 8 without rotary, the held experts' grouped matmuls)
    against the reference's on one seeded sequence; then the cell's
    for_test losses."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import solar_open2
    from paddle_tpu.models.reference import solar_open2 as reference
    from paddle_tpu.ops.pallas import common
    _solar_single_op()
    cfg = _solar_cut()
    sizes = reference.sizes_of(cfg)
    feed = _ints32(solar_open2.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    ids, labels = (jnp.asarray(feed[k]) for k in ('ids', 'labels'))
    experts = {}

    def sample(name, array):
        if name == 'gate':
            return {'%s, %s loaded held expert (layer 0)' % (name, which):
                    array[e] for which, e in experts.items()}
        return {name: array}

    def ref_loss(some, full, biases, chosen=None, dtype=jnp.float32):
        full = list(full)
        for name, w in some.items():
            full[SOLAR_SAMPLED[name]] = w
        return reference.loss(full, biases, ids, labels, sizes=sizes,
                              dtype=dtype, remat=True, chosen=chosen)

    ref_grads = jax.jit(jax.value_and_grad(ref_loss))
    ref_free = jax.jit(lambda full, biases: [
        ref_loss({}, full, biases, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    ref_loads = jax.jit(lambda full, biases: reference.forward(
        full, biases, ids, sizes=sizes)[1])

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = solar_open2.build_pretrain(cfg, seq)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    fetches = [loss] + [pairs[params[i]] for i in SOLAR_SAMPLED.values()]
    routers = [op for op in main.global_block().ops
               if op.type == 'moe_route']
    load_names = [op.output('Load')[0] for op in routers]
    choice_names = [op.output('TopKIdx')[0] for op in routers]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights, bias_values = ([np.asarray(fluid.core.as_array(
            scope.find_var(n))) for n in names]
            for names in (params, biases))
        t0 = time.time()
        got = exe.run(main, feed=feed,
                      fetch_list=fetches + load_names + choice_names)
        got_loss = _scalar(got[:1])
        say('solar f32 train program, 1 x %d tokens, %d layers: loss '
            '%.6f in %.1f s; moe/held_share %.4f, moe/held_rows_max %d, '
            'moe/dropped_tokens %d; kda/calls %d, kda/chunks %d a step; '
            'short_conv/calls %d; flash_attention last dispatch %s; '
            'peak memory %.2f GB'
            % (seq, cfg.layers, got_loss, time.time() - t0,
               monitor.gauge_value('moe/held_share'),
               monitor.gauge_value('moe/held_rows_max'),
               monitor.counter_value('moe/dropped_tokens'),
               monitor.counter_value('kda/calls'),
               monitor.gauge_value('kda/chunks'),
               monitor.counter_value('short_conv/calls'),
               common._LAST.get('flash_attention'),
               _peak_bytes(jax.devices()[:1])[0] / 1e9))
        check(common._LAST.get('flash_attention', {}).get('path') ==
              'fused', 'the grouped causal calls (8 query heads on one '
              'K/V head, no rotary) ran the flash kernels')
        check(monitor.gauge_value('kda/chunks') ==
              3 * 2 * -(-seq // 64), 'kda/chunks counted a forward and '
              'a reverse scan of %d chunks a delta-rule layer'
              % -(-seq // 64))
        check(common._LAST.get('kda_chunk', {}).get('path') == 'fused',
              'the three delta-rule layers\' in-chunk scores ran the '
              'kda_chunk kernels (%d fused dispatches)'
              % monitor.counter_value('pallas/kda_chunk/dispatch_fused'))
        check(common._LAST.get('kda_walk', {}).get('path') == 'fused',
              'and their chunks were walked by the kda_walk kernels (%d '
              'fused dispatches)'
              % monitor.counter_value('pallas/kda_walk/dispatch_fused'))
        chosen = [jnp.asarray(x) for x in got[-len(routers):]]
        program_loads = [np.asarray(x) for x in got[
            len(fetches):len(fetches) + len(routers)]]
        grads_raw = [np.asarray(x) for x in got[1:len(fetches)]]
        del got
        for name in scope.local_var_names():
            scope.erase(name)
    weights, bias_values = ([jnp.asarray(x) for x in part]
                            for part in (weights, bias_values))
    loads = [np.asarray(x) for x in ref_loads(weights, bias_values)]
    loads_off = [int(np.sum(a != b))
                 for a, b in zip(program_loads, loads)]
    say('experts a layer whose load differs between program and '
        'reference (a near-tie token changes two by one): %s; held rows '
        'a layer %s' % (loads_off, [int(x[:8].sum()) for x in loads]))
    check(max(loads_off) <= SOLAR_LOADS_OFF,
          'the program\'s choice is the reference\'s but for near-ties')
    load = loads[0][:8]
    experts.update(most=int(load.argmax()), least=int(load.argmin()))
    grads = {what: x for name, g in zip(SOLAR_SAMPLED, grads_raw)
             for what, x in sample(name, g).items()}
    some = {name: weights[i] for name, i in SOLAR_SAMPLED.items()}
    pinned, want_grads = ref_grads(some, weights, bias_values, chosen)
    want_loss, low = (float(x) for x in ref_free(weights, bias_values))
    rel = abs(got_loss - want_loss) / want_loss
    low_rel = abs(low - want_loss) / want_loss
    say('reference loss %.6f, program %.6f (relative difference %.2e; '
        '%.2e from the reference routed by the program\'s choice); '
        'reference in bfloat16 throughout %.6f (%.2e)'
        % (want_loss, got_loss, rel,
           abs(got_loss - float(pinned)) / float(pinned), low, low_rel))
    check(abs(got_loss - float(pinned)) <= SOLAR_LOSS_RTOL * float(pinned),
          'solar f32 train loss within %g of the reference routed by '
          'the program\'s choice' % SOLAR_LOSS_RTOL)
    check(rel <= SOLAR_CELL_RTOL, 'solar f32 train loss within %g of '
          'the reference by its own choice' % SOLAR_CELL_RTOL)
    check(low_rel > SOLAR_CELL_RTOL, 'the reference in bfloat16 '
          'throughout misses %g' % SOLAR_CELL_RTOL)
    far = 0.0
    for name in SOLAR_SAMPLED:
        for what, y in sample(name, np.asarray(want_grads[name])).items():
            x = grads[what]
            e = float(np.abs(x - y).max() / np.abs(y).max())
            d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
            far = max(far, d)
            say('gradient of %s %s: largest entry difference %.3e of '
                'the largest entry (%.3e), relative L2 distance %.3e'
                % (what, x.shape, e, np.abs(y).max(), d))
    del weights, bias_values, some, want_grads
    _solar_cell_losses(seq, seed)
    check(far <= SOLAR_L2_RTOL,
          'solar gradients: every sampled tensor within %g of the '
          'reference\'s routed by the program\'s choice, relative L2 '
          'distance (worst %.3e; PR 46, the scores dense: 9.1e-5)'
          % (SOLAR_L2_RTOL, far))



# --- Kimi-Linear-48B-A3B ------------------------------------------------
# Published widths (models.kimi_linear.BASE) as the cell cuts them: the
# model's layers 1 to 5 (the dense delta-rule layer, two routed
# delta-rule layers, the routed latent layer, one more delta-rule
# layer), ALL 32 heads of both kinds, experts 0 to 7 of 256, 20480
# vocabulary rows, one 8192-token sequence.  Loss and sampled gradients
# of the f32 TRAIN program (the chunked delta rule inside its recompute
# groups, the latent flash kernels without rotary, the held experts'
# grouped matmuls) against jax.grad of the reference, then the f32
# for_test loss over KIMI_LOSS_BATCHES batches against the reference in
# f32 and in bfloat16 throughout: the two readings the family's
# REFERENCE_RTOL lies between.
KIMI_LAYERS = 5
KIMI_LOSS_RTOL = 2e-6
KIMI_L2_RTOL = 2e-3
KIMI_CELL_RTOL = 5e-6       # = benchmark/families/kimi_linear.py's
KIMI_LOSS_BATCHES = 12
# experts a routed layer whose load differs between program and
# reference: a token whose 8th and 9th biased scores tie to float32
# rounding changes two loads by one, and a token that changed expert
# in one layer is routed on another input in the next, so the count
# grows with depth: 10, 22, 35, 42 of 256 over the four routed layers
# on one 8192-token sequence (my chip run, PR 60; Solar's limit is 24
# at 4096 tokens); a bias that does not pick, or another top-k, moves
# thousands
KIMI_LOADS_OFF = 128
# the delta-rule heads of the step whose reference the host CPU computes
# (8 x 4096 tokens: 36 s and about 21 GB there; 4 GB more a 1024 tokens)
KIMI_HOST_HEADS = 8
# creation order, trainable parameters only: embedding 0; layer 1
# (delta rule, dense) g_op 1 Wq 2 fq 3 Wk 4 fk 5 Wv 6 fv 7 Wf_down 8
# Wf_up 9 A_log 10 dt_bias 11 Wb 12 g_o 13 Wg_down 14 Wg_up 15 Wo 16
# g_ffn 17 gate 18 up 19 down 20; layer 2 (delta rule, routed) from 21:
# the operator's fifteen, g_ffn 37 router 38 gate 39 up 40 down 41
# shared 42 43 44; layer 3 from 45; layer 4 (latent) g_op 69 Wq 70 Wkva
# 71 g_latent 72 Wkvb 73 Wo 74 g_ffn 75 router 76 ...; layer 5 from 83;
# final gain 107, head 108
KIMI_SAMPLED = {'Wq (layer 1)': 2, 'filter q (layer 1)': 3,
                'filter k (layer 1)': 5, 'filter v (layer 1)': 7,
                'filter q (layer 2)': 23, 'filter q (layer 5)': 85,
                'Wf_up (layer 1)': 9, 'A_log (layer 1)': 10,
                'dt_bias (layer 1)': 11, 'Wb (layer 1)': 12,
                'o gain (layer 1)': 13, 'Wo (layer 1)': 16,
                'dense gate (layer 1)': 18, 'router (layer 2)': 38,
                'gate': 39, 'shared gate (layer 2)': 42,
                'A_log (layer 3)': 54, 'Wb (layer 3)': 56,
                'Wq (layer 4)': 70, 'Wkva (layer 4)': 71,
                'latent gain (layer 4)': 72, 'Wkvb (layer 4)': 73,
                'Wo (layer 4)': 74, 'Wb (layer 5)': 94}


def _kimi_cut(layers=KIMI_LAYERS):
    import copy
    from paddle_tpu.models import kimi_linear
    cfg = copy.copy(kimi_linear.BASE)
    cfg.vocab_size, cfg.layers, cfg.experts_held = 20480, layers, (0, 8)
    cfg.bias_init_std = 0.005
    return cfg


def _kimi_cell_losses(seq, seed):
    """The cell's own cut, forward only: the f32 for_test program's
    loss on KIMI_LOSS_BATCHES batches beside the reference's in float32
    and in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.models.reference import kimi_linear as reference
    cfg = _kimi_cut()
    sizes = reference.sizes_of(cfg)
    feeds = [_ints32(kimi_linear.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + KIMI_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = kimi_linear.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights, biases = ([np.asarray(fluid.core.as_array(
            scope.find_var(p.name))) for p in main.all_parameters()
            if p.trainable == kind] for kind in (True, False))
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        for name in scope.local_var_names():
            scope.erase(name)
    weights, biases = ([jnp.asarray(x) for x in part]
                       for part in (weights, biases))
    both = jax.jit(lambda w, b, i, l: [reference.loss(
        w, b, i, l, sizes=sizes, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(weights, biases, *(
            jnp.asarray(feed[k]) for k in ('ids', 'labels'))))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers, batch seed %d: program %.6f, reference %.6f '
            '(relative difference %.2e), reference in bfloat16 '
            'throughout %.6f (%.2e)'
            % (cfg.layers, seed + n, got, full, off[-1], half, low[-1]))
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e %.2e, '
        'largest %.2e, %d within %g'
        % ((len(off), cfg.layers, np.median(off), max(off), min(low)) +
           tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= KIMI_CELL_RTOL for x in low),
            KIMI_CELL_RTOL)))
    check(max(off) <= KIMI_CELL_RTOL, 'kimi f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % KIMI_CELL_RTOL)
    check(2 * sum(x > KIMI_CELL_RTOL for x in low) > len(low),
          'the reference in bfloat16 throughout misses %g on most '
          'batches' % KIMI_CELL_RTOL)


def phase_kimi(seq=8192, seed=0):
    """models.kimi_linear.BASE cut as above: loss and sampled gradients
    of the f32 TRAIN program against the reference's on one seeded
    sequence, twice; then the cell's for_test losses.  At published
    widths the reference is compiled for the TPU and the FILTERS are
    left out of the sample: ``jax.grad`` of the reference as the v5e
    compiler builds it put one tap of a q filter's gradient 2.2e-2 off
    what the host CPU's compile of the same function and the program
    give (which filter moved with the set of gradients asked for;
    PERF.md section 6, PR 60).  At KIMI_HOST_HEADS delta-rule heads x
    half the tokens, the largest size whose reference gradient the
    host's 40 GiB hold, the reference is compiled for the host CPU and
    every sampled tensor, the filters by tap, is held to it."""
    _kimi_train_step(None, seq, seed)
    _kimi_train_step(KIMI_HOST_HEADS, seq // 2, seed)
    _kimi_cell_losses(seq, seed)


def _kimi_train_step(host_heads, seq, seed):
    """One f32 train step against the reference: at published widths
    and the TPU's reference (``host_heads`` None), or at ``host_heads``
    delta-rule heads and the host CPU's."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.models.reference import kimi_linear as reference
    from paddle_tpu.ops.pallas import common
    cfg = _kimi_cut()
    if host_heads:
        cfg.kda_heads = host_heads
    sampled = {name: i for name, i in KIMI_SAMPLED.items()
               if host_heads or not name.startswith('filter')}
    device = jax.devices('cpu' if host_heads else None)[0]
    sizes, held = reference.sizes_of(cfg), cfg.experts_held[1]
    deltas = sum(i not in cfg.full_attn_layers
                 for i in cfg.layer_indices())
    feed = _ints32(kimi_linear.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    ids, labels = (np.asarray(feed[k]) for k in ('ids', 'labels'))
    experts = {}

    def sample(name, array):
        if name == 'gate':
            return {'%s, %s loaded held expert (layer 2)' % (name, which):
                    array[e] for which, e in experts.items()}
        return {name: array}

    def ref_loss(some, full, biases, chosen=None, dtype=jnp.float32):
        full = list(full)
        for name, w in some.items():
            full[sampled[name]] = w
        return reference.loss(full, biases, ids, labels, sizes=sizes,
                              dtype=dtype, remat=True, chosen=chosen)

    ref_grads = jax.jit(jax.value_and_grad(ref_loss))
    ref_free = jax.jit(lambda full, biases: [
        ref_loss({}, full, biases, dtype=dt)
        for dt in (jnp.float32, jnp.bfloat16)])
    ref_loads = jax.jit(lambda full, biases: reference.forward(
        full, biases, ids, sizes=sizes)[1])

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = kimi_linear.build_pretrain(cfg, seq)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    count = sum(int(np.prod(p.shape)) for p in every if p.trainable)
    say('kimi: %d trainable parameters in %d tensors, %d choice biases'
        % (count, len(params), len(biases)))
    fetches = [loss] + [pairs[params[i]] for i in sampled.values()]
    routers = [op for op in main.global_block().ops
               if op.type == 'moe_route']
    load_names = [op.output('Load')[0] for op in routers]
    choice_names = [op.output('TopKIdx')[0] for op in routers]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights, bias_values = ([np.asarray(fluid.core.as_array(
            scope.find_var(n))) for n in names]
            for names in (params, biases))
        t0 = time.time()
        got = exe.run(main, feed=feed,
                      fetch_list=fetches + load_names + choice_names)
        got_loss = _scalar(got[:1])
        say('kimi f32 train program, 1 x %d tokens, %d layers: loss '
            '%.6f in %.1f s; moe/held_share %.4f, moe/held_rows_max %d, '
            'moe/dropped_tokens %d; kda/calls %d, kda/chunks %d a step; '
            'short_conv/calls %d; executor/recompute_groups %d; '
            'flash_attention last dispatch %s; peak memory %.2f GB'
            % (seq, cfg.layers, got_loss, time.time() - t0,
               monitor.gauge_value('moe/held_share'),
               monitor.gauge_value('moe/held_rows_max'),
               monitor.counter_value('moe/dropped_tokens'),
               monitor.counter_value('kda/calls'),
               monitor.gauge_value('kda/chunks'),
               monitor.counter_value('short_conv/calls'),
               monitor.counter_value('executor/recompute_groups'),
               common._LAST.get('flash_attention'),
               _peak_bytes(jax.devices()[:1])[0] / 1e9))
        check(common._LAST.get('flash_attention', {}).get('path') ==
              'fused', 'the latent layer\'s calls (32 heads, 192 over '
              '128, no rotary) ran the flash kernels')
        check(monitor.gauge_value('kda/chunks') ==
              (deltas * 3 - 1) * -(-seq // 64), 'kda/chunks counted a '
              'forward, a recompute group\'s second forward (the last '
              'block is no group) and a reverse scan of %d chunks a '
              'delta-rule layer' % -(-seq // 64))
        check(common._LAST.get('kda_chunk', {}).get('path') == 'fused',
              'the delta-rule layers\' in-chunk scores ran the '
              'kda_chunk kernels (%d fused dispatches)'
              % monitor.counter_value('pallas/kda_chunk/dispatch_fused'))
        check(common._LAST.get('kda_walk', {}).get('path') == 'fused',
              'and their chunks were walked by the kda_walk kernels (%d '
              'fused dispatches)'
              % monitor.counter_value('pallas/kda_walk/dispatch_fused'))
        chosen = [jnp.asarray(x) for x in got[-len(routers):]]
        program_loads = [np.asarray(x) for x in got[
            len(fetches):len(fetches) + len(routers)]]
        grads_raw = [np.asarray(x) for x in got[1:len(fetches)]]
        del got
        for name in scope.local_var_names():
            scope.erase(name)
    weights, bias_values = ([jnp.asarray(x) for x in part]
                            for part in (weights, bias_values))
    loads = [np.asarray(x) for x in ref_loads(weights, bias_values)]
    loads_off = [int(np.sum(a != b))
                 for a, b in zip(program_loads, loads)]
    say('experts a layer whose load differs between program and '
        'reference (a near-tie token changes two by one): %s; held rows '
        'a layer %s' % (loads_off, [int(x[:held].sum()) for x in loads]))
    check(max(loads_off) <= KIMI_LOADS_OFF,
          'the program\'s choice is the reference\'s but for near-ties')
    load = loads[0][:held]
    experts.update(most=int(load.argmax()), least=int(load.argmin()))
    grads = {what: x for name, g in zip(sampled, grads_raw)
             for what, x in sample(name, g).items()}
    some = {name: weights[i] for name, i in sampled.items()}
    t0 = time.time()
    pinned, want_grads = ref_grads(*jax.device_put(
        (some, weights, bias_values, chosen), device))
    want_grads = {name: np.asarray(g) for name, g in want_grads.items()}
    say('the reference\'s loss and %d gradients on %s in %.1f s'
        % (len(want_grads), device, time.time() - t0))
    want_loss, low = (float(x) for x in ref_free(weights, bias_values))
    rel = abs(got_loss - want_loss) / want_loss
    low_rel = abs(low - want_loss) / want_loss
    say('reference loss %.6f, program %.6f (relative difference %.2e; '
        '%.2e from the reference routed by the program\'s choice); '
        'reference in bfloat16 throughout %.6f (%.2e)'
        % (want_loss, got_loss, rel,
           abs(got_loss - float(pinned)) / float(pinned), low, low_rel))
    check(abs(got_loss - float(pinned)) <= KIMI_LOSS_RTOL * float(pinned),
          'kimi f32 train loss within %g of the reference routed by '
          'the program\'s choice' % KIMI_LOSS_RTOL)
    check(rel <= KIMI_CELL_RTOL, 'kimi f32 train loss within %g of '
          'the reference by its own choice' % KIMI_CELL_RTOL)
    far = 0.0
    for name in sampled:
        for what, y in sample(name, np.asarray(want_grads[name])).items():
            x = grads[what]
            e = float(np.abs(x - y).max() / np.abs(y).max())
            d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
            far = max(far, d)
            say('gradient of %s %s: largest entry difference %.3e of '
                'the largest entry (%.3e), relative L2 distance %.3e'
                % (what, x.shape, e, np.abs(y).max(), d))
            if what.startswith('filter'):           # by tap
                say('  by tap: relative L2 %s, norms %s'
                    % (' '.join('%.2e' % (np.linalg.norm(x[:, j] - y[:, j])
                                          / np.linalg.norm(y[:, j]))
                                for j in range(x.shape[1])),
                       ' '.join('%.2e' % np.linalg.norm(y[:, j])
                                for j in range(x.shape[1]))))
    check(far <= KIMI_L2_RTOL,
          'kimi gradients, %d delta-rule heads x %d tokens: all %d '
          'sampled tensors within %g of the reference\'s on %s, routed '
          'by the program\'s choice, relative L2 distance (worst %.3e)'
          % (cfg.kda_heads, seq, len(sampled), KIMI_L2_RTOL, device, far))


# (buffer rows, groups, K, N, live rows) of the routed cells' expert
# products (tools/bench_grouped_matmul.py times the same)
GROUPED_SHAPES = {
    'olmoe': (98304, 64, 2048, 1024, 98304),
    'laguna': (32768, 8, 3072, 1024, 1280),
    'moonlight': (49152, 8, 2048, 1408, 7440),
    'lfm2': (32768, 8, 2048, 1792, 8400),
    'solar': (32768, 8, 4096, 1280, 819),
}


def bf16_units(got, want):
    """How far ``got`` lies from ``want`` at most, in units of the last
    bfloat16 place of ``want``'s largest entry."""
    unit = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
    return float(np.abs(got - want).max() / unit)


# --- SDAR-30B-A3B-Chat --------------------------------------------------
# Published widths (models.sdar.BASE) as the benchmark cuts them
# (experts 0-15 of 128 held, 18992 vocabulary rows), one sequence of
# 4096 data tokens = 8192 positions.  Sampled gradients of the f32
# TRAIN program at TWO layers (a whole one in a recompute group and the
# last, which computes of its clean rows only keys and values; six
# layers' f32 activations do not fit) against jax.grad of the reference
# with a head's scores computed again for its gradient; then the f32
# for_test loss at the cell's own six layers over SDAR_LOSS_BATCHES
# corruptions against the reference in f32 and in bfloat16 throughout:
# the two readings the family's REFERENCE_RTOL lies between.
SDAR_GRAD_LAYERS = 2
SDAR_CELL_LAYERS = 6
SDAR_SEQ = 4096
SDAR_CELL_RTOL = 4e-5       # = benchmark/families/sdar.py's
SDAR_L2_RTOL = 2e-3         # a gradient tensor's relative L2 distance
SDAR_LOSS_BATCHES = 12
# creation order: embedding 0; layer 0 g1 1 Wq 2 gq 3 Wk 4 gk 5 Wv 6 Wo
# 7 g2 8 router 9 gate 10 up 11 down 12; layer 1 from 13; final gain
# 25, head 26
SDAR_SAMPLED = {'embedding': 0, 'Wq (layer 0)': 2, 'q gain (layer 0)': 3,
                'Wk (layer 0)': 4, 'k gain (layer 0)': 5,
                'Wv (layer 0)': 6, 'Wo (layer 0)': 7,
                'router (layer 0)': 9, 'gate (layer 0)': 10,
                'Wq (layer 1)': 14, 'Wk (layer 1)': 16,
                'Wv (layer 1)': 18, 'Wo (layer 1)': 19,
                'router (layer 1)': 21, 'down (layer 1)': 24,
                'final gain': 25}


def _sdar_cut(layers):
    """The cell's cut at ``layers`` layers, the assumed numbers (block
    length, least mask probability, the startup values) as the cell's
    configuration file has them."""
    import copy
    from paddle_tpu.models import sdar
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'benchmark', 'configs',
                           'sdar-30b-a3b-chat.json')) as f:
        assumed = json.load(f)['assumed']
    cfg = copy.copy(sdar.BASE)
    cfg.vocab_size, cfg.layers, cfg.experts_held = 18992, layers, (0, 16)
    cfg.block_length = assumed['block_length']['value']
    cfg.t_min = assumed['t_min']['value']
    cfg.embed_std = assumed['embed_std']['value']
    cfg.qk_gain = assumed['qk_gain']['value']
    return cfg


def _sdar_sizes(cfg):
    return dict(layers=cfg.layers, head_dim=cfg.head_dim,
                top_k=cfg.top_k, block=cfg.block_length,
                first=cfg.experts_held[0], eps=cfg.rms_eps,
                theta=cfg.rope_theta, renormalize=cfg.renormalize)


def _sdar_program(cfg, seq, seed, train):
    """-> (main or its for_test clone, startup, loss, parameter names,
    {param: grad name} or None)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import sdar
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = sdar.build_pretrain(cfg, seq)
        params = [p.name for p in main.all_parameters()]
        if not train:
            return main.clone(for_test=True), startup, loss, params, None
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    return main, startup, loss, params, pairs


def _sdar_cell_losses(seq, seed):
    """The cell's own cut, forward only: the f32 for_test program's
    loss on SDAR_LOSS_BATCHES corruptions beside the reference's in
    float32 and in bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import sdar
    from paddle_tpu.models.reference import sdar as reference
    cfg = _sdar_cut(SDAR_CELL_LAYERS)
    feeds = [sdar.synthetic_batch(cfg, 1, seq, s)
             for s in range(seed, seed + SDAR_LOSS_BATCHES)]
    test, startup, loss, params, _ = _sdar_program(cfg, seq, seed, False)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        say('sdar f32 for_test program, %d layers: sdar/visible_pairs %d '
            'a head, sdar/tiles_visited %d, sdar/masked_share %.4f, '
            'pallas/flash_attention/mask_block %d lowerings'
            % (cfg.layers, monitor.gauge_value('sdar/visible_pairs'),
               monitor.gauge_value('sdar/tiles_visited'),
               monitor.gauge_value('sdar/masked_share'),
               monitor.counter_value('pallas/flash_attention/mask_block')))
        check(monitor.gauge_value('sdar/visible_pairs') ==
              (cfg.layers - 1) * seq * seq +
              seq * (seq - cfg.block_length) // 2,
              'sdar/visible_pairs is L^2 a layer that runs both copies '
              'and L (L - B) / 2 in the last')
        for name in scope.local_var_names():
            scope.erase(name)
    weights = [jnp.asarray(x) for x in weights]
    sizes = _sdar_sizes(cfg)
    both = jax.jit(lambda w, f: [reference.loss(w, f, dtype=dt, **sizes)
                                 for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(
            weights, {k: jnp.asarray(v) for k, v in feed.items()}))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers, batch seed %d (masked share %.3f): program %.6f, '
            'reference %.6f (relative difference %.2e), reference in '
            'bfloat16 throughout %.6f (%.2e)'
            % (cfg.layers, seed + n, (feed['weights'] > 0).mean(), got,
               full, off[-1], half, low[-1]))
    say('over %d batches at %d layers: f32 for_test program against the '
        'reference, relative: median %.2e, largest %.2e; reference in '
        'bfloat16 throughout: smallest %.2e, quartiles %.2e %.2e %.2e, '
        'largest %.2e, %d within %g'
        % ((len(off), cfg.layers, np.median(off), max(off), min(low)) +
           tuple(np.percentile(low, (25, 50, 75))) +
           (max(low), sum(x <= SDAR_CELL_RTOL for x in low),
            SDAR_CELL_RTOL)))
    check(max(off) <= SDAR_CELL_RTOL, 'sdar f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % SDAR_CELL_RTOL)
    check(2 * sum(x > SDAR_CELL_RTOL for x in low) > len(low),
          'the reference in bfloat16 throughout misses %g on most '
          'batches' % SDAR_CELL_RTOL)


def _sdar_train_step(seq, seed):
    """One f32 train step at SDAR_GRAD_LAYERS layers against jax.grad
    of the reference on sampled tensors."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import sdar
    from paddle_tpu.models.reference import sdar as reference
    from paddle_tpu.ops.pallas import common
    cfg = _sdar_cut(SDAR_GRAD_LAYERS)
    sizes = _sdar_sizes(cfg)
    feed = sdar.synthetic_batch(cfg, 1, seq, seed)
    main, startup, loss, params, pairs = _sdar_program(cfg, seq, seed,
                                                       True)
    count = sum(int(np.prod(main.global_block().var(p).shape))
                for p in params)
    say('sdar: %d parameters in %d tensors at %d layers'
        % (count, len(params), cfg.layers))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        t0 = time.time()
        got = exe.run(main, feed=feed, fetch_list=[loss] + [
            pairs[params[i]] for i in SDAR_SAMPLED.values()])
        got_loss = _scalar(got[:1])
        say('sdar f32 train program, 1 x %d data tokens, %d layers: loss '
            '%.6f in %.1f s; moe/held_share %.4f, moe/held_rows_max %d, '
            'moe/dropped_tokens %d; sdar/tiles_visited %d; '
            'executor/recompute_groups %d; flash_attention last '
            'dispatch %s; backward one_pass %d two_pass %d; peak memory '
            '%.2f GB'
            % (seq, cfg.layers, got_loss, time.time() - t0,
               monitor.gauge_value('moe/held_share'),
               monitor.gauge_value('moe/held_rows_max'),
               monitor.counter_value('moe/dropped_tokens'),
               monitor.gauge_value('sdar/tiles_visited'),
               monitor.counter_value('executor/recompute_groups'),
               common._LAST.get('flash_attention'),
               monitor.counter_value(
                   'pallas/flash_attention/backward_one_pass'),
               monitor.counter_value(
                   'pallas/flash_attention/backward_two_pass'),
               _peak_bytes(jax.devices()[:1])[0] / 1e9))
        check(monitor.counter_value(
            'pallas/flash_attention/mask_block') >= 3,
            'the block-mask calls (32 query heads over 4 K/V heads of '
            '128, float32) ran the flash kernels')
        check(monitor.counter_value(
            'pallas/flash_attention/dispatch_small_keys') >= cfg.layers,
            'the corrupted copy\'s own blocks (4 x 4, folded into the '
            'batch, float32) ran the small-keys kernels')
        grads = [np.asarray(x) for x in got[1:]]
        del got
        for name in scope.local_var_names():
            scope.erase(name)
    weights = [jnp.asarray(x) for x in weights]
    fed = {k: jnp.asarray(v) for k, v in feed.items()}

    def ref_loss(some, full, dtype=jnp.float32):
        full = list(full)
        for name, w in some.items():
            full[SDAR_SAMPLED[name]] = w
        return reference.loss(full, fed, dtype=dtype, remat=True, **sizes)

    t0 = time.time()
    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(
        {name: weights[i] for name, i in SDAR_SAMPLED.items()}, weights)
    want_loss = float(want_loss)
    low = float(jax.jit(lambda w: ref_loss({}, w, jnp.bfloat16))(weights))
    say('the reference\'s loss and %d gradients in %.1f s'
        % (len(want_grads), time.time() - t0))
    rel = abs(got_loss - want_loss) / want_loss
    say('reference loss %.6f, program %.6f (relative difference %.2e); '
        'reference in bfloat16 throughout %.6f (%.2e)'
        % (want_loss, got_loss, rel, low,
           abs(low - want_loss) / want_loss))
    check(rel <= SDAR_CELL_RTOL, 'sdar f32 train loss within %g of the '
          'reference' % SDAR_CELL_RTOL)
    far = 0.0
    for (name, _), x in zip(SDAR_SAMPLED.items(), grads):
        y = np.asarray(want_grads[name])
        d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        far = max(far, d)
        say('gradient of %s %s: largest entry difference %.3e of the '
            'largest entry (%.3e), relative L2 distance %.3e'
            % (name, x.shape, np.abs(x - y).max() / np.abs(y).max(),
               np.abs(y).max(), d))
    check(far <= SDAR_L2_RTOL,
          'sdar gradients: all %d sampled tensors within %g of the '
          'reference\'s, relative L2 distance (worst %.3e)'
          % (len(SDAR_SAMPLED), SDAR_L2_RTOL, far))


def phase_sdar(seq=SDAR_SEQ, seed=0):
    """models.sdar.BASE cut as above: loss and sampled gradients of the
    f32 TRAIN program against the reference's on one seeded corruption;
    then the cell's for_test losses."""
    _sdar_train_step(seq, seed)
    _sdar_cell_losses(seq, seed)


# --- NVIDIA-Nemotron-3-Nano-30B-A3B -------------------------------------
# Published widths (models.nemotron_h.BASE) as the benchmark cuts them
# (experts 0-7 of 128 held, 16384 vocabulary rows).  Sampled gradients
# of the f32 TRAIN program on ONE short sequence of a pattern with every
# kind of layer (the chunked ``ssd_scan`` and its backward inside
# recompute groups, the held relu2 experts' grouped products, GQA
# 32-over-2 flash in float32) against jax.grad of the reference, whose
# recurrence steps a token at a time in checkpointed blocks; then the
# cell itself as the harness builds it (nine layers, 8192 tokens), its
# f32 for_test loss over NEMOTRON_LOSS_BATCHES batches through the
# family's OWN comparison (a number and a tolerance for each batch from
# the reference's undecided choices: benchmark/families/nemotron_h.py),
# with the reference in bfloat16 throughout put through the same: the
# program inside every batch's limit, the control outside every one.
# The filters are left out of the sample: jax.grad of a
# reference's shifted sums as the v5e compiler builds it is not what
# the host's compile gives (PERF.md section 6, PR 60); the CPU tests
# hold them.
NEMOTRON_GRAD_PATTERN = 'MEM*E'
NEMOTRON_GRAD_SEQ = 1024
NEMOTRON_TRAIN_LOSS_RTOL = 1e-6     # 1024 tokens, two routed layers:
# 0 to 1.9e-7 read, the reference in bfloat16 throughout 4.3e-5
NEMOTRON_MARGINS = (1e-7, 3e-7, 1e-6, 3e-6, 1e-5, 3e-5)
NEMOTRON_L2_RTOL = 2e-3     # a gradient tensor's relative L2 distance
NEMOTRON_LOSS_BATCHES = 12
NEMOTRON_SAMPLED = (
    'embedding', '0.norm_g', '0.mamba.w_in', '0.mamba.dt_bias',
    '0.mamba.a_log', '0.mamba.d', '0.mamba.norm_g', '0.mamba.w_out',
    '1.moe.router', '1.moe.up', '1.moe.down', '1.moe.shared_up',
    '2.mamba.w_in', '2.mamba.a_log', '3.attention.wq', '3.attention.wk',
    '3.attention.wv', '3.attention.wo', '4.moe.down', 'norm_f', 'head')


def _nemotron_cut(pattern):
    """The cell's cut at ``pattern``'s layers, the assumed numbers as
    the cell's configuration file has them."""
    import copy
    from paddle_tpu.models import nemotron_h
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'benchmark', 'configs',
                           'nemotron-3-nano-30b-a3b.json')) as f:
        assumed = json.load(f)['assumed']
    cfg = copy.copy(nemotron_h.BASE)
    cfg.pattern, cfg.kinds = pattern, nemotron_h.layer_kinds(pattern)
    cfg.vocab_size, cfg.experts_held = 16384, (0, 8)
    cfg.residual_layers = len(nemotron_h.PATTERN)
    cfg.bias_init_std = assumed['bias_init_std']['value']
    cfg.bias_update_rate = assumed['bias_update_rate']['value']
    cfg.embed_std = assumed['embed_std']['value']
    return cfg


def _nemotron_sizes(cfg):
    return dict(pattern=cfg.pattern, head_dim=cfg.head_dim,
                top_k=cfg.top_k, first=cfg.experts_held[0],
                routed_scale=cfg.routed_scale, eps=cfg.rms_eps,
                renormalize=cfg.renormalize)


def _nemotron_program(cfg, seq, seed, train):
    """-> (main or its for_test clone, startup, loss, parameter names,
    {param: grad name} or None)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import nemotron_h
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = nemotron_h.build_pretrain(cfg, seq)
        params = [p.name for p in main.all_parameters()]
        if not train:
            return main.clone(for_test=True), startup, loss, params, None
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    return main, startup, loss, params, pairs


def _nemotron_train_step(seq, seed):
    """One f32 train step at NEMOTRON_GRAD_PATTERN against jax.grad of
    the reference on sampled tensors."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.models.reference import nemotron_h as reference
    from paddle_tpu.ops.pallas import common
    cfg = _nemotron_cut(NEMOTRON_GRAD_PATTERN)
    sizes = _nemotron_sizes(cfg)
    labels = [label for label, _ in nemotron_h.parameter_specs(cfg)]
    sampled = {label: labels.index(label) for label in NEMOTRON_SAMPLED}
    feed = _ints32(nemotron_h.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    main, startup, loss, params, pairs = _nemotron_program(cfg, seq, seed,
                                                           True)
    count = sum(int(np.prod(main.global_block().var(p).shape))
                for p in params)
    say('nemotron_h: %d parameters in %d tensors at %s'
        % (count, len(params), cfg.pattern))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        t0 = time.time()
        scans = monitor.counter_value('pallas/ssd_scan/dispatch_fused')
        got = exe.run(main, feed=feed, fetch_list=[loss] + [
            pairs[params[i]] for i in sampled.values()])
        scans = monitor.counter_value('pallas/ssd_scan/dispatch_fused') - \
            scans
        say('ssd_scan: %d lowerings took the kernels in the f32 train '
            'step, last dispatch %s' % (scans, common._LAST.get('ssd_scan')))
        check(scans >= cfg.pattern.count('M') and
              common._LAST.get('ssd_scan', {}).get('path') == 'fused',
              'the f32 train step\'s chunked scans ran the ssd_scan '
              'kernels (the gradients below are theirs)')
        got_loss = _scalar(got[:1])
        say('nemotron_h f32 train program, 1 x %d tokens, %s: loss %.6f '
            'in %.1f s; ssd/chunks %d, ssd/boundary_state_mb %.1f; '
            'moe/held_share %.4f, moe/load_max_over_mean %.3f, '
            'moe/dropped_tokens %d; executor/recompute_groups %d; '
            'flash_attention last dispatch %s; peak memory %.2f GB'
            % (seq, cfg.pattern, got_loss, time.time() - t0,
               monitor.gauge_value('ssd/chunks'),
               monitor.gauge_value('ssd/boundary_state_mb'),
               monitor.gauge_value('moe/held_share'),
               monitor.gauge_value('moe/load_max_over_mean'),
               monitor.counter_value('moe/dropped_tokens'),
               monitor.counter_value('executor/recompute_groups'),
               common._LAST.get('flash_attention'),
               _peak_bytes(jax.devices()[:1])[0] / 1e9))
        check(common._LAST.get('flash_attention', {}).get('path') ==
              'fused', '32 query heads over 2 K/V heads of 128, float32, '
              'ran the flash kernels')
        grads = [np.asarray(x) for x in got[1:]]
        del got
        for name in scope.local_var_names():
            scope.erase(name)
    weights = [jnp.asarray(x) for x in weights]
    fed = {k: jnp.asarray(v) for k, v in feed.items()}

    def ref_loss(some, full, dtype=jnp.float32):
        full = list(full)
        for label, w in some.items():
            full[sampled[label]] = w
        return reference.loss(full, fed, dtype=dtype, remat=True,
                              block=64, **sizes)

    t0 = time.time()
    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(
        {label: weights[i] for label, i in sampled.items()}, weights)
    want_loss = float(want_loss)
    low = float(jax.jit(lambda w: ref_loss({}, w, jnp.bfloat16))(weights))
    say('the reference\'s loss and %d gradients in %.1f s'
        % (len(want_grads), time.time() - t0))
    rel = abs(got_loss - want_loss) / want_loss
    say('reference loss %.6f, program %.6f (relative difference %.2e); '
        'reference in bfloat16 throughout %.6f (%.2e)'
        % (want_loss, got_loss, rel, low,
           abs(low - want_loss) / want_loss))
    check(rel <= NEMOTRON_TRAIN_LOSS_RTOL, 'nemotron_h f32 train loss '
          'within %g of the reference' % NEMOTRON_TRAIN_LOSS_RTOL)
    check(abs(low - want_loss) / want_loss > NEMOTRON_TRAIN_LOSS_RTOL,
          'the reference in bfloat16 throughout misses it')
    far = 0.0
    for label, x in zip(sampled, grads):
        y = np.asarray(want_grads[label])
        d = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        far = max(far, d)
        say('gradient of %s %s: largest entry difference %.3e of the '
            'largest entry (%.3e), relative L2 distance %.3e'
            % (label, x.shape, np.abs(x - y).max() / np.abs(y).max(),
               np.abs(y).max(), d))
    check(far <= NEMOTRON_L2_RTOL,
          'nemotron_h gradients: all %d sampled tensors within %g of the '
          'reference\'s, relative L2 distance (worst %.3e)'
          % (len(sampled), NEMOTRON_L2_RTOL, far))


def _nemotron_cell():
    """The benchmark's cell, as its harness finds it."""
    from benchmark import run as harness
    return harness.Cell(harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'BENCHMARK.json')),
        'nemotron3_nano_30b_s8192')


def _nemotron_cell_losses(seed):
    """The cell's programs as the harness builds them, on the startup
    state, over NEMOTRON_LOSS_BATCHES batches: (a) the harness's OWN
    reference check, each batch under the number and tolerance the
    family's rule gives it; (b) what the rule reads at each of
    NEMOTRON_MARGINS, beside where the program lies: the margin from
    which the span holds the program's loss says how near a tie the
    choice was that it took the other way; (c) the control, the
    reference in bfloat16 throughout, put through the same comparison:
    it has to be refused on EVERY batch."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import run as harness
    cell = _nemotron_cell()
    config, traffic, family = cell.config, cell.traffic, cell.family
    hosts = [family.batch(config, traffic, 1, s)
             for s in range(seed, seed + NEMOTRON_LOSS_BATCHES)]
    plain = jax.jit(lambda w, f, margin: family.reference_readings(
        config, traffic, w, f, tie_margin=margin))
    low_precision = jax.jit(lambda w, f: family.reference_readings(
        config, traffic, w, f, dtype=jnp.bfloat16)[0])
    with fluid.scope_guard(fluid.Scope()):
        _, startup, test, loss, params = harness.build_programs(cell, seed)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()

        def current():      # a run hands the scope new arrays
            return [fluid.core.as_array(scope.find_var(p))
                    for p in params]

        sound, control, limits, refused = [], [], [], 0
        for i, host in enumerate(hosts):
            check(harness.reference_check(cell, exe, test, loss, params,
                                          host),
                  'the harness\'s reference check, batch seed %d'
                  % (seed + i))
            feed = {k: jnp.asarray(v) for k, v in host.items()}
            got = _scalar(exe.run(test, feed=host, fetch_list=[loss]))
            half = float(low_precision(current(), feed))
            spans = []
            for margin in sorted({family.TIE_MARGIN, *NEMOTRON_MARGINS}):
                want, low, high, undecided = (
                    float(x) for x in plain(current(), feed,
                                            jnp.float32(margin)))
                spans.append('%g: %d, %+.2e %+.2e' % (
                    margin, undecided, low / want, high / want))
                if margin == family.TIE_MARGIN:
                    middle, rtol = family.allowed(want, low, high)
            middle = float(middle)
            limits.append(rtol)
            sound.append(abs(got - middle) / middle / rtol)
            control.append(abs(half - middle) / middle / rtol)
            refused += control[-1] > 1
            say('batch seed %d: reference %.6f; program %+.2e of it, '
                'reference in bfloat16 throughout %+.2e; the number '
                'compared %+.2e, tolerance %.2e: program at %.2f of the '
                'tolerance, bfloat16 at %.2f (%s); undecided choices and '
                'the span by margin: %s'
                % (seed + i, want, (got - want) / want,
                   (half - want) / want, (middle - want) / want, rtol,
                   sound[-1], control[-1],
                   'NOT correct' if control[-1] > 1 else 'correct',
                   '; '.join(spans)))
        say('over %d batches: tolerance %.2e to %.2e; program at most '
            '%.2f of its batch\'s tolerance; bfloat16 control at least '
            '%.2f of its batch\'s, median %.1f, refused on %d'
            % (len(hosts), min(limits), max(limits), max(sound),
               min(control), np.median(control), refused))
        check(max(sound) <= 1, 'nemotron_h f32 for_test loss at the '
              'cell\'s cut within its batch\'s tolerance on every batch')
        check(refused == len(hosts), 'the reference in bfloat16 '
              'throughout is refused on EVERY batch, each under its own '
              'batch\'s tolerance')
        for name in scope.local_var_names():
            scope.erase(name)


# Both paths round both operands of every product to bfloat16 (2^-9
# each) but not at the same place of the algebra, a gradient passes
# through two products and a product sums 128 terms: 4 x 2^-8 of the
# largest entry between the two paths.  Read on the chip (PR 66,
# tools/bench_ssd_scan.py at this shape): the gradients 2.2e-3 to
# 7.5e-3 apart, D's 2e-7.
NEMOTRON_SCAN_BF16_RTOL = 2 ** -6


def _nemotron_scan_arm(seed):
    """The ``ssd_scan`` op ALONE at the cell's own shape ([1, 8192, 64,
    64] over 8 groups of 128 states in chunks of 128) in bfloat16: the
    kernels against the dense path, y and the six gradients, each
    beside its distance from the dense path in float32."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.ops import ssd_ops
    cfg = nemotron_h.BASE
    b, t = 1, 8192
    h, p, g, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.groups, cfg.states
    rng = np.random.RandomState(seed)
    wide = [rng.randn(b, t, h, p), rng.randn(b, t, g, n),
            rng.randn(b, t, g, n), rng.randn(b, t, h, p)]
    # steps 0.001 to 0.1 and rates 1 to 16: Mamba-2's initialisers
    narrow = [jnp.asarray(v, jnp.float32) for v in (
        np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (b, t, h))),
        -rng.uniform(1, 16, (h,)), rng.randn(h))]

    def both(path, dtype):
        x, bm, cm, probe = (jnp.asarray(v, jnp.bfloat16).astype(dtype)
                            for v in wide)
        delta, a, skip = narrow

        def run(*ins):
            out, pull = jax.vjp(
                lambda *v: ssd_ops._scan(*v, cfg.chunk, path), *ins)
            return (out,) + pull(probe)
        return [np.asarray(v, np.float64) for v in jax.jit(run)(
            x, delta, a, bm, cm, skip)]

    exact = both('dense', jnp.float32)
    dense, fused = both('dense', jnp.bfloat16), both('fused', jnp.bfloat16)
    worst = 0.0
    for name, e, d, f in zip(('y', 'dX', 'dDelta', 'dA', 'dB', 'dC', 'dD'),
                             exact, dense, fused):
        top = np.abs(e).max()
        apart = np.abs(f - d).max() / top
        worst = max(worst, apart)
        say('ssd_scan bf16 at the cell\'s shape, %s: fused from dense '
            '%.3e of the largest entry (%.3e); from the float32 dense '
            'path: fused %.3e, dense %.3e'
            % (name, apart, top, np.abs(f - e).max() / top,
               np.abs(d - e).max() / top))
    check(worst <= NEMOTRON_SCAN_BF16_RTOL,
          'ssd_scan in bfloat16 at [1, 8192, 64, 64]: the kernels\' y and '
          'six gradients within %g of the dense path\'s (worst %.3e)'
          % (NEMOTRON_SCAN_BF16_RTOL, worst))


def phase_nemotron_h(seed=0):
    """models.nemotron_h.BASE cut as above: the chunked scan's kernels
    against its dense path in bfloat16 at the cell's shape; loss and
    sampled gradients of the f32 TRAIN program against the reference's
    on one short seeded sequence; then the cell's for_test losses at
    8192 tokens."""
    _nemotron_scan_arm(seed)
    _nemotron_train_step(NEMOTRON_GRAD_SEQ, seed)
    _nemotron_cell_losses(seed)


# --- Ouro-2.6B ----------------------------------------------------------
# Published widths (models.ouro.BASE), one 4096-token sequence, the
# stack applied total_ut_steps = 4 times through ONE While.  Sampled
# gradients of the f32 TRAIN program (the masked scan under the
# whole-program vjp, the flash kernels in float32) against jax.grad of
# the reference: a shared layer's Wq and Wd (each the sum over four
# trips), the last norm, the gate and rows of the head.  At TWO layers:
# the described-chip compile puts the f32 step of the cell's four at
# 18.9 GB and of two at 11.3 GB (PERF.md section 4).  Then the f32
# for_test loss (the lax.while_loop lowering) at the cell's own four
# layers over OURO_LOSS_BATCHES batches against the reference in f32
# and in bfloat16 throughout: the two readings the family's
# REFERENCE_RTOL lies between.
OURO_GRAD_LAYERS = 2
OURO_CELL_LAYERS = 4
OURO_SEQ = 4096
OURO_LOSS_RTOL = 1e-6       # = benchmark/families/ouro.py's
OURO_L2_RTOL = 2e-3         # a gradient tensor's relative L2 distance
OURO_LOSS_BATCHES = 8
OURO_SAMPLED = ('ouro_l0_wq', 'ouro_l0_wd', 'ouro_l0_g2', 'ouro_l1_wq',
                'ouro_l1_wd', 'ouro_g_f', 'ouro_w_head', 'ouro_w_gate',
                'ouro_b_gate')


def _ouro_reference_kw(cfg):
    return dict(layers=cfg.layers, heads=cfg.heads, steps=cfg.steps,
                eps=cfg.rms_eps, theta=cfg.rope_theta,
                beta=cfg.entropy_weight, block=512)


def _ouro_gradients(seq, seed):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import ouro
    from paddle_tpu.models.reference import ouro as reference
    cfg = ouro.OuroConfig(layers=OURO_GRAD_LAYERS)
    feed = _ints32(ouro.synthetic_batch(cfg, 1, seq,
                                        np.random.RandomState(seed)))
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = ouro.build_pretrain(cfg, seq)
            params = [p.name for p in main.all_parameters()]
            pairs = dict((p.name, g.name) for p, g in
                         fluid.optimizer.SGD(0.0).minimize(loss)[1])
        check([op.type for op in main.global_block().ops].count('while')
              == 1 and len(params) == 1 + 11 * cfg.layers + 4,
              'one while op over the stack, each layer\'s parameters once')
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        # the gate's startup weights leave p at (1/2, 1/4, 1/8, 1/8) on
        # every token: spread them, so that its gradient is no rounding
        rng = np.random.RandomState(seed)
        scope.set_var('ouro_w_gate', jnp.asarray(
            rng.randn(cfg.hidden, 1).astype('float32') / 16))
        # host copies first: a run donates the state it may write
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
        fused0 = _fused_dispatches()
        t0 = time.time()
        got = exe.run(main, feed=feed, fetch_list=[loss] + [
            pairs[p] for p in OURO_SAMPLED])
        got_loss = _scalar(got[:1])
        grads = [np.asarray(g) for g in got[1:]]
        say('ouro f32 train program, %d layers x %d passes, 1 x %d '
            'tokens: loss %.6f in %.1f s (with compile); %d flash '
            'dispatches fused; loop/trips %s; exit entropy %s'
            % (cfg.layers, cfg.steps, seq, got_loss, time.time() - t0,
               _fused_dispatches() - fused0,
               monitor.gauge_value('loop/trips', None),
               monitor.gauge_value('ouro/exit_entropy', None)))
        check(monitor.gauge_value('loop/trips', None) == cfg.steps,
              'the loop\'s body ran %d times in the step' % cfg.steps)
        del got
        for n in scope.local_var_names():
            scope.erase(n)
    jfeed = {k: jnp.asarray(v) for k, v in feed.items()}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference.loss_fn(
            w, jfeed['ids'], jfeed['pos_ids'], jfeed['labels'],
            remat=True, **_ouro_reference_kw(cfg))))(
        [jnp.asarray(w) for w in weights])
    want_loss = float(want_loss)
    rel = abs(got_loss - want_loss) / want_loss
    say('reference: loss %.6f; relative difference %.2e'
        % (want_loss, rel))
    check(rel <= OURO_LOSS_RTOL, 'ouro f32 train loss within %g of the '
          'reference' % OURO_LOSS_RTOL)
    worst = 0.0
    for name, x in zip(OURO_SAMPLED, grads):
        y = np.asarray(want[params.index(name)])
        e = float(np.abs(x - y).max() / np.abs(y).max())
        l2 = float(np.linalg.norm(x - y) / np.linalg.norm(y))
        worst = max(worst, l2)
        say('gradient of %s %s: largest entry difference %.3e of the '
            'largest entry (%.3e), relative L2 distance %.3e'
            % (name, x.shape, e, np.abs(y).max(), l2))
    check(worst <= OURO_L2_RTOL, 'ouro gradients: every sampled '
          'parameter within %g relative L2 of the reference\'s (worst '
          '%.3e)' % (OURO_L2_RTOL, worst))


def _ouro_cell_losses(seq, seed):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ouro
    from paddle_tpu.models.reference import ouro as reference
    cfg = ouro.OuroConfig(layers=OURO_CELL_LAYERS)
    feeds = [_ints32(ouro.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(s)))
        for s in range(seed, seed + OURO_LOSS_BATCHES)]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = ouro.build_pretrain(cfg, seq)
            test = main.clone(for_test=True)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p.name)))
                   for p in main.all_parameters()]
        program = [_scalar(exe.run(test, feed=f, fetch_list=[loss]))
                   for f in feeds]
        for n in scope.local_var_names():
            scope.erase(n)
    weights = [jnp.asarray(w) for w in weights]
    both = jax.jit(lambda w, f: [
        reference.loss_fn(w, f['ids'], f['pos_ids'], f['labels'], dtype=dt,
                          **_ouro_reference_kw(cfg))
        for dt in (jnp.float32, jnp.bfloat16)])
    off, low = [], []
    for n, (feed, got) in enumerate(zip(feeds, program)):
        full, half = (float(x) for x in both(
            weights, {k: jnp.asarray(v) for k, v in feed.items()}))
        off.append(abs(got - full) / full)
        low.append(abs(half - full) / full)
        say('%d layers x %d passes, batch seed %d: program %.6f, '
            'reference %.6f (relative difference %.2e), reference in '
            'bfloat16 throughout %.6f (%.2e)'
            % (cfg.layers, cfg.steps, seed + n, got, full, off[-1], half,
               low[-1]))
    say('over %d batches: f32 for_test program against the reference, '
        'relative: median %.2e, largest %.2e; reference in bfloat16 '
        'throughout: smallest %.2e, median %.2e, largest %.2e'
        % (len(off), np.median(off), max(off), min(low), np.median(low),
           max(low)))
    check(max(off) <= OURO_LOSS_RTOL, 'ouro f32 for_test loss at the '
          'cell\'s cut within %g of the reference on every batch'
          % OURO_LOSS_RTOL)
    check(min(low) > OURO_LOSS_RTOL, 'the reference in bfloat16 '
          'throughout misses %g on every batch' % OURO_LOSS_RTOL)


def phase_ouro(seed=0):
    _ouro_gradients(OURO_SEQ, seed)
    _ouro_cell_losses(OURO_SEQ, seed)


# --- Xing4.0-29B-A4B ----------------------------------------------------
# Published widths (models.xing4.BASE), one 4096-token sequence.  (1)
# The hyper-connection ops ALONE at [4096, 4, 3584]: float32 forward
# and all five gradients against jax.grad of the reference's equations
# on 512 tokens (H_res's trips through the sinkhorn kernels, dispatch
# checked), then a bfloat16 stream's forward and forward +
# backward timed against the hand count
# (benchmark/lib/xing_flops.py mhc_train_cost) and the largest distance
# of H_res from the doubly stochastic matrices.  (2) Sampled gradients
# of the f32 TRAIN program (the executor's one vjp through the recompute
# groups; the flash kernels in float32) at the dense layer, ONE sparse
# layer and the module against jax.grad of the reference (the cell's
# check sees the for_test forward only).  (3) The harness's own
# reference check at the cell's cut over XING4_LOSS_BATCHES batches,
# each under the tolerance the family's rule gives it, the reference in
# bfloat16 throughout put through the same comparison (the two readings
# the tolerance lies between), what zeroing one of phi's three blocks or
# cutting the Sinkhorn loop short moves that loss by, and
# mhc/stochastic_err over as many train steps as a window makes.  (4)
# What the compiler says
# the cell's bf16 AMP train step holds with and without the recompute
# groups.
XING4_SEQ = 4096
XING4_GRAD_LAYERS = 2
XING4_LOSS_RTOL = 1e-5      # the f32 train loss: a router's one undecided
                            # choice moves it by up to 4e-6
XING4_WINDOW_BLOCKS = 12    # 132 train steps: a window run makes ~105
XING4_L2_RTOL = 2e-3        # a gradient tensor's relative L2 distance
XING4_LOSS_BATCHES = 6
XING4_SAMPLED = ('xing4_embedding', 'hyper_connection_pre_0.w_0',
                 'hyper_connection_pre_0.w_1', 'hyper_connection_pre_0.w_2',
                 'hyper_connection_pre_3.w_0', 'hyper_connection_pre_3.w_1',
                 'hyper_connection_pre_3.w_2', 'fc_0.w_0', 'rms_norm_1.w_0',
                 'fc_1.w_0', 'xing4_g_final', 'xing4_w_head')


def _xing4_cell():
    """The benchmark's cell, as its harness finds it."""
    from benchmark import run as harness
    return harness.Cell(harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'BENCHMARK.json')),
        'xing4_29b_s4096')


def _xing4_cut(layers=None):
    cell = _xing4_cell()
    cfg = cell.family._zoo_config(cell.config, cell.traffic)
    if layers:
        cfg.layers = layers
    return cfg


def _xing4_single_op(seed=0, tokens=XING4_SEQ, n=4, c=3584):
    import jax
    import jax.numpy as jnp
    from benchmark.lib import xing_flops
    from paddle_tpu.models.reference import xing4 as reference
    from paddle_tpu.ops import registry
    sizes = reference.sizes_of(_xing4_cut())
    rng = np.random.RandomState(seed)
    m = n * n + 2 * n
    ctx = registry.LowerCtx(0)
    attrs = {'sinkhorn_iters': 20, 'epsilon': 1e-6, 'hc_eps': 1e-6,
             'clamp_min': -30.0, 'clamp_max': 30.0}

    def ops(x, y, phi, alpha, b):
        pre = registry.get('hyper_connection_pre').fn(
            ctx, {'X': [x], 'Phi': [phi], 'Alpha': [alpha], 'Bias': [b]},
            attrs)
        out = registry.get('hyper_connection_post').fn(
            ctx, {'X': [x], 'Y': [y], 'HPost': pre['HPost'],
                  'HRes': pre['HRes']}, {})
        return pre['U'][0], out['XOut'][0], pre['Err'][0]

    def want(x, y, phi, alpha, b):
        h_pre, h_post, h_res = reference.hyper_maps(x, phi, alpha, b,
                                                    sizes)
        return (jnp.einsum('btn,btnc->btc', h_pre, x),
                jnp.einsum('btij,btjc->btic', h_res, x) +
                h_post[..., None] * y[:, :, None, :])

    def inputs(t, dtype):
        bias = np.zeros((m,), 'float32')
        bias[2 * n:] = np.eye(n).ravel()
        return (jnp.asarray(rng.randn(1, t, n, c), dtype),
                jnp.asarray(rng.randn(1, t, c), dtype),
                jnp.asarray(rng.randn(n * c, m) / np.sqrt(n * c),
                            jnp.float32),
                jnp.full((3,), 0.5, jnp.float32), jnp.asarray(bias))

    small = inputs(512, jnp.float32)
    w_u = jnp.asarray(rng.randn(1, 512, c), jnp.float32)
    w_x = jnp.asarray(rng.randn(1, 512, n, c), jnp.float32)

    def scalar(fn):
        def f(*args):
            u, out = fn(*args)[:2]
            return jnp.sum(u * w_u) + jnp.sum(out * w_x)
        return f

    with jax.default_matmul_precision('highest'):
        got = jax.jit(jax.grad(scalar(ops), (0, 1, 2, 3, 4)))(*small)
        ref = jax.jit(jax.grad(scalar(want), (0, 1, 2, 3, 4)))(*small)
        u, out, _ = jax.jit(ops)(*small)
        want_u, want_out = jax.jit(want)(*small)
    off = {'U': float(jnp.abs(u - want_u).max() / jnp.abs(want_u).max()),
           'XOut': float(jnp.abs(out - want_out).max() /
                         jnp.abs(want_out).max())}
    for name, a, b in zip(('dX', 'dY', 'dPhi', 'dAlpha', 'dBias'), got,
                          ref):
        off[name] = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    say('hyper-connection ops alone, float32, 512 tokens x %d x %d, '
        'against the reference: %s'
        % (n, c, ', '.join('%s %.2e' % kv for kv in off.items())))
    check(max(off.values()) <= 1e-4, 'the ops and all five gradients '
          'within 1e-4 of the reference at the published width')
    check_dispatch(['sinkhorn'])    # H_res's trips ran the kernels

    big = inputs(tokens, jnp.bfloat16)
    forward = jax.jit(ops)
    both = jax.jit(lambda *a: jax.grad(
        lambda *a: sum(jnp.sum(o.astype(jnp.float32))
                       for o in ops(*a)[:2]), (0, 1, 2, 3, 4))(*a))
    err = float(forward(*big)[2][0])
    fwd_s, both_s = _timed(forward, *big), _timed(both, *big)
    flop, byte = xing_flops.mhc_train_cost(tokens, n, c)
    fwd_bytes = tokens * c * 2 * (3 * n + 2)
    say('hyper-connection ops alone, bfloat16 stream [%d, %d, %d]: '
        'forward %.3f ms (%.0f GB/s of the hand count\'s %.1f MB), '
        'forward + backward %.3f ms (%.0f GB/s of %.1f MB); '
        'mhc/stochastic_err %.2e'
        % (tokens, n, c, fwd_s * 1e3, fwd_bytes / fwd_s / 1e9,
           fwd_bytes / 1e6, both_s * 1e3, byte / both_s / 1e9,
           byte / 1e6, err))
    check(err < 1e-3, 'H_res within 1e-3 of doubly stochastic on every '
          'token')


def _xing4_gradients(seq, seed):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import xing4
    from paddle_tpu.models.reference import xing4 as reference
    cfg = _xing4_cut(XING4_GRAD_LAYERS)
    sizes = reference.sizes_of(cfg)
    rng = np.random.RandomState(seed)
    feed = _ints32(xing4.mtp_batch(
        rng.randint(0, cfg.vocab_size, (1, seq))))
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = xing4.build_pretrain(cfg, seq)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        pairs = dict((p.name, g.name) for p, g in
                     fluid.optimizer.SGD(0.0).minimize(loss)[1])
    missing = [n for n in XING4_SAMPLED if n not in pairs]
    check(not missing, 'the sampled parameters exist: %s' % missing)
    rows = np.unique(feed['ids'])[:64]
    before = _fused_dispatches()

    def mixes():    # (hyper_connection_pre lowerings, of them fused)
        return [monitor.counter_value(name) for name in (
            'mhc/calls', 'pallas/sinkhorn/dispatch_fused')]

    mixes_before = mixes()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights, bias_values = (
            [np.asarray(fluid.core.as_array(scope.find_var(n)))
             for n in names] for names in (params, biases))
        t0 = time.time()
        got = exe.run(main, feed=feed, fetch_list=[loss] + [
            pairs[n] for n in XING4_SAMPLED])
        lowered, fused = (now - was for now, was in
                          zip(mixes(), mixes_before))
        check(lowered == fused > 0, 'every hyper_connection_pre lowering '
              'ran the sinkhorn kernels (%d lowerings, %d fused dispatches)'
              % (lowered, fused))
        say('xing4 f32 train program, %d main layers + the module, 1 x '
            '%d tokens: loss %.6f in %.1f s; mhc/stochastic_err %.2e, '
            'mtp/loss %.4f, mtp/loss_share %.4f; %d fused dispatches'
            % (cfg.layers, seq, _scalar(got[:1]), time.time() - t0,
               monitor.gauge_value('mhc/stochastic_err'),
               monitor.gauge_value('mtp/loss'),
               monitor.gauge_value('mtp/loss_share'),
               _fused_dispatches() - before))
        got = [np.asarray(g) for g in got]
        for n in scope.local_var_names():
            scope.erase(n)
    index = {n: params.index(n) for n in XING4_SAMPLED}
    ids, pos, labels, labels_mtp = (jnp.asarray(feed[k]) for k in (
        'ids', 'pos_ids', 'labels', 'labels_mtp'))

    def ref_loss(some, full, biases):
        full = list(full)
        for name, w in some.items():
            full[index[name]] = w
        return reference.loss(full, biases, ids, pos, labels, labels_mtp,
                              sizes=sizes, remat=True)

    weights = [jnp.asarray(w) for w in weights]
    want_loss, want = jax.jit(jax.value_and_grad(ref_loss))(
        {n: weights[index[n]] for n in XING4_SAMPLED}, weights,
        [jnp.asarray(b) for b in bias_values])
    off = abs(got[0].ravel()[0] - float(want_loss)) / float(want_loss)
    say('xing4 f32 train program against the reference: loss relative '
        'difference %.2e' % off)
    check(off <= XING4_LOSS_RTOL, 'xing4 train loss within %g'
          % XING4_LOSS_RTOL)
    worst = 0.0
    for name, g in zip(XING4_SAMPLED, got[1:]):
        w = np.asarray(want[name])
        if name == 'xing4_embedding':
            g, w = g[rows], w[rows]
        rel = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        worst = max(worst, rel)
        say('  d loss / d %-28s |g| %.3e, relative L2 distance %.2e'
            % (name, np.linalg.norm(w), rel))
    check(worst <= XING4_L2_RTOL, 'every sampled gradient within %g '
          '(relative L2) of jax.grad of the reference' % XING4_L2_RTOL)


def _xing4_cell_losses(seq, seed):
    """The cell's programs as the harness builds them, on the startup
    state: (a) the harness's OWN reference check over
    XING4_LOSS_BATCHES batches, each with the tolerance the family's
    rule gives that batch; (b) the control, the reference in bfloat16
    throughout, put through the same comparison; (c) what the loss
    moves by when one block of phi is zero and when the Sinkhorn loop
    is cut short; (d) ``mhc/stochastic_err`` and the module's share of
    the loss over XING4_WINDOW_BLOCKS blocks of train steps on the one
    fixed sequence, as many as a window run makes."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import run as harness
    from paddle_tpu.fluid import monitor
    cell = _xing4_cell()
    config, traffic, family = cell.config, cell.traffic, cell.family
    cfg = _xing4_cut()
    n, m = cfg.hc_mult, cfg.hc_mult ** 2 + 2 * cfg.hc_mult
    hosts = [family.batch(config, traffic, 1, s)
             for s in range(seed, seed + XING4_LOSS_BATCHES)]

    def readings(dtype=None, **changed):
        c = dict(config, **changed)
        return jax.jit(lambda w, f: family.reference_readings(
            c, traffic, w, f, dtype=dtype))

    def within(got, want, rtol):        # benchmark/run.py's comparison
        return abs(got - want) <= rtol * abs(want)

    with fluid.scope_guard(fluid.Scope()):
        main, startup, test, loss, params = harness.build_programs(
            cell, seed)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()

        def current():      # a run hands the scope new arrays
            return [fluid.core.as_array(scope.find_var(p))
                    for p in params]

        plain, low = readings(), readings(jnp.bfloat16)
        sound, control, limits, refused = [], [], [], 0
        for i, host in enumerate(hosts):
            check(harness.reference_check(cell, exe, test, loss, params,
                                          host),
                  'the harness\'s reference check, batch seed %d'
                  % (seed + i))
            feed = {k: jnp.asarray(v) for k, v in host.items()}
            want, moved, undecided = (float(x) for x in
                                      plain(current(), feed))
            got = _scalar(exe.run(test, feed=host, fetch_list=[loss]))
            half = float(low(current(), feed)[0])
            limits.append(family.allowed(want, moved))
            sound.append(abs(got - want) / want)
            control.append(abs(half - want) / want)
            refused += not within(half, want, limits[-1])
            say('batch seed %d: %d undecided choices move the '
                'reference\'s loss by %.2e: tolerance %.2e; program '
                '%.2e; reference in bfloat16 throughout %.2e (%s)'
                % (seed + i, undecided, moved / want, limits[-1],
                   sound[-1], control[-1],
                   'correct' if within(half, want, limits[-1])
                   else 'NOT correct'))
        say('over %d batches: tolerance %.2e to %.2e; program against '
            'the reference largest %.2e; bfloat16 control smallest '
            '%.2e, median %.2e, largest %.2e, refused on %d'
            % (len(hosts), min(limits), max(limits), max(sound),
               min(control), np.median(control), max(control), refused))
        # (c) on the first batch, against the largest tolerance seen
        feed = {k: jnp.asarray(v) for k, v in hosts[0].items()}
        base = float(plain(current(), feed)[0])
        cut = {k: abs(float(readings(hc_sinkhorn_iters=k)(
            current(), feed)[0]) - base) / base for k in (19, 8, 5, 3)}
        say('a Sinkhorn loop of k normalisations instead of 20 moves '
            'the reference\'s loss by: %s' % ', '.join(
                'k=%d %.2e' % kv for kv in cut.items()))
        got = _scalar(exe.run(test, feed=hosts[0], fetch_list=[loss]))
        phis = [(p, np.asarray(w)) for p, w in zip(params, current())
                if w.shape == (n * cfg.hidden, m)]
        moved = {}
        for tag, columns in (('H_pre\'s block', slice(0, n)),
                             ('H_post\'s block', slice(n, 2 * n)),
                             ('H_res\'s block', slice(2 * n, None))):
            for p, w in phis:
                w = w.copy()
                w[:, columns] = 0
                scope.set_var(p, jnp.asarray(w))
            moved[tag] = abs(_scalar(exe.run(
                test, feed=hosts[0], fetch_list=[loss])) - got) / got
        for p, w in phis:
            scope.set_var(p, jnp.asarray(w))
        say('zeroing one block of phi moves the for_test loss by: %s '
            '(largest tolerance %.2e)' % (', '.join(
                '%s %.2e' % kv for kv in moved.items()), max(limits)))
        # (d) the window's steps
        target, placed = cell.layout.place(main, loss, jax.devices()[:1],
                                           hosts[0])
        runner = harness.Runner(cell, exe, target, placed, loss)
        worst = 0.0
        for block in range(XING4_WINDOW_BLOCKS):
            value = runner.block()
            flat = monitor.flat()
            worst = max(worst, flat['mhc/stochastic_err'])
            say('after %3d train steps: loss %.4f, mhc/stochastic_err '
                '%.2e, mtp/loss_share %.4f'
                % ((block + 1) * traffic['steps_per_block'], value,
                   flat['mhc/stochastic_err'], flat['mtp/loss_share']))
        # every reading is out before a check can stop the phase
        check(refused == len(hosts), 'the comparison refuses the '
              'bfloat16 control on every batch')
        check(min(moved.values()) > family.BASE_RTOL,
              'zeroing any of phi\'s three blocks moves the loss by more '
              'than BASE_RTOL (against this batch\'s own tolerance, '
              '%.2e: %s)' % (limits[0], ', '.join(
                  '%s %s' % (tag, 'over' if v > limits[0] else 'UNDER')
                  for tag, v in moved.items())))
        check(cut[3] > family.BASE_RTOL, 'a loop of three normalisations '
              'misses the tolerance')
        check(worst < 1e-3, 'mhc/stochastic_err under 1e-3 over the '
              'window\'s steps (largest %.2e)' % worst)
        for n_ in scope.local_var_names():
            scope.erase(n_)


def _xing4_step_memory(seed=0):
    """What the compiler says the cell's bf16 AMP train step holds, as
    the model groups it and with the ``__recompute__`` marks taken
    off."""
    import jax
    import paddle_tpu.fluid as fluid
    from benchmark import run as harness
    from paddle_tpu.fluid import memviz
    cell = _xing4_cell()
    host = cell.family.batch(cell.config, cell.traffic, 1, seed)
    for grouped in (True, False):
        main, startup, _, loss, _ = harness.build_programs(cell, seed)
        if not grouped:
            for op in main.global_block().ops:
                op.attrs.pop('__recompute__', None)
        try:
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                step = exe.compile(main, feed_names=sorted(host),
                                   fetch_names=[loss.name])
                scope = fluid.global_scope()

                def spec(a):
                    return jax.ShapeDtypeStruct(a.shape, a.dtype)

                state = {n: spec(fluid.core.as_array(scope.find_var(n)))
                         for n in step.state_names}
                data = {n: spec(host[n]) if n in host else spec(
                    fluid.core.as_array(scope.find_var(n)))
                    for n in step.input_names}
                for n in scope.local_var_names():
                    scope.erase(n)
            compiled = jax.jit(step.fn, donate_argnums=(1,)).lower(
                jax.ShapeDtypeStruct((), np.int32), state, data).compile()
            fields = memviz.analysis_fields(compiled)
            say('the cell\'s train step %s recompute groups, by the '
                'compiler: %s' % ('WITH' if grouped else 'WITHOUT',
                                  json.dumps(fields, sort_keys=True)))
        except Exception as e:      # the chip's compiler refuses it
            say('the cell\'s train step %s recompute groups does not '
                'compile: %s: %s' % ('WITH' if grouped else 'WITHOUT',
                                     type(e).__name__, str(e)[:400]))
            check(not grouped, 'the grouped step compiles')


def phase_xing4(seed=0):
    _xing4_single_op(seed)
    _xing4_gradients(XING4_SEQ, seed)
    _xing4_cell_losses(XING4_SEQ, seed)
    _xing4_step_memory(seed)


# --- Phi-4-mini-flash ---------------------------------------------------
# Published widths (models.phi4flash.BASE but for the cell's cut: 8
# layers, 25008 rows), one 8192-token sequence.  (1) The f32 TRAIN
# program's loss and sampled gradients (the executor's one vjp through
# the recompute groups, the scan's custom_vjp, the two-width grouped
# flash kernels in float32, banded and full) against jax.grad of the
# reference with every layer recomputed and its attention 512 queries
# at a time: a parameter of every kind of layer, among them the
# memory's Mamba (layer 4: what reaches it through the gated memory
# unit) and layer 5's Wqkv (its K and V columns: through the cross
# layer).  (2) The same program under bf16 AMP against the same float32
# reference.  (3) The harness's OWN reference check at the cell's cut
# over PHI4_LOSS_BATCHES batches: the reference in f32, in bfloat16
# throughout, with a bfloat16 scan state alone, and with a part left
# out: the readings the family's REFERENCE_RTOL lies between.
PHI4_SEQ = 8192
PHI4_LOSS_RTOL = 1e-6       # = benchmark/families/phi4flash.py's
# a gradient tensor's relative L2 distance, float32: the order of sums
# through eight layers; the first chip run read 1.0e-6 to 6.7e-5 over
# the eighteen tensors (my chip run, PR 56), the limit has 7 times of
# room over the worst
PHI4_L2_RTOL = 5e-4
# under bf16 AMP against the float32 reference: bfloat16 operands in
# every matmul and flash call, eight layers deep (a bf16 place is
# 4e-3).  The loss read 1.25e-5; the tensors 2.3e-3 to 5.0e-2, median
# 3.8e-2 (the same run): about twice and three times of room
PHI4_AMP_LOSS_RTOL = 1e-4
PHI4_AMP_L2_MEDIAN = 0.08
PHI4_AMP_L2_RTOL = 0.15
# the controls that have to miss the family's limit on EVERY batch; the
# others move the loss by a signed sum that can land near zero (a
# window of 511 drops one key of 512 a query: 2.7e-7 on one batch of
# six, 1.7e-5 to 5.1e-5 on the others): they have to miss it in the
# median, and the phase says on how many batches they did
PHI4_ALWAYS = ('bfloat16 throughout', 'no D * x')
PHI4_LOSS_BATCHES = 6
PHI4_SAMPLED = (
    'phi4flash.embed_tokens',
    'phi4flash.0.mamba.conv_b', 'phi4flash.0.mamba.w_x',
    'phi4flash.0.mamba.b_dt', 'phi4flash.0.mamba.a_log',
    'phi4flash.0.mamba.d',
    'phi4flash.1.sliding_attention.wqkv',
    'phi4flash.1.sliding_attention.lq1',
    'phi4flash.1.sliding_attention.subln_g',
    'phi4flash.3.mlp.w2',
    'phi4flash.4.mamba.w_x', 'phi4flash.4.mamba.w_dt',
    'phi4flash.5.full_attention.wqkv', 'phi4flash.5.full_attention.lk2',
    'phi4flash.6.gmu.w_in', 'phi4flash.7.cross_attention.wq',
    'phi4flash.7.cross_attention.bo', 'phi4flash.ln_f.b')


def _phi4_cell():
    """The benchmark's cell, as its harness finds it."""
    from benchmark import run as harness
    return harness.Cell(harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'BENCHMARK.json')),
        'phi4_mini_flash_s8192')


def _phi4_train(cfg, seq, seed, feed, amp):
    """One step of the train program (SGD at lr 0) -> (loss, sampled
    gradients, weights by name)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import phi4flash
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 1 + seed
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = phi4flash.build_pretrain(cfg, seq)
            params = [p.name for p in main.all_parameters()]
            optimizer = fluid.optimizer.SGD(0.0)
            if amp:
                optimizer = fluid.contrib.mixed_precision.decorate(
                    optimizer, use_dynamic_loss_scaling=False,
                    init_loss_scaling=1.0)
            pairs = dict((p.name, g.name)
                         for p, g in optimizer.minimize(loss)[1])
        check(params == phi4flash.parameter_names(cfg),
              'the program creates the parameters its specs list, in '
              'their order')
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        # host copies first: a run donates the state it may write
        weights = {p: np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params}
        fused0, scans0 = _fused_dispatches(), \
            monitor.flat().get('ssm/calls', 0)

        def scan_dispatches():
            return [monitor.counter_value('pallas/ssm_scan/dispatch_' + k)
                    or 0 for k in ('fused', 'dense')]

        walks0 = scan_dispatches()
        t0 = time.time()
        got = exe.run(main, feed=feed, fetch_list=[loss] + [
            pairs[p] for p in PHI4_SAMPLED])
        got_loss = _scalar(got[:1])
        grads = [np.asarray(g, np.float32) for g in got[1:]]
        fused = _fused_dispatches() - fused0
        say('phi4flash %s train program, %d layers, 1 x %d tokens: loss '
            '%.6f in %.1f s (with compile); %d kernel dispatches fused; '
            'ssm/calls +%d, ssm/chunks %s, ssm/boundary_state_mb %s; '
            'peak HBM %.2f GB'
            % ('bf16 AMP' if amp else 'f32', cfg.layers, seq, got_loss,
               time.time() - t0, fused,
               monitor.flat().get('ssm/calls', 0) - scans0,
               monitor.gauge_value('ssm/chunks', None),
               monitor.gauge_value('ssm/boundary_state_mb', None),
               _peak_bytes(jax.devices()[:1])[0] / 1e9))
        check(fused >= 4, 'the four differential calls (two windowed, '
              'the full one, the cross one) ran the flash kernels (%d '
              'dispatches fused)' % fused)
        walks = [now - was for now, was in zip(scan_dispatches(), walks0)]
        check(walks[0] >= 3 and walks[1] == 0, 'every lowering of the '
              'selective scan took the ssm_scan kernels (%d fused, %d '
              'dense)' % tuple(walks))
        chunks = -(-seq // 256)
        check(monitor.gauge_value('ssm/chunks', None) == 3 * 3 * chunks,
              'three Mamba layers: a forward, a recomputed forward and '
              'a reverse walk of %d chunks each' % chunks)
        del got
        for n in scope.local_var_names():
            scope.erase(n)
    return got_loss, grads, weights


def _phi4_gradients(seq, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import phi4flash
    from paddle_tpu.models.reference import phi4flash as reference
    cell = _phi4_cell()
    cfg = cell.family._zoo_config(cell.config, cell.traffic)
    feed = _ints32(phi4flash.synthetic_batch(
        cfg, 1, seq, np.random.RandomState(seed)))
    f32_loss, f32_grads, weights = _phi4_train(cfg, seq, seed, feed, False)
    amp_loss, amp_grads, _ = _phi4_train(cfg, seq, seed, feed, True)
    sizes = reference.sizes_of(cfg)
    rest = {k: jnp.asarray(v) for k, v in weights.items()
            if k not in PHI4_SAMPLED}
    ids, labels = jnp.asarray(feed['ids']), jnp.asarray(feed['labels'])
    # the other weights go in as an ARGUMENT: closed over they would be
    # 3.6 GB of constants in the program (the first chip run's compile
    # met the machine's 40 GiB)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda sampled, rest: reference.loss(
            dict(rest, **sampled), ids, labels, sizes=sizes, remat=True,
            block=512)))({k: jnp.asarray(weights[k]) for k in PHI4_SAMPLED},
                         rest)
    want_loss = float(want_loss)
    for tag, got_loss, grads, loss_rtol, l2_rtol in (
            ('f32', f32_loss, f32_grads, PHI4_LOSS_RTOL, PHI4_L2_RTOL),
            ('bf16 AMP', amp_loss, amp_grads, PHI4_AMP_LOSS_RTOL,
             PHI4_AMP_L2_RTOL)):
        rel = abs(got_loss - want_loss) / want_loss
        say('%s: program loss %.6f, reference %.6f, relative difference '
            '%.2e' % (tag, got_loss, want_loss, rel))
        distances = []
        for name, x in zip(PHI4_SAMPLED, grads):
            y = np.asarray(want[name])
            e = float(np.abs(x - y).max() / np.abs(y).max())
            l2 = float(np.linalg.norm(x - y) / np.linalg.norm(y))
            distances.append(l2)
            say('%s gradient of %s %s: largest entry difference %.3e of '
                'the largest entry (%.3e), relative L2 distance %.3e'
                % (tag, name, x.shape, e, np.abs(y).max(), l2))
        check(rel <= loss_rtol, 'phi4flash %s train loss within %g of '
              'the reference' % (tag, loss_rtol))
        check(max(distances) <= l2_rtol, 'phi4flash %s gradients: every '
              'sampled parameter within %g relative L2 of the '
              'reference\'s (worst %.3e, median %.3e)'
              % (tag, l2_rtol, max(distances), np.median(distances)))
        if tag != 'f32':
            check(np.median(distances) <= PHI4_AMP_L2_MEDIAN,
                  'their median within %g' % PHI4_AMP_L2_MEDIAN)


def _phi4_cell_losses(seed):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import run as harness
    cell = _phi4_cell()
    family = cell.family
    controls = (('bfloat16 throughout', dict(dtype=jnp.bfloat16)),
                ('bfloat16 scan state', dict(state_dtype=jnp.bfloat16)),
                ('no D * x', dict(without=('skip',))),
                ('lambdas at lam0', dict(without=('lambda',))),
                ('window of 511', dict(without=('window_511',))))
    with fluid.scope_guard(fluid.Scope()):
        _, startup, test, loss, params = harness.build_programs(cell, seed)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        scope = fluid.global_scope()
        weights = [fluid.core.as_array(scope.find_var(p)) for p in params]
        reference = jax.jit(lambda w, f: [
            family.reference_loss(cell.config, cell.traffic, w, f, **kw)
            for _, kw in (('float32', {}),) + controls])
        off, low = [], {name: [] for name, _ in controls}
        for batch_seed in range(seed, seed + PHI4_LOSS_BATCHES):
            feed = {k: jax.device_put(v) for k, v in family.batch(
                cell.config, cell.traffic, 1, batch_seed).items()}
            got = _scalar(exe.run(test, feed=feed, fetch_list=[loss]))
            full, *others = (float(x) for x in reference(weights, feed))
            off.append(abs(got - full) / full)
            for (name, _), other in zip(controls, others):
                low[name].append(abs(other - full) / full)
            say('cell cut, batch seed %d: program %.6f, reference %.6f '
                '(relative difference %.2e); %s'
                % (batch_seed, got, full, off[-1], '; '.join(
                    '%s %.2e' % (name, low[name][-1])
                    for name, _ in controls)))
        for n in scope.local_var_names():
            scope.erase(n)
    rtol = family.REFERENCE_RTOL
    say('over %d batches: f32 for_test program against the reference, '
        'relative: median %.2e, largest %.2e; %s'
        % (len(off), np.median(off), max(off), '; '.join(
            '%s: smallest %.2e, median %.2e, largest %.2e'
            % (name, min(v), np.median(v), max(v))
            for name, v in low.items())))
    check(max(off) <= rtol, 'phi4flash f32 for_test loss at the cell\'s '
          'cut within %g of the reference on every batch' % rtol)
    for name, v in low.items():
        missed = sum(x > rtol for x in v)
        if name in PHI4_ALWAYS:
            check(missed == len(v), 'the reference with %s misses %g on '
                  'every batch' % (name, rtol))
        else:
            check(np.median(v) > rtol, 'the reference with %s misses %g '
                  'in the median (on %d of %d batches)'
                  % (name, rtol, missed, len(v)))


def phase_phi4flash(seed=0):
    _phi4_gradients(PHI4_SEQ, seed)
    _phi4_cell_losses(seed)


def phase_grouped_matmul(seed=0, units=2.0):
    """The three forms of ops/pallas/grouped_matmul.py in bfloat16 at
    the five routed cells' shapes against ``jax.lax.ragged_dot`` and
    its two transposes on the same operands: uneven groups with an
    empty one, the rows past the last group NaN in both inputs.  Every
    row inside a group and every weight gradient has to lie within
    ``units`` of the last bfloat16 place of the result's largest entry
    (both sides round a float32 sum of the same bfloat16 products)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    rng = np.random.RandomState(seed)
    for name, (m, e, k, n, live) in GROUPED_SHAPES.items():
        share = rng.uniform(0.3, 1.7, e)
        share[rng.randint(e)] = 0
        sizes = np.floor(live * share / share.sum()).astype(np.int32)
        sizes[-1] += live - sizes.sum()
        rows = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        cot = jnp.asarray(rng.randn(m, n), jnp.bfloat16)
        w = jnp.asarray(rng.randn(e, k, n) / 32, jnp.bfloat16)
        sizes = jnp.asarray(sizes)

        def dense(rows, w, cot):
            out, pull = jax.vjp(
                lambda r, w: jax.lax.ragged_dot(r, w, sizes), rows, w)
            return (out,) + pull(cot)

        def ours(rows, w, cot):
            walk = gm.visits(sizes, m)
            return (gm.forward(rows, w, walk), gm.transposed(cot, w, walk),
                    gm.weight_gradient(rows, cot, walk))

        want = jax.jit(dense)(rows.at[live:].set(0), w,
                              cot.at[live:].set(0))
        got = jax.jit(ours)(rows.at[live:].set(jnp.nan), w,
                            cot.at[live:].set(jnp.nan))
        off = []
        for form, a, b in zip(('forward', 'transposed',
                               'weight_gradient'), got, want):
            if form != 'weight_gradient':
                a, b = a[:live], b[:live]
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            check(np.isfinite(a).all(),
                  'grouped %s %s: no NaN row reaches a result'
                  % (name, form))
            off.append(bf16_units(a, b))
            check(off[-1] <= units,
                  'grouped %s %s: %.2f <= %.0f bf16 units from ragged_dot'
                  % (name, form, off[-1], units))
        say('grouped %s [%d, %d, %d, %d] groups %s: forward / transposed '
            '/ weight_gradient %.2f / %.2f / %.2f bf16 units from '
            'ragged_dot' % ((name, m, e, k, n,
                             np.asarray(sizes).tolist() if e <= 8 else
                             '%d..%d' % (int(sizes.min()),
                                         int(sizes.max()))) + tuple(off)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    ap.add_argument('--phase',
                    choices=('bert', 'olmoe', 'laguna', 'moonlight',
                             'lfm2', 'evabyte', 'solar', 'ouro', 'xing4',
                             'phi4flash', 'kimi', 'sdar', 'nemotron_h',
                             'grouped'),
                    default='bert',
                    help="'olmoe' / 'laguna' / 'moonlight' / 'lfm2' / "
                    "'evabyte' / 'solar' / 'ouro' / 'xing4' / 'phi4flash' / 'kimi' / 'sdar' / 'nemotron_h': only that model's "
                    "gradient check; 'grouped': only the grouped-matmul "
                    "kernels against ragged_dot")
    args = ap.parse_args()

    import jax
    import jaxlib
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        sys.exit('chip_smoke.py needs a TPU chip and found none: '
                 "jax.devices()[0].platform is %r" % devs[0].platform)
    if len(devs) < args.chips:
        sys.exit('chip_smoke.py --chips %d: only %d TPU chip(s) attached'
                 % (args.chips, len(devs)))

    from paddle_tpu import models
    from paddle_tpu.fluid import compile_cache
    _listen()
    cache_dir = compile_cache.place_jax_cache()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = 'unknown'
    say('jax %s jaxlib %s libtpu %s; %d x %s; jax cache %s'
        % (jax.__version__, jaxlib.__version__, libtpu_version,
           len(devs), devs[0].device_kind, cache_dir))

    try:
        if args.phase == 'olmoe':
            phase_olmoe_gradients()
        elif args.phase == 'laguna':
            phase_laguna_gradients()
        elif args.phase == 'moonlight':
            phase_moonlight_gradients()
        elif args.phase == 'lfm2':
            phase_lfm2_gradients()
        elif args.phase == 'evabyte':
            phase_evabyte()
        elif args.phase == 'solar':
            phase_solar()
        elif args.phase == 'ouro':
            phase_ouro()
        elif args.phase == 'xing4':
            phase_xing4()
        elif args.phase == 'phi4flash':
            phase_phi4flash()
        elif args.phase == 'kimi':
            phase_kimi()
        elif args.phase == 'sdar':
            phase_sdar()
        elif args.phase == 'nemotron_h':
            phase_nemotron_h()
        elif args.phase == 'grouped':
            phase_grouped_matmul()
        elif args.chips == 4:
            phase_four_chips(
                models.bert.BertConfig(dropout=0.0, attn_dropout=0.0),
                global_batch=16, seq=512, steps=4)
            # bert_base_s2048_dp4's own shape and mask: 2 sequences of
            # 2048 a chip, attention dropout on.  Three steps from this
            # start need not lower the loss (one device read 2.7316
            # 15.0020 5.5147: my chip run, PR 37; the cell's falls over
            # its window): the phase is here for the dispatch
            # counters, the Mosaic calls and the first step's loss
            phase_four_chips(
                models.bert.BertConfig(max_pos=2048, dropout=0.0,
                                       attn_dropout=0.1),
                global_batch=8, seq=2048, steps=3, mp=0, falls=False)
        else:
            seq, batch = 2048, 4
            phase_train_eval_roundtrip(
                models.bert.BertConfig(max_pos=seq, attn_dropout=0.0),
                batch, seq, steps=8)
            phase_attn_dropout(
                models.bert.BertConfig(max_pos=seq, attn_dropout=0.1),
                batch, seq)
            phase_dropout_bits()
            phase_flash_vs_dense()
            phase_lenet()
            say('peak HBM %.2f GB of %.2f GB'
                % (devs[0].memory_stats()['peak_bytes_in_use'] / 1e9,
                   devs[0].memory_stats()['bytes_limit'] / 1e9))
    except SmokeFailure as e:
        sys.exit('chip_smoke FAILED: %s' % e)
    # which backward each flash lowering took (one pass, or dq + dkv)
    # and the most scoped VMEM a call asked Mosaic for
    from paddle_tpu.ops.pallas import common
    say('pallas kernels: %s' % json.dumps(
        common.report().get('kernels', {}), sort_keys=True))
    say('jax compiled %(compiles)d programs; its persistent cache: '
        '%(cache_hits)d hits, %(cache_misses)d misses' % _JAX_EVENTS)
    print(json.dumps({'ok': True, 'device': {
        'platform': devs[0].platform, 'kind': devs[0].device_kind,
        'count': args.chips}}))


if __name__ == '__main__':
    main()
