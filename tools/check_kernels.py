"""Pallas kernel-library gate (the ops/pallas analog of
check_health.py's plane gate).

Every kernel registered in ops/pallas/common.py must honor the
auto-dispatch + dense-fallback contract:

  1. registry hygiene: a documented dense fallback per kernel, and
     the expected library members present (a kernel silently dropped
     from the package import would otherwise vanish without a gate);
  2. parity: each kernel's forced-fused (interpret) path against its
     dense reference on CPU — bitwise where the reference is exact
     (blockwise quantize), tolerance-bounded where the kernel body
     sums in another order (flash attention's online softmax, the
     delta rule's in-chunk scores, the experts' grouped products, the
     hyper-connections' Sinkhorn trips);
  3. observability: every dispatch lands a pallas/<kernel>/dispatch_*
     counter and a last-decision record with a reason, and the
     /statusz pallas section renders them — a silent dense fallback
     cannot masquerade as a fused win in an A/B;
  4. flag hygiene: every FLAGS_pallas_* knob is declared in
     fluid/flags.py and read inside the package (tools/staticcheck.py
     enforces the same rule statically; this re-checks it live).

Run from `make check` (CPU: JAX_PLATFORMS=cpu).
"""

import os
import sys

EXPECTED = ('flash_attention', 'grouped_matmul', 'kda_chunk', 'kda_walk',
            'quant_collective', 'sinkhorn', 'ssd_scan', 'ssm_scan')


def _near(got, want, rtol=2e-5):
    """Within ``rtol`` of ``want``'s largest entry: the measure the
    delta rule's tests hold its paths to (the kernels take the running
    decay as a product and the dense form as a ``cumsum``, two float32
    sums an ulp of |G| apart, and both lie as far from the token
    loop)."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


def main():
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import health, monitor
    from paddle_tpu.fluid.flags import _DEFAULTS
    from paddle_tpu.ops import hyper_connection_ops, kda_ops
    from paddle_tpu.ops.pallas import (common, flash_attention,
                                       quant_collective)

    failures = []

    # -- 1. registry hygiene -----------------------------------------
    ks = common.kernels()
    for name in EXPECTED:
        if name not in ks:
            failures.append('kernel %r not registered' % name)
        elif not ks[name].get('dense_fallback'):
            failures.append('kernel %r has no documented dense '
                            'fallback' % name)
    print('kernels registered: %s' % ', '.join(sorted(ks)))

    # -- 2. parity, forced-fused vs dense ----------------------------
    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(1, 32, 2, 8).astype('float32'))
           for _ in range(3)]

    def attend(q, k, v):
        return jnp.sum(flash_attention.flash_attention(
            q, k, v, causal=True, min_seq=0) ** 2)

    fluid.set_flags({'FLAGS_pallas_force': True})
    fused = jax.value_and_grad(attend, (0, 1, 2))(*qkv)
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = jax.value_and_grad(attend, (0, 1, 2))(*qkv)
    for a, b in zip(jax.tree_util.tree_leaves(fused),
                    jax.tree_util.tree_leaves(dense)):
        if not np.allclose(np.asarray(a), np.asarray(b),
                           rtol=5e-5, atol=5e-5):
            failures.append('flash_attention forward/grad parity')
            break

    # the delta rule's preparation through the kda_chunk kernels
    # against the dense one (dk, dv 128: the kernels' layout; 70
    # tokens: a masked tail; twelve heads a grid step are over the
    # walk's VMEM count, so the chunks are walked by the scan)
    delta = [jnp.asarray(x.astype('float32')) for x in (
        rng.randn(1, 70, 12, 128) / 11, rng.randn(1, 70, 12, 128) / 11,
        rng.randn(1, 70, 12, 128), -rng.uniform(0, 2, (1, 70, 12, 128)),
        rng.uniform(0, 2, (1, 70, 12)))]

    def recur(*x):
        return jnp.sum(kda_ops.gated_delta_rule(*x) ** 2)

    fluid.set_flags({'FLAGS_pallas_force': True})
    prepared = monitor.counter_value('pallas/kda_chunk/dispatch_fused') or 0
    fused = jax.value_and_grad(recur, (0, 1, 2, 3, 4))(*delta)
    if monitor.counter_value('pallas/kda_chunk/dispatch_fused') != \
            prepared + 1:
        failures.append('kda_chunk did not dispatch fused at dk, dv 128')
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = jax.value_and_grad(recur, (0, 1, 2, 3, 4))(*delta)
    for a, b in zip(jax.tree_util.tree_leaves(fused),
                    jax.tree_util.tree_leaves(dense)):
        if not _near(a, b):
            failures.append('kda_chunk forward/grad parity')
            break

    # the same with values a lane tile wide, so that the chunks are
    # walked by the kda_walk kernels (two sequences of three heads: the
    # state is zeroed between them) against the scan over ``_step``
    walk = [jnp.asarray(x.astype('float32')) for x in (
        rng.randn(2, 150, 3, 128) / 11, rng.randn(2, 150, 3, 128) / 11,
        rng.randn(2, 150, 3, 128), -rng.uniform(0, 2, (2, 150, 3, 128)),
        rng.uniform(0, 2, (2, 150, 3)))]
    fluid.set_flags({'FLAGS_pallas_force': True})
    walks = monitor.counter_value('pallas/kda_walk/dispatch_fused') or 0
    fused = jax.value_and_grad(recur, (0, 1, 2, 3, 4))(*walk)
    if monitor.counter_value('pallas/kda_walk/dispatch_fused') != walks + 1:
        failures.append('kda_walk did not dispatch fused at dv 128')
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = jax.value_and_grad(recur, (0, 1, 2, 3, 4))(*walk)
    for a, b in zip(jax.tree_util.tree_leaves(fused),
                    jax.tree_util.tree_leaves(dense)):
        if not _near(a, b):
            failures.append('kda_walk forward/grad parity')
            break

    # a held layer's expert MLP through the grouped_matmul kernels
    # against ragged_dot, both in bfloat16: all four gradients
    # within a few last places of their largest entry
    from paddle_tpu.parallel import moe
    sizes = jnp.asarray([130, 0, 70, 100], jnp.int32)
    experts = [jnp.asarray(x.astype('float32')) for x in (
        rng.randn(512, 256), rng.randn(4, 256, 128) / 16,
        rng.randn(4, 256, 128) / 16, rng.randn(4, 128, 256) / 11)]

    def mlp(*x):
        out = moe.held_expert_mlp(x[0], sizes, x[1:3], x[3], 'gated', True)
        return jnp.sum(out[:300].astype(jnp.float32) ** 2)

    fluid.set_flags({'FLAGS_pallas_force': True})
    fused = jax.grad(mlp, (0, 1, 2, 3))(*experts)
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = jax.grad(mlp, (0, 1, 2, 3))(*experts)
    for a, b in zip(fused, dense):
        # rows past the groups are nobody's: the rows' gradient there
        # is whatever the buffer held
        a = np.asarray(a.astype(jnp.float32))[..., :300, :]
        b = np.asarray(b.astype(jnp.float32))[..., :300, :]
        if not np.abs(a - b).max() <= 2.0 ** -5 * np.abs(b).max():
            failures.append('grouped_matmul forward/grad parity')
            break

    # the hyper-connections' projection through the sinkhorn kernels
    # against the scan: 20 trips of a 4 x 4 matrix over 256 tokens
    m0 = jnp.asarray(np.exp(3 * rng.randn(4, 4, 256)).astype('float32'))
    weight = jnp.asarray(rng.randn(4, 4, 256).astype('float32'))

    def projected(m):
        return jnp.sum(weight * hyper_connection_ops.project(m, 20, 1e-6))

    fluid.set_flags({'FLAGS_pallas_force': True})
    fused = jax.value_and_grad(projected)(m0)
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = jax.value_and_grad(projected)(m0)
    for a, b in zip(fused, dense):
        if not np.abs(np.asarray(a) - np.asarray(b)).max() <= \
                1e-5 * np.abs(np.asarray(b)).max():
            failures.append('sinkhorn forward/grad parity')
            break

    # the selective scan through the ssm_scan kernels against the two
    # lax.scans: 40 tokens in chunks of 16, 1024 channels x 4 states
    from paddle_tpu.ops import ssm_ops
    scan = [jnp.asarray(v.astype('float32')) for v in (
        rng.randn(1, 40, 1024), np.exp(rng.uniform(-7, 1, (1, 40, 1024))),
        -np.exp(rng.randn(1024, 4)), rng.randn(1, 40, 4),
        rng.randn(1, 40, 4), rng.randn(1024))]
    weight = jnp.asarray(rng.randn(1, 40, 1024).astype('float32'))

    def scanned(*x):
        out, pull = jax.vjp(
            lambda *x: ssm_ops.selective_scan(*x, chunk=16), *x)
        return (out,) + pull(weight)

    fluid.set_flags({'FLAGS_pallas_force': True})
    fused = scanned(*scan)
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = scanned(*scan)
    for a, b in zip(fused, dense):
        if not np.abs(np.asarray(a) - np.asarray(b)).max() <= \
                1e-5 * np.abs(np.asarray(b)).max():
            failures.append('ssm_scan forward/grad parity')
            break

    # Mamba-2's chunked scan through the ssd_scan kernels against XLA's
    # lowering of every chunk at once: two chunks of 128 tokens, 8 heads
    # of 64 in one group, 128 states
    from paddle_tpu.ops import ssd_ops
    chunked = [jnp.asarray(v.astype('float32')) for v in (
        rng.randn(1, 256, 8, 64), np.exp(rng.uniform(-5, 0.5, (1, 256, 8))),
        -np.exp(rng.uniform(-3, 2, 8)), rng.randn(1, 256, 1, 128) / 4,
        rng.randn(1, 256, 1, 128) / 4, rng.randn(8))]
    weight = jnp.asarray(rng.randn(1, 256, 8, 64).astype('float32'))

    def chunk_scanned(*x):
        out, pull = jax.vjp(lambda *x: ssd_ops.ssd_scan(*x, 128), *x)
        return (out,) + pull(weight)

    fluid.set_flags({'FLAGS_pallas_force': True})
    fused = chunk_scanned(*chunked)
    fluid.set_flags({'FLAGS_pallas_force': False})
    dense = chunk_scanned(*chunked)
    for a, b in zip(fused, dense):
        if not _near(a, b, 1e-5):
            failures.append('ssd_scan forward/grad parity')
            break

    flat = jnp.asarray(rng.randn(16, 256).astype('float32'))
    qv, s = quant_collective.quantize_blocks(flat, True)

    def qref_fn(v):
        # the dense arm's q(), jitted like the arm itself runs — eager
        # evaluation rounds the scale division one ulp differently
        sr = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
        sr = jnp.where(sr > 0, sr, 1.0)
        return (jnp.clip(jnp.rint(v / sr), -127, 127).astype(jnp.int8),
                sr.astype(jnp.float32))

    qref, sref = jax.jit(qref_fn)(flat)
    if not (np.array_equal(np.asarray(qv), np.asarray(qref)) and
            np.array_equal(np.asarray(s), np.asarray(sref))):
        failures.append('quantize_blocks not bitwise vs dense q()')
    print('parity: flash_attention fwd/grad, kda_chunk fwd/grad, '
          'kda_walk fwd/grad, grouped_matmul fwd/grad, sinkhorn fwd/grad, '
          'ssm_scan fwd/grad, ssd_scan fwd/grad, quantize_blocks ok')

    # -- 3. dispatch observability -----------------------------------
    quant_collective.dispatch()
    for name in EXPECTED:
        got = monitor.counter_value(
            'pallas/%s/dispatch_fused' % name) + \
            monitor.counter_value('pallas/%s/dispatch_dense' % name)
        if not got:
            failures.append('kernel %r recorded no dispatch counter'
                            % name)
        if name not in common._LAST:
            failures.append('kernel %r recorded no last decision'
                            % name)
        elif 'reason' not in common._LAST[name]:
            failures.append('kernel %r decision lacks a reason' % name)
    rep = health.statusz().get('pallas')
    if not rep or not rep.get('kernels'):
        failures.append('/statusz pallas section missing or empty')
    else:
        for name in EXPECTED:
            if name not in rep['kernels']:
                failures.append('/statusz pallas section lacks %r'
                                % name)

    # -- 4. flag hygiene ---------------------------------------------
    pallas_flags = [k for k in _DEFAULTS
                    if k.startswith('FLAGS_pallas_')]
    if not pallas_flags:
        failures.append('no FLAGS_pallas_* knobs declared')
    import staticcheck
    reads = staticcheck.flag_reads(
        staticcheck._py_files(staticcheck.PKG))
    for k in pallas_flags:
        if k not in reads:
            failures.append('%s declared but never read inside '
                            'paddle_tpu/' % k)

    if failures:
        for f in failures:
            print('KERNEL GATE  ' + f)
        return 1
    print('pallas kernel library: ok (%d kernels, %d pallas flags)'
          % (len(ks), len(pallas_flags)))
    return 0


if __name__ == '__main__':
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
