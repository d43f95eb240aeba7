"""Pallas kernel-library gate (the ops/pallas analog of
check_health.py's plane gate).

Every kernel registered in ops/pallas/common.py must honor the
auto-dispatch + dense-fallback contract:

  1. registry hygiene: a documented dense fallback per kernel, and
     the expected library members present (a kernel silently dropped
     from the package import would otherwise vanish without a gate);
  2. parity: each kernel's forced-fused (interpret) path against its
     dense reference on CPU — bitwise where the reference is exact
     (embedding gather/scatter, blockwise quantize), tolerance-bounded
     where the compiled kernel body may contract FMAs (the adagrad
     row update);
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

EXPECTED = ('flash_attention', 'embedding_lookup', 'embedding_update',
            'quant_collective')


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
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import common, embedding, quant_collective

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
    w = jnp.asarray(rng.randn(600, 8).astype('float32'))
    ids = jnp.asarray(np.array([3, 3, 0, 599, 3], np.int64))
    fluid.set_flags({'FLAGS_pallas_force': True})
    lf = embedding.embedding_lookup(w, ids, -1)
    gf = jax.grad(lambda w: jnp.sum(
        embedding.embedding_lookup(w, ids, -1) ** 2))(w)
    fluid.set_flags({'FLAGS_pallas_force': False})
    ld = embedding._dense_lookup(w, ids, -1)
    gd = jax.grad(lambda w: jnp.sum(
        embedding._dense_lookup(w, ids, -1) ** 2))(w)
    if not np.array_equal(np.asarray(lf), np.asarray(ld)):
        failures.append('embedding_lookup forward not bitwise')
    if not np.array_equal(np.asarray(gf), np.asarray(gd)):
        failures.append('embedding_lookup scatter-add grad not bitwise')

    mom = jnp.asarray(np.abs(rng.randn(600, 8)).astype('float32'))
    g = jnp.asarray(rng.randn(5, 8).astype('float32'))
    upd_ins = {'Param': [w], 'Moment': [mom], 'Ids': [ids],
               'Grad': [g],
               'LearningRate': [jnp.asarray(np.float32(0.1))]}
    fluid.set_flags({'FLAGS_pallas_force': True})
    uf = embedding.apply_update(registry.LowerCtx(0), upd_ins, {})
    fluid.set_flags({'FLAGS_pallas_force': False})
    ud = embedding.apply_update(registry.LowerCtx(0), upd_ins, {})
    for slot in ('ParamOut', 'MomentOut'):
        if not np.allclose(np.asarray(uf[slot][0]),
                           np.asarray(ud[slot][0]),
                           rtol=2e-6, atol=2e-6):
            failures.append('embedding_update %s parity' % slot)

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
    print('parity: embedding lookup/grad/update, quantize_blocks ok')

    # -- 3. dispatch observability -----------------------------------
    for name in ('embedding_lookup', 'embedding_update'):
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
        for name in ('embedding_lookup', 'embedding_update'):
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
