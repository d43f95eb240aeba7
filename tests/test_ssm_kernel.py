"""The ``ssm_scan`` kernels (``paddle_tpu/ops/pallas/ssm_scan.py``: the
selective state-space scan with its state held on the core, forward and
reverse walk) under the Pallas interpreter against the dense op they
replace (``ops/ssm_ops.py``'s two ``lax.scan``s) and the token-by-token
loop; what ``common.dispatch`` answers for operands the kernels' layout
does not hold; and what the op's three gauges read on both paths.  CPU,
small shapes; what the chip's compiler says of the kernels is
``tests/test_chip_compile.py``'s."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.fluid import monitor
from paddle_tpu.models.reference import phi4flash as reference
from paddle_tpu.ops import registry, ssm_ops
from paddle_tpu.ops.pallas import common, ssm_scan

BLOCK = ssm_scan.BLOCK


def _inputs(seed, b=1, t=40, d=BLOCK, n=4, dtype=jnp.float32):
    """``tests/test_phi4flash.py``'s operands at a width the kernels
    take: steps from 0.001 to 3, A = -(1 .. n) a channel with a
    channel's own factor."""
    rng = np.random.RandomState(seed)
    x = rng.randn(b, t, d)
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (b, t, d)))
    a = -np.arange(1, n + 1) * rng.uniform(0.5, 4.0, (d, 1))
    bm, cm = rng.randn(b, t, n), rng.randn(b, t, n)
    skip = rng.randn(d)
    return [jnp.asarray(v, jnp.float32 if i in (1, 2, 5) else dtype)
            for i, v in enumerate((x, delta, a, bm, cm, skip))]


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


def _count(name):
    return monitor.counter_value('pallas/ssm_scan/' + name) or 0


def _both_passes(args, chunk, probe, **kw):
    out, pull = jax.vjp(
        lambda *x: ssm_ops.selective_scan(*x, chunk=chunk, **kw), *args)
    return out, pull(probe)


@pytest.mark.parametrize('b,t,d,n,chunk', [
    (1, 48, 2 * BLOCK, 4, 16),  # whole chunks, two blocks of channels
    (1, 37, BLOCK, 3, 16),      # a tail that fills no chunk, nor a trip
    (2, 5, BLOCK, 2, 256),      # shorter than one trip of the token loop
    (1, 8, BLOCK, 16, 8),       # the published 16 states, one trip
])
def test_the_fused_scan_and_its_six_gradients_are_the_dense_ops(
        pallas_interpret, b, t, d, n, chunk):
    """The op through the kernels (dispatch counted fused, once a call
    for both passes) against the dense path on the same operands,
    float32, to 1e-6 of the largest entry, and the forward against the
    token-by-token loop."""
    args = _inputs(t, b=b, t=t, d=d, n=n)
    probe = jnp.asarray(np.random.RandomState(1).randn(b, t, d),
                        jnp.float32)
    fused = _count('dispatch_fused')
    got, got_grads = _both_passes(args, chunk, probe)
    assert _count('dispatch_fused') == fused + 1
    assert common._LAST['ssm_scan'] == {
        'path': 'fused', 'reason': 'forced_interpret', 'interpret': True}
    want, pull = jax.vjp(
        lambda *x: ssm_ops._scan(*x, chunk, 'dense'), *args)
    _close(got, want, 1e-6)
    _close(got, jax.jit(reference.selective_scan)(*args), 2e-6)
    for got_grad, want_grad in zip(got_grads, pull(probe)):
        assert got_grad.dtype == want_grad.dtype
        _close(got_grad, want_grad, 1e-6)


def test_no_state_crosses_from_one_sequence_of_a_batch_into_the_next(
        pallas_interpret):
    """Each sequence of a batch of two, three chunks each, is what it
    is alone, output and gradients; replacing the OTHER sequence
    changes nothing, to the bit."""
    args = _inputs(4, b=2, t=40, n=3)
    probe = jnp.asarray(np.random.RandomState(2).randn(2, 40, BLOCK),
                        jnp.float32)
    both, both_grads = _both_passes(args, 16, probe)
    per_sequence = (0, 1, 3, 4)
    for i in range(2):
        alone, grads = _both_passes(
            [x[i:i + 1] if j in per_sequence else x
             for j, x in enumerate(args)], 16, probe[i:i + 1])
        assert (np.asarray(both[i:i + 1]) == np.asarray(alone)).all()
        for j in per_sequence:
            assert (np.asarray(both_grads[j][i:i + 1]) ==
                    np.asarray(grads[j])).all()
    fresh = _inputs(5, b=2, t=40, n=3)
    other = [x.at[0].set(fresh[j][0]) if j in per_sequence else x
             for j, x in enumerate(args)]
    out, grads = _both_passes(other, 16, probe)
    assert (np.asarray(out)[1] == np.asarray(both)[1]).all()
    assert (np.asarray(grads[0])[1] == np.asarray(both_grads[0])[1]).all()


def _equations(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equations(sub, seen)
    return seen


def test_bf16_inputs_keep_the_steps_and_the_state_float32(
        pallas_interpret):
    """bf16 x, B, C beside float32 steps, A and D: the fused output is
    bf16 and the dense path's but for an ulp, the gradients come back
    in each operand's own type, and in the jaxpr of both passes, the
    kernels' bodies included, every exponential, every kept state and
    every scratch that carries one is float32."""
    args = _inputs(7, t=40, n=4, dtype=jnp.bfloat16)
    args[1] = jnp.minimum(args[1], 0.05)        # slow decays: a long memory
    probe = jnp.ones((1, 40, BLOCK), jnp.bfloat16)
    out, grads = _both_passes(args, 16, probe)
    assert out.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [v.dtype for v in args]
    want, pull = jax.vjp(lambda *x: ssm_ops._scan(*x, 16, 'dense'), *args)
    _close(out, want, 2 ** -7)
    for got_grad, want_grad in zip(grads, pull(probe)):
        _close(got_grad, want_grad, 2 ** -6)
    jaxpr = jax.make_jaxpr(
        lambda *x: _both_passes(list(x), 16, probe))(*args)
    equations = _equations(jaxpr.jaxpr, [])
    exps = [e for e in equations if e.primitive.name == 'exp']
    assert exps and all(v.aval.dtype == jnp.float32
                        for e in exps for v in e.invars + e.outvars)
    calls = [e for e in equations if e.primitive.name == 'pallas_call']
    assert len(calls) == 2
    for call in calls:
        carried = [v.aval for v in call.params['jaxpr'].invars
                   if v.aval.shape[-3:-2] == (4,)]
        assert carried and all(v.dtype == jnp.float32 for v in carried)


def test_no_array_of_every_token_s_state_exists_on_either_pass(
        pallas_interpret):
    """The jaxpr of the fused op's value and gradients at 128 tokens in
    chunks of 16, the kernels' bodies and their scratch included, holds
    nothing as large as [B, T, D, N]: the largest that carries a state
    is the backward's scratch of one chunk's states."""
    b, t, d, n, chunk = 1, 128, BLOCK, 4, 16
    args = _inputs(9, b=b, t=t, d=d, n=n)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *x: jnp.sum(ssm_ops.selective_scan(*x, chunk=chunk)),
        argnums=range(6)))(*args)
    equations = _equations(jaxpr.jaxpr, [])
    assert sum(e.primitive.name == 'pallas_call' for e in equations) == 2
    sizes = [int(np.prod(v.aval.shape)) for e in equations
             for v in e.outvars if hasattr(v.aval, 'shape')]
    sizes += [int(np.prod(v.aval.shape)) for e in equations
              if e.primitive.name == 'pallas_call'
              for v in e.params['jaxpr'].invars]
    assert (chunk + 1) * n * d in sizes
    assert max(sizes) == b * t * d < b * t * d * n


@pytest.mark.parametrize('what,kwargs', [
    ('layout', dict(d=24)),                     # no block of 1024 channels
    ('layout', dict(n=ssm_scan.MAX_STATES + 1)),    # too many registers
    ('vmem_over_budget', dict(n=16, t=2048, chunk=2048)),
    ('auto_partitioned', dict(auto_partitioned=True)),
])
def test_the_dispatch_answers_dense_with_its_reason_counted(
        pallas_interpret, what, kwargs):
    """Where the kernels' layout does not hold the operands (a width
    that is no whole block of channels, more states than the registers
    carry), where the backward's scratch of a chunk's states passes
    what a call may ask Mosaic for, and where XLA partitions the
    program, the op traces the dense path and says why."""
    kwargs = dict(kwargs)
    shape = {k: kwargs.pop(k) for k in ('d', 'n', 't') if k in kwargs}
    args = [jax.ShapeDtypeStruct(v.shape, v.dtype)
            for v in _inputs(0, **dict(dict(t=8), **shape))]
    before, fused = _count('fallback/' + what), _count('dispatch_fused')
    jaxpr = jax.make_jaxpr(
        lambda *x: ssm_ops.selective_scan(*x, **kwargs))(*args)
    assert _count('fallback/' + what) == before + 1
    assert _count('dispatch_fused') == fused
    assert common._LAST['ssm_scan'] == {
        'path': 'dense', 'reason': what, 'interpret': False}
    assert not any(e.primitive.name == 'pallas_call'
                   for e in _equations(jaxpr.jaxpr, []))


def test_float64_runs_the_dense_path(pallas_interpret):
    """Under x64 the working dtype is float64, which the kernels do
    not take: reason 'dtype', and the loop to rounding."""
    before = _count('fallback/dtype')
    with jax.enable_x64():
        args = [jnp.asarray(np.asarray(v), jnp.float64)
                for v in _inputs(8, t=20, n=2)]
        _close(ssm_ops.selective_scan(*args, chunk=8),
               reference.selective_scan(*args), 1e-12)
    assert _count('fallback/dtype') == before + 1


def test_off_a_tpu_and_unforced_the_op_is_dense():
    before = _count('fallback/off_tpu')
    args = _inputs(9, t=12, n=2)
    _close(ssm_ops.selective_scan(*args),
           jax.jit(reference.selective_scan)(*args), 2e-6)
    assert _count('fallback/off_tpu') == before + 1
    assert common._LAST['ssm_scan']['path'] == 'dense'


@pytest.mark.parametrize('forced', [False, True], ids=['dense', 'fused'])
def test_the_gauges_read_the_same_on_both_paths(forced):
    """``ssm/chunks`` is the sequential trips over chunks of each walk
    of a traced program, ``ssm/boundary_state_mb`` what the op keeps
    between its passes: 40 tokens in chunks of 16 are three trips
    forward and three in reverse (the fused reverse walk runs a chunk
    forward again INSIDE its trip, as the dense one does), and three
    [B, N, D] float32 states kept."""
    from paddle_tpu.fluid.flags import get_flag, set_flags
    args = _inputs(3, b=2, t=40, n=2)
    probe = jnp.ones((2, 40, BLOCK), jnp.float32)
    was = get_flag('FLAGS_pallas_force', False)
    set_flags({'FLAGS_pallas_force': forced})
    try:
        registry.begin_trace()
        jax.make_jaxpr(lambda *x: _both_passes(list(x), 16, probe))(*args)
    finally:
        set_flags({'FLAGS_pallas_force': was})
    assert common._LAST['ssm_scan']['path'] == \
        ('fused' if forced else 'dense')
    assert monitor.gauge_value('ssm/chunks') == 2 * 3
    assert abs(monitor.gauge_value('ssm/boundary_state_mb') -
               3 * 2 * 2 * BLOCK * 4 / 1e6) < 1e-9
    registry.begin_trace()
    jax.make_jaxpr(lambda *x: ssm_ops.selective_scan(*x, chunk=16))(*args)
    assert monitor.gauge_value('ssm/chunks') == 3
    assert monitor.gauge_value('ssm/boundary_state_mb') == 0


def test_the_chunk_as_the_kernels_run_it():
    """Whole trips of the token loop, no longer than the sequence
    needs; the chunks cover the sequence."""
    assert ssm_scan.layout(8192, 256) == (256, 32)
    assert ssm_scan.layout(300, 256) == (256, 2)
    assert ssm_scan.layout(37, 16) == (16, 3)
    assert ssm_scan.layout(5, 256) == (8, 1)
    assert ssm_scan.layout(24, 20) == (16, 2)
    assert ssm_scan.layout(24, 1) == (8, 3)


def test_the_kernel_is_registered_with_its_dense_fallback():
    entry = common.kernels()['ssm_scan']
    assert entry['has_vjp'] and entry['op_types'] == ('selective_scan',)
    module, name = entry['dense_fallback'].rsplit('.', 1)
    assert module == 'paddle_tpu.ops.ssm_ops' and \
        callable(getattr(ssm_ops, name))
