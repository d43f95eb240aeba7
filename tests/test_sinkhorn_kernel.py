"""The ``sinkhorn`` kernels (``paddle_tpu/ops/pallas/sinkhorn.py``: the
hyper-connections' projection onto the doubly stochastic matrices,
forward and exact backward, one call each) under the Pallas interpreter
against the scan they replace (``ops/hyper_connection_ops.py``
``sinkhorn``) and against the plain Python loop; and what
``common.dispatch`` answers for operands the kernels' layout does not
hold.  CPU; what the chip's compiler says of them is
``tests/test_chip_compile.py``'s, the op's five gradients through them
``tests/test_xing4.py``'s."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.fluid import monitor
from paddle_tpu.ops import hyper_connection_ops as hc_ops
from paddle_tpu.ops import registry
from paddle_tpu.ops.pallas import common, sinkhorn as kernel

EPS = 1e-6


def _matrix(seed, n, s, spread=12.0, dtype=jnp.float32):
    """M_0 = exp(clamp(logits)) as the op makes it, the logits wide
    enough that some stand at the clamp, and a cotangent."""
    rng = np.random.RandomState(seed)
    logits = spread * rng.randn(n, n, s)
    return (jnp.asarray(np.exp(np.clip(logits, -30.0, 30.0)), dtype),
            jnp.asarray(rng.randn(n, n, s), dtype),
            (np.abs(logits) >= 30.0).mean())


def _plain(m, iters):
    for _ in range(iters):
        m = m / (jnp.sum(m, 1, keepdims=True) + EPS)
        m = m / (jnp.sum(m, 0, keepdims=True) + EPS)
    return m


def _close(got, want, rtol):
    """Within ``rtol`` of the largest entry (H_res's is about 1: wide
    logits leave entries of 1e-9 beside it, whose last places follow
    the order a row's four terms are summed in)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


def _count(name):
    return monitor.counter_value('pallas/sinkhorn/' + name) or 0


@pytest.mark.parametrize('s', [128, 1024, 4096])
@pytest.mark.parametrize('iters', [1, 20])
@pytest.mark.parametrize('n', [2, 4])
def test_the_kernels_are_the_scan_and_the_plain_loop(pallas_interpret, n,
                                                     iters, s):
    """H_res to 1e-6 and its gradient to 1e-4 of the scan's and of the
    Python loop's, at one and at twenty trips, a tile that is the whole
    axis (128 tokens), one tile and four, with logits at the clamp."""
    m0, weight, clamped = _matrix(n + iters + s, n, s)
    assert clamped > 0
    fused = _count('dispatch_fused')

    def gradient(project):
        return jax.grad(lambda m: jnp.sum(weight * project(m)))(m0)

    got = hc_ops.project(m0, iters, EPS)
    assert _count('dispatch_fused') == fused + 1
    assert common._LAST['sinkhorn']['reason'] == 'forced_interpret'
    grad = gradient(lambda m: hc_ops.project(m, iters, EPS))
    for want_fn in (lambda m: hc_ops.sinkhorn(m, iters, EPS),
                    lambda m: _plain(m, iters)):
        _close(got, want_fn(m0), 1e-6)
        _close(grad, gradient(want_fn), 1e-4)


def test_a_trip_fewer_is_another_gradient(pallas_interpret):
    """The backward walks every trip: 19 of them give a gradient the
    tolerance above tells from 20's where the loop has not converged
    (wide logits)."""
    m0, weight, _ = _matrix(3, 4, 256)

    def grad(iters):
        return jax.grad(lambda m: jnp.sum(
            weight * hc_ops.project(m, iters, EPS)))(m0)

    full, short = grad(20), grad(19)
    assert float(jnp.abs(full - short).max()) > \
        1e-3 * float(jnp.abs(full).max())


def test_the_fused_projection_is_two_calls_and_no_loop_of_the_program(
        pallas_interpret):
    """Forward: one ``pallas_call``; forward + backward: two (the
    backward call runs the trips again itself), no ``scan`` or
    ``while`` left in the program, and M_0 is all that is kept."""
    m0, weight, _ = _matrix(0, 4, 1024)

    def primitives(fn):
        """The program's primitives, a kernel's own body (its trips'
        ``fori_loop``) not entered."""
        from jax._src import core

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                if eqn.primitive.name != 'pallas_call':
                    for sub in core.jaxprs_in_params(eqn.params):
                        yield from walk(sub)
        return list(walk(jax.make_jaxpr(fn)(m0).jaxpr))

    names = primitives(lambda m: hc_ops.project(m, 20, EPS))
    assert names.count('pallas_call') == 1
    assert not {'scan', 'while'} & set(names)
    names = primitives(jax.grad(lambda m: jnp.sum(
        weight * hc_ops.project(m, 20, EPS))))
    assert names.count('pallas_call') == 2
    assert not {'scan', 'while'} & set(names)
    _, pull = jax.vjp(lambda m: hc_ops.project(m, 20, EPS), m0)
    kept = [x for x in jax.tree_util.tree_leaves(pull)
            if hasattr(x, 'shape') and x.size > 1]
    assert [x.shape for x in kept] == [m0.shape]


@pytest.mark.parametrize('what,kwargs', [
    ('layout', dict(s=100)),                 # no whole 128-lane rows
    ('layout', dict(s=12)),
    ('vmem_over_budget', dict(n=8, s=1024)),    # 64 entries x 40 half-trips
    ('auto_partitioned', dict(s=256, auto_partitioned=True)),
])
def test_the_dispatch_answers_dense_with_its_reason_counted(
        pallas_interpret, what, kwargs):
    """Where the kernels' layout does not hold, where the backward's
    scratch passes the budget and where XLA partitions the program, the
    projection is the scan, says why, and is the Python loop's."""
    kwargs = dict(kwargs)
    m0, _, _ = _matrix(7, kwargs.pop('n', 4), kwargs.pop('s'))
    before, fused = _count('fallback/' + what), _count('dispatch_fused')
    got = hc_ops.project(m0, 20, EPS, **kwargs)
    assert _count('fallback/' + what) == before + 1
    assert _count('dispatch_fused') == fused
    assert common._LAST['sinkhorn'] == {
        'path': 'dense', 'reason': what, 'interpret': False}
    _close(got, _plain(m0, 20), 1e-6)


def test_float64_runs_the_scan(pallas_interpret):
    before = _count('fallback/dtype')
    with jax.enable_x64():
        m0, _, _ = _matrix(8, 4, 128, dtype=jnp.float64)
        got = hc_ops.project(m0, 20, EPS)
        assert got.dtype == jnp.float64
        np.testing.assert_allclose(got, _plain(m0, 20), rtol=1e-12)
    assert _count('fallback/dtype') == before + 1


def test_off_a_tpu_and_unforced_the_projection_is_the_scan():
    before = _count('fallback/off_tpu')
    m0, _, _ = _matrix(9, 4, 128)
    text = str(jax.make_jaxpr(lambda m: hc_ops.project(m, 20, EPS))(m0))
    assert 'scan[' in text and 'pallas_call[' not in text
    assert _count('fallback/off_tpu') == before + 1


def test_on_a_tpu_a_lowering_of_the_op_counts_one_fused_dispatch(
        monkeypatch):
    """``hyper_connection_pre`` traced as on a TPU (nothing runs):
    ``pallas/sinkhorn/dispatch_fused`` rises by one a lowering, which
    is what ``pallas_fused_calls`` sums; under the GSPMD runner the
    same lowering answers dense."""
    monkeypatch.setattr(common, 'on_tpu', lambda: True)
    lower = registry.get('hyper_connection_pre').fn
    n, c, m = 4, 16, 24
    ins = {'X': [jax.ShapeDtypeStruct((1, 256, n, c), jnp.bfloat16)],
           'Phi': [jax.ShapeDtypeStruct((n * c, m), jnp.float32)],
           'Alpha': [jax.ShapeDtypeStruct((3,), jnp.float32)],
           'Bias': [jax.ShapeDtypeStruct((m,), jnp.float32)]}
    fused, dense = _count('dispatch_fused'), _count('dispatch_dense')
    jax.eval_shape(lambda ins: lower(registry.LowerCtx(0), ins,
                                     {'sinkhorn_iters': 20}), ins)
    assert _count('dispatch_fused') == fused + 1
    assert common._LAST['sinkhorn'] == {
        'path': 'fused', 'reason': 'tpu', 'interpret': False}
    ctx = registry.LowerCtx(0)
    ctx.auto_partitioned = True
    jax.eval_shape(lambda ins: lower(ctx, ins, {'sinkhorn_iters': 20}),
                   ins)
    assert _count('dispatch_dense') == dense + 1
    assert common._LAST['sinkhorn']['reason'] == 'auto_partitioned'
    assert 'sinkhorn' in common.report()['kernels']


@pytest.mark.parametrize('n,iters,s,tile,count', [
    (4, 20, 4096, 8, 46 * 16 * 4096),      # the Xing4 cell: 3.0 MB
    (4, 20, 128, 1, 46 * 16 * 4096),       # one row lies in 8 sublanes
    (4, 20, 128 * 23, 23, 46 * 16 * 3 * 4096),
    (2, 1, 1024, 8, 8 * 4 * 4096),
])
def test_the_backward_counts_its_scratch_as_it_lies(n, iters, s, tile,
                                                    count):
    assert kernel.tile_rows(s // kernel.LANES) == tile
    assert kernel.backward_vmem(n, iters, s // kernel.LANES) == count
    assert all(ok for _, ok in kernel.checks((n, n, s), jnp.float32,
                                             iters))


def test_the_largest_block_that_is_no_whole_tiles_is_23_rows():
    """17 to 23 rows lie in 24 sublanes, 9.0 MB; 25 lie in 32, 12.1 MB,
    over the 10.5 the kernels keep to: the scan."""
    def admitted(rows):
        return dict(kernel.checks((4, 4, 128 * rows), jnp.float32, 20))[
            'vmem_over_budget']
    assert admitted(23) and not admitted(25) and admitted(32)


def test_the_kernel_is_registered_with_its_dense_fallback():
    entry = common.kernels()['sinkhorn']
    assert entry['has_vjp']
    assert entry['op_types'] == ('hyper_connection_pre',)
    module, name = entry['dense_fallback'].rsplit('.', 1)
    assert module == 'paddle_tpu.ops.hyper_connection_ops'
    assert getattr(hc_ops, name) is hc_ops.sinkhorn
