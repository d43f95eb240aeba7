"""The ``ssd_scan`` kernels (``paddle_tpu/ops/pallas/ssd_scan.py``:
Mamba-2's chunked recurrence with a chunk's scores and weights in VMEM
and a group's state held on the core, forward and backward) under the
Pallas interpreter against the dense op they replace
(``ops/ssd_ops.py``'s products over every chunk at once) and the
token-by-token recurrence; what ``common.dispatch`` answers for
operands the kernels' layout does not hold; and what the op's gauges
read on both paths.  CPU, small shapes; what the chip's compiler says
of the kernels is ``tests/test_chip_compile.py``'s."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.fluid import monitor
from paddle_tpu.models.reference import nemotron_h as reference
from paddle_tpu.ops import registry, ssd_ops
from paddle_tpu.ops.pallas import common, ssd_scan

SLOTS = ('X', 'Delta', 'A', 'B', 'C', 'D')
# two sequences, two chunks of 128, 16 heads of 64 in 2 groups, 128
# states: the smallest shape every gate passes at the cell's own
# grouping (8 heads of 64 channels a group)
GATED = dict(b=2, t=256, h=16, p=64, g=2, n=128)


def _inputs(seed, b, t, h, p, g, n, dtype=jnp.float32):
    """``tests/test_nemotron_h.py``'s operands: steps from 0.007 to 1.6
    and decay rates of every size, so a head's state lives from two
    tokens to hundreds."""
    rng = np.random.RandomState(seed)
    x = rng.randn(b, t, h, p)
    delta = np.exp(rng.uniform(-5.0, 0.5, (b, t, h)))
    a = -np.exp(rng.uniform(-3.0, 2.0, (h,)))
    bm, cm = rng.randn(b, t, g, n) / 4, rng.randn(b, t, g, n) / 4
    skip = rng.randn(h)
    return [jnp.asarray(v, jnp.float32 if i in (1, 2, 5) else dtype)
            for i, v in enumerate((x, delta, a, bm, cm, skip))]


def _close(got, want, rtol, what=''):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), \
        (what, np.abs(got - want).max() / np.abs(want).max())


def _count(name):
    return monitor.counter_value('pallas/ssd_scan/' + name) or 0


def _both_passes(args, probe, chunk=128, **kw):
    out, pull = jax.vjp(
        lambda *x: ssd_ops.ssd_scan(*x, chunk, **kw), *args)
    return out, pull(probe)


def _dense_passes(args, probe, chunk=128):
    out, pull = jax.vjp(
        lambda *x: ssd_ops._scan(*x, chunk, 'dense'), *args)
    return out, pull(probe)


def _probe(seed, args):
    return jnp.asarray(np.random.RandomState(seed).randn(*args[0].shape),
                       args[0].dtype)


# float32: both paths multiply at full precision, so they differ by
# the order of their sums.  bfloat16: both round the same operands of
# every product to 8 bits but not at the same place of the algebra,
# and a gradient is a sum of up to 128 such products a chunk: 2^-6 of
# the largest entry, twice that for A's, a sum over every token of a
# head.
@pytest.mark.parametrize('dtype,rtol,rtol_a', [
    (jnp.float32, 2e-6, 4e-6), (jnp.bfloat16, 2 ** -6, 2 ** -5)],
    ids=['float32', 'bfloat16'])
def test_the_fused_scan_and_its_six_gradients_are_the_dense_ops(
        pallas_interpret, dtype, rtol, rtol_a):
    """The op through the kernels (dispatch counted fused, once a call
    for both passes) against the dense path on the same operands, the
    gradients in each operand's own type, and in float32 the forward
    against the token-by-token recurrence."""
    args = _inputs(0, dtype=dtype, **GATED)
    probe = _probe(1, args)
    fused = _count('dispatch_fused')
    got, got_grads = _both_passes(args, probe)
    assert _count('dispatch_fused') == fused + 1
    assert common._LAST['ssd_scan'] == {
        'path': 'fused', 'reason': 'forced_interpret', 'interpret': True}
    want, want_grads = _dense_passes(args, probe)
    assert got.dtype == want.dtype == dtype
    _close(got, want, rtol, 'Out')
    if dtype == jnp.float32:
        with jax.default_matmul_precision('highest'):
            _close(got, reference.recurrence(*args), 2e-5, 'recurrence')
    for slot, got_grad, want_grad in zip(SLOTS, got_grads, want_grads):
        assert got_grad.dtype == want_grad.dtype, slot
        _close(got_grad, want_grad, rtol_a if slot == 'A' else rtol, slot)


@pytest.mark.parametrize('shape', [
    dict(b=1, t=128, h=8, p=128, g=1, n=128),    # wider heads
    dict(b=1, t=256, h=8, p=16, g=1, n=128),     # one 16-bit tile of rows
    dict(b=1, t=384, h=3, p=128, g=1, n=256),    # one group of 3 heads
], ids=['p128', 'p16', 'odd_heads'])
def test_the_fused_scan_at_other_heads_and_states(pallas_interpret, shape):
    """A head's P rows may be any whole number of 16-row tiles, N any
    number of lane tiles, and one group may hold any number of heads:
    float32 against the dense path."""
    args = _inputs(2, **shape)
    probe = _probe(3, args)
    got, got_grads = _both_passes(args, probe)
    assert common._LAST['ssd_scan']['path'] == 'fused'
    want, want_grads = _dense_passes(args, probe)
    _close(got, want, 2e-6, 'Out')
    for slot, got_grad, want_grad in zip(SLOTS, got_grads, want_grads):
        _close(got_grad, want_grad, 1e-5, slot)


def test_no_state_crosses_from_one_sequence_of_a_batch_into_the_next(
        pallas_interpret):
    """The state is zero at each sequence's first chunk:
    ``test_ssd_scan_holds_the_sequences_of_a_batch_apart``'s probe
    through the fused path.  Each sequence of a batch of two is what it
    is alone, output and gradients, to the bit, and the second
    sequence's cotangent reaches nothing of the first."""
    args = _inputs(4, **GATED)
    probe = _probe(5, args)
    both, both_grads = _both_passes(args, probe)
    assert common._LAST['ssd_scan']['path'] == 'fused'
    for i in (0, 1):
        alone, grads = _both_passes(
            [v[i:i + 1] if v.ndim > 1 else v for v in args],
            probe[i:i + 1])
        assert (np.asarray(both[i:i + 1]) == np.asarray(alone)).all()
        for slot, a, b in zip(SLOTS, both_grads, grads):
            if a.ndim > 1:      # what comes a sequence
                assert (np.asarray(a[i:i + 1]) == np.asarray(b)).all(), slot
    _, only_second = _both_passes(args, probe.at[0].set(0.0))
    for slot, g in zip(SLOTS, only_second):
        if g.ndim > 1:
            assert not np.asarray(g[0]).any(), slot


def _equations(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equations(sub, seen)
    return seen


def test_nothing_chunk_by_chunk_sized_leaves_the_calls_but_the_starts(
        pallas_interpret):
    """The jaxpr of the fused op's value and gradients at four chunks:
    two ``pallas_call``s, every exponential float32, and outside the
    kernels' bodies no [.., Q, Q] array a head and chunk and nothing
    [P, N]-sized a head and chunk but the kept start states."""
    shape = dict(GATED, b=1, t=512)
    args = _inputs(6, dtype=jnp.bfloat16, **shape)
    probe = _probe(7, args)
    jaxpr = jax.make_jaxpr(lambda *x: _both_passes(list(x), probe))(*args)
    equations = _equations(jaxpr.jaxpr, [])
    assert sum(e.primitive.name == 'pallas_call' for e in equations) == 2
    exps = [e for e in equations if e.primitive.name == 'exp']
    assert exps and all(v.aval.dtype == jnp.float32
                        for e in exps for v in e.invars + e.outvars)
    outside = []
    for eqn in _outside_the_kernels(jaxpr.jaxpr, []):
        outside += [v.aval for v in eqn.outvars if hasattr(v.aval, 'shape')]
    chunks, h, p, n = 4, shape['h'], shape['p'], shape['n']
    starts = chunks * h * p * n
    sizes = sorted({int(np.prod(v.shape)) for v in outside})
    assert starts in sizes
    assert chunks * h * 128 * 128 not in sizes
    # (N = Q here, so the starts are as large as x; nothing is larger)
    assert max(sizes) == starts == shape['t'] * h * p


def _outside_the_kernels(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.append(eqn)
        if eqn.primitive.name != 'pallas_call':
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _outside_the_kernels(sub, seen)
    return seen


@pytest.mark.parametrize('what,shape,kwargs', [
    ('layout', dict(GATED), dict(chunk=16)),        # no lane tile of tokens
    ('layout', dict(GATED, t=200), {}),             # a ragged tail
    ('layout', dict(GATED, n=64), {}),              # N off the lanes
    ('layout', dict(GATED, p=40), {}),              # P rows fill no 16-bit tile
    ('layout', dict(GATED, h=8, g=2), {}),          # 4 rows of a [B, H, T]
    ('vmem_over_budget', dict(GATED, h=64, p=128, g=2), {}),
    ('auto_partitioned', dict(GATED), dict(auto_partitioned=True)),
])
def test_the_dispatch_answers_dense_with_its_reason_counted(
        pallas_interpret, what, shape, kwargs):
    """Where the kernels' layout does not hold the operands (a chunk
    or N off whole lane tiles, a head's rows off whole sublane tiles, a
    tail that fills no chunk: the dense path pads it, the kernels do
    not), where a
    group's blocks pass the budget, and where XLA partitions the
    program, the op traces the dense path and says why."""
    args = [jax.ShapeDtypeStruct(v.shape, v.dtype)
            for v in _inputs(0, **shape)]
    before, fused = _count('fallback/' + what), _count('dispatch_fused')
    chunk = kwargs.pop('chunk', 128)
    jaxpr = jax.make_jaxpr(
        lambda *x: ssd_ops.ssd_scan(*x, chunk, **kwargs))(*args)
    assert _count('fallback/' + what) == before + 1
    assert _count('dispatch_fused') == fused
    assert common._LAST['ssd_scan'] == {
        'path': 'dense', 'reason': what, 'interpret': False}
    assert not any(e.primitive.name == 'pallas_call'
                   for e in _equations(jaxpr.jaxpr, []))


def test_a_ragged_tail_runs_dense_and_is_the_recurrence(pallas_interpret):
    """200 tokens in chunks of 128: the kernels take whole chunks only,
    so the call answers 'layout' and the dense path pads the tail with
    tokens of step 0."""
    args = _inputs(8, **dict(GATED, b=1, t=200))
    before = _count('fallback/layout')
    with jax.default_matmul_precision('highest'):
        _close(ssd_ops.ssd_scan(*args, 128), reference.recurrence(*args),
               2e-5)
    assert _count('fallback/layout') == before + 1


def test_float64_runs_the_dense_path(pallas_interpret):
    """Under x64 the working dtype is float64, which the kernels do
    not take: reason 'dtype', and the recurrence to rounding."""
    before = _count('fallback/dtype')
    with jax.enable_x64():
        args = [jnp.asarray(np.asarray(v), jnp.float64)
                for v in _inputs(9, **dict(GATED, b=1, t=128, h=8, g=1))]
        _close(ssd_ops.ssd_scan(*args, 128),
               reference.recurrence(*args), 1e-11)
    assert _count('fallback/dtype') == before + 1


def test_off_a_tpu_and_unforced_the_op_is_dense():
    before = _count('fallback/off_tpu')
    args = _inputs(10, **dict(GATED, b=1))
    want = ssd_ops._scan(*args, 128, 'dense')
    assert (np.asarray(ssd_ops.ssd_scan(*args, 128)) ==
            np.asarray(want)).all()
    assert _count('fallback/off_tpu') == before + 1
    assert common._LAST['ssd_scan']['path'] == 'dense'


def test_heads_that_fill_no_whole_groups_are_still_refused(
        pallas_interpret):
    """The kernels' gate sends them to the dense path, which raises
    as it always has."""
    args = _inputs(11, b=1, t=128, h=12, p=64, g=8, n=128)
    with pytest.raises(ValueError, match='whole number'):
        ssd_ops.ssd_scan(*args)
    assert common._LAST['ssd_scan']['reason'] == 'layout'


@pytest.mark.parametrize('forced', [False, True], ids=['dense', 'fused'])
def test_the_gauges_read_the_same_on_both_paths(forced):
    """``ssd/chunks`` is the sequential trips over chunks of each pass
    of a traced program, ``ssd/boundary_state_mb`` what the op keeps
    between its passes: 256 tokens in chunks of 128 are two trips
    forward and two in reverse, and two [H, P, N] float32 states a
    sequence kept."""
    from paddle_tpu.fluid.flags import get_flag, set_flags
    args = _inputs(12, **GATED)
    probe = _probe(13, args)
    was = get_flag('FLAGS_pallas_force', False)
    set_flags({'FLAGS_pallas_force': forced})
    try:
        registry.begin_trace()
        jax.make_jaxpr(lambda *x: _both_passes(list(x), probe))(*args)
    finally:
        set_flags({'FLAGS_pallas_force': was})
    assert common._LAST['ssd_scan']['path'] == \
        ('fused' if forced else 'dense')
    assert monitor.gauge_value('ssd/chunks') == 2 * 2
    kept = GATED['b'] * 2 * GATED['h'] * GATED['p'] * GATED['n'] * 4 / 1e6
    assert abs(monitor.gauge_value('ssd/boundary_state_mb') - kept) < 1e-9
    registry.begin_trace()
    jax.make_jaxpr(lambda *x: ssd_ops.ssd_scan(*x, 128))(*args)
    assert monitor.gauge_value('ssd/chunks') == 2
    assert monitor.gauge_value('ssd/boundary_state_mb') == 0


def test_the_backward_s_count_at_the_cell_and_past_the_budget():
    """What ``checks`` weighs: a group of Nemotron's layer (8 heads of
    64, 128 states, chunks of 128) counts under 4 MB in bfloat16 and 5
    in float32, far under the budget of a call that asks Mosaic for
    nothing; a group of 32 heads of 128 passes it."""
    assert ssd_scan.backward_vmem(128, 512, 128, 2) < 4 << 20
    assert ssd_scan.backward_vmem(128, 512, 128, 4) < 5 << 20
    assert ssd_scan.backward_vmem(128, 4096, 128, 2) > \
        common.VMEM_BUDGET_BYTES
    cell = ssd_scan.checks((1, 8192, 64, 64), 8, 128, 128, jnp.float32, 2)
    assert all(ok for _, ok in cell)


def test_the_kernel_is_registered_with_its_dense_fallback():
    entry = common.kernels()['ssd_scan']
    assert entry['has_vjp'] and entry['op_types'] == ('ssd_scan',)
    module, name = entry['dense_fallback'].rsplit('.', 1)
    assert module == 'paddle_tpu.ops.ssd_ops' and \
        callable(getattr(ssd_ops, name))
