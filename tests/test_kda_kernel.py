"""The ``kda_chunk`` kernels (``paddle_tpu/ops/pallas/kda_chunk.py``: the
gated delta rule's preparation of its chunks from the op's inputs as
they arrive, forward and backward) and the ``kda_walk`` kernels
(``ops/pallas/kda_walk.py``: its walk over the chunks, forward and
reverse) under the Pallas interpreter against the dense forms they
replace (``ops/kda_ops.py`` ``_prepare`` with ``_scores``; the
``lax.scan`` over ``_step`` and its ``jax.vjp``) and, through the whole
op, against the token-by-token recurrence; what ``common.dispatch``
answers for shapes the kernels' layouts do not hold; and ``_prepare``'s
unit-triangular system, inverted by blocks, against float64 NumPy and
``triangular_solve``.  CPU; what the chip's compiler says of them is
``tests/test_chip_compile.py``'s."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from paddle_tpu.fluid import monitor
from paddle_tpu.models.reference import solar_open2 as reference
from paddle_tpu.ops import kda_ops
from paddle_tpu.ops.pallas import common, kda_chunk, kda_walk


def _sequence(seed, t, b=2, h=3, dk=128, dv=8, rate=16.0,
              dtype=jnp.float32):
    """``tests/test_solar_open2.py``'s inputs at a head width the
    kernels take; the log decays stay float32."""
    rng = np.random.RandomState(seed)
    q, k = (rng.randn(b, t, h, dk) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, t, h, dv)
    a = -rate * np.log1p(np.exp(rng.randn(b, t, h, dk))) * \
        rng.uniform(0, 1, (b, t, h, dk))
    beta = 2 / (1 + np.exp(-1 - rng.randn(b, t, h)))
    return [jnp.asarray(x, jnp.float32 if x is a else dtype)
            for x in (q, k, v, a, beta)]


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


def _fused(kernel='kda_chunk'):
    return monitor.counter_value('pallas/%s/dispatch_fused' % kernel) or 0


RESULTS = ('beta A', 'B', 'Qbar', 'Khat', 'beta Kbar', 'beta V', 'exp(G_C)')


def _unsolved(system, written):
    """In ``_solve``'s place, so that ``_prepare`` hands over what it
    made BEFORE the solve: the system beside its right-hand side."""
    return jnp.concatenate([system, written], -1)


@pytest.mark.parametrize('t,h,dk,rate,dtype', [
    (128, 3, 128, 1.0, 'float32'),      # whole chunks of C 64
    (128, 3, 128, 16.0, 'float32'),     # G passes -88 inside a chunk
    (32, 3, 128, 4.0, 'float32'),       # a chunk of two sub-chunks
    (16, 3, 256, 1.0, 'float32'),       # one sub-chunk, two lane tiles
    (100, 3, 128, 16.0, 'float32'),     # a ragged tail, masked on the chip
    (24, 8, 128, 1.0, 'float32'),       # less than a chunk, ragged
    (100, 8, 128, 1.0, 'bfloat16'),     # AMP's inputs, eight heads
    (128, 8, 128, 16.0, 'bfloat16'),
])
def test_the_kernels_scores_and_gradients_are_the_dense_forms(
        monkeypatch, t, h, dk, rate, dtype):
    """The fused preparation (``kda_chunk.prepare``: the op's inputs as
    they arrive -> the system, B, Qbar, Khat, the right-hand side's two
    halves, exp(G_C)) and its five pull-backs against ``_prepare`` over
    ``_chunked`` and ``_scores`` under ``jax.vjp``, at whole chunks, at
    the shorter chunks a short sequence runs as, at two lane tiles of
    channels, at lengths that are no whole number of chunks (the tail
    read past the array and masked in the kernel), at 3 heads and 8, on
    float32 and bfloat16 inputs, and at decays whose running sum passes
    -88 inside the chunk.  G is a product with a triangle of ones in the
    kernel and a ``cumsum`` densely: two float32 sums in different
    orders, each an ulp of |G| from the other (6e-5 at |G| of 1000),
    which every exponent inherits."""
    args = _sequence(t + h, t, h=h, dk=dk, dv=128, rate=rate,
                     dtype=jnp.dtype(dtype))
    if rate == 16.0:
        assert float(jnp.cumsum(args[3][:, :64], 1).min()) < -200
    c, n = kda_ops._layout(t, kda_ops.CHUNK)
    monkeypatch.setattr(kda_ops, '_solve', _unsolved)

    def dense(*x):
        w_k, w_v, q_bar, b_mat, k_hat, decay = kda_ops._prepare(*(
            kda_ops._chunked(y, c, n, jnp.float32) for y in x))
        both = jnp.concatenate([w_k, w_v], -1)  # [system | its right side]
        return (both[..., :c], b_mat, q_bar, k_hat, both[..., c:c + dk],
                both[..., c + dk:], decay)

    def fused(*x):
        system, b_mat, q_bar, k_hat, written, decay = kda_chunk.prepare(
            *x, c, True)
        return (system, b_mat, q_bar, k_hat, written[..., :dk],
                written[..., dk:], decay)

    want, pull = jax.vjp(dense, *args)
    got, pull_kernel = jax.vjp(fused, *args)
    rtol = 4e-5 if rate == 16.0 else 5e-6
    for name, x, y in zip(RESULTS, got, want):
        assert x.shape == y.shape and x.dtype == jnp.float32, name
        if float(jnp.abs(y).max()):
            _close(x, y, rtol)
        else:                           # exp(G_C) under 1e-38
            assert not np.asarray(x).any(), name
    upper = np.triu(np.ones((c, c), bool))
    assert (np.asarray(got[0])[..., upper] == 0).all()
    assert (np.asarray(got[1])[..., np.triu(upper, 1)] == 0).all()
    rng = np.random.RandomState(1)
    cotangents = tuple(jnp.asarray(rng.randn(*x.shape), jnp.float32)
                       for x in want)
    for x, y in zip(pull_kernel(cotangents), pull(cotangents)):
        assert x.shape == y.shape and x.dtype == y.dtype
        _close(x, y, 2 ** -7 if y.dtype == jnp.bfloat16 else rtol)


@pytest.mark.parametrize('t,h', [(100, 12), (64, 12), (24, 12),
                                 (100, 3), (128, 3), (24, 3)])
def test_the_fused_op_is_the_recurrence_at_whole_and_ragged_lengths(
        pallas_interpret, t, h):
    """The whole op through the kernels (dispatch counted fused),
    forward and all five gradients, float32 against the token loop at
    rate 16: one chunk, less than one (a chunk of two sub-chunks) and
    no whole number of them (a masked tail).  At 12 heads the
    preparation's kernels run and the chunks are walked by the scan
    (twelve heads a grid step of whole chunks are over the walk's VMEM
    count); at 3, and at 12 heads of a 32-token chunk, the walk's
    kernels run too."""
    args = _sequence(t, t, h=h, dv=128)
    walked = h == 3 or t < 64
    before, walks = _fused(), _fused('kda_walk')
    with jax.default_matmul_precision('highest'):
        got, pull = jax.vjp(kda_ops.gated_delta_rule, *args)
        probe = jnp.asarray(
            np.random.RandomState(1).randn(*got.shape), jnp.float32)
        got_grads = pull(probe)
        want, pull_want = jax.vjp(reference.kda_recurrence, *args)
        want_grads = pull_want(probe)
    assert _fused() == before + 1
    assert common._LAST['kda_chunk']['reason'] == 'forced_interpret'
    assert _fused('kda_walk') == walks + walked
    assert common._LAST['kda_walk']['reason'] == (
        'forced_interpret' if walked else 'vmem_over_budget')
    _close(got, want, 2e-5)
    for got_grad, want_grad in zip(got_grads, want_grads):
        _close(got_grad, want_grad, 5e-5)


@pytest.mark.parametrize('h', [12, 3])
def test_the_fused_op_and_the_dense_op_agree_on_bf16_inputs(
        pallas_interpret, h):
    """bf16 q, k, v, beta beside float32 log decays: the preparation's
    kernels read them as they are and widen them on the chip to what
    the dense form's float32 working copies hold (the walk's give o and
    take its cotangent in float32, cast as the dense form's), and the
    two paths' outputs and gradients round to the same bf16 but for an
    ulp."""
    args = _sequence(5, 100, h=h, dv=128, dtype=jnp.bfloat16)
    fused, pull = jax.vjp(kda_ops.gated_delta_rule, *args)
    probe = jnp.asarray(np.random.RandomState(2).randn(*fused.shape),
                        jnp.bfloat16)
    dense, pull_dense = jax.vjp(
        lambda *x: kda_ops._rule(*x, kda_ops.CHUNK, ('dense', 'dense')),
        *args)
    assert fused.dtype == jnp.bfloat16
    assert common._LAST['kda_chunk']['path'] == 'fused'
    assert common._LAST['kda_walk']['path'] == (
        'fused' if h == 3 else 'dense')
    _close(fused, dense, 2 ** -7)
    for x, y in zip(pull(probe), pull_dense(probe)):
        assert x.dtype == y.dtype
        _close(x, y, 2 ** -6)


@pytest.mark.parametrize('kernel,what,kwargs', [
    ('kda_chunk', 'layout', dict(dk=16)),           # dk is no lane tile
    ('kda_chunk', 'layout', dict(dk=128, chunk=40)),    # no sub-chunks
    ('kda_chunk', 'layout', dict(dk=128, dv=8)),    # dv is no lane tile
    ('kda_chunk', 'auto_partitioned', dict(dk=128, auto_partitioned=True)),
    ('kda_walk', 'layout', dict(dk=16, dv=128)),
    ('kda_walk', 'layout', dict(dk=128, dv=192)),   # dv is no lane tile
    ('kda_walk', 'layout', dict(dk=128, dv=128, chunk=12)),   # no tiles
    ('kda_walk', 'vmem_over_budget', dict(dk=128, dv=128, h=12)),
    ('kda_walk', 'auto_partitioned',
     dict(dk=128, dv=128, auto_partitioned=True)),
])
def test_the_dispatch_answers_dense_with_its_reason_counted(
        pallas_interpret, kernel, what, kwargs):
    """Where a kernel's layout does not hold (dk or dv off the 128
    lanes, a chunk of no whole sub-chunks; for the walk, more heads a
    grid step than its VMEM count admits) and where XLA partitions the
    program, the op runs that kernel's dense form, says why, and is the
    recurrence."""
    kwargs = dict(kwargs)
    args = _sequence(7, 50, b=1, h=kwargs.pop('h', 2), dk=kwargs.pop('dk'),
                     dv=kwargs.pop('dv', 128), rate=4.0)
    name = 'pallas/%s/fallback/%s' % (kernel, what)
    before = monitor.counter_value(name) or 0
    fused = _fused(kernel)
    got = kda_ops.gated_delta_rule(*args, **kwargs)
    assert (monitor.counter_value(name) or 0) == before + 1
    assert _fused(kernel) == fused
    assert common._LAST[kernel] == {
        'path': 'dense', 'reason': what, 'interpret': False}
    with jax.default_matmul_precision('highest'):
        _close(got, reference.kda_recurrence(*args), 2e-5)


@pytest.mark.parametrize('kernel', ['kda_chunk', 'kda_walk'])
def test_float64_runs_the_dense_form(pallas_interpret, kernel):
    """Under x64 the working dtype is float64, which the kernels do
    not take: reason 'dtype'."""
    name = 'pallas/%s/fallback/dtype' % kernel
    before = monitor.counter_value(name) or 0
    with jax.enable_x64():
        args = _sequence(8, 40, b=1, h=1, dv=128, dtype=jnp.float64)
        args[3] = args[3].astype(jnp.float64)
        got = kda_ops.gated_delta_rule(*args)
        _close(got, reference.kda_recurrence(*args), 1e-12)
    assert monitor.counter_value(name) == before + 1


@pytest.mark.parametrize('kernel', ['kda_chunk', 'kda_walk'])
def test_off_a_tpu_and_unforced_the_op_is_dense(kernel):
    name = 'pallas/%s/fallback/off_tpu' % kernel
    before = monitor.counter_value(name) or 0
    kda_ops.gated_delta_rule(*_sequence(9, 20, b=1, h=1, dv=128))
    assert monitor.counter_value(name) == before + 1


@pytest.mark.parametrize('kernel', ['kda_chunk', 'kda_walk'])
def test_the_kernel_is_registered_with_its_dense_fallback(kernel):
    entry = common.kernels()[kernel]
    assert entry['has_vjp'] and entry['op_types'] == ('kda_attention',)
    module, name = entry['dense_fallback'].rsplit('.', 1)
    assert module == 'ops.kda_ops' and callable(getattr(kda_ops, name))


def _system(seed, c, dtype, lead=(3, 2), d=24):
    """A chunk's system at its hardest: keys that nearly repeat (every
    entry of ``A`` within a few percent of 1), no decay between them
    and write strengths up to 2, so ``m = beta A`` holds entries near 2
    and the substitution's terms cancel instead of dying out."""
    rng = np.random.RandomState(seed)
    k = rng.randn(*(lead + (1, 32))) + 0.1 * rng.randn(*(lead + (c, 32)))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0, 2, lead + (c, 1))
    beta[..., ::5, :] = 2.0
    m = beta * np.tril(k @ np.swapaxes(k, -1, -2), -1)
    assert np.tril(m / beta, -1).max() > 0.98
    rhs = rng.randn(*(lead + (c, d)))
    return [jnp.asarray(x, dtype) for x in (m, rhs)]


_SYSTEMS = [(c, dtype) for c in (16, 32, 64)
            for dtype in ('float32', 'float64')]


@pytest.mark.parametrize('c,dtype', _SYSTEMS)
def test_the_block_inverse_is_numpys_float64_solve(c, dtype):
    """``_unit_lower_inverse`` and ``_solve`` against
    ``numpy.linalg.solve`` in float64, at one, two and four sub-chunks:
    float32 within what ``triangular_solve`` itself reads on these
    inputs (a few 1e-7 of the largest entry), float64 to rounding."""
    with jax.enable_x64(dtype == 'float64'):
        m, rhs = _system(c, c, dtype)
        inverse = kda_ops._unit_lower_inverse(m)
        got = kda_ops._solve(m, rhs)
        assert inverse.dtype == got.dtype == jnp.dtype(dtype)
    system = np.eye(c) + np.asarray(m, np.float64)
    rtol = 2e-6 if dtype == 'float32' else 1e-14
    _close(inverse, np.linalg.solve(
        system, np.broadcast_to(np.eye(c), system.shape)), rtol)
    _close(got, np.linalg.solve(system, np.asarray(rhs, np.float64)), rtol)
    assert (np.asarray(inverse)[..., np.triu(np.ones((c, c), bool), 1)]
            == 0).all()


@pytest.mark.parametrize('c,dtype', _SYSTEMS)
def test_the_block_inverses_gradients_are_triangular_solves(c, dtype):
    """The closed-form backward of ``_solve`` against autodiff through
    ``jax.lax.linalg.triangular_solve`` (what ``_prepare`` called until
    PR 59) on the same system and cotangent."""
    def reference(m, rhs):
        return jax.lax.linalg.triangular_solve(
            jnp.eye(c, dtype=m.dtype) + m, rhs, left_side=True,
            lower=True, unit_diagonal=True)

    with jax.enable_x64(dtype == 'float64'):
        m, rhs = _system(100 + c, c, dtype)
        probe = jnp.asarray(
            np.random.RandomState(3).randn(*rhs.shape), dtype)
        got = jax.vjp(kda_ops._solve, m, rhs)[1](probe)
        want = jax.vjp(reference, m, rhs)[1](probe)
    for x, y in zip(got, want):
        assert x.dtype == jnp.dtype(dtype)
        _close(x, y, 5e-6 if dtype == 'float32' else 1e-13)


def _held(eqn):
    """The jaxprs an equation holds (a scan's, a custom_vjp call's, a
    pjit's)."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            sub = getattr(sub, 'jaxpr', sub)
            if hasattr(sub, 'eqns'):
                yield sub


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr and in the jaxprs its
    equations hold (scans, custom_vjp calls, pjit), a kernel's body
    left out: a ``pallas_call`` is one equation."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name != 'pallas_call':
            for sub in _held(eqn):
                yield from _primitives(sub)


def _preparations(jaxpr):
    """(the jaxpr it stands in, the equation, the operands that are
    the op's inputs) of every ``kda_chunk`` call under ``jaxpr`` and of
    every equation that holds one."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            if eqn.params['name'].startswith('kda_chunk'):
                yield jaxpr, eqn, 5
            continue
        inside = [x for sub in _held(eqn) for x in _preparations(sub)]
        if inside:
            yield jaxpr, eqn, len(eqn.invars)
            yield from inside


def _made_from(jaxpr, call, operands):
    """The primitives of ``jaxpr``'s own equations that ``call``'s
    first ``operands`` operands are made by, back to its inputs."""
    made_by = {out: eqn for eqn in jaxpr.eqns for out in eqn.outvars}
    found, todo = set(), list(call.invars[:operands])
    while todo:
        var = todo.pop()
        eqn = None if isinstance(var, Literal) else made_by.get(var)
        if eqn is None:
            continue
        found.add(eqn.primitive.name)
        if eqn.primitive.name == 'optimization_barrier':
            # a barrier hands each operand through as it is
            todo.append(eqn.invars[eqn.outvars.index(var)])
        else:
            todo.extend(eqn.invars)
    return found


@pytest.mark.parametrize('path', [('dense', 'dense'), ('interpret', 'dense'),
                                  ('interpret', 'interpret')])
def test_the_op_holds_no_solve_and_no_loop_but_its_scans(path):
    """Forward and backward of the whole op, traced: no
    ``triangular_solve`` and no ``while`` on any path; the products are
    there and, outside the kernels' bodies, the two scans alone where
    the chunks are walked densely and NO loop where the walk's kernels
    run: five kernel calls then (the preparation's forward and the
    forward walk; the preparation's forward again, the reverse walk, the
    preparation's backward), three with the preparation's kernels
    alone.  On the fused path no running sum is XLA's, and what the
    preparation's calls read of the op's five inputs is the inputs
    themselves, reshaped (in the backward behind the barrier that
    keeps the recomputation there): no ``cumsum``, ``concatenate``,
    ``transpose``, ``pad`` or cast stands between."""
    args = _sequence(11, 100, b=1, h=2, dv=128)

    def both(*x):
        out, pull = jax.vjp(
            lambda *y: kda_ops._rule(*y, kda_ops.CHUNK, path), *x)
        return pull(out)

    jaxpr = jax.make_jaxpr(both)(*args).jaxpr
    found = list(_primitives(jaxpr))
    assert 'dot_general' in found
    assert found.count('scan') == (2 if path[1] == 'dense' else 0)
    assert found.count('pallas_call') == \
        3 * (path[0] != 'dense') + 2 * (path[1] != 'dense')
    assert not {'triangular_solve', 'while'} & set(found)
    assert ('cumsum' in found) == (path[0] == 'dense')
    prepared = list(_preparations(jaxpr))
    assert sum(eqn.primitive.name == 'pallas_call'
               for _, eqn, _ in prepared) == 3 * (path[0] != 'dense')
    for inside, eqn, operands in prepared:
        assert _made_from(inside, eqn, operands) <= {
            'reshape', 'optimization_barrier'}, eqn


def _walked(operands):
    """The dense walk: the ``lax.scan`` over ``_step`` as ``_forward``
    runs it -> (o [N, B, H, C, dv], the state at each chunk's start)."""
    def step(state, x):
        after, out = kda_ops._step(state, x)
        return after, (out, state)

    w_k, w_v = operands[0], operands[1]
    zero = jnp.zeros(w_k.shape[1:3] + (w_k.shape[-1], w_v.shape[-1]),
                     w_k.dtype)
    return jax.lax.scan(step, zero, operands)[1]


def _walked_back(operands, starts, d_out):
    """The dense reverse walk, as ``_rule_bwd`` runs it -> (the start
    state's cotangent, the six operands' cotangents)."""
    def step(d_state, x):
        chunk_operands, start, d_chunk_out = x
        _, pull_step = jax.vjp(kda_ops._step, start, chunk_operands)
        return pull_step((d_state, d_chunk_out))

    return jax.lax.scan(step, jnp.zeros_like(starts[0]),
                        (operands, starts, d_out), reverse=True)


@pytest.mark.parametrize('t,h,heads', [
    (128, 4, 2),        # whole chunks, two head blocks a sequence
    (128, 4, None),     # the same in one block (heads_a_step(4) == 4)
    (100, 4, 1),        # a padded tail, a head a grid step
    (64, 3, None),      # one chunk
    (24, 2, 1),         # less than one: a chunk of two sub-chunks
    (200, 16, None),    # eight of sixteen heads a grid step
])
def test_the_walks_are_the_scans_over_the_step(t, h, heads):
    """The forward walk's o and starts and the reverse walk's
    cotangents and final dS against the ``lax.scan`` over ``_step`` and
    over its ``jax.vjp``, on operands ``_prepare`` made from two
    sequences (the state is zeroed at each sequence's first chunk: the
    scratch still holds the one before's last state).  The kernels
    read W_k and W_v out of the ONE array the solve's product leaves,
    [W_k | W_v], and write their cotangents into one."""
    args = _sequence(t + h, t, b=2, h=h, dv=128)
    operands = kda_ops._operands(*args, kda_ops.CHUNK, ('dense', 'dense'))
    one = kda_ops._operands(*args, kda_ops.CHUNK, ('dense', 'interpret'))
    size, n = kda_ops._layout(t, kda_ops.CHUNK)
    assert operands[0].shape[:4] == (n, 2, h, size)
    assert len(one) == 5 and one[0].shape == (n, 2, h, size, 128 + 128)
    if heads is None:
        assert kda_walk.heads_a_step(h) == min(h, kda_walk.HEADS)
    out, starts = _walked(operands)
    got, got_starts = kda_walk.forward(one, heads=heads, interpret=True)
    assert got.shape == out.shape and got.dtype == jnp.float32
    _close(got, out, 1e-6)
    # the kernels keep the state transposed, S^T [dv, dk]
    _close(jnp.swapaxes(got_starts, -1, -2), starts, 1e-6)
    assert (np.asarray(got_starts[0]) == 0).all()
    # a cotangent of o as the op hands it over: zero on the padded tail
    probe = kda_ops._chunked(jnp.asarray(np.random.RandomState(2).randn(
        2, t, h, 128), jnp.float32), size, n, jnp.float32)
    d_start, grads = _walked_back(operands, starts, probe)
    got_grads, got_d_start = kda_walk.reverse(
        one, got_starts, probe, heads=heads, interpret=True)
    assert len(got_grads) == 5
    got_grads = (got_grads[0][..., :128], got_grads[0][..., 128:]) + \
        got_grads[1:]
    for x, y in zip(got_grads, grads):
        assert x.shape == y.shape and x.dtype == y.dtype
        _close(x, y, 2e-6)
    _close(jnp.swapaxes(got_d_start, -1, -2), d_start, 2e-6)


def test_the_walks_gates_read_the_shapes():
    """``kda_walk.checks`` at the two cells' layer shapes and off them:
    float32, both widths whole lane tiles, whole sublane tiles a chunk, and
    the reverse call's VMEM count (eight heads a step at both cells'
    head counts) under the budget of a call that asks for nothing."""
    for heads in (8, 32):
        assert kda_walk.heads_a_step(heads) == 8
        assert all(ok for _, ok in kda_walk.checks(
            heads, 64, 128, 128, jnp.float32))
    assert kda_walk.reverse_vmem(8, 64, 128, 128) < \
        common.VMEM_BUDGET_BYTES < kda_walk.reverse_vmem(16, 64, 128, 128)
    failing = {
        'dtype': kda_walk.checks(8, 64, 128, 128, jnp.float64),
        'layout': kda_walk.checks(8, 64, 128, 64, jnp.float32),
        'vmem_over_budget': kda_walk.checks(8, 64, 512, 512, jnp.float32),
    }
    for reason, gates in failing.items():
        assert [name for name, ok in gates if not ok][0] == reason
