"""Phi-4-mini-flash through fluid against its plain reference
(``paddle_tpu/models/reference/phi4flash.py``): the ``selective_scan``
op (chunked, a ``custom_vjp`` that keeps boundary states) against the
recurrence stepped a token at a time, forward and all six gradients,
at one token, at lengths that are no whole number of chunks, with bf16
inputs beside a float32 state, sequences of a batch apart, and no [B,
T, D, N] array on either pass; ``short_conv``'s bias; differential
attention as ONE call against the four products; the layer rule and the
parameter count; the zoo program's loss and every parameter's gradient,
with and without the recompute groups, among them the gradients that
reach layer L/2's Mamba through the gated memory units and layer L/2 +
1's keys and values through the cross layer.  CPU, tiny sizes; the
published widths are checked on the chip (``chip_smoke.py --phase
phi4flash``, PERF.md)."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import phi4flash as zoo
from paddle_tpu.models.reference import phi4flash as reference
from paddle_tpu.ops import registry, ssm_ops

SEQ = 24
SLOTS = ('X', 'Delta', 'A', 'B', 'C', 'D')


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


# --- the op -----------------------------------------------------------


class _Ctx(object):
    auto_partitioned = False


@jax.jit
def _op(*args):
    return registry.get('selective_scan').run(
        _Ctx(), {s: [x] for s, x in zip(SLOTS, args)}, {})['Out'][0]


@jax.jit
def _op_grads(probe, *args):
    ins = {s: [x] for s, x in zip(SLOTS, args)}
    ins['GRAD::Out'] = [probe]
    out = registry.get('selective_scan_grad').run(_Ctx(), ins, {})
    return tuple(out['GRAD::' + s][0] for s in SLOTS)


_loop = jax.jit(reference.selective_scan)


def _inputs(seed, b=2, t=100, d=24, n=4, dtype=jnp.float32):
    """Steps from 0.001 to 3 (decays from nearly 1 to exp(-48) a
    token), A = -(1 .. n) a channel with a channel's own factor."""
    rng = np.random.RandomState(seed)
    x = rng.randn(b, t, d)
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (b, t, d)))
    a = -np.arange(1, n + 1) * rng.uniform(0.5, 4.0, (d, 1))
    bm, cm = rng.randn(b, t, n), rng.randn(b, t, n)
    skip = rng.randn(d)
    return [jnp.asarray(v, jnp.float32 if i in (1, 2, 5) else dtype)
            for i, v in enumerate((x, delta, a, bm, cm, skip))]


@pytest.mark.parametrize('t,chunk', [(100, 256), (1, 256), (300, 256),
                                     (37, 8), (64, 16), (5, 1)])
def test_the_scan_is_the_token_loop_forward_and_in_all_six_gradients(
        t, chunk):
    """float32 against the token-by-token loop, on one token, on
    lengths under a chunk, a whole number of chunks and no whole number
    of them: the registered op and its gradient at the op's own chunk
    of 256, the function they lower to at another."""
    args = _inputs(t, t=t)
    want = _loop(*args)
    probe = jnp.asarray(np.random.RandomState(1).randn(*want.shape),
                        jnp.float32)
    if chunk == ssm_ops.CHUNK:
        got, got_grads = _op(*args), _op_grads(probe, *args)
    else:
        got, pull = jax.vjp(
            lambda *x: ssm_ops.selective_scan(*x, chunk=chunk), *args)
        got_grads = pull(probe)
    want_grads = jax.jit(jax.grad(
        lambda *x: jnp.sum(reference.selective_scan(*x) * probe),
        argnums=range(6)))(*args)
    _close(got, want, 2e-6)
    for got_grad, want_grad in zip(got_grads, want_grads):
        _close(got_grad, want_grad, 2e-5)


def test_float64_is_the_loop_to_rounding():
    """No clamp, floor or dropped term stands behind the float32
    agreement: in float64 the op and its gradients are the loop's to
    1e-12."""
    with jax.enable_x64():
        args = [jnp.asarray(np.asarray(v), jnp.float64)
                for v in _inputs(2, t=70)]
        probe = jnp.asarray(np.random.RandomState(3).randn(2, 70, 24))
        got = jax.grad(lambda *x: jnp.sum(
            ssm_ops.selective_scan(*x, chunk=16) * probe),
            argnums=range(6))(*args)
        want = jax.grad(lambda *x: jnp.sum(
            reference.selective_scan(*x) * probe), argnums=range(6))(*args)
        _close(ssm_ops.selective_scan(*args, chunk=16),
               reference.selective_scan(*args), 1e-12)
        for g, w in zip(got, want):
            _close(g, w, 1e-11)


def test_no_state_crosses_from_one_sequence_of_a_batch_into_the_next():
    """Each sequence's output in a batch of two is what it is alone,
    and replacing the OTHER sequence changes nothing."""
    args = _inputs(4, t=80)
    both = np.asarray(_op(*args))
    per_sequence = (0, 1, 3, 4)
    for i in range(2):
        alone = np.asarray(_op(*(
            x[i:i + 1] if j in per_sequence else x
            for j, x in enumerate(args))))
        assert np.abs(both[i:i + 1] - alone).max() <= 1e-6
    fresh = _inputs(5, t=80)
    other = [x.at[0].set(fresh[j][0]) if j in per_sequence else x
             for j, x in enumerate(args)]
    assert (np.asarray(_op(*other))[1] == both[1]).all()


def test_bf16_inputs_keep_the_steps_and_the_state_float32():
    """bf16 x, B, C beside float32 steps, A and D: the output is bf16,
    and it is the float32 loop on those same rounded inputs to bf16's
    own rounding of each element of the OUTPUT, which a bf16 state
    between tokens misses by more than twice that in over a twentieth of
    the elements.  The gradients come back in
    each operand's own type.  The counter moves once a lowering."""
    args = _inputs(7, dtype=jnp.bfloat16)
    args[1] = jnp.minimum(args[1], 0.05)        # slow decays: a long memory
    before = monitor.flat().get('ssm/calls', 0)
    out = _op(*args)
    assert out.dtype == jnp.bfloat16
    assert monitor.flat()['ssm/calls'] == before + 1
    wide = [v.astype(jnp.float32) for v in args]
    want = np.asarray(_loop(*wide))
    crude = np.asarray(jax.jit(functools.partial(
        reference.selective_scan, state_dtype=jnp.bfloat16))(*wide))
    # element by element: one bf16 rounding of the output itself
    allowed = 2 ** -8 * np.abs(want) + 1e-6 * np.abs(want).max()
    assert (np.abs(np.asarray(out, np.float32) - want) <= allowed).all()
    assert (np.abs(crude - want) > 2 * allowed).mean() > 0.05
    grads = _op_grads(jnp.ones_like(out), *args)
    assert [g.dtype for g in grads] == [v.dtype for v in args]


def _every_shape(jaxpr, seen):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, 'shape'):
                seen.append(tuple(v.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _every_shape(sub, seen)
    return seen


def test_no_array_of_every_token_s_state_exists_on_either_pass():
    """The jaxpr of the op's value and gradients at 2048 tokens in
    chunks of 64 holds no array as large as [B, T, D, N]: the largest
    that carries the state's two axes is a chunk's ([64, B, N, D], the
    states before each of its tokens) and what crosses the passes is
    the state at each chunk's start ([32, B, N, D])."""
    b, t, d, n, chunk = 1, 2048, 24, 4, 64
    args = _inputs(9, b=b, t=t, d=d, n=n)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *x: jnp.sum(ssm_ops.selective_scan(*x, chunk=chunk)),
        argnums=range(6)))(*args)
    shapes = _every_shape(jaxpr.jaxpr, [])
    assert max(int(np.prod(s)) for s in shapes) < b * t * d * n
    with_state = [s for s in shapes if s[-2:] == (n, d)]
    assert (chunk, b, n, d) in with_state
    assert (t // chunk, b, n, d) in with_state
    assert max(int(np.prod(s)) for s in with_state) == chunk * b * n * d


def test_the_layer_infers_its_shape_and_counts_its_chunks():
    """``layers.selective_scan`` on a length that is no whole number of
    chunks: the output has x's shape; a run of the program sets
    ``ssm/chunks`` to the trips of its one scan (2 chunks of 256 for
    300 tokens) and, with no gradient asked, keeps no boundary
    state."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x, delta = (layers.data(n, shape=[300, 24], dtype='float32')
                    for n in ('x', 'delta'))
        bm, cm = (layers.data(n, shape=[300, 4], dtype='float32')
                  for n in ('bm', 'cm'))
        a = layers.data('a', shape=[24, 4], dtype='float32',
                        append_batch_size=False)
        skip = layers.data('skip', shape=[24], dtype='float32',
                           append_batch_size=False)
        out = layers.selective_scan(x, delta, a, bm, cm, skip)
    assert tuple(out.shape) == (-1, 300, 24)
    args = _inputs(8, b=1, t=300)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        got, = exe.run(main, feed=dict(zip(
            ('x', 'delta', 'a', 'bm', 'cm', 'skip'),
            (np.asarray(v) for v in args))), fetch_list=[out])
    assert monitor.gauge_value('ssm/chunks') == 2
    assert monitor.gauge_value('ssm/boundary_state_mb') == 0
    _close(got, _loop(*args), 2e-6)


# --- short_conv's bias ------------------------------------------------


def test_short_conv_adds_its_bias_before_the_cast_and_sums_its_gradient():
    """Filter + Bias in one op: the reference's filter, in bf16 as in
    float32, the bias's gradient the cotangent's sum over B and T; no
    Bias, no change (LFM2's and Solar's calls)."""
    rng = np.random.RandomState(0)
    x, w, b = (jnp.asarray(rng.randn(*s), jnp.float32)
               for s in ((2, 30, 6), (6, 4), (6,)))
    conv = registry.get('short_conv')

    def run(x, w, b=None):
        ins = {'X': [x], 'Filter': [w]}
        if b is not None:
            ins['Bias'] = [b]
        return conv.run(_Ctx(), ins, {})['Out'][0]

    _close(run(x, w, b), reference.causal_filter(x, w, b), 1e-6)
    assert (np.asarray(run(x, w)) ==
            np.asarray(reference.causal_filter(x, w, 0.0))).all()
    low = run(x.astype(jnp.bfloat16), w, b)
    assert low.dtype == jnp.bfloat16
    _close(low.astype(jnp.float32), reference.causal_filter(
        x.astype(jnp.bfloat16).astype(jnp.float32), w, b), 2 ** -8)
    probe = jnp.asarray(rng.randn(2, 30, 6), jnp.float32)
    grads = registry.get('short_conv_grad').run(_Ctx(), {
        'X': [x], 'Filter': [w], 'Bias': [b], 'GRAD::Out': [probe]}, {})
    _close(grads['GRAD::Bias'][0], probe.sum((0, 1)), 1e-6)
    want = jax.grad(lambda x, w: jnp.sum(
        reference.causal_filter(x, w, b) * probe), argnums=(0, 1))(x, w)
    _close(grads['GRAD::X'][0], want[0], 1e-6)
    _close(grads['GRAD::Filter'][0], want[1], 1e-6)


# --- differential attention -------------------------------------------


@pytest.mark.parametrize('window', [0, 5])
def test_one_call_of_forty_heads_is_the_four_products(window):
    """Q = [q1 heads, q2 heads] over K = [k1, k2] and V = [V, V] in ONE
    ``fused_multihead_attention`` call, then attn1 - lam attn2, the
    sub-norm and (1 - lam0): the reference's four products P(q_a, k_a)
    v_b, full and under a window."""
    cfg, i = zoo.TINY, 3
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    rng = np.random.RandomState(window)
    q = rng.randn(2, SEQ, h * d).astype('float32')
    k, v = (rng.randn(2, SEQ, kv * d).astype('float32') for _ in range(2))
    p = {n: rng.randn(*s).astype('float32') * 0.5 for n, s in (
        ('lq1', (d,)), ('lk1', (d,)), ('lq2', (d,)), ('lk2', (d,)))}
    p['subln_g'] = 1 + 0.1 * rng.randn(2 * d).astype('float32')
    p['wo'] = np.eye(h * d, cfg.hidden, dtype='float32')
    p['bo'] = np.zeros(cfg.hidden, 'float32')

    class Given(object):
        """The parameters as the operator asks for them, at the values
        above."""
        @staticmethod
        def take(what):
            return layers.assign(p[what])

        @staticmethod
        def attr(what):
            return fluid.ParamAttr(
                initializer=fluid.initializer.NumpyArrayInitializer(p[what]))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = [layers.data(n, shape=[SEQ, w * d], dtype='float32')
                 for n, w in (('q', h), ('k', kv), ('v', kv))]
        keys, values = zoo.shared_keys_values(feeds[1], feeds[2], cfg)
        out = zoo.differential_attention(feeds[0], keys, values, i,
                                         window, Given, cfg)
    call, = [op for op in main.global_block().ops
             if op.type == 'fused_multihead_attention']
    block = main.global_block()
    assert [tuple(block.var(call.inputs[s][0]).shape)[2:]
            for s in 'QKV'] == [(h, d), (kv, d), (kv, 2 * d)]
    assert call.attrs.get('window', 0) == window
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, = exe.run(main, feed={'q': q, 'k': k, 'v': v},
                       fetch_list=[out])
    sizes = reference.sizes_of(cfg)
    with jax.default_matmul_precision('highest'):
        want = reference.differential_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), p, i, window,
            sizes)
        plain = reference.differential_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), p, i, window,
            sizes, without=('lambda',))
    _close(got, want, 5e-6)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2


# --- the model --------------------------------------------------------


@pytest.mark.parametrize('layers_,counts', [
    (8, (3, 2, 1, 1, 1)), (16, (5, 4, 1, 3, 3)), (32, (9, 8, 1, 7, 7))])
def test_the_layer_rule_places_every_kind(layers_, counts):
    """Mamba, windowed, full, GMU, cross at L = 8, 16, 32 (the
    published 9 / 8 / 1 / 7 / 7); the memory's layer is a Mamba, the
    layer after it the one full attention, and program, reference and
    the benchmark's count read the same rule."""
    from benchmark.lib import phi4flash_flops
    kinds = zoo.layer_kinds(layers_)
    assert kinds == reference.layer_kinds(layers_) == \
        phi4flash_flops.layer_kinds(layers_)
    assert tuple(kinds.count(k) for k in (
        zoo.MAMBA, zoo.WINDOW, zoo.FULL, zoo.GMU, zoo.CROSS)) == counts
    half = layers_ // 2
    assert kinds[half] == zoo.MAMBA and kinds[half + 1] == zoo.FULL
    assert kinds[half + 2] == zoo.GMU and kinds[half + 3] == zoo.CROSS
    with pytest.raises(ValueError):
        zoo.layer_kinds(layers_ + 2)


def test_the_published_sizes_count_the_model_card_s_parameters():
    """32 layers and 200064 rows at the published widths: 3.85 B
    parameters within 1% (the model card's 3.8 B); the cell's cut (8
    layers, 25008 rows) 915 M; the specs the program creates its
    parameters from and the benchmark's hand count agree to the
    parameter."""
    from benchmark.lib import phi4flash_flops
    specs = zoo.parameter_specs(zoo.BASE)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert abs(total - 3.85e9) <= 0.01 * 3.85e9
    sizes = reference.sizes_of(zoo.BASE)
    assert phi4flash_flops.parameter_count(sizes) == total
    cut = zoo.Phi4FlashConfig(layers=8, vocab_size=25008)
    held = sum(int(np.prod(shape))
               for _, shape, _ in zoo.parameter_specs(cut))
    assert abs(held - 915.1e6) <= 0.005 * 915.1e6
    assert phi4flash_flops.parameter_count(reference.sizes_of(cut)) == held
    assert zoo.BASE.dt_rank == 160 and zoo.BASE.d_inner == 5120


def _seeded_weights(specs, seed):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains and skips around 1, biases and
    filters of order 1, lambdas of order 1/2, steps from 0.02 to 1."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape, _ in specs:
        what = name.rsplit('.', 1)[1]
        if what == 'a_log':
            w = np.log(np.tile(np.arange(1, shape[1] + 1.0), (shape[0], 1)))
        elif what == 'b_dt':
            w = rng.uniform(-4, 0, shape)
        elif what in ('g', 'subln_g', 'd'):
            w = 1 + 0.1 * rng.randn(*shape)
        elif what in ('lq1', 'lk1', 'lq2', 'lk2'):
            w = 0.5 * rng.randn(*shape)
        elif len(shape) == 1:
            w = 0.3 * rng.randn(*shape)
        elif what in ('conv_w', 'embed_tokens'):
            w = rng.randn(*shape)
        else:
            w = rng.randn(*shape) / np.sqrt(shape[0])
        out[name] = w.astype('float32')
    return out


def _weights_and_feed(cfg, seed):
    return (_seeded_weights(zoo.parameter_specs(cfg), seed),
            zoo.synthetic_batch(cfg, 2, SEQ, np.random.RandomState(seed)))


def _program(cfg, seed, recompute=True, amp=False, extra=()):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights -> (loss, {param: grad}, weights,
    feed, the ``extra`` fetches)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, _, loss = zoo.build_pretrain(cfg, SEQ, recompute=recompute)
            names = [p.name for p in main.all_parameters()]
            optimizer = fluid.optimizer.SGD(0.0)
            if amp:
                optimizer = fluid.contrib.mixed_precision.decorate(
                    optimizer, use_dynamic_loss_scaling=False,
                    init_loss_scaling=1.0)
            pairs = optimizer.minimize(loss)[1]
        assert [(n, list(main.global_block().var(n).shape))
                for n in names] == [(n, shape) for n, shape, _ in
                                    zoo.parameter_specs(cfg)]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights, feed = _weights_and_feed(cfg, seed)
        scope = fluid.global_scope()
        for name, w in weights.items():
            scope.set_var(name, jnp.asarray(w))
        fetched = [n(main) for n in extra]
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs] +
                      fetched, return_numpy=False)
    n = 1 + len(pairs)
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:n])}
    return _scalar(out[0]), grads, weights, feed, out[n:]


def _reference(cfg, weights, feed, **kw):
    f = reference.loss if kw else reference.loss_and_grads
    return jax.jit(functools.partial(
        f, sizes=reference.sizes_of(cfg), **kw))(
        weights, feed['ids'], feed['labels'])


def test_tiny_f32_loss_and_every_gradient_match_the_reference():
    """Float32 program against the float32 reference at L = 8, both at
    full matmul precision: every kind of layer, every parameter.  With
    and without the recompute groups the loss is the same to the bit
    and the gradients to rounding.  The gradients of the memory's Mamba
    (layer 4) and of the shared K and V (layer 5's Wqkv) hold what
    reaches them THROUGH the gated memory unit and the cross layer: the
    reference without those paths gives other numbers.  ``ssm/chunks``
    and ``ssm/boundary_state_mb`` are sums over ONE traced train
    program."""
    cfg = zoo.TINY
    groups = monitor.flat().get('executor/recompute_groups', 0)
    loss, grads, weights, feed, _ = _program(cfg, 3)
    assert monitor.flat()['executor/recompute_groups'] >= groups + 8
    # three Mamba layers, one chunk each at 24 tokens: scanned forward,
    # once more in the recompute group's second forward, walked in
    # reverse; one [B, N, D] float32 state kept a chunk and layer
    assert monitor.gauge_value('ssm/chunks') == 3 * 1 * 3
    kept = 3 * 2 * cfg.d_state * cfg.d_inner * 4 / 1e6
    assert abs(monitor.gauge_value('ssm/boundary_state_mb') - kept) < 1e-9
    plain_loss, plain_grads, _, _, _ = _program(cfg, 3, recompute=False)
    assert monitor.gauge_value('ssm/chunks') == 3 * 1 * 2
    assert abs(monitor.gauge_value('ssm/boundary_state_mb') - kept) < 1e-9
    want, want_grads = _reference(cfg, weights, feed)
    assert loss == plain_loss
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(weights) == set(plain_grads)
    for name in weights:
        g = np.asarray(want_grads[name])
        assert np.abs(g).max() > 0, name
        assert np.abs(grads[name] - g).max() <= 1e-4 * np.abs(g).max(), \
            name
        assert np.abs(grads[name] - plain_grads[name]).max() <= \
            1e-5 * np.abs(g).max(), name
    memory, shared = 'phi4flash.4.mamba.w_x', \
        'phi4flash.5.full_attention.wqkv'
    cut = jax.jit(jax.grad(lambda p: reference.loss(
        p, feed['ids'], feed['labels'], sizes=reference.sizes_of(cfg),
        without=('memory_gradient', 'shared_gradient'))))(
        {k: jnp.asarray(v) for k, v in weights.items()})
    for name in (memory, shared):
        g = np.asarray(want_grads[name])
        assert np.abs(np.asarray(cut[name]) - g).max() > \
            1e-2 * np.abs(g).max(), name
    # the query columns of layer 5's Wqkv see no cross layer
    q_columns = cfg.heads * cfg.head_dim
    g = np.asarray(want_grads[shared])
    assert np.abs(np.asarray(cut[shared]) - g)[:, :q_columns].max() <= \
        1e-5 * np.abs(g).max()


@pytest.mark.parametrize('part', ['skip', 'lambda', 'window_511'])
def test_each_part_moves_the_loss(part):
    """A dropped D * x, a lambda left at lam0 and a window of one key
    fewer each move the reference's loss by far more than the program
    lies from it."""
    weights, feed = _weights_and_feed(zoo.TINY, 5)
    want = float(_reference(zoo.TINY, weights, feed)[0])
    without = float(_reference(zoo.TINY, weights, feed, without=(part,)))
    assert abs(without - want) > 1e-4 * abs(want)


def _input_of(op_type, slot, nth=0):
    def name(main):
        ops = [op for op in main.global_block().ops if op.type == op_type]
        return ops[nth].inputs[slot][0]
    return name


def test_bf16_amp_keeps_the_steps_float32_beside_bf16_x_b_c():
    """Under bf16 AMP the scan's x, B and C arrive bf16 and its steps,
    A and D float32 (``keep_float32`` on the add that meets ``b_dt``;
    the op is exempt from the gray rule's cast down); the attention
    call's operands are bf16; the loss is the float32 reference's to
    bf16 matmuls' rounding."""
    extra = [_input_of('selective_scan', s) for s in SLOTS] + \
        [_input_of('fused_multihead_attention', s) for s in 'QKV']
    loss, _, weights, feed, fetched = _program(zoo.TINY, 5, amp=True,
                                               extra=extra)
    dtypes = [jnp.asarray(x).dtype.name for x in fetched]
    assert dtypes == ['bfloat16', 'float32', 'float32', 'bfloat16',
                      'bfloat16', 'float32'] + ['bfloat16'] * 3
    assert (np.asarray(fetched[1]) > 0).all()
    assert (np.asarray(fetched[2]) < 0).all()
    want = float(_reference(zoo.TINY, weights, feed)[0])
    assert 0 < abs(loss - want) <= 1e-2 * want


def test_the_program_holds_the_model_s_own_operators_in_their_order():
    """Eight layers: Mamba, window, Mamba, window, Mamba, full, GMU,
    cross; the filters carry their bias; the cross layer projects q
    alone and reads layer 5's K and V; the GMU reads layer 4's scan
    output."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        zoo.build_pretrain(zoo.TINY, SEQ)
    ops = main.global_block().ops
    assert all(sorted(op.inputs) == ['Bias', 'Filter', 'X'] for op in ops
               if op.type == 'short_conv')
    kinds = [(op.type, op.attrs.get('window', 0)) for op in ops
             if op.type in ('selective_scan', 'fused_multihead_attention')]
    scan, window, full = ('selective_scan', 0), \
        ('fused_multihead_attention', zoo.TINY.window), \
        ('fused_multihead_attention', 0)
    assert kinds == [scan, window, scan, window, scan, full, full]
    calls = [op for op in ops if op.type == 'fused_multihead_attention']
    assert calls[3].inputs['K'] == calls[2].inputs['K']
    assert calls[3].inputs['V'] == calls[2].inputs['V']
    assert calls[3].inputs['Q'] != calls[2].inputs['Q']
    memory = [op for op in ops
              if op.type == 'selective_scan'][2].outputs['Out'][0]
    readers = [op for op in ops if memory in
               [n for names in op.inputs.values() for n in names]]
    assert [op.type for op in readers].count('elementwise_mul') == 2
    names = zoo.parameter_names(zoo.TINY)
    assert 'phi4flash.7.cross_attention.wq' in names
    assert not any('phi4flash.7.cross_attention.wqkv' in n for n in names)
    assert [n for n in names if n.startswith('phi4flash.6.gmu.')] == \
        ['phi4flash.6.gmu.w_in', 'phi4flash.6.gmu.w_out']


def test_the_startup_draws_keep_a_random_model_finite():
    """A = -(1 .. N) a channel, D = 1, steps log-uniform in about
    (0.001, 0.1), filters and their bias Uniform(-1/2, 1/2), lambdas
    Normal(0, 0.1), gains 1: the startup state's loss is near log V."""
    cfg = zoo.TINY
    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = 11
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, _, loss = zoo.build_pretrain(cfg, SEQ)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        values = {n: np.asarray(fluid.core.as_array(scope.find_var(n)))
                  for n in zoo.parameter_names(cfg)}
        feed = zoo.synthetic_batch(cfg, 2, SEQ, np.random.RandomState(0))
        got = _scalar(exe.run(main.clone(for_test=True), feed=feed,
                              fetch_list=[loss])[0])
    a_log = values['phi4flash.0.mamba.a_log']
    assert np.allclose(np.exp(a_log), np.arange(1, cfg.d_state + 1))
    assert (values['phi4flash.0.mamba.d'] == 1).all()
    steps = np.log1p(np.exp(values['phi4flash.2.mamba.b_dt']))
    assert 0.9e-3 < steps.min() and steps.max() < 0.11
    assert np.abs(values['phi4flash.0.mamba.conv_w']).max() <= 0.5
    assert np.abs(values['phi4flash.0.mamba.conv_b']).max() <= 0.5
    assert (values['phi4flash.1.ln1.g'] == 1).all()
    assert abs(got - np.log(cfg.vocab_size)) < 0.2
    want = float(_reference(cfg, values, feed)[0])
    assert abs(got - want) <= 2e-6 * want
