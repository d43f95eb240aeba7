"""Solar Open 2 through fluid against its plain reference
(``paddle_tpu/models/reference/solar_open2.py``): the ``kda_attention``
op (the gated delta rule with a per-channel decay, in chunks) against
the recurrence stepped a token at a time, forward and all five
gradients, at lengths that are no whole number of chunks, with beta
above 1, with decays whose factored form overflows float32, sequences
of a batch apart and the future unseen; the zoo program's loss and
every parameter's gradient; the float32 log decays under bf16 AMP; the
shares of a layer's HEADS and of its experts adding up to the uncut
layer.  CPU, tiny sizes; the published widths are checked on the chip
(``chip_smoke.py --phase solar``, PERF.md)."""

import copy
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import solar_open2 as solar
from paddle_tpu.models.reference import solar_open2 as reference
from paddle_tpu.ops import kda_ops, registry

SEQ = 40

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(solar.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


# --- the op -----------------------------------------------------------


class _Ctx(object):
    auto_partitioned = False


@jax.jit
def _op(q, k, v, a, beta):
    return registry.get('kda_attention').run(
        _Ctx(), {'Q': [q], 'K': [k], 'V': [v], 'A': [a], 'Beta': [beta]},
        {})['Out'][0]


@jax.jit
def _op_grads(q, k, v, a, beta, probe):
    out = registry.get('kda_attention_grad').run(_Ctx(), {
        'Q': [q], 'K': [k], 'V': [v], 'A': [a], 'Beta': [beta],
        'GRAD::Out': [probe]}, {})
    return tuple(out['GRAD::' + slot][0]
                 for slot in ('Q', 'K', 'V', 'A', 'Beta'))


_recurrence = jax.jit(reference.kda_recurrence)


def _inputs(seed, b=2, t=100, h=3, dk=16, dv=8, rate=16.0,
            dtype=jnp.float32):
    """Unit q and k, beta in (0, 2) with most of it above 1, log decays
    down to -rate x softplus(.) a token and channel: at ``rate`` 16 the
    running sum passes -88 (where exp(-G) leaves float32) inside one
    chunk."""
    rng = np.random.RandomState(seed)
    q, k = (rng.randn(b, t, h, dk) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, t, h, dv)
    a = -rate * np.log1p(np.exp(rng.randn(b, t, h, dk))) * \
        rng.uniform(0, 1, (b, t, h, dk))
    beta = 2 / (1 + np.exp(-1 - rng.randn(b, t, h)))
    return [jnp.asarray(x, dtype) for x in (q, k, v, a, beta)]


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


@pytest.mark.parametrize('t,chunk', [(100, 64), (64, 64), (24, 64),
                                     (130, 32), (7, 64)])
def test_the_op_is_the_recurrence_forward_and_in_all_five_gradients(
        t, chunk):
    """float32 against the token-by-token recurrence, on lengths that
    are one chunk, less than one, and no whole number of them, with
    beta above 1 (negative eigenvalues) and decays of rate 16: the
    registered op and its gradient at the op's own chunk of 64, the
    function they lower to at another (several boundaries in 130
    tokens)."""
    args = _inputs(t, t=t)
    assert float(args[4].max()) > 1.5
    with jax.default_matmul_precision('highest'):
        want = _recurrence(*args)
        probe = jnp.asarray(
            np.random.RandomState(1).randn(*want.shape), jnp.float32)
        if chunk == kda_ops.CHUNK:
            got, got_grads = _op(*args), _op_grads(*args, probe)
        else:
            got, pull = jax.vjp(
                lambda *x: kda_ops.gated_delta_rule(*x, chunk), *args)
            got_grads = pull(probe)
        want_grads = jax.jit(jax.grad(
            lambda *x: jnp.sum(reference.kda_recurrence(*x) * probe),
            argnums=(0, 1, 2, 3, 4)))(*args)
    _close(got, want, 2e-5)
    for got_grad, want_grad in zip(got_grads, want_grads):
        _close(got_grad, want_grad, 5e-5)


@pytest.mark.parametrize('path,dk', [('dense', 16), ('fused', 128)])
def test_the_decays_overflow_the_factored_form_and_not_the_op(
        path, dk, request):
    """At rate 16 the running sum of the log decays falls under -88
    inside a chunk, so ``exp(-G)`` alone is inf in float32 and the
    factored scores ``(k exp(G)) . (k exp(-G))`` are inf or NaN; the op
    is finite, on the dense path and through the ``kda_chunk`` kernels
    (their bodies under the interpreter, at a head width they take)
    alike, and in float64 it is the recurrence to rounding: no clamp,
    floor or dropped term stands behind the float32 agreement."""
    if path == 'fused':
        request.getfixturevalue('pallas_interpret')
    dv = dk if path == 'fused' else 8   # the kernels take whole lane tiles
    args = _inputs(3, t=128, dk=dk, dv=dv)
    running = np.cumsum(np.asarray(args[3])[:, :64], 1)
    assert running.min() < -200
    with np.errstate(over='ignore'):
        assert np.isinf(np.exp(-running.astype('float32'))).any()
    fused = monitor.counter_value('pallas/kda_chunk/dispatch_fused') or 0
    out = jax.jit(_op.__wrapped__)(*args)
    assert np.isfinite(np.asarray(out)).all()
    assert (monitor.counter_value('pallas/kda_chunk/dispatch_fused') or
            0) == fused + (path == 'fused')
    with jax.enable_x64():
        exact = _inputs(3, t=128, dk=dk, dv=dv, dtype=jnp.float64)
        probe = jnp.asarray(np.random.RandomState(2).randn(2, 128, 3, dv))
        got = jax.jit(jax.grad(
            lambda *x: jnp.sum(kda_ops.gated_delta_rule(*x) * probe),
            argnums=(0, 1, 2, 3, 4)))(*exact)
        want = jax.jit(jax.grad(
            lambda *x: jnp.sum(reference.kda_recurrence(*x) * probe),
            argnums=(0, 1, 2, 3, 4)))(*exact)
        recurrence = jax.jit(reference.kda_recurrence)(*exact)
        _close(jax.jit(kda_ops.gated_delta_rule)(*exact), recurrence,
               1e-12)
        for g, w in zip(got, want):
            _close(g, w, 1e-11)
        _close(out, recurrence, 2e-5)


def test_no_state_crosses_from_one_sequence_of_a_batch_into_the_next():
    """Each sequence's output in a batch of two is what it is alone,
    and replacing the OTHER sequence changes nothing."""
    args = _inputs(4, t=80)
    both = np.asarray(_op(*args))
    for i in range(2):
        alone = np.asarray(_op(*(x[i:i + 1] for x in args)))
        assert np.abs(both[i:i + 1] - alone).max() <= 1e-6
    other = [x.at[0].set(y[0]) for x, y in zip(args, _inputs(5, t=80))]
    assert (np.asarray(_op(*other))[1] == both[1]).all()


def test_nothing_later_than_a_token_enters_its_output():
    """Everything from token 70 on (inside the second chunk) is
    replaced, inputs, decays and betas alike: the outputs before it are
    what they were, and those from it on are not.  (NaNs are no probe
    here: inside a chunk the causal mask is a product with 0.)"""
    args = _inputs(6, t=100)
    clean = np.asarray(_op(*args))
    other = [x.at[:, 70:].set(y[:, 70:])
             for x, y in zip(args, _inputs(7, t=100))]
    late = np.asarray(_op(*other))
    assert np.abs(late[:, :70] - clean[:, :70]).max() <= 1e-7
    assert np.abs(late[:, 70:] - clean[:, 70:]).max() > 1e-2


def test_bf16_inputs_keep_the_decays_and_the_state_float32():
    """bf16 q, k, v, beta beside float32 log decays: the output is
    bf16, and it is the float32 recurrence on those same rounded inputs
    to bf16's own rounding of the OUTPUT (a bf16 state would lose a
    hundred times that over 100 tokens).  The counters move once a
    lowering."""
    args = _inputs(7, rate=0.2)
    low = [x.astype(jnp.bfloat16) for x in args[:3]] + \
        [args[3], args[4].astype(jnp.bfloat16)]
    before = monitor.flat().get('kda/calls', 0)
    out = _op(*low)
    assert out.dtype == jnp.bfloat16
    assert monitor.flat()['kda/calls'] == before + 1
    want = _recurrence(*(x.astype(jnp.float32) for x in low))
    _close(out.astype(jnp.float32), want, 2 ** -8)
    crude = _recurrence(
        *(x.astype(jnp.bfloat16) for x in low)).astype(jnp.float32)
    assert np.abs(np.asarray(crude) - np.asarray(want)).max() > \
        4 * np.abs(np.asarray(out, np.float32) - np.asarray(want)).max()


def test_the_layer_infers_its_shape_and_counts_its_chunks():
    """``layers.kda_attention`` on a length that is no whole number of
    chunks: the output has v's shape; a run of the program sets
    ``kda/chunks`` to the trips of its one scan (3 chunks of 64 for 130
    tokens)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, a = (layers.data(n, shape=[130, 2, 16], dtype='float32')
                   for n in 'qka')
        v = layers.data('v', shape=[130, 2, 8], dtype='float32')
        beta = layers.data('beta', shape=[130, 2], dtype='float32')
        out = layers.kda_attention(q, k, v, a, beta)
    assert tuple(out.shape) == (-1, 130, 2, 8)
    op, = [op for op in main.global_block().ops
           if op.type == 'kda_attention']
    assert sorted(op.inputs) == ['A', 'Beta', 'K', 'Q', 'V']
    args = _inputs(8, b=1, t=130, h=2)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        got, = exe.run(main, feed=dict(zip(
            ('q', 'k', 'v', 'a', 'beta'), (np.asarray(x) for x in args))),
            fetch_list=[out])
    assert monitor.gauge_value('kda/chunks') == 3
    _close(got, _recurrence(*args), 2e-5)


# --- the program ------------------------------------------------------


def _seeded_weights(shapes, cfg, seed, router_scale=4.0):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains around 1, filters of order 1,
    decays of every size, a router whose top-k margins are wide."""
    rng = np.random.RandomState(seed)
    h, d = cfg.kda_heads, cfg.kda_head_dim
    out = []
    for s in shapes:
        if s == (h,):
            w = np.log(rng.uniform(1, 16, s))           # A_log
        elif s == (h * d,):
            w = rng.uniform(-4, 0, s)                   # dt_bias
        elif len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif s == (h * d, cfg.conv_taps):
            w = rng.randn(*s)
        elif s == (cfg.hidden, cfg.experts):
            w = router_scale * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _build(cfg, lr=0.0, amp=False):
    """-> (main, startup, loss, trainable names, their shapes, bias
    names, (param, grad) pairs)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = solar.build_pretrain(cfg, SEQ)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        shapes = [tuple(main.global_block().var(p).shape) for p in params]
        optimizer = fluid.optimizer.SGD(lr)
        if amp:
            optimizer = fluid.contrib.mixed_precision.decorate(
                optimizer, use_dynamic_loss_scaling=False,
                init_loss_scaling=1.0)
        pairs = optimizer.minimize(loss)[1]
    return main, startup, loss, params, shapes, biases, pairs


def _program_and_reference(cfg, seed, amp=False, bias_scale=0.3,
                           extra=()):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights and a seeded choice bias -> (loss,
    {param: grad}, params in creation order, weights, bias values,
    feed, the ``extra`` fetches)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup, loss, params, shapes, biases, pairs = _build(
            cfg, amp=amp)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, cfg, seed)
        rng = np.random.RandomState(seed + 100)
        bias_values = [(bias_scale * rng.randn(cfg.experts)).astype(
            'float32') for _ in biases]
        scope = fluid.global_scope()
        for name, w in zip(params + biases, weights + bias_values):
            scope.set_var(name, jnp.asarray(w))
        feed = solar.synthetic_batch(cfg, 2, SEQ,
                                     np.random.RandomState(seed))
        names = [n(main) for n in extra]
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs] +
                      names, return_numpy=False)
    n = 1 + len(pairs)
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:n])}
    return (_scalar(out[0]), grads, params, weights, bias_values, feed,
            out[n:])


def _reference(cfg, weights, biases, feed, **kw):
    sizes = reference.sizes_of(cfg)
    f = reference.loss if kw else reference.loss_and_grads
    return jax.jit(functools.partial(f, sizes=sizes, **kw))(
        weights, biases, feed['ids'], feed['labels'])


@pytest.mark.parametrize('cfg', [HELD, solar.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision, under a choice bias large enough to change the
    choice: what is left is the order of float32 sums through four
    layers (the recurrence in chunks against a token at a time).  The
    bias is no parameter and gets no gradient."""
    loss, grads, params, weights, biases, feed, _ = \
        _program_and_reference(cfg, 3)
    want, want_grads = _reference(cfg, weights, biases, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    assert len(biases) == 4
    # embedding, final gain, head; a softmax layer 6, a delta-rule
    # layer 16; every layer's norm, router and 3 + 3 expert matrices
    assert len(params) == 3 + 6 + 3 * 16 + 4 * 8
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 2e-4 * np.abs(g).max(), \
            name
    unbiased = _reference(cfg, weights, [0 * b for b in biases], feed,
                          dtype=jnp.float32)
    assert abs(float(unbiased) - float(want)) > 1e-4 * float(want)


def _input_of(op_type, slot, nth=0):
    def name(main):
        ops = [op for op in main.global_block().ops if op.type == op_type]
        return ops[nth].inputs[slot][0]
    return name


def test_bf16_amp_keeps_the_log_decays_float32_beside_bf16_q_k_v():
    """Under bf16 AMP the delta rule's q, k, v and beta arrive bf16 and
    its log decays float32 (``keep_float32`` on the add that meets
    ``dt_bias``; the op is exempt from the gray rule's cast down), and
    they are the float32 program's to a bf16 rounding of the
    projection under them; the loss is the float32 reference's to bf16
    matmuls' rounding."""
    slots = ('Q', 'K', 'V', 'A', 'Beta')
    extra = [_input_of('kda_attention', s) for s in slots]
    amp = _program_and_reference(HELD, 5, amp=True, extra=extra)
    f32 = _program_and_reference(HELD, 5, extra=extra)
    dtypes = {s: jnp.asarray(x).dtype.name for s, x in zip(slots, amp[6])}
    assert dtypes == {'Q': 'bfloat16', 'K': 'bfloat16', 'V': 'bfloat16',
                      'A': 'float32', 'Beta': 'bfloat16'}
    a_amp, a_f32 = (np.asarray(jnp.asarray(r[6][3]), np.float32)
                    for r in (amp, f32))
    assert (a_amp <= 0).all()
    assert np.abs(a_amp - a_f32).max() <= 3e-2 * np.abs(a_f32).max()
    assert np.abs(a_amp - a_f32).max() > 0
    _, _, _, weights, biases, feed, _ = f32
    want = float(_reference(HELD, weights, biases, feed)[0])
    assert abs(f32[0] - want) <= 2e-6 * want
    # bf16 matmuls under float32 norms, router, decays and state
    assert 0 < abs(amp[0] - want) <= 5e-3 * want


def test_a_train_step_counts_a_forward_and_a_reverse_scan_a_layer():
    """``kda/chunks`` over ONE traced train program: three delta-rule
    layers, one chunk each at 40 tokens, scanned forward and walked in
    reverse; ``begin_trace`` took what shape inference had lowered out
    of the reading."""
    _program_and_reference(solar.TINY, 6)
    assert monitor.gauge_value('kda/chunks') == 3 * 1 * 2


def test_the_cut_runs_the_models_own_layers_in_their_order():
    """Four layers from layer 0: softmax attention, then three
    delta-rule layers with three ungated filters each, every one
    routed; from layer 3 on the period starts with a delta-rule layer
    and reaches layer 4's softmax."""
    def kinds(cfg):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            solar.build_pretrain(cfg, SEQ)
        ops = main.global_block().ops
        assert all(sorted(op.inputs) == ['Filter', 'X'] for op in ops
                   if op.type == 'short_conv')
        return [op.type for op in ops
                if op.type in ('kda_attention', 'moe_route',
                               'fused_multihead_attention')]
    delta = ['kda_attention', 'moe_route']
    softmax = ['fused_multihead_attention', 'moe_route']
    assert kinds(solar.TINY) == softmax + 3 * delta
    later = copy.copy(solar.TINY)
    later.first_layer, later.layers = 3, 2
    assert kinds(later) == delta + softmax


def test_the_startup_draws_of_the_decay_s_parameters():
    """``A_log`` is the log of Uniform(1, 16), ``dt_bias`` Uniform(log
    0.001, log 0.1), the filters Uniform(-1/2, 1/2) at four taps: the
    log decays of a fresh model lie in about (-1.6, -0.001) a token."""
    cfg = copy.copy(solar.TINY)
    cfg.kda_heads, cfg.kda_head_dim = 24, 32
    with fluid.scope_guard(fluid.Scope()):
        main, startup, _, params, shapes, _, _ = _build(cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        values = {s: np.asarray(fluid.core.as_array(scope.find_var(p)))
                  for p, s in zip(params, shapes)}
    a_log, dt_bias = values[(24,)], values[(24 * 32,)]
    filters = values[(24 * 32, 4)]
    assert 0 < a_log.min() and a_log.max() < np.log(16)
    # log of a uniform: the median of A is 8.5, not 4
    assert np.exp(np.median(a_log)) > 5
    assert np.log(1e-3) <= dt_bias.min() and dt_bias.max() <= np.log(0.1)
    assert np.abs(filters).max() <= 0.5 and np.abs(filters).max() > 0.4
    rate = np.exp(a_log)[:, None] * np.log1p(np.exp(
        dt_bias.reshape(24, 32)))
    assert 1e-3 < rate.min() and rate.max() < 1.6


# --- the shares -------------------------------------------------------


def _run_sum(build, feeds, weight_lists):
    """One program: ``build()`` called once a share inside it (each
    creating its own parameters, in the order of ``weight_lists``'
    entry), the outputs summed; -> the sum on the given weights."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            total, names = None, []
            for i in range(len(weight_lists)):
                before = len(main.all_parameters())
                out = build(i)
                names.append([p.name for p in
                              main.all_parameters()[before:]])
                total = out if total is None else \
                    layers.elementwise_add(total, out)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for share_names, share_weights in zip(names, weight_lists):
            assert len(share_names) == len(share_weights)
            for name, w in zip(share_names, share_weights):
                assert tuple(main.global_block().var(name).shape) == \
                    w.shape, name
                scope.set_var(name, jnp.asarray(w))
        got, = exe.run(main, feed=feeds, fetch_list=[total])
    return np.asarray(got)


def _columns(w, i, per, d):
    """The columns of heads i*per .. (i+1)*per - 1, ``d`` wide each."""
    return w[..., i * per * d:(i + 1) * per * d]


def test_the_eight_head_shares_of_a_softmax_layer_add_up_to_the_whole():
    """16 query heads over 8 K/V heads in eight shares of 2 : 1 (the
    deployment's 8 : 1 at toy counts): each share's program is built at
    its own columns of Wq, Wk, Wv, Wgate and rows of Wo, and the eight
    operator results add up to the whole-head reference's."""
    rng = np.random.RandomState(0)
    width, d, heads, kv, shares = 32, 8, 16, 8, 8
    u = rng.randn(2, SEQ, width).astype('float32')
    wq, wgate = (rng.randn(width, heads * d).astype('float32') /
                 np.sqrt(width) for _ in range(2))
    wk, wv = (rng.randn(width, kv * d).astype('float32') /
              np.sqrt(width) for _ in range(2))
    wo = rng.randn(heads * d, width).astype('float32') / np.sqrt(heads * d)
    sizes = dict(head_dim=d)
    with jax.default_matmul_precision('highest'):
        whole = np.asarray(reference.gqa_operator(
            jnp.asarray(u), wq, wk, wv, wgate, wo, sizes))
    cfg = copy.copy(solar.TINY)
    cfg.hidden, cfg.head_dim = width, d
    cfg.heads, cfg.kv_heads = heads // shares, kv // shares
    per = heads // shares

    def build(i):
        return solar.gqa_operator(
            layers.data('u', shape=[SEQ, width], dtype='float32'), cfg)

    parts = [[_columns(wq, i, per, d), _columns(wk, i, 1, d),
              _columns(wv, i, 1, d), _columns(wgate, i, per, d),
              wo[i * per * d:(i + 1) * per * d]] for i in range(shares)]
    total = _run_sum(build, {'u': u}, parts)
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()
    # and one share alone is far from the whole
    alone = _run_sum(build, {'u': u}, parts[:1])
    assert np.abs(alone - whole).max() > 0.3 * np.abs(whole).max()


def test_the_eight_head_shares_of_a_delta_rule_layer_add_up_to_the_whole():
    """8 delta-rule heads in eight shares of one: each share holds its
    heads' columns of Wq, Wk, Wv, Wb and of the two gates'
    up-projections, their filters, ``A_log`` and ``dt_bias`` entries and
    rows of Wo; the 16-wide down-projections and the output norm's gain
    are every share's alike.  The eight operator results add up to the
    whole-head reference's."""
    rng = np.random.RandomState(1)
    width, d, heads, taps = 32, 16, 8, 4
    u = rng.randn(2, SEQ, width).astype('float32')

    def matrix(rows, cols):
        return (rng.randn(rows, cols) / np.sqrt(rows)).astype('float32')

    wq, wk, wv = (matrix(width, heads * d) for _ in range(3))
    fq, fk, fv = (rng.randn(heads * d, taps).astype('float32')
                  for _ in range(3))
    wf_down, wg_down = matrix(width, d), matrix(width, d)
    wf_up, wg_up = matrix(d, heads * d), matrix(d, heads * d)
    a_log = np.log(rng.uniform(1, 16, heads)).astype('float32')
    dt_bias = rng.uniform(-4, 0, heads * d).astype('float32')
    wb = matrix(width, heads)
    g_o = (1 + 0.3 * rng.randn(d)).astype('float32')
    wo = matrix(heads * d, width)
    sizes = dict(kda_head_dim=d, neg_eigval=True, rms_eps=1e-5)
    with jax.default_matmul_precision('highest'):
        whole = np.asarray(reference.kda_operator(
            jnp.asarray(u), wq, fq, wk, fk, wv, fv, wf_down, wf_up,
            a_log, dt_bias, wb, g_o, wg_down, wg_up, wo, sizes))
    cfg = copy.copy(solar.TINY)
    cfg.hidden, cfg.kda_head_dim, cfg.kda_heads = width, d, 1

    def build(i):
        return solar.kda_operator(
            layers.data('u', shape=[SEQ, width], dtype='float32'), cfg)

    def rows(w, i):
        return w[i * d:(i + 1) * d]

    parts = [[_columns(wq, i, 1, d), rows(fq, i), _columns(wk, i, 1, d),
              rows(fk, i), _columns(wv, i, 1, d), rows(fv, i), wf_down,
              _columns(wf_up, i, 1, d), a_log[i:i + 1], rows(dt_bias, i),
              wb[:, i:i + 1], g_o, wg_down, _columns(wg_up, i, 1, d),
              rows(wo, i)] for i in range(heads)]
    total = _run_sum(build, {'u': u}, parts)
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()
    alone = _run_sum(build, {'u': u}, parts[:1])
    assert np.abs(alone - whole).max() > 0.3 * np.abs(whole).max()


def test_the_forty_expert_shares_and_the_shared_expert_once_add_up():
    """40 routed experts top-4 in forty shares of one under a nonzero
    choice bias, beside one shared expert: the parts of the routed sum
    the forty shares give, plus the shared expert counted ONCE, add up
    to what the uncut reference gives for the whole MLP."""
    rng = np.random.RandomState(2)
    b, t, d, experts, top_k, hidden = 2, 12, 16, 40, 4, 8
    x = rng.randn(b, t, d).astype('float32')
    wr = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    bias = (0.3 * rng.randn(experts)).astype('float32')
    shared = [rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(hidden, d).astype('float32') / np.sqrt(hidden)]
    sizes = dict(top_k=top_k, routed_scale=1.0, experts_held=None)
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        routed, _ = reference.routed_share(flat, wr, bias, gate, up, down,
                                           sizes)
        whole = np.asarray(routed + reference.gated_mlp(flat, *shared))
        twice = whole + np.asarray(reference.gated_mlp(flat, *shared))
    cfg = copy.copy(solar.TINY)
    cfg.hidden, cfg.expert_hidden = d, hidden

    def build(i):
        xv = layers.data('x', shape=[t, d], dtype='float32')
        if i == experts:                # the shared expert, once
            return solar.gated_mlp(xv, hidden, cfg)
        out, _ = layers.moe(
            xv, num_experts=experts, hidden_size=hidden,
            capacity_factor=None, top_k=top_k, renormalize=True,
            experts_held=(i, 1), aux_weight=0.0, score_func='sigmoid',
            score_bias=True)
        return out

    parts = [[wr, gate[i:i + 1], up[i:i + 1], down[i:i + 1], bias]
             for i in range(experts)] + [shared]
    total = _run_sum(build, {'x': x}, parts).reshape(b * t, d)
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()
    assert np.abs(total - twice).max() > 0.05 * np.abs(whole).max()


# --- counting ---------------------------------------------------------


def test_base_is_the_published_model_and_counts_what_the_issue_counts():
    """Parameters of the published widths with a share of an eighth of
    the heads, as the issue's arithmetic has them (millions): a
    delta-rule layer 18.1, a softmax layer 13.6, a layer's MLP 142.9
    (router 1.3, shared 15.7, 8 held experts), embedding and head
    201.3; the cell's cut 840.8."""
    c = solar.BASE
    assert (c.layers, c.heads, c.kv_heads, c.head_dim, c.kda_heads,
            c.kda_head_dim, c.conv_taps, c.experts, c.top_k) == \
        (48, 64, 8, 128, 64, 128, 4, 320, 8)
    assert c.gqa_layers == tuple(range(0, 48, 4))
    heads, kv, d = c.heads // 8, c.kv_heads // 8, c.head_dim
    softmax = c.hidden * (3 * heads + 2 * kv) * d
    delta = 4 * c.hidden * heads * d + 2 * (c.hidden * d + d * heads * d) \
        + 3 * heads * d * c.conv_taps + heads + heads * d + \
        c.hidden * heads + d
    expert = 3 * c.hidden * c.expert_hidden
    mlp = c.hidden * c.experts + expert + 8 * expert
    ends = 2 * 24576 * c.hidden
    assert [round(n / 1e6, 1) for n in (delta, softmax, mlp, ends)] == \
        [18.1, 13.6, 142.9, 201.3]
    norms = 4 * 2 * c.hidden + c.hidden
    cut = softmax + 3 * delta + 4 * mlp + ends + norms
    # the issue's 840.8 rounds its terms first; to the parameter:
    assert cut == 840871320
    # and the program the benchmark's file builds has exactly those
    held = copy.copy(c)
    held.vocab_size, held.layers, held.experts_held = 24576, 4, (0, 8)
    held.heads, held.kv_heads, held.kda_heads = heads, kv, heads
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        solar.build_pretrain(held, 4096)
    built = sum(int(np.prod(p.shape)) for p in main.all_parameters()
                if p.trainable)
    assert built == cut
