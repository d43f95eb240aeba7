"""LFM2 through fluid against its plain reference
(``paddle_tpu/models/reference/lfm2.py``): the zoo program's loss and
every parameter's gradient with one chip's share of the experts and
with all of them; the ``short_conv`` op against the sum over taps (the
future poisoned, its gradient by finite differences, sequences of a
batch apart); QK-norm over each head BEFORE the rotary embedding; the
renormalisation's epsilon as an attribute whose default is the program
it was; the shares adding up to the uncut layer; the tied table's two
gradients.  CPU, tiny sizes; the published widths are checked on the
chip (``chip_smoke.py --phase lfm2``, PERF.md)."""

import copy

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import lfm2, moonlight
from paddle_tpu.models.reference import lfm2 as reference
from paddle_tpu.ops import registry
from paddle_tpu.parallel import moe as pmoe

SEQ = 24

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(lfm2.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, cfg, seed, router_scale=4.0):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains around 1, filters of order 1, a
    router whose top-k margins are wide."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif s == (cfg.hidden, cfg.conv_taps):
            w = rng.randn(*s)
        elif s == (cfg.hidden, cfg.experts):
            w = router_scale * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _build(cfg, lr=0.0):
    """-> (main, startup, loss, trainable names, their shapes, bias
    names, (param, grad) pairs)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = lfm2.build_pretrain(cfg, SEQ)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        shapes = [tuple(main.global_block().var(p).shape) for p in params]
        pairs = fluid.optimizer.SGD(lr).minimize(loss)[1]
    return main, startup, loss, params, shapes, biases, pairs


def _program_and_reference(cfg, seed, bias_scale=0.3):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights and a seeded choice bias -> (loss,
    {param: grad}, params in creation order, weights, bias values,
    feed)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup, loss, params, shapes, biases, pairs = _build(cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, cfg, seed)
        rng = np.random.RandomState(seed + 100)
        bias_values = [(bias_scale * rng.randn(cfg.experts)).astype(
            'float32') for _ in biases]
        scope = fluid.global_scope()
        for name, w in zip(params + biases, weights + bias_values):
            scope.set_var(name, jnp.asarray(w))
        feed = lfm2.synthetic_batch(cfg, 2, SEQ,
                                    np.random.RandomState(seed))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs])
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:])}
    return _scalar(out[0]), grads, params, weights, bias_values, feed


def _reference(cfg, weights, biases, feed, **kw):
    sizes = reference.sizes_of(cfg)
    if kw:
        return reference.loss(weights, biases, feed['ids'],
                              feed['pos_ids'], feed['labels'],
                              sizes=sizes, **kw)
    return reference.loss_and_grads(weights, biases, feed['ids'],
                                    feed['pos_ids'], feed['labels'],
                                    sizes=sizes)


@pytest.mark.parametrize('cfg', [HELD, lfm2.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision, under a choice bias large enough to change the
    choice: what is left is the order of float32 sums through five
    layers.  A wrong order of the thirds or of the taps, rotary before
    the norm, an untied head, a 1e-20 for the 1e-6 at these scores, or
    a wrong held range moves gradients by whole percents.  The bias is
    no parameter and gets no gradient."""
    loss, grads, params, weights, biases, feed = \
        _program_and_reference(cfg, 3)
    want, want_grads = _reference(cfg, weights, biases, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    assert len(biases) == 4
    # embedding, final gain; a dense conv layer 8; sparse: conv 9, attn 12
    assert len(params) == 2 + 8 + 12 + 3 * 9
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 1e-4 * np.abs(g).max(), \
            name
    # and the bias did change the choice the reference made
    unbiased = _reference(cfg, weights, [0 * b for b in biases], feed,
                          dtype=jnp.float32)
    assert abs(float(unbiased) - float(want)) > 1e-4 * float(want)


def test_the_tied_table_takes_both_gradients():
    """The embedding is the head: a row no token of the batch looks up
    still moves (the head's product reaches every row), a row that is
    looked up gets the scatter-add on top, and the sum is what
    ``jax.grad`` of the reference gives."""
    loss, grads, params, weights, biases, feed = \
        _program_and_reference(lfm2.TINY, 5)
    assert params[0] == lfm2.EMBEDDING
    _, want_grads = _reference(lfm2.TINY, weights, biases, feed)
    got, want = grads[lfm2.EMBEDDING], np.asarray(want_grads[0])
    seen = np.zeros(lfm2.TINY.vocab_size, bool)
    seen[np.asarray(feed['ids']).ravel()] = True
    assert seen.any() and (~seen).any()
    assert np.abs(got[~seen]).max() > 0             # the head alone
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # rows that are looked up hold more than the head gives the others
    assert np.abs(got[seen]).mean() > 1.5 * np.abs(got[~seen]).mean()


# --- short_conv -------------------------------------------------------


class _Ctx(object):
    auto_partitioned = False


def _short_conv(x, w, gate_in=None, gate_out=None):
    ins = {'X': [x], 'Filter': [w]}
    if gate_in is not None:
        ins['GateIn'] = [gate_in]
    if gate_out is not None:
        ins['GateOut'] = [gate_out]
    return registry.get('short_conv').run(_Ctx(), ins, {})['Out'][0]


def _tap_sum(x, w, gate_in=None, gate_out=None):
    """out[b, t] = sum_j w[:, j] * z[b, t - (L - 1) + j], written out."""
    z = x if gate_in is None else x * gate_in
    b, t, c = z.shape
    taps = w.shape[1]
    out = np.zeros_like(z)
    for ti in range(t):
        for j in range(taps):
            src = ti - (taps - 1) + j
            if src >= 0:
                out[:, ti] += w[:, j] * z[:, src]
    return out if gate_out is None else out * gate_out


@pytest.mark.parametrize('taps', [1, 3, 4])
@pytest.mark.parametrize('gated', [False, True], ids=['plain', 'gated'])
def test_short_conv_is_the_tap_sum_and_never_sees_the_future(taps, gated):
    """Against the sum written out; then everything from token 7 on is
    NaN (inputs and gates alike) and the outputs before it are what
    they were: nothing later than a token enters it."""
    rng = np.random.RandomState(taps)
    x, g1, g2 = (rng.randn(2, 12, 5).astype('float32') for _ in range(3))
    w = rng.randn(5, taps).astype('float32')
    gates = (g1, g2) if gated else (None, None)
    want = _tap_sum(x, w, *gates)
    got = np.asarray(_short_conv(x, w, *gates))
    assert np.abs(got - want).max() <= 1e-5
    poisoned = [None if a is None else a.copy() for a in (x,) + gates]
    for a in poisoned:
        if a is not None:
            a[:, 7:] = np.nan
    late = np.asarray(_short_conv(poisoned[0], w, *poisoned[1:]))
    assert np.isfinite(late[:, :7]).all()
    assert (late[:, :7] == got[:, :7]).all()
    assert np.isnan(late[:, 7:]).all()


def test_short_conv_keeps_the_sequences_of_a_batch_apart():
    """Two sequences in a batch: each one's output is what it is
    alone, and the first tokens of the second see zeros, not the end
    of the first."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 9, 4).astype('float32')
    w = rng.randn(4, 3).astype('float32')
    both = np.asarray(_short_conv(x, w))
    for i in range(2):
        alone = np.asarray(_short_conv(x[i:i + 1], w))
        assert (both[i:i + 1] == alone).all()
    assert np.abs(both[1, 0] - w[:, 2] * x[1, 0]).max() <= 1e-6
    other = x.copy()
    other[0] = rng.randn(9, 4)
    assert (np.asarray(_short_conv(other, w))[1] == both[1]).all()


def test_short_conv_gradient_by_finite_differences():
    """The synthesized ``short_conv_grad`` against central differences
    of the op, for the input, both gates and the filter."""
    rng = np.random.RandomState(2)
    arrays = [rng.randn(2, 6, 3) for _ in range(3)] + [rng.randn(3, 3)]
    probe = rng.randn(2, 6, 3)

    def f(x, g1, g2, w):
        return jnp.sum(_short_conv(x, w, g1, g2) * probe)

    with jax.enable_x64():
        arrays = [jnp.asarray(a, jnp.float64) for a in arrays]
        grad_op = registry.get('short_conv_grad')
        out = grad_op.run(_Ctx(), {
            'X': [arrays[0]], 'GateIn': [arrays[1]],
            'GateOut': [arrays[2]], 'Filter': [arrays[3]],
            'GRAD::Out': [jnp.asarray(probe, jnp.float64)]}, {})
        got = [out['GRAD::' + s][0]
               for s in ('X', 'GateIn', 'GateOut', 'Filter')]
        eps = 1e-6
        for k, (a, g) in enumerate(zip(arrays, got)):
            flat = np.asarray(a).ravel()
            for i in rng.choice(flat.size, 8, replace=False):
                up, down = flat.copy(), flat.copy()
                up[i] += eps
                down[i] -= eps
                args = list(arrays)
                args[k] = jnp.asarray(up.reshape(a.shape))
                hi = float(f(*args))
                args[k] = jnp.asarray(down.reshape(a.shape))
                lo = float(f(*args))
                assert np.asarray(g).ravel()[i] == pytest.approx(
                    (hi - lo) / (2 * eps), rel=1e-5, abs=1e-7)


def test_short_conv_keeps_a_bf16_stream_bf16_and_counts_its_calls():
    """Float32 inside, the output in the input's dtype past the f32
    filter; the counter moves once a lowering."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 8, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4, 3), jnp.float32)
    before = monitor.flat().get('short_conv/calls', 0)
    out = _short_conv(x, w, x, x)
    assert out.dtype == jnp.bfloat16
    assert monitor.flat()['short_conv/calls'] == before + 1
    want = _tap_sum(*(np.asarray(a, np.float32) for a in (x, w, x, x)))
    assert np.abs(np.asarray(out, np.float32) - want).max() <= \
        2e-2 * np.abs(want).max()


def test_the_layer_makes_a_filter_a_channel_and_fuses_the_gates():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data('x', shape=[6, 4], dtype='float32')
        g = layers.data('g', shape=[6, 4], dtype='float32')
        out = layers.short_conv(x, 3, gate_in=g, gate_out=g)
        (w,) = main.all_parameters()
    assert tuple(w.shape) == (4, 3) and tuple(out.shape) == (-1, 6, 4)
    op, = [op for op in main.global_block().ops
           if op.type == 'short_conv']
    assert sorted(op.inputs) == ['Filter', 'GateIn', 'GateOut', 'X']


# --- attention --------------------------------------------------------


def _attention_program(cfg, weights, feed_u, pos):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            u = layers.data('u', shape=[SEQ, cfg.hidden], dtype='float32')
            p = layers.data('pos', shape=[SEQ], dtype='int64')
            out = lfm2.attention_operator(u, p, cfg)
            names = [v.name for v in main.all_parameters()]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for name, w in zip(names, weights):
            fluid.global_scope().set_var(name, jnp.asarray(w))
        got, = exe.run(main, feed={'u': feed_u, 'pos': pos},
                       fetch_list=[out])
    return np.asarray(got)


def test_qk_norm_is_per_head_and_before_the_rotary_embedding():
    """The program's attention operator against the reference's; the
    same with the rotation BEFORE the norm (a gain that differs by
    feature does not commute with the rotation) and with one norm over
    all heads at once both miss by far."""
    cfg = lfm2.TINY
    rng = np.random.RandomState(6)
    d, h, kv, width = cfg.head_dim, cfg.heads, cfg.kv_heads, cfg.hidden
    weights = [rng.randn(width, h * d) / 8, rng.randn(width, kv * d) / 8,
               rng.randn(width, kv * d) / 8, 1 + 0.5 * rng.randn(d),
               1 + 0.5 * rng.randn(d), rng.randn(h * d, width) / 8]
    weights = [w.astype('float32') for w in weights]
    u = rng.randn(2, SEQ, width).astype('float32')
    pos = np.tile(np.arange(SEQ), (2, 1)).astype('int64')
    got = _attention_program(cfg, weights, u, pos)
    sizes = reference.sizes_of(cfg)
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.attention_operator(
            jnp.asarray(u), jnp.asarray(pos), *weights, sizes))
        real_rope, real_norm = reference.rope, reference.rms_norm
        try:
            # rotary first: norm(rope(x)) for rope(norm(x))
            reference.rms_norm = lambda x, g, eps: x
            reference.rope = lambda x, p, theta: real_norm(
                real_rope(x, p, theta),
                jnp.asarray(weights[3] if x.shape[2] == h
                            else weights[4]), cfg.rms_eps)
            swapped = np.asarray(reference.attention_operator(
                jnp.asarray(u), jnp.asarray(pos), *weights, sizes))
            # one norm over all heads' features at once
            reference.rope = real_rope

            def whole(x, g, eps):
                b, t, n, _ = x.shape
                flat = real_norm(x.reshape(b, t, n * d),
                                 jnp.tile(jnp.asarray(g), n), eps)
                return flat.reshape(x.shape)
            reference.rms_norm = whole
            together = np.asarray(reference.attention_operator(
                jnp.asarray(u), jnp.asarray(pos), *weights, sizes))
        finally:
            reference.rope, reference.rms_norm = real_rope, real_norm
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-5 * scale
    assert np.abs(swapped - want).max() > 1e-2 * scale
    assert np.abs(together - want).max() > 1e-2 * scale


# --- the router -------------------------------------------------------


def test_the_epsilon_changes_the_gates_and_its_default_is_what_it_was():
    """Sigmoid scores of very negative logits sum to about 1e-6: over
    (sum + 1e-6) the gates sum to sum / (sum + 1e-6), a tenth under 1;
    over (sum + 1e-20) to 1.  The default argument is the 1e-20 that
    was written in: the same numbers bit for bit."""
    rng = np.random.RandomState(7)
    x = jnp.ones((5, 1), jnp.float32)
    logits = jnp.asarray(-14 + rng.randn(1, 6), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    old = pmoe.route_topk(x, logits, 2, True, 1.0, 'sigmoid')
    same = pmoe.route_topk(x, logits, 2, True, 1.0, 'sigmoid',
                           renorm_eps=1e-20)
    new = pmoe.route_topk(x, logits, 2, True, 1.0, 'sigmoid',
                          renorm_eps=1e-6)
    for a, b in zip(old, same):
        assert (np.asarray(a) == np.asarray(b)).all()
    top = np.sort(s)[-2:].sum()
    assert np.asarray(old[1]).sum(-1) == pytest.approx(1.0, rel=1e-6)
    assert np.asarray(new[1]).sum(-1) == pytest.approx(
        top / (top + 1e-6), rel=1e-5)
    assert top / (top + 1e-6) < 0.95
    assert (np.asarray(new[0]) == np.asarray(old[0])).all()


def _route_ops(build, cfg):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        build(cfg, SEQ)
    return [op for op in main.global_block().ops
            if op.type == 'moe_route']


def test_only_a_layer_that_asks_carries_the_epsilon():
    """Moonlight's routers are built as they were (no such attribute
    on the op: the program's fingerprint and its trace are the
    parent's); LFM2's carry 1e-6."""
    theirs = _route_ops(moonlight.build_pretrain, moonlight.TINY)
    ours = _route_ops(lfm2.build_pretrain, lfm2.TINY)
    assert len(theirs) == 2 and len(ours) == 4
    assert all('renorm_eps' not in op.attrs for op in theirs)
    assert all(op.attrs['renorm_eps'] == 1e-6 for op in ours)
    assert all(op.attrs['score_func'] == 'sigmoid' for op in ours)


def _moe_layer(x, held, weights, bias, experts, top_k, hidden):
    """``layers.moe`` as LFM2 calls it, on given weights -> (out,
    monitor's counters)."""
    b, t, d = x.shape
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            xv = layers.data('x', shape=[t, d], dtype='float32')
            out, _ = layers.moe(xv, num_experts=experts,
                                hidden_size=hidden, capacity_factor=None,
                                top_k=top_k, renormalize=True,
                                experts_held=held, aux_weight=0.0,
                                score_func='sigmoid', score_bias=True,
                                renorm_eps=1e-6)
            every = main.all_parameters()
            params = [p.name for p in every if p.trainable]
            bias_name, = [p.name for p in every if not p.trainable]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for name, w in zip(params + [bias_name], weights + [bias]):
            scope.set_var(name, jnp.asarray(w))
        monitor.reset()
        got, = exe.run(main, feed={'x': x}, fetch_list=[out])
        counters = dict(monitor.flat())
    return np.asarray(got), counters


def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts top-4 in four shares of 8 (the deployment's) under a
    nonzero choice bias: the parts of the routed sum the four shares
    give add up to what the uncut reference gives for the whole layer;
    there is no shared expert to count once.  Also what each share
    reports: rows held summing to the rows routed, no drop."""
    rng = np.random.RandomState(0)
    b, t, d, experts, top_k, hidden = 2, 20, 32, 32, 4, 24
    x = rng.randn(b, t, d).astype('float32')
    wg = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    bias = (0.3 * rng.randn(experts)).astype('float32')
    sizes = dict(top_k=top_k, routed_scale=1.0, renorm_eps=1e-6,
                 experts_held=None)
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        whole, load = reference.routed_share(flat, wg, bias, gate, up,
                                             down, sizes)
        plain, _ = reference.routed_share(flat, wg, 0 * bias, gate, up,
                                          down, sizes)
    whole = np.asarray(whole)
    assert np.abs(np.asarray(plain) - whole).max() > 1e-2
    total, held_rows = np.zeros_like(whole), 0.0
    for first in range(0, experts, 8):
        part, counters = _moe_layer(
            x, (first, 8), [wg, gate[first:first + 8],
                            up[first:first + 8], down[first:first + 8]],
            bias, experts, top_k, hidden)
        total = total + part.reshape(b * t, d)
        assert counters['moe/dropped_tokens'] == 0
        assert counters['moe/tokens_routed'] == b * t * top_k
        assert counters['moe/rows_held'] == \
            float(np.asarray(load)[first:first + 8].sum())
        held_rows += counters['moe/rows_held']
    assert held_rows == b * t * top_k
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()


# --- counting ---------------------------------------------------------


def test_base_is_the_published_model_and_counts_what_the_issue_counts():
    """Parameters of the published widths, as the issue's arithmetic
    has them (millions): conv operator 16.78, attention operator 10.49,
    dense MLP 44.04, one expert 11.01, router 0.07; the cell's cut
    (layers 1 to 5, 8 experts held, 16384 rows) 507.8."""
    c = lfm2.BASE
    conv = c.hidden * 3 * c.hidden + c.hidden * c.hidden
    attention = 2 * c.hidden * c.heads * c.head_dim + \
        2 * c.hidden * c.kv_heads * c.head_dim
    dense, expert = (3 * c.hidden * w
                     for w in (c.dense_hidden, c.expert_hidden))
    router = c.hidden * c.experts
    assert [round(n / 1e6, 2) for n in (conv, attention, dense, expert,
                                        router)] == \
        [16.78, 10.49, 44.04, 11.01, 0.07]
    assert (c.layers, c.top_k, c.head_dim, c.rope_theta, c.conv_taps) == \
        (24, 4, 64, 1e6, 3)
    assert [i for i, kind in enumerate(c.layer_types)
            if kind == lfm2.ATTENTION] == [2, 6, 10, 14, 18, 21]
    cut = (conv + dense) + (attention + router + 8 * expert) + \
        3 * (conv + router + 8 * expert) + 16384 * c.hidden
    assert round(cut / 1e6, 1) == 507.8


def test_the_cut_runs_the_models_own_layers_in_their_order():
    """``first_layer`` 1, five layers: a dense conv layer, then a
    sparse attention layer and three sparse conv layers, as the
    model's layers 1 to 5 are."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        lfm2.build_pretrain(lfm2.TINY, SEQ)
    kinds = [op.type for op in main.global_block().ops
             if op.type in ('short_conv', 'fused_multihead_attention',
                            'moe_route')]
    assert kinds == ['short_conv', 'fused_multihead_attention',
                     'moe_route', 'short_conv', 'moe_route',
                     'short_conv', 'moe_route', 'short_conv',
                     'moe_route']


def test_the_reference_routed_by_a_given_choice_is_itself_on_its_own():
    """``chosen=`` replaces the reference's choice of experts and
    nothing else (``chip_smoke.py --phase lfm2`` hands it the
    program's, to compare gradients apart from near-tie tokens)."""
    rng = np.random.RandomState(9)
    w = jnp.asarray(rng.randn(10, 6), jnp.float32)
    wg = jnp.asarray(rng.randn(6, 5), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(5), jnp.float32)
    own, gates, load = reference.route(w, wg, bias, 2, 1.0, 1e-6)
    again = reference.route(w, wg, bias, 2, 1.0, 1e-6, chosen=own)
    assert (np.asarray(again[1]) == np.asarray(gates)).all()
    other = reference.route(w, wg, bias, 2, 1.0, 1e-6,
                            chosen=(own + 1) % 5)
    assert np.abs(np.asarray(other[1]) - np.asarray(gates)).max() > 0
    assert np.asarray(other[2]).tolist() == np.roll(np.asarray(load),
                                                    1).tolist()
