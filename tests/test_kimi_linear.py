"""Kimi Linear through fluid against its plain reference
(``paddle_tpu/models/reference/kimi_linear.py``): the zoo program's
loss and every parameter's gradient, through its recompute groups;
what tells it from a Solar-shaped or a Moonlight-shaped build
(beta without the factor 2, no ``rotary_embedding`` op, no ``pos_ids``
feed, the shared key slice in the product as projected); the float32
log decays under bf16 AMP; the 32 expert shares and the shared expert
once adding up to the uncut layer.  The delta rule's op itself is held
to the token-by-token recurrence by ``tests/test_solar_open2.py``.
CPU, tiny sizes; the published widths are checked on the chip
(``chip_smoke.py --phase kimi``, PERF.md)."""

import copy
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import kimi_linear as kimi
from paddle_tpu.models import moonlight
from paddle_tpu.models.reference import kimi_linear as reference
from paddle_tpu.models.reference import moonlight as moonlight_reference

SEQ = 40

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(kimi.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, cfg, seed):
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
            w = 4.0 * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _build(cfg, amp=False):
    """-> (main, startup, loss, trainable names, their shapes, bias
    names, (param, grad) pairs)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = kimi.build_pretrain(cfg, SEQ)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        shapes = [tuple(main.global_block().var(p).shape) for p in params]
        optimizer = fluid.optimizer.SGD(0.0)
        if amp:
            optimizer = fluid.contrib.mixed_precision.decorate(
                optimizer, use_dynamic_loss_scaling=False,
                init_loss_scaling=1.0)
        pairs = optimizer.minimize(loss)[1]
    return main, startup, loss, params, shapes, biases, pairs


def _program_and_reference(cfg, seed, amp=False, extra=()):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights and a seeded choice bias -> (loss,
    {param: grad}, params in creation order, weights, bias values,
    feed, the ``extra`` fetches)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup, loss, params, shapes, biases, pairs = _build(
            cfg, amp)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, cfg, seed)
        rng = np.random.RandomState(seed + 100)
        bias_values = [(0.3 * rng.randn(cfg.experts)).astype(
            'float32') for _ in biases]
        scope = fluid.global_scope()
        for name, w in zip(params + biases, weights + bias_values):
            scope.set_var(name, jnp.asarray(w))
        feed = kimi.synthetic_batch(cfg, 2, SEQ,
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


# --- the program ------------------------------------------------------


@pytest.mark.parametrize('cfg', [HELD, kimi.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision, under a choice bias large enough to change the
    choice: what is left is the order of float32 sums through five
    layers (the recurrence in chunks against a token at a time).  The
    bias is no parameter and gets no gradient.  Every block but the
    last is a recompute group: the delta rule's scan and its chunked
    backward run inside ``jax.checkpoint``, routers and their bias
    updates too."""
    groups = monitor.counter_value('executor/recompute_groups') or 0
    loss, grads, params, weights, biases, feed, _ = \
        _program_and_reference(cfg, 3)
    assert (monitor.counter_value('executor/recompute_groups') or
            0) - groups >= 4
    want, want_grads = _reference(cfg, weights, biases, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    assert len(biases) == 4
    # embedding, final gain, head; four delta-rule operators of 15, one
    # latent of 5; every layer's two norms; a dense MLP of 3, four
    # sparse ones of router + 3 + 3
    assert len(params) == 3 + 4 * 15 + 5 + 5 * 2 + 3 + 4 * 7
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


def test_beta_is_a_plain_sigmoid_with_no_factor_two():
    """The delta rule's step size lies in (0, 1): the program hands
    ``kda_attention`` betas under 1 on weights that push Solar's 2 x
    sigmoid well over it, and the loss is the reference's with
    ``neg_eigval`` off and NOT its loss with the factor 2."""
    loss, _, _, weights, biases, feed, (beta,) = _program_and_reference(
        HELD, 7, extra=[_input_of('kda_attention', 'Beta')])
    beta = np.asarray(beta)
    assert 0.0 < beta.min() and 0.9 < beta.max() < 1.0
    want = float(_reference(HELD, weights, biases, feed)[0])
    assert abs(loss - want) <= 2e-6 * want
    from paddle_tpu.models.reference import solar_open2
    real = solar_open2.kda_inputs

    def doubled(*args):
        q, k, v, a, b = real(*args)
        return q, k, v, a, 2.0 * b
    solar_open2.kda_inputs = doubled
    try:
        solar_shaped = float(_reference(HELD, weights, biases, feed,
                                        dtype=jnp.float32))
    finally:
        solar_open2.kda_inputs = real
    assert abs(solar_shaped - want) > 1e-3 * want


def test_no_position_enters_the_model():
    """No ``rotary_embedding`` op, no ``pos_ids`` feed or variable, no
    doubling ``scale`` between a sigmoid and the delta rule; the
    model's layers 1 to 5 in their own order (delta rule with a dense
    MLP, two routed delta-rule layers, the routed latent layer, one
    more delta-rule layer); from layer 4 on the run starts with the
    latent layer.  The latent layer's query reaches the attention op as
    projected (a reshape of the projection, no split and no concat),
    its key as [k_nope | the one shared slice repeated]."""
    def build(cfg):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            feeds, _, _ = kimi.build_pretrain(cfg, SEQ)
        return main, feeds

    def kinds(main):
        return [op.type for op in main.global_block().ops
                if op.type in ('kda_attention', 'moe_route',
                               'fused_multihead_attention')]

    main, feeds = build(kimi.TINY)
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert sorted(feeds) == ['ids', 'labels']
    assert 'rotary_embedding' not in types
    assert not [v for v in main.global_block().vars if 'pos' in v]
    assert all(op.attrs.get('scale') != 2.0 for op in ops
               if op.type == 'scale')
    delta, latent = 'kda_attention', 'fused_multihead_attention'
    assert kinds(main) == [delta, delta, 'moe_route', delta, 'moe_route',
                           latent, 'moe_route', delta, 'moe_route']
    later = copy.copy(kimi.TINY)
    later.first_layer, later.layers = 4, 2
    assert kinds(build(later)[0]) == [latent, 'moe_route', delta,
                                      'moe_route']
    attend, = [op for op in ops if op.type == latent]
    producer = {n: op for op in ops for names in op.outputs.values()
                for n in names}
    assert producer[attend.inputs['Q'][0]].type in ('reshape', 'reshape2')
    key = producer[attend.inputs['K'][0]]
    assert key.type == 'concat'
    assert [producer[n].type for n in key.inputs['X']][1] == 'expand'

    # a Moonlight-shaped build of the same layer rotates, and feeds
    # positions
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        moonlight.build_pretrain(moonlight.TINY, SEQ)
    assert 'rotary_embedding' in [op.type
                                  for op in main.global_block().ops]


def test_the_shared_key_slice_enters_the_product_as_projected():
    """``moonlight.attention`` handed no positions against the
    reference's NoPE attention on the same weights, and against
    Moonlight's reference at positions 0 .. T-1, which it must miss:
    the helper's switch changes the result, in the direction the
    config says."""
    rng = np.random.RandomState(4)
    cfg = copy.copy(kimi.TINY)
    u = rng.randn(2, SEQ, cfg.hidden).astype('float32')
    shapes = [(cfg.hidden, cfg.heads * (cfg.qk_nope + cfg.qk_rope)),
              (cfg.hidden, cfg.kv_rank + cfg.qk_rope), (cfg.kv_rank,),
              (cfg.kv_rank, cfg.heads * (cfg.qk_nope + cfg.v_dim)),
              (cfg.heads * cfg.v_dim, cfg.hidden)]
    weights = _seeded_weights(shapes, cfg, 5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            out = moonlight.attention(
                layers.data('u', shape=[SEQ, cfg.hidden],
                            dtype='float32'), None, cfg)
            names = [p.name for p in main.all_parameters()]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for name, w in zip(names, weights):
            fluid.global_scope().set_var(name, jnp.asarray(w))
        got, = exe.run(main, feed={'u': u}, fetch_list=[out])
    sizes = reference.sizes_of(cfg)
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.nope_attention(
            jnp.asarray(u), *weights, sizes))
        rotated = np.asarray(moonlight_reference.attention(
            jnp.asarray(u), jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ)),
            *weights, dict(sizes, rope_theta=10000.0)))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    assert np.abs(got - rotated).max() > 0.05 * np.abs(want).max()


def test_bf16_amp_keeps_the_log_decays_float32_beside_bf16_q_k_v():
    """Under bf16 AMP the delta rule's q, k, v and beta arrive bf16 and
    its log decays float32, inside a recompute group;
    the latent layer's q, k, v arrive bf16; the loss is the float32
    reference's to bf16 matmuls' rounding."""
    slots = ('Q', 'K', 'V', 'A', 'Beta')
    extra = [_input_of('kda_attention', s) for s in slots] + \
        [_input_of('fused_multihead_attention', s) for s in 'QKV']
    amp = _program_and_reference(HELD, 5, amp=True, extra=extra)
    dtypes = [jnp.asarray(x).dtype.name for x in amp[6]]
    assert dtypes == ['bfloat16'] * 3 + ['float32'] + ['bfloat16'] * 4
    assert (np.asarray(amp[6][3]) <= 0).all()
    _, _, _, weights, biases, feed, _ = amp
    want = float(_reference(HELD, weights, biases, feed)[0])
    assert 0 < abs(amp[0] - want) <= 5e-3 * want


def test_a_train_step_counts_every_delta_rule_layers_scans():
    """``kda/chunks`` over ONE traced train program: four delta-rule
    layers, one chunk each at 40 tokens, THREE walks a grouped layer:
    the forward scan, the recompute group's second forward, the
    reverse walk (as ``ssm/chunks`` counts Phi-4-mini-flash's scans);
    the last block is no group and walks twice."""
    _program_and_reference(kimi.TINY, 6)
    assert monitor.gauge_value('kda/chunks') == 3 * 1 * 3 + 2


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


@pytest.mark.parametrize('experts,top_k', [(32, 4), (256, 8)],
                         ids=['1_a_share', '8_a_share'])
def test_the_32_expert_shares_and_the_shared_expert_once_add_up(experts,
                                                                top_k):
    """The deployment's 32 chips a layer: the routed experts in 32
    shares (one or, as published, eight of 256 a share) under a
    nonzero choice bias and the 2.446, beside one shared expert.  The
    parts of the routed sum the 32 shares give through
    ``moonlight.sparse_mlp``'s ``layers.moe`` call, plus the shared
    expert counted ONCE, add up to what the uncut reference gives for
    the whole MLP; counted 32 times they do not."""
    rng = np.random.RandomState(2)
    b, t, d, hidden, shares = 2, 12, 16, 8, 32
    per = experts // shares
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
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        routed, _ = reference.routed_share(
            flat, wr, bias, gate, up, down, top_k, 2.446, None)
        once = np.asarray(reference.gated_mlp(flat, *shared))
        whole = np.asarray(routed) + once
    cfg = copy.copy(kimi.TINY)
    cfg.hidden, cfg.expert_hidden = d, hidden
    cfg.experts, cfg.top_k, cfg.bias_update_rate = experts, top_k, 0.0

    def build(i):
        xv = layers.data('x', shape=[t, d], dtype='float32')
        if i == shares:                 # the shared expert, once
            return moonlight.gated_mlp(xv, hidden, cfg)
        held = copy.copy(cfg)
        held.experts_held = (i * per, per)
        # sparse_mlp gives x + shared + routed: the routed part alone
        # is what is left once a zero-weight shared expert and x go
        return layers.elementwise_sub(moonlight.sparse_mlp(xv, xv, held),
                                      xv)

    zero = [0 * w for w in shared]
    parts = [[wr, gate[i * per:(i + 1) * per], up[i * per:(i + 1) * per],
              down[i * per:(i + 1) * per], bias] + zero
             for i in range(shares)] + [shared]
    total = _run_sum(build, {'x': x}, parts).reshape(b * t, d)
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()
    every_time = _run_sum(lambda i: build(shares), {'x': x},
                          [shared] * (shares - 1)).reshape(b * t, d)
    assert np.abs(every_time - (shares - 1) * once).max() <= \
        2e-5 * shares * np.abs(once).max()
    assert np.abs(every_time).max() > 0.5 * np.abs(whole).max()
    # and one share alone is far from the whole
    alone = _run_sum(lambda i: build((0, shares)[i]), {'x': x},
                     parts[:1] + parts[-1:]).reshape(b * t, d)
    assert np.abs(alone - whole).max() > 0.3 * np.abs(whole).max()


def test_the_cell_s_cut_builds_the_published_parameter_count():
    """The published widths, as the cell cuts them (layers 1 to 5,
    experts 0 to 7 of 256, 20480 vocabulary rows), BUILT and counted,
    nothing run: 602,433,408 trainable parameters, by layer as the
    issue reckons them, and four 256-wide choice biases that are no
    parameter of the optimizer."""
    cfg = copy.copy(kimi.BASE)
    cfg.layers, cfg.experts_held, cfg.vocab_size = 5, (0, 8), 20480
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        kimi.build_pretrain(cfg, 64)
        every = main.all_parameters()
    count = sum(int(np.prod(p.shape)) for p in every if p.trainable)
    assert count == 602433408
    assert [tuple(p.shape) for p in every if not p.trainable] == \
        [(256,)] * 4
    operator = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + \
        3 * 4096 * 4 + 32 + 4096 + 2304 * 32 + 128
    assert operator == 39514272
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + \
        4096 * 2304
    assert latent == 29114880
    sparse = 2304 * 256 + 9 * 3 * 2304 * 1024 + 2 * 2304
    assert operator + 3 * 2304 * 9216 + 2 * 2304 == 103219872
    assert operator + sparse == 103809696 and latent + sparse == 93410304
    assert count == 103219872 + 3 * 103809696 + 93410304 + \
        2 * 20480 * 2304 + 2304

