"""Moonlight through fluid against its plain reference
(``paddle_tpu/models/reference/moonlight.py``): the zoo program's loss
and every parameter's gradient with one chip's share of the experts
and with all of them, the shares adding up to the uncut layer under a
nonzero choice bias, the router (the bias picks and never weighs, moves
by gamma x sign after a train step, takes no gradient, is left alone by
a ``for_test`` clone), the flash kernels at a query / key width that
differs from the value width (under the interpreter against the dense
chain, with the shared rotary key's gradient), the rotary op's
interleaved pairing.  CPU, tiny sizes; the published widths are checked
on the chip (``chip_smoke.py --phase moonlight``, PERF.md)."""

import copy

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import moonlight
from paddle_tpu.models.reference import moonlight as reference
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.parallel import moe as pmoe

SEQ = 24

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(moonlight.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, cfg, seed, router_scale=4.0):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains around 1, a router whose top-k
    margins are wide."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif len(s) == 2 and s == (cfg.hidden, cfg.experts):
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
        _, _, loss = moonlight.build_pretrain(cfg, SEQ)
        every = main.all_parameters()
        params = [p.name for p in every if p.trainable]
        biases = [p.name for p in every if not p.trainable]
        shapes = [tuple(main.global_block().var(p).shape) for p in params]
        opt = fluid.optimizer.SGD(lr)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(
                opt, use_dynamic_loss_scaling=False,
                init_loss_scaling=1.0)
        pairs = opt.minimize(loss)[1]
    return main, startup, loss, params, shapes, biases, pairs


def _program_and_reference(cfg, seed, amp=False, bias_scale=0.3):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights and a seeded choice bias -> (loss,
    {param: grad}, params in creation order, weights, bias values,
    feed)."""
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
        feed = moonlight.synthetic_batch(cfg, 2, SEQ,
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


@pytest.mark.parametrize('cfg', [HELD, moonlight.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision, under a choice bias large enough to change the
    choice: what is left is the order of float32 sums through three
    layers.  A wrong split of the 192, a rotary key that is not
    shared, a scale from the value width, a bias that weighs, a
    missing 2.446 or a wrong held range moves gradients by whole
    percents.  The bias itself is no parameter and gets no gradient."""
    loss, grads, params, weights, biases, feed = \
        _program_and_reference(cfg, 3)
    want, want_grads = _reference(cfg, weights, biases, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    assert len(biases) == 2
    assert len(params) == 3 + 10 + 14 * 2
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 1e-4 * np.abs(g).max(), \
            name
    # and the bias did change the choice the reference made
    unbiased = _reference(cfg, weights, [0 * b for b in biases], feed,
                          dtype=jnp.float32)
    assert abs(float(unbiased) - float(want)) > 1e-4 * float(want)


def test_tiny_bf16_amp_is_nearer_the_reference_than_all_bf16():
    """bf16 AMP (bf16 matmuls; f32 master weights, router, norms,
    rotary and the loss) against the f32 reference, beside the
    reference in bfloat16 THROUGHOUT, mean relative loss error over
    three seeds: the program has to be the nearer one."""
    amp_err, low_err = [], []
    for seed in (1, 2, 3):
        loss, _, _, weights, biases, feed = _program_and_reference(
            HELD, seed, True)
        want = float(_reference(HELD, weights, biases, feed,
                                dtype=jnp.float32))
        low = float(_reference(HELD, weights, biases, feed,
                               dtype=jnp.bfloat16))
        amp_err.append(abs(loss - want) / want)
        low_err.append(abs(low - want) / want)
    assert np.mean(amp_err) < np.mean(low_err), (amp_err, low_err)
    assert np.mean(amp_err) <= 2e-3, amp_err


# --- the router -------------------------------------------------------


def _moe_layer(x, held, weights, bias, experts=16, top_k=4, hidden=24,
               scale=2.446, gamma=0.0, for_test=False, runs=1):
    """``layers.moe`` with sigmoid scores and a choice bias on given
    weights -> (out, the bias after ``runs`` runs, monitor's counters)."""
    b, t, d = x.shape
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            xv = layers.data('x', shape=[t, d], dtype='float32')
            out, _ = layers.moe(xv, num_experts=experts,
                                hidden_size=hidden, capacity_factor=None,
                                top_k=top_k, renormalize=True,
                                gate_scale=scale, experts_held=held,
                                aux_weight=0.0, score_func='sigmoid',
                                score_bias=True, bias_update_rate=gamma)
            every = main.all_parameters()
            params = [p.name for p in every if p.trainable]
            bias_name, = [p.name for p in every if not p.trainable]
        program = main.clone(for_test=True) if for_test else main
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for name, w in zip(params + [bias_name], weights + [bias]):
            scope.set_var(name, jnp.asarray(w))
        monitor.reset()
        for _ in range(runs):
            got, = exe.run(program, feed={'x': x}, fetch_list=[out])
        counters = dict(monitor.flat())
        after = np.asarray(scope.find_var(bias_name))
    return np.asarray(got), after, counters


def _layer_weights(rng, d, experts, hidden):
    wg = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    return wg, gate, up, down


def test_the_four_shares_and_the_shared_experts_add_up_to_the_layer():
    """16 experts top-4 in four shares of 4 under a nonzero choice
    bias: the parts of the routed sum the four shares give add up to
    what the uncut reference gives for the whole layer, the shared
    experts (every chip computes them alike) counted once.  Also what
    each share reports: rows held summing to the rows routed, no
    drop."""
    rng = np.random.RandomState(0)
    b, t, d, experts, top_k, hidden = 2, 20, 32, 16, 4, 24
    x = rng.randn(b, t, d).astype('float32')
    wg, gate, up, down = _layer_weights(rng, d, experts, hidden)
    bias = (0.3 * rng.randn(experts)).astype('float32')
    shared = [rng.randn(d, 2 * hidden).astype('float32') / np.sqrt(d),
              rng.randn(d, 2 * hidden).astype('float32') / np.sqrt(d),
              rng.randn(2 * hidden, d).astype('float32') /
              np.sqrt(2 * hidden)]
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        whole, load = reference.routed_share(
            flat, wg, bias, gate, up, down, top_k, 2.446, None)
        plain, _ = reference.routed_share(
            flat, wg, 0 * bias, gate, up, down, top_k, 2.446, None)
        whole = np.asarray(whole + reference.gated_mlp(flat, *shared))
    assert np.abs(np.asarray(plain) - np.asarray(whole)).max() > 1e-2
    total = np.asarray(reference.gated_mlp(flat, *shared))   # once
    held_rows = 0.0
    for first in range(0, experts, 4):
        part, _, counters = _moe_layer(
            x, (first, 4), [wg, gate[first:first + 4],
                            up[first:first + 4], down[first:first + 4]],
            bias)
        total = total + part.reshape(b * t, d)
        assert counters['moe/dropped_tokens'] == 0
        assert counters['moe/tokens_routed'] == b * t * top_k
        want = float(np.asarray(load)[first:first + 4].sum())
        assert counters['moe/rows_held'] == want
        held_rows += counters['moe/rows_held']
    assert held_rows == b * t * top_k
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()


def test_the_bias_picks_and_never_weighs():
    """One token, four experts, top-2.  Scores (sigmoids of the
    logits) put experts 0 and 1 first; a bias of +1 on expert 3 makes
    the choice {0, 3}.  The gates are the PLAIN scores of 0 and 3 over
    (their sum + 1e-20) times the scale: no trace of the bias; the
    gradient with respect to the bias is zero; with no bias the choice
    is the plain top-2."""
    logits = jnp.asarray([[2.0, 1.0, -1.0, 0.5]], jnp.float32)
    x = jnp.ones((1, 1), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0], jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))[0]

    idx, weight, _, _, load = pmoe.route_topk(
        x, logits, 2, True, 2.5, 'sigmoid', bias)
    assert sorted(idx[0].tolist()) == [0, 3]
    want = {0: s[0] / (s[0] + s[3] + 1e-20) * 2.5,
            3: s[3] / (s[0] + s[3] + 1e-20) * 2.5}
    for e, w in zip(idx[0].tolist(), weight[0].tolist()):
        assert w == pytest.approx(want[e], rel=1e-6)
    assert load.tolist() == [1, 0, 0, 1]

    plain, plain_w, _, _, _ = pmoe.route_topk(x, logits, 2, True, 2.5,
                                              'sigmoid')
    assert sorted(plain[0].tolist()) == [0, 1]
    # without renormalisation the gates are the scores themselves
    _, raw, _, _, _ = pmoe.route_topk(x, logits, 2, False, 1.0,
                                      'sigmoid', bias)
    assert sorted(raw[0].tolist()) == pytest.approx(sorted([s[0], s[3]]))

    def gate_sum(bias, wg):
        return jnp.sum(pmoe.route_topk(x, wg, 2, True, 2.5, 'sigmoid',
                                       bias)[1] ** 2)
    d_bias, d_wg = jax.grad(gate_sum, (0, 1))(bias, logits)
    assert float(jnp.abs(d_bias).max()) == 0
    assert float(jnp.abs(d_wg).max()) > 0


def test_softmax_routing_is_what_it_was():
    """The default arguments trace the parent's router: the same
    numbers as the formulas written out, with and without
    renormalisation (no 1e-20 there)."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(9, 6), jnp.float32)
    wg = jnp.asarray(rng.randn(6, 5), jnp.float32)
    probs = jax.nn.softmax(jnp.dot(x, wg, precision='highest'), -1)
    top, idx = jax.lax.top_k(probs, 2)
    got = pmoe.route_topk(x, wg, 2, True, 2.0)
    assert (np.asarray(got[0]) == np.asarray(idx)).all()
    want = top / jnp.sum(top, -1, keepdims=True) * 2.0
    assert np.abs(np.asarray(got[1]) - np.asarray(want)).max() <= 1e-6


@pytest.mark.parametrize('for_test', [False, True],
                         ids=['train_program', 'for_test_clone'])
def test_the_bias_moves_by_gamma_sign_in_the_train_program_only(
        for_test):
    """Two runs of the train program move each expert's bias by gamma
    towards the mean load each time, from that run's loads (read back
    from the scope, checked against the reference's rule on the
    reference's loads); the ``for_test`` clone routes with the bias
    and leaves it as it is, and reports nothing."""
    rng = np.random.RandomState(5)
    b, t, d, experts, top_k, hidden, gamma = 2, 16, 16, 8, 2, 8, 0.01
    x = rng.randn(b, t, d).astype('float32')
    weights = list(_layer_weights(rng, d, experts, hidden))
    bias = (0.2 * rng.randn(experts)).astype('float32')
    _, after, counters = _moe_layer(
        x, None, weights, bias, experts=experts, top_k=top_k,
        hidden=hidden, gamma=gamma, for_test=for_test, runs=2)
    if for_test:
        assert (after == bias).all()
        assert 'moe/bias_updates' not in counters
        return
    flat = jnp.asarray(x.reshape(b * t, d))
    want = jnp.asarray(bias)
    for _ in range(2):
        with jax.default_matmul_precision('highest'):
            _, _, load = reference.route(flat, weights[0], want, top_k,
                                         2.446)
        want = reference.bias_update(want, load, gamma)
    moved = np.asarray(after) - bias
    assert np.abs(after - np.asarray(want)).max() <= 1e-7
    assert set(np.round(np.abs(moved) / gamma).tolist()) <= {0., 1., 2.}
    assert np.abs(moved).max() > 0
    assert counters['moe/bias_updates'] == 2
    assert counters['moe/score_bias_abs_max'] == pytest.approx(
        float(np.abs(after).max()))


def test_the_model_moves_its_biases_and_sgd_never_touches_them():
    """The tiny model under SGD with a real learning rate: after one
    step every trainable parameter has moved by its gradient, each
    sparse layer's bias by exactly gamma x sign(mean load - load) of
    the loads the reference computes on the startup weights, and the
    ``for_test`` clone run afterwards changes nothing."""
    cfg = HELD
    with fluid.scope_guard(fluid.Scope()):
        main, startup, loss, params, shapes, biases, _ = _build(
            cfg, lr=0.1)
        test_program = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        weights = _seeded_weights(shapes, cfg, 7)
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        before = [np.asarray(scope.find_var(n)) for n in biases]
        assert all(np.abs(b).max() > 0 for b in before)     # TINY's std
        feed = moonlight.synthetic_batch(cfg, 2, SEQ,
                                         np.random.RandomState(7))
        exe.run(main, feed=feed, fetch_list=[loss])
        after = [np.asarray(scope.find_var(n)) for n in biases]
        first, = exe.run(test_program, feed=feed, fetch_list=[loss])
        second, = exe.run(test_program, feed=feed, fetch_list=[loss])
        still = [np.asarray(scope.find_var(n)) for n in biases]
    _, loads = reference.forward(weights, before, feed['ids'],
                                 feed['pos_ids'],
                                 sizes=reference.sizes_of(cfg))
    for b0, b1, b2, load in zip(before, after, still, loads):
        want = reference.bias_update(jnp.asarray(b0), load,
                                     cfg.bias_update_rate)
        assert np.abs(b1 - np.asarray(want)).max() <= 1e-7
        assert (b1 == b2).all()
    assert _scalar(first) == _scalar(second)


def test_moe_names_what_a_bias_needs():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data('x', shape=[4, 8], dtype='float32')
        with pytest.raises(ValueError, match='score_bias corrects the '
                                             'CHOICE among sigmoid'):
            layers.moe(x, num_experts=8, hidden_size=4,
                       capacity_factor=None, top_k=2, score_bias=True)
        with pytest.raises(ValueError, match='score_bias'):
            layers.moe(x, num_experts=8, hidden_size=4,
                       capacity_factor=2.0, top_k=2, score_bias=True)
        with pytest.raises(ValueError, match="score_func='sigmoid' needs "
                                             'the dropless path'):
            layers.moe(x, num_experts=8, hidden_size=4,
                       capacity_factor=2.0, top_k=2, score_func='sigmoid')
        with pytest.raises(ValueError, match='score_func is'):
            layers.moe(x, num_experts=8, hidden_size=4,
                       capacity_factor=None, top_k=2, score_func='tanh')
        with pytest.raises(ValueError, match='bias_update_rate'):
            layers.moe(x, num_experts=8, hidden_size=4,
                       capacity_factor=None, top_k=2,
                       score_func='sigmoid', bias_update_rate=0.1)


def test_the_startup_bias_of_the_cell_changes_one_choice_in_twenty():
    """The benchmark draws the choice bias's startup values at
    Normal(0, ``bias_init_std``) (its configuration file says why).  At
    the published router's size (2048 -> 64, top-6, Normal(0, 0.02)
    weights on unit-RMS inputs) that changes the chosen set of at
    least 5% of the tokens."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), '..', 'benchmark',
                           'configs', 'moonlight-16b-a3b.json')) as f:
        std = json.load(f)['assumed']['bias_init_std']['value']
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2048, 2048), jnp.float32)
    wg = jnp.asarray(0.02 * rng.randn(2048, 64), jnp.float32)
    bias = jnp.asarray(std * rng.randn(64), jnp.float32)
    plain = np.sort(np.asarray(pmoe.route_topk(
        x, wg, 6, True, 2.446, 'sigmoid')[0]), -1)
    biased = np.sort(np.asarray(pmoe.route_topk(
        x, wg, 6, True, 2.446, 'sigmoid', bias)[0]), -1)
    changed = np.mean((plain != biased).any(-1))
    assert 0.05 <= changed, changed


# --- attention at 192 over 128 ---------------------------------------


def _dense_attention(q, k, v):
    """The plain form: scores over 1/sqrt(width of q and k)."""
    t = q.shape[1]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   precision='highest') / np.sqrt(q.shape[-1])
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v, precision='highest')


def _latent_qkv(t, h, nope, rope, dv, b=2, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*s), jnp.float32) for s in (
        (b, t, h, nope + rope), (b, t, h, nope), (b, t, 1, rope),
        (b, t, h, dv), (b, t, h, dv))]


@pytest.mark.parametrize('fused', [True, False],
                         ids=['fused_bwd', 'two_pass_bwd'])
@pytest.mark.parametrize('nope,rope,dv', [(128, 64, 128), (16, 8, 40)],
                         ids=['192_over_128', '24_under_40'])
def test_kernels_at_a_key_width_that_is_not_the_value_width(
        pallas_interpret, monkeypatch, nope, rope, dv, fused):
    """Forward and both backward paths under the interpreter against
    the plain dense form, with the key built as the model builds it:
    each head's position-free part joined to ONE rotary key repeated
    over the heads, whose gradient is then the sum over the heads."""
    monkeypatch.setattr(fa, 'FUSED_BWD', fused)
    for name in ('DEFAULT_BLOCK_Q', 'DEFAULT_BLOCK_K', 'FUSED_BLOCK_Q',
                 'FUSED_BLOCK_K'):
        monkeypatch.setattr(fa, name, 128)
    t, h = 256, 3
    q, k_nope, k_rope, v, do = _latent_qkv(t, h, nope, rope, dv)

    def through(attend):
        def fn(q, k_nope, k_rope, v):
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] +
                                          (rope,))], -1)
            return attend(q, k, v)
        out, vjp = jax.vjp(fn, q, k_nope, k_rope, v)
        return (out,) + vjp(do)

    got = through(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, min_seq=0))
    assert fa._common._LAST['flash_attention']['path'] == 'fused'
    want = through(_dense_attention)
    assert got[0].shape == (2, t, h, dv)
    assert got[3].shape == (2, t, 1, rope)
    for name, x, y in zip(('o', 'dq', 'dk_nope', 'dk_rope', 'dv'), got,
                          want):
        assert x.shape == y.shape, name
        assert float(jnp.abs(x - y).max()) <= 2e-5 * float(
            jnp.abs(y).max()), name


def test_the_dense_arm_takes_the_two_widths_and_scales_by_the_keys():
    q, k_nope, k_rope, v, _ = _latent_qkv(40, 3, 16, 8, 12)
    k = jnp.concatenate([k_nope, jnp.repeat(k_rope, 3, 2)], -1)
    got = fa.flash_attention(q, k, v, causal=True)
    assert fa._common._LAST['flash_attention']['path'] == 'dense'
    assert got.shape == (2, 40, 3, 12)
    assert float(jnp.abs(got - _dense_attention(q, k, v)).max()) <= 1e-5


def test_attention_rejects_a_key_narrower_than_the_query():
    q, k_nope, _, v, _ = _latent_qkv(16, 3, 16, 8, 12)
    with pytest.raises(ValueError, match='K the width of Q'):
        fa.flash_attention(q, k_nope, v, causal=True)


def test_equal_widths_keep_the_vmem_model_and_ask_for_no_more():
    """With one width the residency estimates, the block clamp and the
    fused / two-pass choice are the parent's numbers, and no call asks
    Mosaic for more than its default scoped VMEM; only rows that no
    block size fits (f32 at 8k) do."""
    from paddle_tpu.ops.pallas import common
    for t, d, item in ((2048, 64, 2), (4096, 128, 2), (4096, 128, 4),
                       (512, 64, 2)):
        assert common.vmem_estimate(t, d, 512, 512, item) == \
            common.vmem_estimate(t, d, 512, 512, item, d) == \
            2 * t * d * item + 3 * 512 * d * item + 3 * 512 * 512 * 4 + \
            (1 << 18)
        assert fa._fused_bwd_resident(t, d, 512, item) == \
            4 * t * d * item + t * d * 4 + 4 * 512 * d * 4 + (1 << 19)
        assert fa._fused_bwd_resident(t, d, 512, item, 3) == \
            4 * t * d * item + 3 * t * d * 4 + 4 * 512 * d * 4 + (1 << 19)
        blocks = common.block_sizes(t, 512, 1024, d, item)
        assert common.scoped_vmem(t, d, *blocks, item) is None
    assert common.block_sizes(2048, 512, 1024, 64, 2) == (512, 1024)
    assert common.block_sizes(4096, 512, 1024, 128, 4) == (512, 512)
    # the cell's calls: two buffers of bf16 rows at 8k are the clamp's
    # whole budget, and f32 rows no block size fits
    assert common.block_sizes(8192, 512, 1024, 192, 2, 128) == (512, 512)
    assert common.scoped_vmem(8192, 192, 512, 512, 2, 128) > \
        common.SCOPED_VMEM_BYTES
    blocks = common.block_sizes(8192, 512, 1024, 192, 4, 128)
    assert min(blocks) >= 512
    assert common.scoped_vmem(8192, 192, *blocks, 4, 128) > \
        common.scoped_vmem(8192, 192, 512, 512, 2, 128)


class _Ctx(object):
    auto_partitioned = False

    def dropout_seed(self, attrs):
        return None


def test_a_call_with_two_widths_is_lowered_in_a_scope_of_its_own():
    """The device trace tells the latent layers' calls, and the
    transposes around them, by the scope the op lowers them in:
    ``qk<D>v<Dv>`` inside the op's own; a call with one width has
    none."""
    from paddle_tpu.ops import registry
    fn = registry.get('fused_multihead_attention').fn

    def lowered(q, k, v):
        with jax.named_scope('fused_multihead_attention'):
            return fn(_Ctx(), {'Q': [q], 'K': [k], 'V': [v]},
                      {'causal': True})['Out'][0]

    def text(d, dv):
        return jax.jit(lowered).lower(
            jax.ShapeDtypeStruct((2, 16, 4, d), jnp.float32),
            jax.ShapeDtypeStruct((2, 16, 4, d), jnp.float32),
            jax.ShapeDtypeStruct((2, 16, 4, dv), jnp.float32)).as_text(
                debug_info=True)
    assert 'fused_multihead_attention/qk24v16' in text(24, 16)
    assert 'fused_multihead_attention/qk' not in text(16, 16)


# --- rotary -----------------------------------------------------------


def _rotary_op(q, k, pos, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            qv = layers.data('q', shape=list(q.shape[1:]),
                             dtype='float32')
            kv = layers.data('k', shape=list(k.shape[1:]),
                             dtype='float32')
            pv = layers.data('pos', shape=[q.shape[1]], dtype='int64')
            qo, ko = layers.rotary_embedding(qv, kv, pv, **kw)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.asarray(x) for x in exe.run(
            main, feed={'q': q, 'k': k, 'pos': pos},
            fetch_list=[qo, ko])]


def _hf_interleave(x, pos, theta):
    """HF ``apply_rotary_pos_emb_interleave`` written out in numpy on
    [B, T, H, R]: view the features as (R/2, 2), transpose to (2, R/2),
    then rotate-half with cos / sin of the frequencies repeated twice."""
    b, t, h, r = x.shape
    x = x.reshape(b, t, h, r // 2, 2).transpose(0, 1, 2, 4, 3).reshape(
        b, t, h, r)
    inv_freq = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    angle = pos.astype(np.float64)[:, :, None, None] * inv_freq
    emb = np.concatenate([angle, angle], -1)
    rotated = np.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * np.cos(emb) + rotated * np.sin(emb)


def test_rotary_interleaved_is_the_reference_and_hf_with_one_key_head():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 10, 6, 16).astype('float32')
    k = rng.randn(2, 10, 1, 16).astype('float32')       # one key head
    pos = np.tile(np.arange(3, 13)[None], (2, 1)).astype('int64')
    got_q, got_k = _rotary_op(q, k, pos, theta=50000.0, interleaved=True)
    assert got_k.shape == (2, 10, 1, 16)
    for got, x in ((got_q, q), (got_k, k)):
        want = np.asarray(reference.rope_interleaved(
            jnp.asarray(x), jnp.asarray(pos), 50000.0))
        assert np.abs(got - want).max() <= 1e-6
        assert np.abs(got - _hf_interleave(x, pos, 50000.0)).max() <= 2e-6
    # the pairing matters: rotate-half of the same input is not this
    half_q, _ = _rotary_op(q, k, pos, theta=50000.0)
    assert np.abs(half_q - got_q).max() > 0.1
    # and the scores of a query and a key do not depend on the order
    # the output is left in: position enters by the difference alone
    shifted_q, shifted_k = _rotary_op(q, k, pos + 5, theta=50000.0,
                                      interleaved=True)
    a = np.einsum('bqhd,bkd->bhqk', got_q, got_k[:, :, 0])
    b_ = np.einsum('bqhd,bkd->bhqk', shifted_q, shifted_k[:, :, 0])
    assert np.abs(a - b_).max() <= 1e-3


# --- counting ---------------------------------------------------------


def test_base_is_the_published_model_and_counts_what_the_issue_counts():
    """Parameters of the published widths, as the issue's arithmetic
    has them (millions): attention 13.76 a layer, shared experts 17.30,
    router 0.13, one routed expert 8.65, the dense layer's MLP
    69.21."""
    c = moonlight.BASE
    attention = c.hidden * c.heads * (c.qk_nope + c.qk_rope) + \
        c.hidden * (c.kv_rank + c.qk_rope) + \
        c.kv_rank * c.heads * (c.qk_nope + c.v_dim) + \
        c.heads * c.v_dim * c.hidden
    assert round(attention / 1e6, 2) == 13.76
    assert round(3 * c.hidden * c.shared_experts * c.expert_hidden / 1e6,
                 2) == 17.30
    assert round(c.hidden * c.experts / 1e6, 2) == 0.13
    assert round(3 * c.hidden * c.expert_hidden / 1e6, 2) == 8.65
    assert round((attention + 3 * c.hidden * c.dense_hidden) / 1e6,
                 2) == 82.97
    assert (c.layers, c.top_k, c.routed_scale, c.rope_theta) == \
        (27, 6, 2.446, 50000.0)


def test_the_reference_routed_by_a_given_choice_is_itself_on_its_own():
    """``chosen=`` replaces the reference's choice of experts and
    nothing else (``chip_smoke.py --phase moonlight`` hands it the
    program's, to compare gradients apart from near-tie tokens): its
    own choice gives its own loss, another choice another loss."""
    rng = np.random.RandomState(9)
    w = jnp.asarray(rng.randn(10, 6), jnp.float32)
    wg = jnp.asarray(rng.randn(6, 5), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(5), jnp.float32)
    own, gates, load = reference.route(w, wg, bias, 2, 2.446)
    again = reference.route(w, wg, bias, 2, 2.446, chosen=own)
    assert (np.asarray(again[1]) == np.asarray(gates)).all()
    other = reference.route(w, wg, bias, 2, 2.446, chosen=(own + 1) % 5)
    assert np.abs(np.asarray(other[1]) - np.asarray(gates)).max() > 0
    assert np.asarray(other[2]).tolist() == np.roll(np.asarray(load),
                                                    1).tolist()
