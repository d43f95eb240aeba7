"""Laguna through fluid against its plain reference
(``paddle_tpu/models/reference/laguna.py``): the zoo program's loss and
every parameter's gradient with one chip's share of the experts, the
shares adding up to the uncut layer, banded and grouped-K/V attention
(kernels under the interpreter against the dense chain), the rotary
op's partial width and YaRN table, the counters.  CPU, tiny sizes; the
published widths are checked on the chip (``chip_smoke.py --phase
laguna``, PERF.md)."""

import copy
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import laguna
from paddle_tpu.models.reference import laguna as reference
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.parallel import moe as pmoe

SEQ = 24        # three windows of TINY's 8: the band is narrower than T


# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(laguna.TINY)
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


def _program_and_reference(cfg, seed, amp=False):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights -> (loss, {param: grad}, params in
    creation order, weights, feed)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(fluid.Scope()):
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, _, loss = laguna.build_pretrain(cfg, SEQ)
            params = [p.name for p in main.all_parameters()]
            shapes = [tuple(main.global_block().var(p).shape)
                      for p in params]
            opt = fluid.optimizer.SGD(0.0)
            if amp:
                opt = fluid.contrib.mixed_precision.decorate(
                    opt, use_dynamic_loss_scaling=False,
                    init_loss_scaling=1.0)
            pairs = opt.minimize(loss)[1]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, cfg, seed)
        scope = fluid.global_scope()
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        feed = laguna.synthetic_batch(cfg, 2, SEQ,
                                      np.random.RandomState(seed))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs])
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:])}
    return _scalar(out[0]), grads, params, weights, feed


def _reference(cfg, weights, feed, **kw):
    sizes = reference.sizes_of(cfg)
    if kw:
        return reference.loss(weights, feed['ids'], feed['pos_ids'],
                              feed['labels'], sizes=sizes, **kw)
    return reference.loss_and_grads(weights, feed['ids'],
                                    feed['pos_ids'], feed['labels'],
                                    sizes=sizes)


@pytest.mark.parametrize('cfg', [HELD, laguna.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Float32 program against the float32 reference, both at full
    matmul precision: what is left is the order of float32 sums
    through five layers, measured up to 3.1e-5 of a gradient's largest
    entry (the embedding's, which every layer's error flows into); the
    bound is 3x that.  A wrong K/V group, window edge, rotated width,
    YaRN ramp, gate, scale or held range moves gradients by whole
    percents."""
    loss, grads, params, weights, feed = _program_and_reference(cfg, 3)
    want, want_grads = _reference(cfg, weights, feed)
    assert abs(loss - float(want)) <= 2e-6 * abs(float(want))
    assert set(grads) == set(params)
    assert len(params) == 3 + 10 + 14 * 4
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(grads[name] - g).max() <= 1e-4 * np.abs(g).max(), \
            name


def test_tiny_bf16_amp_is_nearer_the_reference_than_all_bf16():
    """bf16 AMP (bf16 matmuls; f32 master weights, router, norms,
    rotary tables, the gates' sigmoid and the loss) against the f32
    reference, beside the reference in bfloat16 THROUGHOUT, mean
    relative loss error over three seeds: the program has to be the
    nearer one."""
    amp_err, low_err = [], []
    for seed in (1, 2, 3):
        loss, _, _, weights, feed = _program_and_reference(HELD, seed,
                                                           True)
        want = float(_reference(HELD, weights, feed,
                                dtype=jnp.float32))
        low = float(_reference(HELD, weights, feed, dtype=jnp.bfloat16))
        amp_err.append(abs(loss - want) / want)
        low_err.append(abs(low - want) / want)
    assert np.mean(amp_err) < np.mean(low_err), (amp_err, low_err)
    assert np.mean(amp_err) <= 2e-3, amp_err


# --- the share -------------------------------------------------------


def _moe_layer(x, held, weights, experts=16, top_k=4, hidden=24,
               scale=2.5):
    """``layers.moe`` with a held range on given weights -> (out,
    load, dropped, monitor's counters after one fetching run)."""
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
                                aux_weight=0.0)
            params = [p.name for p in main.all_parameters()]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        monitor.reset()
        got, = exe.run(main, feed={'x': x}, fetch_list=[out])
        counters = dict(monitor.flat())
    return np.asarray(got), counters


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts top-4 in four shares of 4: the parts of the routed
    sum the four shares give add up to what the uncut reference gives
    for the whole layer, the shared expert (every chip computes it
    alike) counted once.  Also what each share reports: rows held
    summing to the rows routed, no drop."""
    rng = np.random.RandomState(0)
    b, t, d, experts, top_k, hidden = 2, 20, 32, 16, 4, 24
    x = rng.randn(b, t, d).astype('float32')
    wr = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    shared = [rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(d, hidden).astype('float32') / np.sqrt(d),
              rng.randn(hidden, d).astype('float32') / np.sqrt(hidden)]
    flat = jnp.asarray(x.reshape(b * t, d))
    with jax.default_matmul_precision('highest'):
        whole, load = reference.routed_share(flat, wr, gate, up, down,
                                             top_k, 2.5, None)
        whole = np.asarray(whole + reference.gated_mlp(flat, *shared))
    total = np.asarray(reference.gated_mlp(flat, *shared))   # once
    held_rows = 0.0
    for first in range(0, experts, 4):
        part, counters = _moe_layer(
            x, (first, 4), [wr, gate[first:first + 4],
                            up[first:first + 4], down[first:first + 4]])
        total = total + part.reshape(b * t, d)
        assert counters['moe/dropped_tokens'] == 0
        assert counters['moe/tokens_routed'] == b * t * top_k
        want = float(np.asarray(load)[first:first + 4].sum())
        assert counters['moe/rows_held'] == want
        assert counters['moe/held_share'] == pytest.approx(
            want / (b * t * top_k))
        held_rows += counters['moe/rows_held']
    assert held_rows == b * t * top_k
    assert np.abs(total - whole).max() <= 2e-5 * np.abs(whole).max()


def test_a_share_that_holds_nothing_chosen_adds_nothing_and_drops_none():
    """A router that sends every token to experts 0 .. 3: the share
    that holds 8 .. 11 computes zero rows, returns zeros, and counts
    no drop; the one that holds 0 .. 3 gets every row (the buffer's
    bound, tokens x min(top_k, count), exactly filled)."""
    rng = np.random.RandomState(1)
    b, t, d, experts, hidden = 1, 12, 16, 16, 8
    x = np.abs(rng.randn(b, t, d)).astype('float32')
    wr = np.full((d, experts), -5.0, 'float32')
    wr[:, :4] = 5.0 + rng.rand(d, 4)
    weights = [wr] + [rng.randn(4, *s).astype('float32')
                      for s in ((d, hidden), (d, hidden), (hidden, d))]
    none, counters = _moe_layer(x, (8, 4), weights, hidden=hidden)
    assert np.abs(none).max() == 0
    assert counters['moe/rows_held'] == 0
    assert counters['moe/dropped_tokens'] == 0
    every, counters = _moe_layer(x, (0, 4), weights, hidden=hidden)
    assert counters['moe/rows_held'] == b * t * 4
    assert counters['moe/held_share'] == 1.0
    assert counters['moe/dropped_tokens'] == 0
    assert np.abs(every).max() > 0


def test_the_sorted_buffer_is_bounded_by_what_can_be_held():
    assert pmoe.held_rows_bound(4096, 10, (0, 8)) == 4096 * 8
    assert pmoe.held_rows_bound(4096, 10, (0, 32)) == 4096 * 10
    assert pmoe.held_rows_bound(4096, 10) == 4096 * 10
    idx = jnp.asarray([[9, 2, 5], [3, 0, 2]], jnp.int32)
    assert pmoe.sort_keys(idx, (2, 2)).tolist() == [2, 0, 2, 1, 2, 0]
    order, inverse = pmoe.sort_by_expert(idx, (2, 2))
    assert order.tolist() == [1, 5, 3, 0, 2, 4]
    assert inverse[order].tolist() == list(range(6))


def _pair_side(x, w, y, g_rows, g_out, order, inverse, k, n_held):
    """The plain pair-side formulas (what the whole-buffer body
    computes), in numpy at float64, reading only the rows of the held
    experts -> (rows, dx, out, dy, dweight)."""
    x, w, y, g_rows, g_out = (np.asarray(a, np.float64)
                              for a in (x, w, y, g_rows, g_out))
    order, inverse = np.asarray(order), np.asarray(inverse)
    s, bound = w.shape[0], y.shape[0]
    live = inverse < n_held                       # by pair
    at = np.minimum(inverse, bound - 1)
    rows = x[order[:bound] // k]
    dx = np.where(live[:, None], g_rows[at], 0).reshape(s, k, -1).sum(1)
    picked = np.where(live[:, None], y[at], 0).reshape(s, k, -1)
    out = (picked * w[:, :, None]).sum(1)
    dy = (g_out[:, None, :] * w[:, :, None]).reshape(s * k, -1)[
        order[:bound]] * (np.arange(bound) < n_held)[:, None]
    dweight = (picked * g_out[:, None, :]).sum(-1)
    return rows, dx, out, dy, dweight


def _routing(rng, s, k, n_held):
    """idx [s, k] over 8 experts of which 0 and 1 are held, with
    exactly ``n_held`` pairs on them (at most one of each a token),
    and the sort's (order, inverse) for held = (0, 2)."""
    idx = np.stack([2 + rng.permutation(6)[:k] for _ in range(s)])
    for j in rng.permutation(s * 2)[:n_held]:
        idx[j // 2, j % 2] = j % 2
    idx = jnp.asarray(idx, jnp.int32)
    assert int(jnp.sum(pmoe.sort_keys(idx, (0, 2)) < 2)) == n_held
    return (idx,) + pmoe.sort_by_expert(idx, (0, 2))


# a buffer of 24 rows walked in chunks of 8 (which divide it), of 7
# (which do not: the last chunk starts early) and of 24 (one trip);
# nothing held, part of a chunk, a chunk exactly, a chunk and a row,
# the whole buffer
@pytest.mark.parametrize('chunk', [8, 7, 24])
@pytest.mark.parametrize('n_held', [0, 5, 8, 9, 24])
def test_rows_past_the_held_groups_carry_nothing_whatever_they_hold(
        n_held, chunk, monkeypatch):
    """On the chip the grouped matmuls leave the rows past their last
    group unwritten, in the forward pass and in the gradient they hand
    back (found by ``chip_smoke.py --phase laguna``: gradients 1e5
    times too large upstream of a routed layer; the CPU's ragged_dot
    writes zeros there, so no test on the tiny model can see it).
    Poison those rows of the experts' output and of the dispatch's
    cotangent with NaN: dispatch_rows / combine_rows and their two
    backward bodies equal the pair-side formulas written out above
    within float32 rounding, whatever the chunk the loops walk the
    buffer in and wherever in a chunk the held rows end."""
    monkeypatch.setattr(pmoe, 'held_rows_chunk',
                        lambda n_rows: min(n_rows, chunk))
    rng = np.random.RandomState(n_held)
    s, k, d, held = 12, 3, 5, (0, 2)
    idx, order, inverse = _routing(rng, s, k, n_held)
    bound = pmoe.held_rows_bound(s, k, held)
    kept = order[:bound]
    x = jnp.asarray(rng.randn(s, d), jnp.float32)
    w = jnp.asarray(rng.rand(s, k), jnp.float32)
    g_out = jnp.asarray(rng.randn(s, d), jnp.float32)
    poison = jnp.where((jnp.arange(bound) >= n_held)[:, None],
                       jnp.nan, 0.0)
    y = jnp.asarray(rng.randn(bound, d), jnp.float32) + poison
    g_rows = jnp.asarray(rng.randn(bound, d), jnp.float32) + poison
    held_rows = jnp.int32(n_held)

    @jax.jit
    def through(x, w, y, g_rows, g_out, held_rows):
        rows, back = jax.vjp(lambda x: pmoe.dispatch_rows(
            x, kept, inverse, k, held_rows), x)
        out, vjp = jax.vjp(lambda y, w: pmoe.combine_rows(
            y, w, kept, inverse, held_rows), y, w)
        return (rows, back(g_rows)[0], out) + vjp(g_out)

    got = through(x, w, y, g_rows, g_out, held_rows)
    want = _pair_side(x, w, jnp.nan_to_num(y), jnp.nan_to_num(g_rows),
                      g_out, order, inverse, k, n_held)
    for name, a, b in zip(('rows', 'dx', 'out', 'dy', 'dweight'), got,
                          want):
        a = np.asarray(a)
        assert np.isfinite(a).all(), name
        if name == 'dy':          # past the held rows: no gradient
            assert (a[n_held:] == 0).all()
        assert np.abs(a - b).max(initial=0) <= 1e-6 * max(
            np.abs(b).max(initial=0), 1), name
    # one loop in each body but the forward gather, and no conditional
    text = str(jax.make_jaxpr(through)(x, w, y, g_rows, g_out,
                                       held_rows))
    assert text.count('while[') == 3 and 'cond[' not in text


def _experts_inputs(n_held, dtype, n_rows=24, d=6, hidden=5,
                    experts=3):
    """A buffer of ``n_rows`` whose first ``n_held`` rows are the
    groups of ``experts`` held experts, the rest NaN, in ``rows`` and
    in the output's cotangent -> (sizes, rows, dout, three weights)."""
    rng = np.random.RandomState(n_held)
    sizes = np.bincount(rng.randint(experts, size=n_held),
                        minlength=experts).astype(np.int32)
    dead = (np.arange(n_rows) >= n_held)[:, None]
    rows, dout = (jnp.asarray(np.where(dead, np.nan, rng.randn(n_rows, d)),
                              dtype) for _ in range(2))
    weights = [jnp.asarray(rng.randn(*shape) / 2, jnp.float32)
               for shape in ((experts, d, hidden), (experts, d, hidden),
                             (experts, hidden, d))]
    return jnp.asarray(sizes), rows, dout, weights


def _through_experts(body, sizes, low, rows, dout, *weights):
    out, vjp = jax.vjp(
        lambda rows, *w: body(rows, sizes, *w, low), rows, *weights)
    return (out,) + vjp(dout)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('chunk', [8, 7, 24])
@pytest.mark.parametrize('n_held', [0, 5, 8, 9, 24])
def test_the_held_experts_body_is_the_plain_one_on_the_rows_held(
        n_held, chunk, dtype, monkeypatch):
    """held_expert_mlp's gated form (the SiLU product, its backward and
    the sum of the rows' two cotangents walked in chunks, in place; its
    own backward that computes gate and up again) against
    ``jax.checkpoint(grouped_expert_mlp)``, which it replaced in the
    layers that hold a range of their experts: with the rows past the
    held ones NaN in ``rows`` and in the output's cotangent, the
    output and the rows' gradient on the held rows and the three
    weight gradients are equal within float32 rounding (bfloat16 under
    AMP's casts: two units) and finite, whatever the chunk and
    wherever in a chunk the held rows end; one loop forward, two
    backward and no conditional."""
    monkeypatch.setattr(pmoe, 'held_rows_chunk',
                        lambda n_rows: min(n_rows, chunk))
    low = dtype == 'bfloat16'
    sizes, rows, dout, weights = _experts_inputs(n_held, dtype)

    def plain(rows, sizes, w_gate, w_up, w_down, low):
        return jax.checkpoint(functools.partial(
            pmoe.grouped_expert_mlp, low_precision=low))(
                rows, sizes, (w_gate, w_up), w_down)

    def held(rows, sizes, w_gate, w_up, w_down, low):
        return pmoe.held_expert_mlp(rows, sizes, (w_gate, w_up), w_down,
                                    'gated', low)

    got = jax.jit(functools.partial(_through_experts, held, sizes, low))(
        rows, dout, *weights)
    want = jax.jit(functools.partial(_through_experts, plain, sizes, low))(
        jnp.nan_to_num(rows), jnp.nan_to_num(dout), *weights)
    unit = 2 * 2.0 ** -8 if low else 1e-6
    for name, a, b in zip(('out', 'drows', 'dw_gate', 'dw_up', 'dw_down'),
                          got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        if name in ('out', 'drows'):    # past the held rows: anything
            a, b = a[:n_held], b[:n_held]
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max(initial=0) <= unit * max(
            np.abs(b).max(initial=0), 1), name
    text = str(jax.make_jaxpr(functools.partial(
        _through_experts, held, sizes, low))(rows, dout, *weights))
    assert text.count('while[') == 3 and 'cond[' not in text


@pytest.mark.parametrize('held', [None, (0, 3)], ids=['all', 'a_range'])
def test_only_a_held_layers_experts_loop(held):
    """``moe_experts`` without ``experts_held`` (OLMoE: every row of
    the buffer is some expert's) traces to grouped_expert_mlp as it
    was: three products forward, no loop, no body with a backward of
    its own, nothing computed twice.  With a held range: the same
    three products forward, the loops, no conditional."""
    from paddle_tpu.ops import registry
    sizes, rows, dout, weights = _experts_inputs(9, 'float32')

    def experts(rows, *w):
        return registry.get('moe_experts').fn(
            _Ctx(), {'Rows': [rows], 'GroupSizes': [sizes], 'WGate': [w[0]],
                     'WUp': [w[1]], 'WDown': [w[2]]},
            {'experts_held': held})['Out'][0]

    forward = str(jax.make_jaxpr(experts)(rows, *weights))
    both = str(jax.make_jaxpr(
        lambda *a: jax.vjp(experts, *a)[1](dout))(rows, *weights))
    assert 'cond[' not in both and 'checkpoint' not in both
    if held is None:
        plain = str(jax.make_jaxpr(
            lambda rows, *w: pmoe.grouped_expert_mlp(
                rows, sizes, w[:2], w[2]))(rows, *weights))
        assert forward == plain and 'custom_vjp' not in forward
        assert 'while[' not in both
    else:
        assert forward.count('while[') == 1 and both.count('while[') == 3
        assert 'optimization_barrier' in both
    assert forward.count('ragged_dot_general[') == 3


@pytest.mark.parametrize('chunk', [5, 16, 512])
def test_both_sums_are_the_whole_length_scatter_add_bit_for_bit(
        chunk, monkeypatch):
    """The loops keep the buffer's row order, so each token's sum is
    the same chain of float32 adds as one scatter-add of the masked
    rows over the whole buffer: the dispatch's gradient and the
    weighted sum back equal ``jax.ops.segment_sum`` to the last bit,
    for random routings and every chunk (a seed trains the trajectory
    it trained before the buffer was walked in chunks)."""
    monkeypatch.setattr(pmoe, 'held_rows_chunk',
                        lambda n_rows: min(n_rows, chunk))
    s, k, d, held = 40, 3, 33, (0, 2)
    bound = pmoe.held_rows_bound(s, k, held)
    for seed, n_held in enumerate((0, 1, 17, 48, 63, 80)):
        rng = np.random.RandomState(seed)
        _, order, inverse = _routing(rng, s, k, n_held)
        kept, held_rows = order[:bound], jnp.int32(n_held)
        x = jnp.zeros((s, d), jnp.float32)
        w = jnp.asarray(rng.rand(s, k), jnp.float32)
        y = jnp.asarray(rng.randn(bound, d) * 10 ** rng.uniform(
            -3, 3, (bound, 1)), jnp.float32)
        live = (jnp.arange(bound) < n_held)[:, None]

        def whole(rows):
            return jax.ops.segment_sum(jnp.where(live, rows, 0),
                                       kept // k, num_segments=s)

        dx = jax.jit(lambda g: jax.vjp(lambda x: pmoe.dispatch_rows(
            x, kept, inverse, k, held_rows), x)[1](g)[0])(y)
        assert (np.asarray(dx) == np.asarray(jax.jit(whole)(y))).all()
        out = jax.jit(lambda y, w: pmoe.combine_rows(
            y, w, kept, inverse, held_rows))(y, w)
        gated = jax.jit(lambda y, w: whole(
            y * w.reshape(-1)[kept][:, None]))(y, w)
        assert (np.asarray(out) == np.asarray(gated)).all()


@pytest.mark.parametrize('tokens,top_k,held,n_rows', [
    (4096, 10, (0, 8), 32768), (8192, 6, (0, 8), 49152),
    (12, 3, (0, 2), 24)], ids=['laguna', 'moonlight', 'tiny'])
def test_the_chunk_comes_from_the_buffers_length_alone(
        tokens, top_k, held, n_rows):
    """Both held cells' layers walk their buffer 512 rows a trip (an
    even routing holds 1,280 of Laguna's 32,768 rows and 6,144 of
    Moonlight's 49,152: 3 and 12 trips); a buffer shorter than that is
    one chunk.  No constant tuned on a cell's loads is left."""
    assert pmoe.held_rows_bound(tokens, top_k, held) == n_rows
    chunk = pmoe.held_rows_chunk(n_rows)
    assert chunk == min(n_rows, 512)
    assert not hasattr(pmoe, 'held_rows_prefix')
    assert not hasattr(pmoe, 'PREFIX_OVER_EVEN')


def _layer_jaxpr(held, experts, tokens=512):
    """What the permutation of a ``layers.moe`` (top-2, forward and
    backward) traces to: the layer's own ops with the attributes it
    gave them, route -> dispatch -> combine."""
    from paddle_tpu.ops import registry
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        xv = layers.data('x', shape=[tokens, 8], dtype='float32')
        layers.moe(xv, num_experts=experts, hidden_size=4,
                   capacity_factor=None, top_k=2, experts_held=held)
    ops = {op.type: op for op in main.global_block().ops}

    def run(kind, ins):
        return registry.get(kind).fn(_Ctx(), ins, ops[kind].attrs)

    def permuted(x, wg):
        route = run('moe_route', {'X': [x], 'Gate': [wg]})
        sizes = route.get('HeldLoad', route['Load'])
        rows = run('moe_dispatch', {'X': [x], 'TopKIdx': route['TopKIdx'],
                                    'GroupSizes': sizes})
        ins = {'Rows': rows['Rows'], 'TopKWeight': route['TopKWeight'],
               'Order': rows['Order'], 'Inverse': rows['Inverse']}
        if held is not None:
            ins['GroupSizes'] = sizes
        return jnp.sum(run('moe_combine', ins)['Out'][0])

    x, wg = jnp.ones((tokens, 8)), jnp.ones((8, experts))
    return str(jax.make_jaxpr(jax.grad(permuted, (0, 1)))(x, wg))


@pytest.mark.parametrize('held,experts,loops', [
    (None, 16, 0), ((0, 2), 16, 3), ((3, 1), 64, 3), ((0, 64), 64, 3)],
    ids=['all_experts', 'an_eighth', 'one_of_64', 'a_range_that_is_all'])
def test_a_held_layer_traces_three_loops_and_no_conditional(
        held, experts, loops):
    """A layer that holds a range of its experts, however large a
    share, loops in the combine and in both backward bodies, with one
    body a direction: no conditional, no second arm.  All experts
    (no ``experts_held``): the pair-side program, neither."""
    text = _layer_jaxpr(held, experts)
    assert text.count('while[') == loops and 'cond[' not in text


@pytest.mark.parametrize('rows,walked', [
    ([0, 0], 0), ([1, 0], 512), ([100, 413], 1024)],
    ids=['none_held', 'one_row', 'a_chunk_and_a_row'])
def test_held_layers_report_their_largest_load_and_the_share_walked(
        rows, walked):
    """Two layers of 512 tokens top-2 that hold 2 of 64 experts (a
    buffer of 1,024 rows, two chunks of 512), the first always with 40
    rows in its first chunk: ``moe/held_rows_max`` is the larger
    layer's rows, ``moe/walked_share`` the rows both layers' loops
    walk, whole chunks up to the last held row, over both buffers."""
    from paddle_tpu.fluid import moe_stats
    assert pmoe.held_rows_chunk(pmoe.held_rows_bound(
        512, 2, (0, 2))) == 512
    values = []
    for n in (40, sum(rows)):
        load = np.zeros(64, np.int32)
        load[4:6] = [n - n // 2, n // 2] if n == 40 else rows
        load[63] = 1024 - load.sum()
        values += [load, load[4:6]]
    record = moe_stats.HeldLayers()
    record.top_k += [2, 2]
    monitor.reset()
    record(values)
    assert monitor.gauge_value('moe/walked_share') == \
        (512 + walked) / 2048.
    assert monitor.gauge_value('moe/held_rows_max') == max(sum(rows), 40)
    assert monitor.counter_value('moe/rows_held') == 40 + sum(rows)
    assert monitor.gauge_value('moe/held_share') == pytest.approx(
        (40 + sum(rows)) / 2048.)
    assert 'moe/prefix_overflows' not in monitor.flat()


def test_moe_rejects_a_held_range_it_cannot_hold():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data('x', shape=[4, 8], dtype='float32')
        for held, cf in (((6, 4), None), ((0, 0), None), ((0, 2), 2.0)):
            with pytest.raises(ValueError, match='experts_held'):
                layers.moe(x, num_experts=8, hidden_size=4,
                           capacity_factor=cf, top_k=2,
                           experts_held=held)


# --- banded and grouped-K/V attention --------------------------------


def _dense_attention(q, k, v, window):
    """The plain form: repeat K/V over the group, band the mask."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   precision='highest') / np.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = j <= i
    if window:
        visible = visible & (i - j < window)
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v, precision='highest')


def _qkv(t, h, hkv, d=16, b=2, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, t, h, d), jnp.float32),
            jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32),
            jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32),
            jnp.asarray(rng.randn(b, t, h, d), jnp.float32))


# T = 384 in blocks of 128: three key blocks; window 100 is no
# multiple of anything here, 160 spans two blocks, 384 and 1000 are
# plain causal
@pytest.mark.parametrize('fused', [True, False],
                         ids=['fused_bwd', 'two_pass_bwd'])
@pytest.mark.parametrize('window', [0, 100, 160, 384, 1000])
@pytest.mark.parametrize('h,hkv', [(6, 2), (4, 4)],
                         ids=['group3', 'group1'])
def test_banded_grouped_kernels_match_the_dense_chain(
        pallas_interpret, monkeypatch, h, hkv, window, fused):
    """Forward and both backward paths under the interpreter, against
    the plain dense form, at a length that is no multiple of the
    window; ``window >= T`` equals causal."""
    monkeypatch.setattr(fa, 'FUSED_BWD', fused)
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_Q', 128)
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_K', 128)
    monkeypatch.setattr(fa, 'FUSED_BLOCK_Q', 128)
    monkeypatch.setattr(fa, 'FUSED_BLOCK_K', 128)
    t = 384
    q, k, v, do = _qkv(t, h, hkv)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, min_seq=0))
    assert fa._common._LAST['flash_attention']['path'] == 'fused'
    want = run(lambda q, k, v: _dense_attention(q, k, v, window))
    for name, x, y in zip(('o', 'dq', 'dk', 'dv'), got, want):
        assert x.shape == y.shape, name
        assert float(jnp.abs(x - y).max()) <= 2e-5 * float(
            jnp.abs(y).max()), name
    if window >= t:
        causal = run(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, min_seq=0))
        for x, y in zip(got, causal):
            assert float(jnp.abs(x - y).max()) == 0


def test_grouped_kernels_with_a_key_bias_and_dropout(pallas_interpret,
                                                     monkeypatch):
    """The bias and the in-kernel dropout draw are indexed by QUERY
    head in every kernel: grouped K/V with both against the module's
    own dense arm (same hash), forward and two-pass backward."""
    monkeypatch.setattr(fa, 'FUSED_BWD', False)
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_Q', 128)
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_K', 128)
    t = 256
    q, k, v, do = _qkv(t, 4, 2, seed=1)
    bias = jnp.asarray(np.random.RandomState(2).randn(2, t), jnp.float32)
    seed = jnp.uint32(11)

    def run(min_seq):
        def fn(q, k, v, bias):
            return fa.flash_attention(
                q, k, v, causal=True, window=96, key_bias=bias,
                dropout_rate=0.25, dropout_seed=seed, min_seq=min_seq)
        out, vjp = jax.vjp(fn, q, k, v, bias)
        return (out,) + vjp(do)

    got, want = run(0), run(10 ** 9)
    for name, x, y in zip(('o', 'dq', 'dk', 'dv', 'dbias'), got, want):
        assert float(jnp.abs(x - y).max()) <= 3e-5 * float(
            jnp.abs(y).max()), name


def test_the_dense_arm_bands_and_groups_too():
    """Off a TPU the op answers dense: the same numbers."""
    q, k, v, _ = _qkv(40, 6, 2)
    got = fa.flash_attention(q, k, v, causal=True, window=7)
    assert fa._common._LAST['flash_attention']['path'] == 'dense'
    want = _dense_attention(q, k, v, 7)
    assert float(jnp.abs(got - want).max()) <= 1e-5


def test_attention_rejects_a_window_without_causal_and_odd_groups():
    q, k, v, _ = _qkv(16, 6, 2)
    with pytest.raises(ValueError, match='window'):
        fa.flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match='head count'):
        fa.flash_attention(q, k[:, :, :1].repeat(4, 2), v, causal=True)


def test_a_windowed_call_is_lowered_in_a_scope_of_its_own():
    """The device trace tells windowed from full calls by the scope
    the op lowers them in: ``window<n>`` inside the op's own."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data('q', shape=[16, 4, 8], dtype='float32')
        k = layers.data('k', shape=[16, 2, 8], dtype='float32')
        out = laguna._attend(q, k, k, 5)
        full = laguna._attend(q, k, k, 0)
    from paddle_tpu.ops import registry
    ops = [op for op in main.global_block().ops
           if op.type == 'fused_multihead_attention']
    texts = []
    for op in ops:
        fn = registry.get(op.type).fn

        def lowered(q, k, op=op):
            with jax.named_scope(op.type):
                return fn(registry.LoweringContext()
                          if hasattr(registry, 'LoweringContext')
                          else _Ctx(), {'Q': [q], 'K': [k], 'V': [k]},
                          op.attrs)['Out'][0]
        spec = jax.ShapeDtypeStruct((2, 16, 4, 8), jnp.float32)
        kspec = jax.ShapeDtypeStruct((2, 16, 2, 8), jnp.float32)
        texts.append(jax.jit(lowered).lower(spec, kspec).as_text(
            debug_info=True))
    assert 'fused_multihead_attention/window5' in texts[0]
    assert 'fused_multihead_attention/window' not in texts[1]
    assert out.shape == full.shape == (-1, 16, 4, 8)


class _Ctx(object):
    auto_partitioned = False

    def dropout_seed(self, attrs):
        return None


# --- rotary ----------------------------------------------------------


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
            if 'inv_freq' in kw:
                kw['inv_freq'] = layers.assign(kw['inv_freq'])
            qo, ko = layers.rotary_embedding(qv, kv, pv, **kw)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.asarray(x) for x in exe.run(
            main, feed={'q': q, 'k': k, 'pos': pos},
            fetch_list=[qo, ko])]


def test_rotary_partial_width_table_and_factor_match_the_plain_form():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 10, 6, 16).astype('float32')
    k = rng.randn(2, 10, 2, 16).astype('float32')
    pos = np.tile(np.arange(10), (2, 1)).astype('int64')
    table = laguna.yarn_inv_freq(8, **laguna.TINY.yarn)
    got_q, got_k = _rotary_op(q, k, pos, rotary_dim=8, inv_freq=table,
                              attention_factor=1.25)
    want_q = np.asarray(reference.rope(jnp.asarray(q), jnp.asarray(pos),
                                       table, 1.25))
    want_k = np.asarray(reference.rope(jnp.asarray(k), jnp.asarray(pos),
                                       table, 1.25))
    assert np.abs(got_q - want_q).max() <= 1e-6
    assert np.abs(got_k - want_k).max() <= 1e-6
    # the features past the rotated width pass through untouched
    assert (got_q[..., 8:] == q[..., 8:]).all()
    # the default case is the whole head at theta's frequencies
    whole_q, _ = _rotary_op(q, k, pos, theta=10000.0)
    plain = 10000.0 ** (-np.arange(8, dtype=np.float32) / 8)
    assert np.abs(whole_q - np.asarray(reference.rope(
        jnp.asarray(q), jnp.asarray(pos), plain))).max() <= 1e-6


@pytest.mark.parametrize('factor,original,dim', [
    (128.0, 8192, 64),          # Laguna-S-2.1's full layers
    (4.0, 16, 8),               # TINY's
    (40.0, 4096, 128),
])
def test_yarn_table_is_transformers(factor, original, dim):
    rope_utils = pytest.importorskip('transformers.modeling_rope_utils')
    pytest.importorskip('torch')

    class Config(object):
        rope_theta = 500000.0
        partial_rotary_factor = 0.5
        head_dim = 2 * dim
        hidden_size, num_attention_heads = 2 * dim, 1
        max_position_embeddings = 1048576
        rope_scaling = {
            'rope_type': 'yarn', 'factor': factor,
            'original_max_position_embeddings': original,
            'beta_fast': 32, 'beta_slow': 1,
            'attention_factor': 1.4852030263919618}

    want, attention_factor = rope_utils._compute_yarn_parameters(
        Config(), 'cpu')
    assert attention_factor == 1.4852030263919618
    kw = dict(rope_theta=500000.0, factor=factor,
              original_max_position_embeddings=original,
              beta_fast=32.0, beta_slow=1.0)
    for got in (laguna.yarn_inv_freq(dim, **kw),
                reference.yarn_inv_freq(dim, 500000.0, factor, original,
                                        32.0, 1.0)):
        assert got.shape == (dim // 2,)
        assert np.abs(got - want.numpy()).max() <= \
            1e-6 * np.abs(want.numpy()).max()


# --- the programs that were there ------------------------------------


def test_olmoe_and_bert_build_the_ops_they_built():
    """The default case of every op this model extended is the old
    one: OLMoE's and BERT's programs carry none of the new attributes
    or inputs, so their lowerings take the branches they took."""
    from paddle_tpu.models import bert, olmoe
    flash = bert.BertConfig(vocab_size=1000, hidden=64, layers=2,
                            heads=4, use_flash=True)
    routed = olmoe.OlmoeConfig(vocab_size=97, hidden=64, layers=2,
                               heads=4, expert_hidden=32, experts=8,
                               top_k=3, max_pos=128)
    # the fused attention op, as the cells' programs hold it
    flash.flash_min_len = routed.flash_min_len = 32
    for build in (lambda: olmoe.build_pretrain(routed, 32),
                  lambda: bert.build_pretrain(flash, 32)):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            build()
        types = {op.type for op in main.global_block().ops}
        assert 'fused_multihead_attention' in types
        for op in main.global_block().ops:
            assert 'experts_held' not in op.attrs, op.type
            assert 'window' not in op.attrs, op.type
            assert 'rotary_dim' not in op.attrs, op.type
            assert 'scale' not in op.attrs or op.type != 'moe_route'
            assert 'InvFreq' not in op.inputs, op.type
            assert 'HeldLoad' not in op.outputs, op.type


def test_olmoe_loss_is_what_it_was_bit_for_bit():
    """OLMoE's tiny train program on seeded weights: the loss and a
    checksum of every gradient as the tree before this model gave
    them (measured on the parent commit f1c29bc, this jax, CPU).  The
    routed layer's default path, the rotary op's default case and the
    attention op without a window are the code they were."""
    import tests.test_olmoe as t
    loss, grads, params, _, _ = t._program_and_reference(3, False)
    assert loss == 5.076165676116943
    assert float(sum(np.abs(grads[p]).sum() for p in params)) == \
        440.795166015625


def test_a_mis_shaped_feed_is_named_under_the_compile_plane_too(tmp_path):
    """With a compile-cache directory the AOT plane lowers a segment
    before any dispatch (``Executor._run_segment``), and a shape error
    there carried no note naming the diverging feed: the whole suite's
    ``test_feed_shape_mismatch_is_named_in_error`` failed whenever an
    earlier test of its worker had left the plane on."""
    prev = fluid.flags.get_flag('FLAGS_compile_cache_dir')
    fluid.set_flags({'FLAGS_compile_cache_dir': str(tmp_path)})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[4], dtype='float32')
            y = layers.fc(x, 2)
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with pytest.raises(Exception) as info:
                exe.run(main, feed={'x': np.zeros((3, 5), 'float32')},
                        fetch_list=[y])
        notes = '\n'.join(getattr(info.value, '__notes__', []))
        assert "feed 'x': shape (3, 5), declared (-1, 4)" in notes, notes
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': prev})
