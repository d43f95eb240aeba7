"""EvaByte through fluid against its plain reference
(``paddle_tpu/models/reference/evabyte.py``): the zoo program's loss,
logits and every parameter's gradient; the flash kernels under the
coarse mask over keys of another length than the queries, in the
interpreter against the dense arm, forward and both backward forms,
the log-sum-exp's cotangent included; the layer with one window IS
causal attention; a chunk of equal keys; the first window knows no
phi or mu; the merge at an empty second set; what AMP keeps float32;
the unit-offset norm.  CPU, tiny sizes; the published widths are
checked on the chip (``chip_smoke.py --phase evabyte``, PERF.md)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.fluid.contrib import mixed_precision
from paddle_tpu.models import evabyte
from paddle_tpu.models.reference import evabyte as reference
from paddle_tpu.ops import registry
from paddle_tpu.ops.pallas import flash_attention as fa

TINY = evabyte.TINY            # window 32, chunk 4, 3 heads of prediction


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


def _seeded_weights(shapes, seed):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gain offsets around 0, phi and mu of
    order 1."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 0.1 * rng.randn(*s)
        elif s == (TINY.heads, TINY.head_dim):
            w = rng.randn(*s)
        elif s[0] == TINY.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[0])
        out.append(w.astype('float32'))
    return out


def _program_and_reference(seq, seed):
    """The train program (SGD at lr 0: the fetched gradients are the
    whole step) on seeded weights -> (loss, logits, {param: grad},
    params in creation order, weights, feed)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, logits, loss = evabyte.build_pretrain(TINY, seq)
            params = [p.name for p in main.all_parameters()]
            shapes = [tuple(main.global_block().var(p).shape)
                      for p in params]
            pairs = fluid.optimizer.SGD(0.0).minimize(loss)[1]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, seed)
        scope = fluid.global_scope()
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        feed = evabyte.synthetic_batch(TINY, 2, seq,
                                       np.random.RandomState(seed + 1))
        out = exe.run(main, feed=feed,
                      fetch_list=[loss, logits] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, out[2:])}
    return _scalar(out[0]), np.asarray(out[1]), grads, params, weights, \
        feed


# Both sides are float32 with full-precision products; what differs is
# the order of the sums (two streams merged by their log-sum-exps
# against one softmax over all keys) and the reductions' shapes: the
# loss and logits agree to a few float32 roundings, a gradient to
# 2e-5 of its largest entry (measured 7e-7 at most, PR 38; a dropped
# remote stream moves the loss by 1e-2 and Wq's gradient by 0.3)
@pytest.mark.parametrize('seq', [96, 128])
def test_tiny_f32_loss_logits_and_every_gradient_match_the_reference(seq):
    got, logits, grads, params, weights, feed = \
        _program_and_reference(seq, 5)
    jfeed = {k: jnp.asarray(v) for k, v in feed.items()}

    def plain(w):
        return reference.loss(w, jfeed, TINY, block=32)

    want, want_grads = jax.value_and_grad(plain)(
        [jnp.asarray(w) for w in weights])
    assert abs(got - float(want)) <= 2e-6 * abs(float(want))
    want_logits = reference.forward(weights, jfeed['ids'],
                                    jfeed['pos_ids'], TINY, block=32)
    np.testing.assert_allclose(logits, np.asarray(want_logits),
                               atol=2e-5 * np.abs(want_logits).max())
    assert sorted(grads) == sorted(params)      # phi and mu among them
    for name, w in zip(params, want_grads):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(grads[name], w,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)


def test_the_reference_sees_both_kinds_of_keys():
    """Leaving the remote stream, or mu, out of the REFERENCE moves
    its loss by far more than the tolerance above: the agreement is of
    the whole mathematics."""
    _, _, _, _, weights, feed = _program_and_reference(96, 5)
    jfeed = {k: jnp.asarray(v) for k, v in feed.items()}
    whole = float(reference.loss(weights, jfeed, TINY, block=32))
    import copy
    one_window = copy.copy(TINY)
    one_window.window = 96      # every key exact: no summaries at all
    assert abs(float(reference.loss(weights, jfeed, one_window,
                                    block=32)) - whole) > 1e-4 * whole
    no_mu = [np.zeros_like(w) if i in (6, 17) else w    # the two mu
             for i, w in enumerate(weights)]
    assert weights[6].shape == (TINY.heads, TINY.head_dim)
    assert abs(float(reference.loss(no_mu, jfeed, TINY, block=32)) -
               whole) > 1e-5 * whole


def _coarse_case(seed, b=2, t=128, h=2, d=16, window=32, chunk=4):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(b, t // chunk, h, d), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    cot_lse = jnp.asarray(rng.randn(b, h, t), jnp.float32)
    return q, k, v, cot, cot_lse, (window, chunk)


def _coarse_outputs(q, k, v, cot, cot_lse, coarse, **kw):
    """(o, lse, dq, dk, dv) with a cotangent on o AND on lse (where it
    is finite: a row without keys has none to give)."""
    def loss(q, k, v):
        o, lse = fa.flash_attention(q, k, v, coarse=coarse,
                                    with_lse=True, min_seq=0, **kw)
        return jnp.vdot(o, cot) + jnp.vdot(
            jnp.where(jnp.isfinite(lse), lse, 0.0), cot_lse)

    o, lse = fa.flash_attention(q, k, v, coarse=coarse, with_lse=True,
                                min_seq=0, **kw)
    return (o, lse) + jax.grad(loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize('fused', [True, False],
                         ids=['one-pass', 'dq+dkv'])
@pytest.mark.parametrize('t,window', [(128, 32), (96, 48)])
def test_coarse_kernels_match_the_dense_arm(pallas_interpret, monkeypatch,
                                            fused, t, window):
    """Tk != Tq and the third mask, in the interpreter: forward, the
    fused backward and the two-pass one, with the lse's cotangent; a
    query block that spans windows (one block of 128, or of 96 over
    windows of 48) masks per element."""
    case = _coarse_case(3, t=t, window=window)
    from paddle_tpu.fluid.flags import set_flags
    set_flags({'FLAGS_pallas_force': False})
    want = _coarse_outputs(*case)
    set_flags({'FLAGS_pallas_force': True})
    monkeypatch.setattr(fa, 'FUSED_BWD', fused)
    before = monitor.counter_value('pallas/flash_attention/'
                                   'dispatch_fused') or 0
    got = _coarse_outputs(*case)
    assert monitor.counter_value('pallas/flash_attention/'
                                 'dispatch_fused') > before
    # the first window's rows: no key, o = 0, lse = -inf, on both arms
    for arm in (got, want):
        assert np.all(np.asarray(arm[0][:, :window]) == 0)
        assert np.all(np.isneginf(np.asarray(arm[1][:, :, :window])))
        assert np.all(np.isfinite(np.asarray(arm[1][:, :, window:])))
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        keep = np.isfinite(b)
        np.testing.assert_allclose(a[keep], b[keep], atol=5e-6)


def test_coarse_dense_arm_is_the_masked_softmax():
    q, k, v, _, _, coarse = _coarse_case(4)
    o, lse = fa.flash_attention(q, k, v, coarse=coarse, with_lse=True)
    window, chunk = coarse
    s = jnp.einsum('bthd,bshd->bhts', q, k) / 4.0
    seen = (jnp.arange(32)[None, :] <
            (jnp.arange(128)[:, None] // window) * (window // chunk))
    s = jnp.where(seen, s, -jnp.inf)[:, :, window:]
    np.testing.assert_allclose(
        np.asarray(o[:, window:]),
        np.asarray(jnp.einsum('bhts,bshd->bthd',
                              jax.nn.softmax(s, -1), v)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse[:, :, window:]),
                               np.asarray(jax.nn.logsumexp(s, -1)),
                               atol=1e-5)


@pytest.mark.parametrize('coarse,causal,window,tk,message', [
    ((32, 5), False, 0, 32, 'multiple of the chunk'),
    ((32, 4), True, 0, 32, 'mask of its own'),
    ((32, 4), False, 0, 16, 'do not cover'),
    (None, True, 0, 32, 'another length'),
])
def test_the_argument_checks_know_the_coarse_mask(coarse, causal, window,
                                                  tk, message):
    q = jnp.zeros((1, 128, 2, 16))
    k = v = jnp.zeros((1, tk, 2, 16))
    with pytest.raises(ValueError, match=message):
        fa.flash_attention(q, k, v, causal=causal, window=window,
                           coarse=coarse)


def _attention_programs(t, window, chunk=4, heads=4, d=16):
    """eva_attention and plain causal attention on the same q, k, v,
    phi, mu -> a function (feed) -> (eva out, causal out, grads of
    sum(eva out * cot) w.r.t. phi, mu)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v, cot = (layers.data(n, shape=[t, heads, d],
                                    dtype='float32')
                        for n in ('q', 'k', 'v', 'cot'))
        for x in (q, k, v):
            x.stop_gradient = False
        phi, mu = (layers.create_parameter([heads, d], 'float32')
                   for _ in range(2))
        eva = layers.eva_attention(q, k, v, window, chunk, phi, mu)
        plain = layers.flash_attention(q, k, v, causal=True)
        loss = layers.reduce_sum(layers.elementwise_mul(eva, cot))
        grads = fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    by_name = {p.name: g for p, g in grads}

    def run(feed, phi_value, mu_value):
        scope = fluid.global_scope()
        for var, value in ((phi, phi_value), (mu, mu_value)):
            scope.set_var(var.name, jnp.asarray(value))
        fetch = [eva, plain] + [by_name[n] for n in (phi.name, mu.name)
                                if n in by_name]
        return [np.asarray(x) for x in exe.run(main, feed=feed,
                                               fetch_list=fetch)]
    return run


def _attention_feed(t, seed, heads=4, d=16):
    rng = np.random.RandomState(seed)
    return {n: rng.randn(2, t, heads, d).astype('float32')
            for n in ('q', 'k', 'v', 'cot')}


def test_with_one_window_the_layer_is_causal_attention():
    """window_size >= T: no earlier window, no summary: the shared
    causal path on the same inputs, bit for bit, and phi and mu take
    no gradient."""
    with fluid.scope_guard(fluid.Scope()):
        run = _attention_programs(64, 64)
        rng = np.random.RandomState(0)
        out = run(_attention_feed(64, 1), rng.randn(4, 16).astype('f4'),
                  rng.randn(4, 16).astype('f4'))
    assert len(out) == 2            # neither phi nor mu has a gradient
    np.testing.assert_array_equal(out[0], out[1])


def test_the_first_window_does_not_depend_on_phi_or_mu():
    with fluid.scope_guard(fluid.Scope()):
        run = _attention_programs(96, 32)
        feed = _attention_feed(96, 2)
        rng = np.random.RandomState(3)
        one = run(feed, rng.randn(4, 16).astype('f4'),
                  rng.randn(4, 16).astype('f4'))
        other = run(feed, rng.randn(4, 16).astype('f4'),
                    rng.randn(4, 16).astype('f4'))
    # the first window is the exact stream alone (and equals causal
    # attention there); the later windows see the summaries
    np.testing.assert_array_equal(one[0][:, :32], other[0][:, :32])
    np.testing.assert_allclose(one[0][:, :32], one[1][:, :32], atol=1e-6)
    assert np.abs(one[0][:, 32:] - other[0][:, 32:]).max() > 1e-3
    assert np.abs(one[0][:, 32:] - one[1][:, 32:]).max() > 1e-3
    assert all(np.abs(g).max() > 0 for g in one[2:]) and len(one) == 4


@pytest.mark.parametrize('t,window,chunk', [(100, 32, 4), (96, 32, 5)])
def test_a_length_that_is_no_whole_number_of_windows_is_refused(
        t, window, chunk):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = layers.data('q', shape=[t, 2, 8], dtype='float32')
        phi = layers.create_parameter([2, 8], 'float32')
        with pytest.raises(ValueError, match='whole number'):
            layers.eva_attention(q, q, q, window, chunk, phi, phi)


def _run_op(op_type, ins, attrs=None):
    return registry.get(op_type).run(registry.LowerCtx(0), ins,
                                     attrs or {})


def test_a_chunk_of_equal_keys_summarises_to_that_key_plus_mu():
    rng = np.random.RandomState(7)
    key, value = rng.randn(2, 3, 8), rng.randn(2, 3, 8)     # [n, H, d]
    k = jnp.asarray(np.repeat(key, 16, axis=0)[None], jnp.float32)
    v = jnp.asarray(np.repeat(value, 16, axis=0)[None], jnp.float32)
    phi, mu = (jnp.asarray(rng.randn(3, 8), jnp.float32)
               for _ in range(2))
    out = _run_op('eva_chunk_summary',
                  {'K': [k], 'V': [v], 'Phi': [phi], 'Mu': [mu]},
                  {'chunk_size': 16})
    np.testing.assert_allclose(np.asarray(out['KS'][0][0]),
                               key + np.asarray(mu), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out['VS'][0][0]), value,
                               atol=1e-5)
    with pytest.raises(ValueError, match='whole number'):
        _run_op('eva_chunk_summary',
                {'K': [k[:, :30]], 'V': [v[:, :30]], 'Phi': [phi],
                 'Mu': [mu]}, {'chunk_size': 16})


def test_chunk_summary_keeps_a_bf16_stream_bf16_and_pools_in_f32():
    rng = np.random.RandomState(8)
    k, v = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.bfloat16)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.randn(2, 8), jnp.float32)
               for _ in range(2))
    out = _run_op('eva_chunk_summary',
                  {'K': [k], 'V': [v], 'Phi': [phi], 'Mu': [mu]},
                  {'chunk_size': 4})
    assert out['KS'][0].dtype == out['VS'][0].dtype == jnp.bfloat16
    want = reference.chunk_summaries(k.astype(jnp.float32),
                                     v.astype(jnp.float32), phi, mu, 4)
    np.testing.assert_allclose(
        np.asarray(out['KS'][0], np.float32), np.asarray(want[0]),
        atol=2e-2)


def test_the_merge_of_an_empty_second_set_is_the_first_with_a_zero_gradient():
    rng = np.random.RandomState(9)
    x1, x2 = (jnp.asarray(rng.randn(1, 6, 2, 4), jnp.float32)
              for _ in range(2))
    lse1 = jnp.asarray(rng.randn(1, 6, 2), jnp.float32)
    lse2 = jnp.asarray(rng.randn(1, 6, 2), jnp.float32).at[:, :3].set(
        -jnp.inf)

    def merged(x1, lse1, x2, lse2):
        return _run_op('attention_merge', {
            'X1': [x1], 'Lse1': [lse1], 'X2': [x2], 'Lse2': [lse2]})

    out = merged(x1, lse1, x2, lse2)
    np.testing.assert_array_equal(np.asarray(out['Out'][0][:, :3]),
                                  np.asarray(x1[:, :3]))
    w = jax.nn.sigmoid(lse2 - lse1)[..., None]
    np.testing.assert_allclose(np.asarray(out['Out'][0]),
                               np.asarray((1 - w) * x1 + w * x2),
                               atol=1e-6)
    np.testing.assert_allclose(_scalar(out['SecondWeight'][0]),
                               float(jnp.mean(w[:, 3:])), rtol=1e-6)
    grads = jax.grad(lambda *a: jnp.sum(merged(*a)['Out'][0] ** 2),
                     (0, 1, 2, 3))(x1, lse1, x2, lse2)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    # nothing reaches the second set, or either lse, through an empty row
    for g in grads[1:]:
        np.testing.assert_array_equal(np.asarray(g[:, :3]), 0.0)
    # the joint softmax over both sets, from scratch
    s1, s2 = rng.randn(5), rng.randn(3)
    v1, v2 = rng.randn(5, 4), rng.randn(3, 4)
    p = jax.nn.softmax(jnp.asarray(np.concatenate([s1, s2])))
    joint = merged(
        jnp.asarray(jax.nn.softmax(jnp.asarray(s1)) @ v1).reshape(
            1, 1, 1, 4),
        jax.nn.logsumexp(jnp.asarray(s1)).reshape(1, 1, 1),
        jnp.asarray(jax.nn.softmax(jnp.asarray(s2)) @ v2).reshape(
            1, 1, 1, 4),
        jax.nn.logsumexp(jnp.asarray(s2)).reshape(1, 1, 1))
    np.testing.assert_allclose(
        np.asarray(joint['Out'][0]).ravel(),
        np.asarray(p @ np.concatenate([v1, v2])), atol=1e-6)


def test_amp_keeps_the_residual_adds_and_the_logits_float32():
    """bf16 AMP over the tiny model: the two adds a layer and the
    heads' products carry the keep mark and none of the lists' marks;
    every other matmul is white, the MLP's product gray; run, the
    logits are float32 and the step's matmuls of the block bfloat16."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, logits, loss = evabyte.build_pretrain(TINY, 96)
        optimizer = mixed_precision.decorate(
            fluid.optimizer.SGD(0.0), init_loss_scaling=1.0,
            use_dynamic_loss_scaling=False)
        optimizer.minimize(loss)
    forward = [op for op in main.global_block().ops
               if op.attrs.get('__op_role__') == 'forward']
    kept = [op for op in forward
            if op.attrs.get(mixed_precision.decorator.KEEP_FLOAT32)]
    assert sorted(op.type for op in kept) == \
        ['elementwise_add'] * (2 * TINY.layers) + ['mul'] * TINY.pred_heads
    for op in kept:
        assert not any(a in op.attrs for a in (
            '__amp__', '__amp_gray__', '__amp_black__'))
    muls = [op for op in forward if op.type == 'mul']
    assert sum('__amp__' in op.attrs for op in muls) == 7 * TINY.layers
    assert all('__amp_gray__' in op.attrs for op in forward
               if op.type == 'elementwise_mul')
    # the loss's own adds (head i's term joins the sum) stay gray
    assert sum(op.type == 'elementwise_add' and
               '__amp_gray__' in op.attrs for op in forward) == \
        TINY.pred_heads - 1

    # what the marks do: the ops' outputs as the lowerings type them
    block = main.global_block()
    stream = [block.var(op.output('Out')[0]) for op in kept
              if op.type == 'elementwise_add']
    branch = [block.var(op.output('Out')[0]) for op in muls
              if '__amp__' in op.attrs]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = evabyte.synthetic_batch(TINY, 1, 96,
                                       np.random.RandomState(0))
        out = exe.run(main, feed=feed,
                      fetch_list=[logits] + stream + branch,
                      return_numpy=False)
    assert out[0].dtype == jnp.float32
    assert all(x.dtype == jnp.float32 for x in out[1:1 + len(stream)])
    assert all(x.dtype == jnp.bfloat16 for x in out[1 + len(stream):])


def test_a_program_without_the_mark_is_marked_as_it_was():
    """The mark is an attribute of single ops: a model that sets none
    (OLMoE's block: a bf16 stream) gets the lists' placement,
    residual adds gray."""
    from paddle_tpu.models import olmoe
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss = olmoe.build_pretrain(olmoe.TINY, 32)
        mixed_precision.decorate(
            fluid.optimizer.SGD(0.0), init_loss_scaling=1.0,
            use_dynamic_loss_scaling=False).minimize(loss)
    ops = main.global_block().ops
    assert not any(mixed_precision.decorator.KEEP_FLOAT32 in op.attrs
                   for op in ops)
    adds = [op for op in ops if op.type == 'elementwise_add' and
            op.attrs.get('__op_role__') == 'forward']
    assert adds and all('__amp_gray__' in op.attrs for op in adds)


def test_rms_norm_with_a_unit_offset_at_zero_is_the_plain_norm():
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(2, 5, 16), jnp.float32)
    g = jnp.asarray(0.3 * rng.randn(16), jnp.float32)

    def norm(scale, **attrs):
        return np.asarray(_run_op('rms_norm', {'X': [x], 'Scale': [scale]},
                                  dict(epsilon=1e-5, **attrs))['Y'][0])

    np.testing.assert_array_equal(norm(jnp.zeros(16), unit_offset=True),
                                  norm(jnp.ones(16)))
    np.testing.assert_allclose(norm(g, unit_offset=True), norm(1.0 + g),
                               rtol=1e-6)
    # the layer: the parameter starts at 0 and only then is the
    # attribute on the op
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xin = layers.data('x', shape=[5, 16], dtype='float32')
        plain = layers.rms_norm(xin)
        offset = layers.rms_norm(xin, unit_offset=True)
    ops = [op for op in main.global_block().ops if op.type == 'rms_norm']
    assert [('unit_offset' in op.attrs) for op in ops] == [False, True]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        a, b = exe.run(main, feed={'x': np.asarray(x)},
                       fetch_list=[plain, offset])
    np.testing.assert_array_equal(a, b)


def test_base_is_the_published_model_and_counts_what_the_issue_counts():
    base = evabyte.BASE
    assert (base.hidden, base.layers, base.heads, base.head_dim,
            base.intermediate, base.vocab_size, base.pred_heads,
            base.window, base.chunk, base.max_pos) == \
        (4096, 32, 32, 128, 11008, 320, 8, 2048, 16, 32768)
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202391552
    assert 4 * layer + 320 * 4096 + 8 * 4096 * 320 + 4096 == 821366784
    # the tiny model's parameters, in the order the reference takes them
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        evabyte.build_pretrain(TINY, 96)
    shapes = [tuple(p.shape) for p in main.all_parameters()]
    assert len(shapes) == 1 + reference.PER_LAYER * TINY.layers + 1 + \
        TINY.pred_heads
    assert shapes[1:1 + reference.PER_LAYER] == [
        (64,), (64, 64), (64, 64), (64, 64), (4, 16), (4, 16), (64, 64),
        (64,), (64, 96), (64, 96), (96, 64)]


def test_the_layer_reports_its_pairs_and_the_remote_weight():
    monitor.set_gauge('eva/remote_weight_mean', -1.0)
    _program_and_reference(96, 5)
    # a head and step, batch 2, three windows of 32 over chunks of 4
    assert monitor.gauge_value('eva/local_pairs') == 2 * 3 * 32 * 33 // 2
    assert monitor.gauge_value('eva/remote_pairs') == 2 * 32 * 8 * (1 + 2)
    assert monitor.gauge_value('eva/chunks') == 2 * 24
    assert 0.0 < monitor.gauge_value('eva/remote_weight_mean') < 1.0


def test_labels_shift_by_one_more_for_each_head():
    feed = evabyte.lm_batch(np.arange(10)[None], 3)
    assert feed['labels'].shape == (1, 10, 3)
    np.testing.assert_array_equal(feed['labels'][0, :, 0],
                                  list(range(1, 10)) + [-1])
    np.testing.assert_array_equal(feed['labels'][0, :, 2],
                                  list(range(3, 10)) + [-1] * 3)
