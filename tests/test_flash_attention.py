"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.parallel.ring_attention import reference_attention

pytestmark = pytest.mark.usefixtures('pallas_interpret')


@pytest.fixture(params=[True, False], ids=['fused_bwd', 'two_pass_bwd'])
def fused_bwd(request, monkeypatch):
    """Both backward schemes: the one-pass kernel every BERT cell runs
    and the dq + dkv pair (long sequences, d128), which only the chip
    ran before PR 29."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, 'FUSED_BWD', request.param)
    return request.param


def _parent_dropout_keep(seed, g, qpos, kpos, keep_threshold):
    """The keep hash as the kernels computed it before PR 29, every
    term on the full tile: a literal copy, kept here so that the row /
    column form (and every arm that calls it) is held to these bits."""
    h = (qpos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ \
        (kpos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)) ^ \
        (jnp.asarray(g, jnp.uint32) * jnp.uint32(0xC2B2AE3D)) ^ seed
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> jnp.uint32(16))
    return (h >> jnp.uint32(8)) < jnp.uint32(keep_threshold)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_matches_dense(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 64, 4, 16).astype('float32')
    k = rng.randn(2, 64, 4, 16).astype('float32')
    v = rng.randn(2, 64, 4, 16).astype('float32')
    out = flash_attention(jnp.asarray(q), jnp.asarray(k),
                          jnp.asarray(v), causal=causal)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grad(fused_bwd):
    rng = np.random.RandomState(1)
    q = rng.randn(1, 32, 2, 8).astype('float32')
    k = rng.randn(1, 32, 2, 8).astype('float32')
    v = rng.randn(1, 32, 2, 8).astype('float32')

    def f_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def r_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(f_loss, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v))
    gr = jax.grad(r_loss, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_fused_op_registered():
    from paddle_tpu.ops import registry
    rng = np.random.RandomState(2)
    q = rng.randn(1, 16, 2, 8).astype('float32')
    out = registry.get('fused_multihead_attention').fn(
        registry.LowerCtx(0), {'Q': [q], 'K': [q], 'V': [q]},
        {'causal': False})
    assert out['Out'][0].shape == q.shape


@pytest.mark.parametrize('causal', [False, True])
def test_flash_grad_noncausal_and_odd_t(causal, fused_bwd):
    """Backward Pallas kernels (dq + dkv) against the dense vjp at a
    sequence length that forces block-size shrinkage (t=48)."""
    rng = np.random.RandomState(3)
    q = rng.randn(1, 48, 2, 8).astype('float32')
    k = rng.randn(1, 48, 2, 8).astype('float32')
    v = rng.randn(1, 48, 2, 8).astype('float32')
    cot = rng.randn(1, 48, 2, 8).astype('float32')

    def f(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal),
                        jnp.asarray(cot))

    def r(q, k, v):
        return jnp.vdot(reference_attention(q, k, v, causal=causal),
                        jnp.asarray(cot))

    gf = jax.grad(f, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v))
    gr = jax.grad(r, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_grad_bf16(fused_bwd):
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 32, 1, 8), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 32, 1, 8), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 32, 1, 8), jnp.bfloat16)
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2),
        (0, 1, 2))(q, k, v)
    for a in g:
        assert a.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(a, np.float32)).all()


@pytest.mark.parametrize('dtype,full', [('float32', True),
                                        ('bfloat16', False)])
def test_kernel_products_follow_the_operand_dtype(dtype, full):
    """An f32 program gets f32 answers from every matmul op
    (ops/math_ops.py: HIGHEST); the kernels' products, forward and
    backward, ask the same of Mosaic for f32 operands and stay one
    native pass for bf16 ones (AMP: the timed programs)."""
    q = jnp.zeros((1, 32, 2, 8), dtype)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, min_seq=0).astype(
            jnp.float32)), (0, 1, 2)))(q, q, q))
    assert 'pallas_call' in text
    n_dots = text.count('dot_general[')
    assert n_dots >= 7, text        # 2 forward + 5 backward products
    assert text.count('HIGHEST') >= n_dots if full \
        else 'HIGHEST' not in text, text


def test_bert_flash_path_parity():
    """BERT encoder with the fused flash op == naive attention chain
    (same weights/seeds), forward loss and parameter gradients."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    def run(use_flash):
        cfg = models.bert.BertConfig(
            vocab_size=500, hidden=32, layers=2, heads=2,
            intermediate=64, max_pos=64, dropout=0.0,
            attn_dropout=0.0, use_flash=use_flash)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 31
        with fluid.program_guard(main, startup):
            feeds, enc, loss = models.bert.build_pretrain(cfg, 16)
            fluid.optimizer.SGD(0.1).minimize(loss)
        rng = np.random.RandomState(0)
        batch = models.bert.synthetic_batch(cfg, 4, 16, rng)
        batch['input_mask'][:, 12:] = 0.0  # exercise the key bias
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            out = [exe.run(main, feed=batch, fetch_list=[loss])[0]
                   for _ in range(3)]
        return [float(np.asarray(l).ravel()[0]) for l in out]

    flash, naive = run(True), run(False)
    np.testing.assert_allclose(flash, naive, rtol=2e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_ring_flash_attention_parity(causal):
    """Flash-in-the-ring (sequence parallelism with the Pallas kernel
    per block): output and gradients match dense attention on a 4-way
    'sp' mesh."""
    from paddle_tpu.parallel import mesh as pmesh
    from paddle_tpu.parallel.ring_attention import ring_flash_attention

    mesh = pmesh.create_mesh(sp=4, devices=jax.devices()[:4])
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32)
    cot = jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32)

    def rf(q, k, v):
        return jnp.vdot(ring_flash_attention(q, k, v, mesh,
                                             causal=causal), cot)

    def dense(q, k, v):
        return jnp.vdot(reference_attention(q, k, v, causal=causal),
                        cot)

    out = ring_flash_attention(q, k, v, mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    gf = jax.grad(rf, (0, 1, 2))(q, k, v)
    gr = jax.grad(dense, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_auto_dispatch_and_vmem_clamp():
    """Round-4 VERDICT item 7: below the measured crossover the public
    entry runs the dense XLA chain (same math), and oversized block
    configs clamp to the VMEM budget instead of failing to compile."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(7)
    b, t, h, d = 2, 128, 2, 64
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    assert t < fa.FLASH_MIN_SEQ  # the regression pocket
    o_auto = fa.flash_attention(q, k, v, causal=True)
    o_forced = fa.flash_attention(q, k, v, causal=True, min_seq=0)
    o_dense = fa.flash_attention(q, k, v, causal=True, min_seq=10 ** 9)
    np.testing.assert_allclose(np.asarray(o_auto), np.asarray(o_dense),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_forced),
                               np.asarray(o_dense), rtol=2e-4,
                               atol=2e-5)
    # dense fallback honors the key bias too
    bias = jnp.asarray(rng.randn(b, t) * -2.0, jnp.float32)
    ob_auto = fa.flash_attention(q, k, v, key_bias=bias)
    ob_forced = fa.flash_attention(q, k, v, key_bias=bias, min_seq=0)
    np.testing.assert_allclose(np.asarray(ob_auto),
                               np.asarray(ob_forced), rtol=2e-4,
                               atol=2e-5)
    # grads agree across the dispatch boundary
    gf = jax.grad(lambda q_: jnp.sum(
        fa.flash_attention(q_, k, v, causal=True, min_seq=0) ** 2))(q)
    gd = jax.grad(lambda q_: jnp.sum(
        fa.flash_attention(q_, k, v, causal=True,
                           min_seq=10 ** 9) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               rtol=5e-3, atol=5e-4)
    # oversized blocks degrade inside the budget, never raise
    bq, bk = fa._block_sizes(4096, 4096, 4096, d=128, itemsize=2)
    assert fa._vmem_estimate(4096, 128, bq, bk, 2) <= \
        fa.VMEM_BUDGET_BYTES
    # d=128 runs through the kernels (interpret off-TPU)
    q2 = jnp.asarray(rng.randn(1, 64, 2, 128), jnp.float32)
    o2 = fa.flash_attention(q2, q2, q2, min_seq=0)
    assert o2.shape == (1, 64, 2, 128)


def test_conv_precision_flag():
    """FLAGS_conv_precision selects the f32 MXU algorithm without
    changing results beyond algorithm tolerance."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.ops.nn_ops import _f32_conv_precision
    import jax

    assert _f32_conv_precision() == jax.lax.Precision.HIGHEST
    rng = np.random.RandomState(0)
    x = rng.rand(4, 3, 16, 16).astype('float32')

    def run():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.program_guard(main, startup):
            img = layers.data('img', shape=[3, 16, 16],
                              dtype='float32')
            out = layers.conv2d(img, num_filters=8, filter_size=3)
            loss = layers.reduce_mean(out)
            fluid.optimizer.SGD(0.1).minimize(loss)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            l, = exe.run(main, feed={'img': x}, fetch_list=[loss])
        return float(np.asarray(l).ravel()[0])

    base = run()
    try:
        fluid.flags.set_flags({'FLAGS_conv_precision': 'default'})
        assert _f32_conv_precision() == jax.lax.Precision.DEFAULT
        got = run()
    finally:
        fluid.flags.set_flags({'FLAGS_conv_precision': 'highest'})
    # single-pass bf16 vs 6-pass: same value within bf16 tolerance
    assert abs(got - base) < 5e-2 * max(1.0, abs(base)), (got, base)


def test_conv_precision_flag_rekeys_executable_cache():
    """Toggling FLAGS_conv_precision after first compile must produce
    a NEW executable for the SAME program (the cache keys on it), not
    silently reuse the stale one."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.executor import _Segment
    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 8, 8).astype('float32')
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 9
    with fluid.program_guard(main, startup):
        img = layers.data('img', shape=[3, 8, 8], dtype='float32')
        out = layers.conv2d(img, num_filters=4, filter_size=3)
        loss = layers.reduce_mean(out)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'img': x}, fetch_list=[loss])
        try:
            fluid.flags.set_flags({'FLAGS_conv_precision': 'default'})
            exe.run(main, feed={'img': x}, fetch_list=[loss])
        finally:
            fluid.flags.set_flags({'FLAGS_conv_precision': 'highest'})
        plan = exe._get_plan(main, ('img',), (loss.name,))
        seg = next(it for it in plan if isinstance(it, _Segment))
        precs = {k[1] for k in seg.compiled if isinstance(k, tuple)
                 and len(k) >= 2 and isinstance(k[1], str)}
    assert {'highest', 'default'} <= precs, seg.compiled.keys()


# ---------------------------------------------------------------------------
# In-kernel attention dropout (round 5).  Reference default: dropout on
# the attention probabilities (python/paddle/fluid/layers/nn.py dropout
# around softmax, operators/dropout_op.cu); the flash kernels apply it
# to the probs without materializing [T, T], mask keyed on
# (seed, head, q, k) via a counter hash shared by fwd, both bwd
# kernels, and the dense dispatch arm.
# ---------------------------------------------------------------------------


def test_flash_dropout_matches_dense_same_mask():
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 2, 16).astype('float32'))
    k = jnp.asarray(rng.randn(2, 64, 2, 16).astype('float32'))
    v = jnp.asarray(rng.randn(2, 64, 2, 16).astype('float32'))
    seed = jnp.uint32(1234)
    out = fa.flash_attention(q, k, v, min_seq=0, dropout_rate=0.3,
                             dropout_seed=seed)
    ref = fa._dense_path(q, k, v, False, None, 0.3, seed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_dropout_grads_match_dense_same_mask(causal, fused_bwd):
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 32, 2, 8).astype('float32'))
    k = jnp.asarray(rng.randn(1, 32, 2, 8).astype('float32'))
    v = jnp.asarray(rng.randn(1, 32, 2, 8).astype('float32'))
    seed = jnp.uint32(77)

    def f_loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, min_seq=0,
                               dropout_rate=0.25, dropout_seed=seed)
        return jnp.sum(o ** 2)

    def r_loss(q, k, v):
        o = fa._dense_path(q, k, v, causal, None, 0.25, seed)
        return jnp.sum(o ** 2)

    gf = jax.grad(f_loss, (0, 1, 2))(q, k, v)
    gr = jax.grad(r_loss, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_dropout_key_bias_grad_matches_dense(fused_bwd):
    """dbias under dropout: the key-bias gradient rides ds_raw, which
    now carries the dropout-masked dp term."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 32, 2, 8).astype('float32'))
    k = jnp.asarray(rng.randn(2, 32, 2, 8).astype('float32'))
    v = jnp.asarray(rng.randn(2, 32, 2, 8).astype('float32'))
    bias = jnp.asarray(rng.randn(2, 32).astype('float32'))
    seed = jnp.uint32(99)

    def f_loss(bias):
        o = fa.flash_attention(q, k, v, key_bias=bias, min_seq=0,
                               dropout_rate=0.2, dropout_seed=seed)
        return jnp.sum(o ** 2)

    def r_loss(bias):
        d = q.shape[-1]
        s = jnp.einsum('bthd,bshd->bhts', q, k) / (d ** 0.5)
        s = s + bias[:, None, None, :]
        p = jax.nn.softmax(s, axis=-1)
        b, t, h, _ = q.shape
        # per-element head index array: matches the kernels' scalar
        # program_id per grid instance
        g = (jax.lax.broadcasted_iota(jnp.int32, (b, h, t, t), 0) * h +
             jax.lax.broadcasted_iota(jnp.int32, (b, h, t, t), 1))
        qp = jax.lax.broadcasted_iota(jnp.int32, (b, h, t, t), 2)
        kp = jax.lax.broadcasted_iota(jnp.int32, (b, h, t, t), 3)
        keep = _parent_dropout_keep(seed, g, qp, kp,
                                    fa._keep_threshold(0.2))
        p = jnp.where(keep, p / 0.8, 0.0)
        o = jnp.einsum('bhts,bshd->bthd', p, v)
        return jnp.sum(o ** 2)

    gf = jax.grad(f_loss)(bias)
    gr = jax.grad(r_loss)(bias)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize('case', range(6))
def test_dropout_keep_row_column_form_draws_the_parent_bits(case):
    """PR 29 draws the mask from a [rows, 1] term and a [1, cols] term
    and one broadcast xor; every bit must be the one the per-element
    formula drew, for any seed, head, position and offset (ring
    attention and dp meshes pass non-zero ones)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(100 + case)
    seed = jnp.uint32(rng.randint(0, 2 ** 32, dtype=np.uint64))
    b, h = (int(x) for x in rng.randint(1, 5, 2))
    tq, tk = (int(x) for x in rng.randint(8, 70, 2))
    q_off, k_off, g_off = (int(x) for x in rng.randint(0, 2 ** 20, 3)) \
        if case else (0, 0, 0)
    rate = float(rng.choice([0.1, 0.25, 0.5]))
    shape = (b, h, tq, tk)
    g = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * h + \
        jax.lax.broadcasted_iota(jnp.int32, shape, 1) + g_off
    qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    want = _parent_dropout_keep(seed, g, qpos, kpos,
                                fa._keep_threshold(rate))
    got = fa.dropout_keep_dense(seed, b, h, tq, tk, q_off, k_off, g_off,
                                rate)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the kernels' own spelling: one head, 1-D positions
    rows = fa._keep_rows(seed, g_off + 1, qpos[0, 0, :, :1])
    cols = fa._keep_cols(kpos[0, 0, :1, :])
    np.testing.assert_array_equal(
        np.asarray(fa._dropout_keep(rows, cols,
                                    fa._keep_threshold(rate))),
        np.asarray(_parent_dropout_keep(
            seed, g_off + 1, qpos[0, 0], kpos[0, 0],
            fa._keep_threshold(rate))))
    assert 0.0 < float(jnp.mean(got)) < 1.0


@pytest.mark.parametrize('rate', [0.0, 0.2])
@pytest.mark.parametrize('causal', [False, True])
def test_fully_masked_rows_give_zeros_not_nans(causal, rate, fused_bwd):
    """A key bias of -inf on EVERY key of one batch element: every
    score of its rows is -inf.  The kernels carry no isfinite guard
    around exp since PR 29 (exp(-inf - finite) is 0 and the running
    max / the saved lse are kept finite), so this holds what the
    guarded kernels gave: a zero output row, lse = log(1e-20), and
    zero, finite gradients — in the forward, the fused and the
    two-pass backward.  (The dense chain gives NaN here; the values
    are the parent kernels', written out.)"""
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(11)
    q, k, v, cot = (jnp.asarray(rng.randn(2, 32, 2, 8), jnp.float32)
                    for _ in range(4))
    bias = jnp.asarray(np.stack([np.full(32, -np.inf),
                                 rng.randn(32)]), jnp.float32)
    kw = dict(causal=causal, dropout_rate=rate,
              dropout_seed=jnp.uint32(5) if rate else None)

    def loss(q, k, v, bias):
        return jnp.vdot(fa.flash_attention(q, k, v, key_bias=bias,
                                           min_seq=0, **kw), cot)

    o, lse = fa.flash_attention(q, k, v, key_bias=bias, min_seq=0,
                                 with_lse=True, **kw)
    grads = jax.grad(loss, (0, 1, 2, 3))(q, k, v, bias)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_array_equal(np.asarray(o[0]), 0.0)
    np.testing.assert_allclose(np.asarray(lse[0]), np.log(1e-20),
                               rtol=1e-6)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g[0]), 0.0)
    # the other batch element is ordinary attention
    ref = fa._dense_path(q[1:], k[1:], v[1:], causal, bias[1:], rate,
                         kw['dropout_seed'], dropout_g_offset=2)
    np.testing.assert_allclose(np.asarray(o[1:]), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    assert np.abs(np.asarray(grads[0][1])).max() > 0


@pytest.mark.parametrize('d', [64, 128])
def test_flash_parity_on_both_sides_of_the_exact_scale(d, fused_bwd):
    """1/sqrt(64) only moves the exponent, so the kernels fold it into
    the q or k tile their loop holds fixed; 1/sqrt(128) does not, and
    multiplies the score tile as before.  Output and every gradient
    against the dense chain on the same mask, both ways."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._scale_is_exact(1.0 / d ** 0.5) == (d == 64)
    rng = np.random.RandomState(d)
    q, k, v, cot = (jnp.asarray(rng.randn(1, 64, 2, d), jnp.float32)
                    for _ in range(4))
    bias = jnp.asarray(rng.randn(1, 64), jnp.float32)
    seed = jnp.uint32(21)

    def f(q, k, v, bias):
        return jnp.vdot(fa.flash_attention(
            q, k, v, key_bias=bias, min_seq=0, dropout_rate=0.1,
            dropout_seed=seed), cot)

    def r(q, k, v, bias):
        return jnp.vdot(fa._dense_path(q, k, v, False, bias, 0.1, seed),
                        cot)

    np.testing.assert_allclose(float(f(q, k, v, bias)),
                               float(r(q, k, v, bias)), rtol=1e-4)
    for a, b in zip(jax.grad(f, (0, 1, 2, 3))(q, k, v, bias),
                    jax.grad(r, (0, 1, 2, 3))(q, k, v, bias)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize('lo,hi,tiles', [
    (0, 8, 2),
    (0, 6, 2),
    (2, 6, 2),
    (0, 2, 2),
    (1, 4, 1),
    (0, 8, 1),
    (0, 1, 1),
])
def test_loop_runs_every_tile_once_and_in_order(lo, hi, tiles):
    """_loop puts ``tiles`` tiles in one loop trip (so that the chip
    overlaps one tile's vector chain with another's products): the
    same steps in the same order as one tile a trip."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def step(i, c):
        return jax.lax.rem(c * 3 + i, 1000003)      # order-sensitive

    def run(hi_):
        return fa._loop(lo, hi_, step, jnp.int32(1), tiles)

    want = jax.lax.fori_loop(lo, hi, step, jnp.int32(1))
    assert int(run(hi)) == int(want)
    if tiles == 1:
        assert int(jax.jit(run)(hi)) == int(want)   # traced bound
    text = str(jax.make_jaxpr(lambda: run(hi))())
    assert text.count(' rem ') == tiles, text


@pytest.mark.parametrize('t,d,dtype,causal,fwd,dkv,fused', [
    # (tiles a trip, dO V^T issued early) of the forward / dq loop,
    # the dkv loop and the fused backward's
    (2048, 64, 'bfloat16', False, (2, False), (2, False), (2, False)),
    (512, 64, 'bfloat16', False, (1, True), (1, True), (1, True)),
    (1024, 64, 'bfloat16', False, (1, True), (2, False), (2, False)),
    # olmoe: a dynamic bound, and no room beside 4096 rows of 128
    # under Mosaic's default; the one-pass call asks for 38.5 MB
    (4096, 128, 'bfloat16', True, (1, False), (1, False), (1, True)),
    (2048, 128, 'bfloat16', True, (1, True), (1, True), (1, True)),
    (2048, 128, 'bfloat16', False, (2, False), (2, False), (2, False)),
    (8192, 64, 'bfloat16', False, (1, False), (1, False), (2, False)),
    # f32 tiles count twice; the one-pass calls ask for 43.5 and 30 MB
    (2048, 64, 'float32', False, (1, False), (1, False), (2, False)),
    (1024, 128, 'float32', False, (1, False), (1, False), (2, False)),
    (512, 64, 'float32', False, (1, True), (1, True), (1, True)),
])
def test_the_second_tile_follows_the_vmem_model(t, d, dtype, causal, fwd,
                                                dkv, fused):
    """What each kernel does with the room for a second score tile at
    the shapes that decide (_second_tile over
    common.room_for_second_tile): two tiles a loop trip where the
    trip count is even and known at trace time, else the backward's
    dO V^T issued early, and neither where two tiles do not fit
    beside the instance's rows under the scoped VMEM the call runs
    with: Mosaic's default, or what the one-pass call asks for."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    isz = jnp.dtype(dtype).itemsize
    bq, bk = fa._block_sizes(t, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K,
                             d, isz)
    rows = fa._rows_resident(t, d, bq, bk, isz)
    assert fa._second_tile(None if causal else t // bk, rows, bq, bk,
                           isz) == fwd
    assert fa._second_tile(None if causal else t // bq, rows, bq, bk,
                           isz) == dkv
    from paddle_tpu.ops.pallas import common
    fq, fk = min(bq, fa.FUSED_BLOCK_Q), min(bk, fa.FUSED_BLOCK_K)
    _, limit = common.one_pass_backward_limit(fa._one_pass_vmem(
        t, t, d, d, fq, fk, isz, 1, False, False))
    assert fa._second_tile(
        None if causal else t // fq,
        fa._fused_bwd_resident(t, d, fk, isz), fq, fk, isz,
        limit) == fused


@pytest.fixture
def fresh_calls():
    """_fwd_call / _bwd_call keep their trace by their static
    arguments; the VMEM model's limit, which a test patches under
    them, is not among those."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    fa._fwd_call.clear_cache()
    fa._bwd_call.clear_cache()
    yield
    fa._fwd_call.clear_cache()
    fa._bwd_call.clear_cache()


MB = 1 << 20


@pytest.mark.parametrize('name,shape,dtype,count,asked', [
    # (t, tk, d, dv, group, key bias, lse cotangent) -> MB an instance
    # of the one-pass backward counts, and MB the call asks Mosaic for
    # (None: nothing, the default; False: over the cap, two-pass)
    ('bert_s2048', (2048, 2048, 64, 64, 1, True, False), 'bfloat16',
     14.5, None),
    ('bert_s512', (512, 512, 64, 64, 1, True, False), 'bfloat16',
     8.125, None),
    ('eva_local', (2048, 2048, 128, 128, 1, False, True), 'bfloat16',
     14.375, None),
    ('olmoe', (4096, 4096, 128, 128, 1, False, False), 'bfloat16',
     22.5, 38.5),
    ('laguna_window', (4096, 4096, 128, 128, 9, False, False),
     'bfloat16', 26.5, 42.5),
    ('laguna_full', (4096, 4096, 128, 128, 6, False, False), 'bfloat16',
     26.5, 42.5),
    ('lfm2', (8192, 8192, 64, 64, 4, False, False), 'bfloat16', 47.0,
     63.0),
    ('moonlight', (8192, 8192, 192, 128, 1, False, False), 'bfloat16',
     59.0, 75.0),
    # float32: the 8k rows of Moonlight pass the cap, LFM2's do not;
    # BERT's s2048 b12, which ROADMAP S3 (6) lists as refused, asks
    ('moonlight_f32', (8192, 8192, 192, 128, 1, False, False),
     'float32', 109.0, False),
    ('lfm2_f32', (8192, 8192, 64, 64, 4, False, False), 'float32', 81.0,
     97.0),
    ('bert_s2048_f32', (2048, 2048, 64, 64, 1, True, False), 'float32',
     27.5, 43.5),
    ('bert_s512_f32', (512, 512, 64, 64, 1, True, False), 'float32',
     15.875, None),
])
def test_the_one_pass_backward_is_admitted_by_its_vmem_count(
        name, shape, dtype, count, asked):
    """common.one_pass_backward_vmem at the cells' shapes and
    [512, 512] tiles: rows in two buffers and in the lanes they lie
    in, f32 scratch, vectors, two chains' tiles.  Under Mosaic's 16
    MiB default the call asks for nothing; over it, for the count and
    16 MB; past 100 MB the two-pass kernels run."""
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    t, tk, d, dv, group, bias, glse = shape
    got = fa._one_pass_vmem(t, tk, d, dv, min(512, t), min(512, tk),
                            jnp.dtype(dtype).itemsize, group, bias, glse)
    assert got == count * MB
    admitted, limit = common.one_pass_backward_limit(got)
    assert admitted == (asked is not False)
    if admitted:
        assert limit == (None if asked is None else asked * MB)
        assert (limit is None) == (got <= common.SCOPED_VMEM_BYTES)
    else:
        assert limit > common.VMEM_LIMIT_CAP_BYTES


def _mosaic_limits(fn, *specs):
    """``vmem_limit_bytes`` of each pallas_call fn traces for a chip
    (None where the call passes no compiler_params at all)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                params = dict(eqn.params['compiler_params'])
                found.append(params['mosaic_tpu'].vmem_limit_bytes
                             if params else None)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*specs).jaxpr)
    return found


@pytest.mark.parametrize('shape,kwargs,limits', [
    # bert_base_s2048's and s512_b48's calls hold a PAIR of heads a
    # step since PR 51, four tiles alive: at 2048 keys the forward
    # asks for its 27.25 MB and the one-pass backward for its 20.5,
    # each with the headroom; at 512 both stay under the default and
    # pass no compiler_params
    ((2, 2048, 12, 12, 64, 64), dict(bias=True, rate=0.1),
     [43.25 * MB, 36.5 * MB]),
    ((2, 512, 12, 12, 64, 64), dict(bias=True, rate=0.1), [None, None]),
    # an odd head count at the same width: a head a step on [B x H, T,
    # D], and no compiler_params, as before PR 42
    ((2, 2048, 3, 3, 64, 64), dict(bias=True, rate=0.1), [None, None]),
    # OLMoE's: the backward asks, the forward does not
    ((1, 4096, 2, 2, 128, 128), dict(causal=True), [None, 38.5 * MB]),
    # Moonlight's: both ask, the forward by common.scoped_vmem
    ((1, 8192, 2, 2, 192, 128), dict(causal=True),
     [33.375 * MB, 75 * MB]),
])
def test_a_call_under_the_default_asks_mosaic_for_nothing(
        monkeypatch, fresh_calls, shape, kwargs, limits):
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(common, 'on_tpu', lambda: True)
    b, t, h, hkv, d, dv = shape

    def step(q, k, v, bias):
        def loss(q, k, v):
            o = fa.flash_attention(
                q, k, v, causal=kwargs.get('causal', False),
                key_bias=bias if kwargs.get('bias') else None,
                dropout_rate=kwargs.get('rate', 0.0),
                dropout_seed=jnp.uint32(7))
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    monitor.remove_gauge('pallas/flash_attention/vmem_asked_max')
    got = _mosaic_limits(
        step, *(jax.ShapeDtypeStruct((b, t, n, w), jnp.bfloat16)
                for n, w in ((h, d), (hkv, d), (hkv, dv))),
        jax.ShapeDtypeStruct((b, t), jnp.float32))
    assert got == limits
    assert monitor.gauge_value('pallas/flash_attention/vmem_asked_max') \
        == max(x or 0 for x in limits)


@pytest.mark.parametrize('shape,window', [
    ((1, 1024, 2, 2, 192, 128), 0),     # two widths, as Moonlight's
    ((1, 1024, 4, 2, 128, 128), 512),   # grouped and banded (Laguna)
    ((1, 1024, 2, 2, 128, 128), 0),     # causal d128 (OLMoE, EvaByte)
], ids=['qk192v128', 'grouped_banded', 'causal_d128'])
def test_one_pass_backward_parity_at_the_cells_blocks(shape, window):
    """The one-pass backward with [512, 512] tiles, two key blocks by
    two query blocks under the diagonal, against the dense chain on
    the same mask: the widths, grouping and band of the calls that
    ran the dq + dkv kernels before PR 42."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops.pallas import flash_attention as fa
    b, t, h, hkv, d, dv = shape
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, dv), jnp.float32)
    cot = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)

    def f(q, k, v):
        return jnp.vdot(fa.flash_attention(q, k, v, causal=True,
                                           window=window), cot)

    def r(q, k, v):
        return jnp.vdot(fa._dense_path(q, k, v, True, None,
                                       window=window), cot)

    before = monitor.counter_value(
        'pallas/flash_attention/backward_one_pass')
    got = jax.grad(f, (0, 1, 2))(q, k, v)
    assert monitor.counter_value(
        'pallas/flash_attention/backward_one_pass') == before + 1
    for a, w in zip(got, jax.grad(r, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_the_backward_kind_is_counted_and_shown_in_statusz(monkeypatch):
    """pallas/flash_attention/backward_one_pass and backward_two_pass
    count the lowerings where _flash_bwd decides, and common.report()
    (the /statusz section) shows them with the largest VMEM asked."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    names = ['pallas/flash_attention/backward_%s' % kind
             for kind in ('one_pass', 'two_pass')]
    q = jnp.asarray(np.random.RandomState(3).randn(1, 32, 2, 8),
                    jnp.float32)

    def grad():
        return jax.grad(lambda x: jnp.sum(
            fa.flash_attention(x, q, q, causal=True, min_seq=0)))(q)

    before = [monitor.counter_value(n) for n in names]
    one = grad()
    assert [monitor.counter_value(n) for n in names] == \
        [before[0] + 1, before[1]]
    # what the count refuses runs the dq + dkv kernels, same numbers
    monkeypatch.setattr(common, 'VMEM_LIMIT_CAP_BYTES', 0)
    monkeypatch.setattr(common, 'SCOPED_VMEM_BYTES', 0)
    two = grad()
    assert [monitor.counter_value(n) for n in names] == \
        [before[0] + 1, before[1] + 1]
    np.testing.assert_allclose(np.asarray(one), np.asarray(two),
                               rtol=1e-5, atol=1e-6)
    monitor.set_gauge('pallas/flash_attention/vmem_asked_max', 75 * MB)
    entry = common.report()['kernels']['flash_attention']
    assert entry['backward_one_pass'] == before[0] + 1
    assert entry['backward_two_pass'] == before[1] + 1
    assert entry['vmem_asked_max'] == 75 * MB
    from paddle_tpu.fluid import health
    assert health.statusz()['pallas']['kernels']['flash_attention'][
        'backward_one_pass'] == before[0] + 1


@pytest.mark.parametrize('tokens,room', [
    (128, True),     # eight tiles an instance: four trips of two
    (48, True),      # three: one a trip, dO V^T issued early
    (48, False),     # no room: one a trip, the products in turn
])
def test_flash_parity_with_and_without_a_second_tile(
        fused_bwd, fresh_calls, monkeypatch, tokens, room):
    """bf16 operands with several [16, 16] tiles a kernel instance
    against the dense chain on the same mask, in each of the three
    ways _second_tile() can answer: a tile visited twice, skipped or
    out of place would be far outside bf16's tolerance."""
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    for name in ('DEFAULT_BLOCK_Q', 'DEFAULT_BLOCK_K', 'FUSED_BLOCK_Q',
                 'FUSED_BLOCK_K'):
        monkeypatch.setattr(fa, name, 16)
    if not room:    # neither by default nor by asking (the one-pass call)
        monkeypatch.setattr(common, 'SCOPED_VMEM_BYTES', 0)
        monkeypatch.setattr(common, 'VMEM_HEADROOM_BYTES', 0)
    assert fa._second_tile(tokens // 16, 0, 16, 16, 2) == {
        (128, True): (2, False), (48, True): (1, True),
        (48, False): (1, False)}[tokens, room]
    rng = np.random.RandomState(8)
    q, k, v, cot = (jnp.asarray(rng.randn(1, tokens, 2, 16), jnp.bfloat16)
                    for _ in range(4))
    bias = jnp.asarray(rng.randn(1, tokens), jnp.float32)
    seed = jnp.uint32(3)

    def f(q, k, v, bias):
        o = fa.flash_attention(q, k, v, key_bias=bias, min_seq=0,
                               dropout_rate=0.1, dropout_seed=seed)
        return jnp.vdot(o.astype(jnp.float32), cot.astype(jnp.float32))

    def r(q, k, v, bias):
        o = fa._dense_path(q, k, v, False, bias, 0.1, seed)
        return jnp.vdot(o.astype(jnp.float32), cot.astype(jnp.float32))

    np.testing.assert_allclose(float(f(q, k, v, bias)),
                               float(r(q, k, v, bias)), rtol=2e-2)
    for a, b in zip(jax.grad(f, (0, 1, 2, 3))(q, k, v, bias),
                    jax.grad(r, (0, 1, 2, 3))(q, k, v, bias)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 0.05 * np.abs(b).max() + 0.02, \
            np.abs(a - b).max()


def test_flash_dropout_deterministic_and_seed_sensitive():
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 64, 2, 8).astype('float32'))
    o1 = fa.flash_attention(q, q, q, min_seq=0, dropout_rate=0.5,
                            dropout_seed=jnp.uint32(42))
    o2 = fa.flash_attention(q, q, q, min_seq=0, dropout_rate=0.5,
                            dropout_seed=jnp.uint32(42))
    o3 = fa.flash_attention(q, q, q, min_seq=0, dropout_rate=0.5,
                            dropout_seed=jnp.uint32(43))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))
    # expectation stays the undropped attention (upscale_in_train):
    # the across-seed mean converges to the dropout-free output — a
    # statistical check, so the tolerance is generous (64 seeds,
    # per-element sampling std ~ o/sqrt(64))
    o0 = fa.flash_attention(q, q, q, min_seq=0)
    outs = [fa.flash_attention(q, q, q, min_seq=0, dropout_rate=0.5,
                               dropout_seed=jnp.uint32(s))
            for s in range(64)]
    mean = np.mean([np.asarray(o) for o in outs], axis=0)
    err = np.abs(mean - np.asarray(o0))
    assert np.mean(err) < 0.08, np.mean(err)
    assert np.max(err) < 0.6, np.max(err)


def test_bert_trains_with_attn_dropout_on_flash_path():
    """Reference-default config (attn dropout 0.1) takes the flash path
    and per-op vs whole-program backward produce IDENTICAL losses (the
    counter-hash mask regenerates bit-for-bit in any replay)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.flags import get_flag, set_flags
    from paddle_tpu import models

    def run(wpg):
        cfg = models.bert.BertConfig(
            vocab_size=500, hidden=32, layers=2, heads=2,
            intermediate=64, max_pos=64, dropout=0.1,
            attn_dropout=0.1, use_flash=True)
        cfg.flash_min_len = 16  # force flash at this tiny seq
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 77
        with fluid.program_guard(main, startup):
            feeds, enc, loss = models.bert.build_pretrain(cfg, 16)
            fluid.optimizer.SGD(0.1).minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert 'fused_multihead_attention' in types
        for op in main.global_block().ops:
            if op.type == 'fused_multihead_attention':
                assert op.attrs['dropout_rate'] == 0.1
        rng = np.random.RandomState(0)
        batch = models.bert.synthetic_batch(cfg, 4, 16, rng)
        old = get_flag('FLAGS_whole_program_grad')
        set_flags({'FLAGS_whole_program_grad': wpg})
        try:
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                out = [exe.run(main, feed=batch, fetch_list=[loss])[0]
                       for _ in range(3)]
        finally:
            set_flags({'FLAGS_whole_program_grad': old})
        return [float(np.asarray(l).ravel()[0]) for l in out]

    wpg, per_op = run(True), run(False)
    assert all(np.isfinite(wpg))
    np.testing.assert_allclose(wpg, per_op, rtol=2e-5)


_COUNTED = ('dispatch_fused', 'dispatch_sharded',
            'fallback/auto_partitioned', 'fallback/batch_not_split')


def _flash_counts():
    from paddle_tpu.fluid import monitor
    return np.array([monitor.counter_value('pallas/flash_attention/' + c)
                     or 0 for c in _COUNTED])


def _mesh(axes):
    from jax.sharding import Mesh
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(*axes.values()),
                tuple(axes))


@pytest.mark.parametrize('axes', [{'dp': 4}, {'dp': 2, 'mp': 2}])
def test_kernels_run_on_each_shard_under_a_gspmd_mesh(axes):
    """with_data_parallel / with_mesh trace ONE program for GSPMD,
    which cannot partition a Mosaic kernel: the flash op opens a
    shard_map over the axes the runner split the batch over and runs
    the kernels (here forced, under the interpreter) on each device's
    share, counted as `dispatch_sharded`, and nothing answers dense
    for the mesh's sake.  The sharded losses are the single-device
    losses, dropout mask included (every shard hashes its GLOBAL
    batch x head index), over a model axis too (the call is replicated
    over it)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    seq = 512      # flash_attention()'s own floor: kernels, not dense
    cfg = models.bert.BertConfig(
        vocab_size=512, hidden=128, layers=1, heads=2, intermediate=128,
        max_pos=seq, dropout=0.0, attn_dropout=0.1)
    batch = models.bert.synthetic_batch(cfg, 4, seq,
                                        np.random.RandomState(0))

    def run(mesh):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            _, _, loss = models.bert.build_pretrain(cfg, seq)
            fluid.optimizer.Adam(1e-3).minimize(loss)
        target = main if mesh is None else fluid.CompiledProgram(
            main).with_data_parallel(loss_name=loss.name).with_mesh(mesh)
        before = _flash_counts()   # building infers shapes through dispatch()
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                target, feed=batch, fetch_list=[loss])[0]).ravel()[0])
                for _ in range(2)]
        return losses, dict(zip(_COUNTED, _flash_counts() - before))

    single, counted = run(None)
    assert counted['dispatch_fused'] > 0 and \
        not counted['dispatch_sharded'] and \
        not counted['fallback/auto_partitioned'], counted
    sharded, counted = run(_mesh(axes))
    assert counted['dispatch_fused'] > 0 and \
        counted['dispatch_sharded'] > 0 and \
        not counted['fallback/auto_partitioned'] and \
        not counted['fallback/batch_not_split'], counted
    np.testing.assert_allclose(sharded, single, rtol=2e-4)


def _lowered(mesh, batch_axes, attrs, op_seed=3):
    """fused_multihead_attention's lowering as the GSPMD runner traces
    it: under the published mesh and batch axes.  -> (fn(q, k, v[,
    bias]) -> Out, the op's dropout seed)."""
    from paddle_tpu.ops import registry
    from paddle_tpu.parallel import mesh as pmesh

    def fn(q, k, v, bias=None):
        ins = {'Q': [q], 'K': [k], 'V': [v]}
        if bias is not None:
            ins['KeyBias'] = [bias]
        with pmesh.use_trace_mesh(mesh, batch_axes):
            ctx = registry.LowerCtx(jnp.uint32(0), op_seed)
            return registry.get('fused_multihead_attention').fn(
                ctx, ins, attrs)['Out'][0]
    return fn, registry.LowerCtx(jnp.uint32(0), op_seed).dropout_seed(attrs)


def _operands(b, t, h, hkv, d, dv, bias, seed=0):
    rng = np.random.RandomState(seed)
    qkv = [jnp.asarray(rng.randn(b, t, n, w).astype('float32') * 0.5)
           for n, w in ((h, d), (hkv, d), (hkv, dv))]
    if bias:
        qkv.append(jnp.asarray(np.where(
            rng.rand(b, t) < 0.2, -10000.0, 0.0).astype('float32')))
    return qkv


@pytest.mark.parametrize('batch,axes,batch_axes', [
    (3, {'dp': 2}, ('dp',)),          # the axes do not divide the batch
    (2, {'mp': 2}, ()),               # a tp-only plan: no batch axis
    (2, {'dp': 1, 'mp': 2}, ('dp',)),   # none of more than one device
], ids=['indivisible', 'no_batch_axis', 'batch_axis_of_one'])
def test_a_batch_the_mesh_does_not_split_answers_dense_and_is_counted(
        batch, axes, batch_axes):
    """No shard to hand the kernels: the dense chain answers under a
    reason of its own, not under `auto_partitioned` (a caller that
    wraps nothing) and not in silence."""
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas.flash_attention import _dense_path
    attrs = {'dropout_rate': 0.1}
    fn, seed = _lowered(_mesh(axes), batch_axes, attrs)
    qkv = _operands(batch, 512, 2, 2, 8, 8, bias=True)
    before = _flash_counts()
    out = jax.jit(fn)(*qkv)
    counted = dict(zip(_COUNTED, _flash_counts() - before))
    assert counted == {'dispatch_fused': 0, 'dispatch_sharded': 0,
                       'fallback/auto_partitioned': 0,
                       'fallback/batch_not_split': 1}, counted
    assert common._LAST['flash_attention'] == {
        'path': 'dense', 'reason': 'batch_not_split', 'interpret': False}
    assert common.report()['kernels']['flash_attention'][
        'fallbacks']['batch_not_split'] >= 1
    np.testing.assert_allclose(
        out, _dense_path(*qkv[:3], False, qkv[3], 0.1, seed), atol=1e-6)


@pytest.mark.parametrize('shape,attrs,bias,axes', [
    # (b, t, h, hkv, d, dv)
    ((4, 512, 2, 2, 64, 64), {'dropout_rate': 0.1}, True, {'dp': 2}),
    ((2, 512, 4, 2, 16, 16), {'causal': True, 'window': 128,
                              'dropout_rate': 0.1}, False, {'dp': 2}),
    ((2, 512, 2, 2, 24, 16), {'causal': True}, False, {'dp': 2}),
    ((4, 512, 2, 2, 16, 16), {'dropout_rate': 0.1}, True,
     {'dp': 2, 'fsdp': 2}),
    ((2, 512, 2, 2, 16, 16), {'dropout_rate': 0.1}, True,
     {'dp': 2, 'mp': 2}),
], ids=['key_bias_dropout_d64', 'window_grouped_kv', 'dv_is_not_d',
        'two_batch_axes', 'replicated_over_mp'])
def test_wrapped_op_and_its_gradients_match_dense_on_a_dp_mesh(
        shape, attrs, bias, axes, fused_bwd):
    """The op under a dp mesh of 2 (the kernels on each device's half
    of the batch, shard_map's transpose for the gradient) against the
    dense chain on the whole batch with the same seed: the second
    shard's mask is the one-device run's (its head index starts at
    local_batch x heads), through window, grouped K/V and Dv != D, and
    over two batch axes (an auto plan's dp x fsdp: the shard's index
    is the row-major one of the batch's PartitionSpec), and beside a
    model axis the call is replicated over (the gradient is the one
    call's, not the sum of two)."""
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas.flash_attention import _dense_path
    fn, seed = _lowered(_mesh(axes), tuple(a for a in axes if a != 'mp'),
                        attrs)
    operands = _operands(*shape, bias=bias)
    rate = attrs.get('dropout_rate', 0.0)
    w = jnp.asarray(np.random.RandomState(1).randn(
        shape[0], shape[1], shape[2], shape[5]).astype('float32'))

    def dense(q, k, v, key_bias=None):
        return _dense_path(q, k, v, attrs.get('causal', False), key_bias,
                           rate, seed if rate else None,
                           window=attrs.get('window', 0))

    def grads(f):
        def loss(*xs):
            out = f(*xs)
            return jnp.sum(out * w), out
        return jax.jit(jax.grad(
            loss, argnums=tuple(range(len(operands))),
            has_aux=True))(*operands)

    before = _flash_counts()
    got_grads, got = grads(fn)
    counted = dict(zip(_COUNTED, _flash_counts() - before))
    assert counted['dispatch_sharded'] >= 1 and \
        counted['dispatch_fused'] >= 1 and \
        not counted['fallback/auto_partitioned'], counted
    assert common.report()['kernels']['flash_attention'][
        'dispatch_sharded'] >= 1
    want_grads, want = grads(dense)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for g, wg in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, wg, rtol=2e-4, atol=2e-5)


def test_a_one_device_lowering_holds_no_shard_map():
    """No trace mesh (the one-chip runner), or a mesh of one device:
    the op traces to flash_attention()'s own jaxpr, kernels and all,
    with no shard_map in it, so no one-chip program moves."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    attrs = {'dropout_rate': 0.1}
    qkv = _operands(2, 512, 2, 2, 64, 64, bias=True)
    plain, _ = _lowered(None, (), attrs)
    one, _ = _lowered(_mesh({'dp': 1}), ('dp',), attrs)

    def direct(q, k, v, bias):
        from paddle_tpu.ops import registry
        return fa.flash_attention(
            q, k, v, key_bias=bias, dropout_rate=0.1,
            dropout_seed=registry.LowerCtx(jnp.uint32(0), 3).dropout_seed(
                attrs))

    want = str(jax.make_jaxpr(direct)(*qkv))
    assert 'pallas_call' in want and 'shard_map' not in want
    assert str(jax.make_jaxpr(plain)(*qkv)) == want
    assert str(jax.make_jaxpr(one)(*qkv)) == want
    two, _ = _lowered(_mesh({'dp': 2}), ('dp',), attrs)
    assert 'shard_map' in str(jax.make_jaxpr(two)(*qkv))


# ---- d64 calls in the op's own [B, T, H x 64] layout, a pair a step ----

def _flash_and_grads(impl, operands, with_lse):
    """(o, [lse,] dq, dk, dv[, dbias]) of ``impl`` under fixed
    cotangents on o and lse."""
    q, k, v, bias, cot, lse_cot = operands

    def loss(q, k, v, bias):
        out = impl(q, k, v, bias)
        o, lse = out if with_lse else (out, None)
        total = jnp.vdot(o.astype(jnp.float32), cot.astype(jnp.float32))
        if with_lse:
            total = total + jnp.vdot(lse, lse_cot)
        return total, out

    args = (0, 1, 2) + ((3,) if bias is not None else ())
    (_, out), grads = jax.value_and_grad(loss, args, has_aux=True)(
        q, k, v, bias)
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves((out, grads))]


@pytest.mark.parametrize('bias,rate,with_lse,offsets,causal,dtype', [
    (True, 0.1, False, None, False, 'float32'),     # BERT's call
    (False, 0.0, False, None, False, 'float32'),
    (True, 0.0, True, None, False, 'float32'),
    (False, 0.1, True, (7, 11), False, 'float32'),  # a ring's block
    (True, 0.1, True, (64, 0), True, 'float32'),
    (True, 0.1, False, None, False, 'bfloat16'),
], ids=['bias_drop', 'plain', 'bias_lse', 'drop_lse_offsets',
        'causal_all', 'bf16_bias_drop'])
def test_paired_layout_matches_dense_and_the_transposed_path(
        monkeypatch, bias, rate, with_lse, offsets, causal, dtype):
    """A d64 call with an even number of ungrouped heads reads and
    writes [B, T, H x 64], two heads a grid step: o, lse, dq, dk, dv
    and dbias against the dense chain on the same mask AND against the
    [B x H, T, D] kernels on the same inputs: o and lse keep their
    bits; the gradients lie within float32 rounding of theirs (delta
    is a product with the heads' lane selector there, a reduce here;
    dbias sums pairs first)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    b, t, h, d = 2, 64, 4, 64
    rng = np.random.RandomState(17)
    q, k, v, cot = (jnp.asarray(rng.randn(b, t, h, d), dtype)
                    for _ in range(4))
    operands = (q, k, v,
                jnp.asarray(rng.randn(b, t), jnp.float32) if bias
                else None, cot,
                jnp.asarray(rng.randn(b, h, t), jnp.float32))
    seed = jnp.uint32(5)

    def flash(q, k, v, bias):
        return fa.flash_attention(
            q, k, v, causal=causal, key_bias=bias, min_seq=0,
            dropout_rate=rate, dropout_seed=seed if rate else None,
            with_lse=with_lse, dropout_offsets=offsets,
            dropout_g_offset=3 if offsets else 0)

    def dense(q, k, v, bias):
        return fa._dense_path(q, k, v, causal, bias, rate, seed, offsets,
                              3 if offsets else 0, with_lse=with_lse)

    assert fa._heads_a_step(q, k, v, bias, with_lse, 0, None) == 2
    paired = _flash_and_grads(flash, operands, with_lse)
    monkeypatch.setattr(fa, '_heads_a_step', lambda *a: 1)
    transposed = _flash_and_grads(flash, operands, with_lse)
    wanted = _flash_and_grads(dense, operands, with_lse)
    assert len(paired) == 4 + with_lse + bias
    tol = 2e-5 if dtype == 'float32' else 3e-2
    for i, (got, same, want) in enumerate(zip(paired, transposed, wanted)):
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * np.abs(want).max())
        if i < 1 + with_lse:
            np.testing.assert_array_equal(got, same)
        else:       # f32 rounding, or the last bfloat16 place
            near = 1e-5 if dtype == 'float32' else 2.0 ** -7
            np.testing.assert_allclose(got, same, rtol=near,
                                       atol=near * np.abs(same).max())


@pytest.mark.parametrize('rate,offsets,g_off', [
    (0.1, None, 0), (0.5, (128, 64), 0), (0.25, (3, 5), 24)])
def test_a_pair_draws_keep_hashs_bits_for_both_of_its_heads(
        rate, offsets, g_off):
    """Uniform scores over one-hot values make o[i, :] = keep[i, :] /
    (T (1 - rate)): the mask each head of a pair drew, read off the
    kernel's output, is ops/keep_hash.py's for head index b H + h, bit
    for bit, offsets and all."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    b, t, h, d = 2, 64, 4, 64
    zeros = jnp.zeros((b, t, h, d), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(t, d)[None, :, None, :], (b, t, h, d))
    seed = jnp.uint32(99)
    assert fa._heads_a_step(zeros, zeros, v, False, False, 0, None) == 2
    o = fa.flash_attention(zeros, zeros, v, min_seq=0, dropout_rate=rate,
                           dropout_seed=seed, dropout_offsets=offsets,
                           dropout_g_offset=g_off)
    qo, ko = offsets or (0, 0)
    want = fa.dropout_keep_dense(seed, b, h, t, t, qo, ko, g_off, rate)
    got = np.asarray(o).transpose(0, 2, 1, 3) > 0      # [b, h, q, key]
    np.testing.assert_array_equal(got, np.asarray(want))
    assert 0 < got.mean() < 1


def _pallas_operand_shapes(jaxpr):
    """The operand shapes of every pallas_call in a jaxpr, and whether
    a transpose of a 4-D tensor is in it."""
    shapes, transposes = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                shapes.append([v.aval.shape for v in eqn.invars])
            if eqn.primitive.name == 'transpose' and \
                    len(eqn.invars[0].aval.shape) == 4:
                transposes.append(eqn.invars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return shapes, transposes


_OTHER_SIDE = {
    # (q heads, kv heads, d, dv), mask
    'odd_heads': ((3, 3, 64, 64), {}),
    'd128': ((2, 2, 128, 128), {}),
    'grouped_d64': ((4, 2, 64, 64), {}),
    'wide_values': ((2, 2, 64, 128), {}),
    'window': ((2, 2, 64, 64), dict(causal=True, window=32)),
    'coarse': ((2, 2, 64, 64), dict(coarse=(32, 16))),
}


@pytest.mark.parametrize('case', ['paired'] + sorted(_OTHER_SIDE))
def test_only_the_paired_shape_leaves_the_transposed_jaxpr(
        monkeypatch, case):
    """The shape decides and nothing else does: an odd head count, a
    width other than 64 (of q / k or of v), grouped K/V heads, a band
    or a coarse mask trace to the jaxpr they traced to before there
    was a second layout (what _heads_a_step = 1 gives: [B x H, T, D]
    operands behind 4-D transposes), counted under layout_transposed;
    the paired call's jaxpr holds no 4-D transpose and hands the
    kernels [B, T, H x 64]."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops.pallas import flash_attention as fa
    (h, hkv, d, dv), mask = _OTHER_SIDE.get(case, ((4, 4, 64, 64), {}))
    b, t = 2, 64
    tk = t // 2 if 'coarse' in mask else t
    q = jnp.zeros((b, t, h, d), jnp.float32)
    k = jnp.zeros((b, tk, hkv, d), jnp.float32)
    v = jnp.zeros((b, tk, hkv, dv), jnp.float32)

    def trace():        # a function of its own: make_jaxpr keeps one's
        return jax.make_jaxpr(lambda q, k, v: jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, min_seq=0, **mask)), (0, 1, 2))(q, k, v))(q, k, v)

    names = ['pallas/flash_attention/layout_' + n
             for n in ('paired', 'transposed')]
    before = [monitor.counter_value(n) or 0 for n in names]
    traced = trace()
    counted = [(monitor.counter_value(n) or 0) - x
               for n, x in zip(names, before)]
    shapes, transposes = _pallas_operand_shapes(traced)
    monkeypatch.setattr(fa, '_heads_a_step', lambda *a: 1)
    forced = trace()
    if case == 'paired':
        assert counted == [1, 0]
        assert not transposes and str(traced) != str(forced)
        assert all(s[0] == (b, t, h * d) for s in shapes) and \
            len(shapes) == 2
    else:
        assert counted == [0, 1]
        assert str(traced) == str(forced)
        assert transposes and all(s[0] == (b * h, t, d) for s in shapes)


def test_a_backward_the_count_refuses_takes_the_transposed_path_whole(
        monkeypatch):
    """Forward and backward go one way: where the one-pass count does
    not admit the pair's instance (or FUSED_BWD is off), the forward
    reads [B x H, T, D] too."""
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    q = jnp.zeros((1, 64, 2, 64), jnp.float32)
    assert fa._heads_a_step(q, q, q, True, False, 0, None) == 2
    monkeypatch.setattr(fa, 'FUSED_BWD', False)
    assert fa._heads_a_step(q, q, q, True, False, 0, None) == 1
    monkeypatch.setattr(fa, 'FUSED_BWD', True)
    monkeypatch.setattr(common, 'VMEM_LIMIT_CAP_BYTES', 0)
    monkeypatch.setattr(common, 'SCOPED_VMEM_BYTES', 0)
    assert fa._heads_a_step(q, q, q, True, False, 0, None) == 1
    shapes, _ = _pallas_operand_shapes(jax.make_jaxpr(
        lambda q: fa.flash_attention(q, q, q, min_seq=0))(q))
    assert shapes[0][0] == (2, 64, 64)


def test_the_layout_is_counted_and_shown_in_statusz():
    """pallas/flash_attention/layout_paired and layout_transposed: one
    a fused lowering, under the kernel's entry of common.report() and
    /statusz beside the backward's kind."""
    from paddle_tpu.fluid import health, monitor
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.ops.pallas import flash_attention as fa
    names = ['pallas/flash_attention/layout_' + n
             for n in ('paired', 'transposed')]
    before = [monitor.counter_value(n) or 0 for n in names]
    pair = jnp.zeros((1, 64, 2, 64), jnp.float32)
    fa.flash_attention(pair, pair, pair, min_seq=0)
    fa.flash_attention(pair[:, :, :1], pair[:, :, :1], pair[:, :, :1],
                       min_seq=0)
    fa.flash_attention(pair, pair, pair)    # below the floor: dense
    assert [monitor.counter_value(n) for n in names] == \
        [before[0] + 1, before[1] + 1]
    for entry in (common.report()['kernels']['flash_attention'],
                  health.statusz()['pallas']['kernels'][
                      'flash_attention']):
        assert entry['layout_paired'] == before[0] + 1
        assert entry['layout_transposed'] == before[1] + 1
