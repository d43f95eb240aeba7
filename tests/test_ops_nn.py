"""Per-op tests: NN ops (conv/pool/norm/dropout/losses/tensor manip).

Mirrors reference tests test_conv2d_op.py, test_pool2d_op.py,
test_batch_norm_op.py, test_softmax_with_cross_entropy_op.py, etc.
"""

import functools

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from op_test import OpTest

rng = np.random.RandomState(7)


def ref_conv2d(x, w, stride, pad):
    n, c, h, wdt = x.shape
    oc, ic, kh, kw = w.shape
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wdt + 2 * pad - kw) // stride + 1
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    out = np.zeros((n, oc, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh,
                       j * stride:j * stride + kw]
            out[:, :, i, j] = np.tensordot(patch, w, ([1, 2, 3],
                                                      [1, 2, 3]))
    return out


class TestConv2D(OpTest):
    def test_forward(self):
        x = rng.randn(2, 3, 8, 8).astype('float32')
        w = rng.randn(4, 3, 3, 3).astype('float32')
        self.check_output('conv2d', {'Input': x, 'Filter': w},
                          attrs={'strides': [1, 1], 'paddings': [1, 1]},
                          expect={'Output': ref_conv2d(x, w, 1, 1)},
                          atol=1e-3, rtol=1e-3)

    def test_stride2(self):
        x = rng.randn(1, 2, 9, 9).astype('float32')
        w = rng.randn(3, 2, 3, 3).astype('float32')
        self.check_output('conv2d', {'Input': x, 'Filter': w},
                          attrs={'strides': [2, 2], 'paddings': [0, 0]},
                          expect={'Output': ref_conv2d(x, w, 2, 0)},
                          atol=1e-3, rtol=1e-3)

    def test_grad(self):
        x = rng.randn(1, 2, 5, 5).astype('float32')
        w = rng.randn(2, 2, 3, 3).astype('float32')
        self.check_grad('conv2d', {'Input': x, 'Filter': w},
                        attrs={'strides': [1, 1], 'paddings': [1, 1]},
                        out_slot='Output', atol=2e-2, rtol=2e-2)


class TestPool2D(OpTest):
    def test_maxpool(self):
        x = rng.randn(2, 3, 4, 4).astype('float32')
        expect = x.reshape(2, 3, 2, 2, 2, 2).max(axis=(3, 5))
        self.check_output('pool2d', {'X': x},
                          attrs={'pooling_type': 'max', 'ksize': [2, 2],
                                 'strides': [2, 2], 'paddings': [0, 0]},
                          expect={'Out': expect})

    def test_avgpool(self):
        x = rng.randn(2, 3, 4, 4).astype('float32')
        expect = x.reshape(2, 3, 2, 2, 2, 2).mean(axis=(3, 5))
        self.check_output('pool2d', {'X': x},
                          attrs={'pooling_type': 'avg', 'ksize': [2, 2],
                                 'strides': [2, 2], 'paddings': [0, 0]},
                          expect={'Out': expect})

    def test_global(self):
        x = rng.randn(2, 3, 4, 4).astype('float32')
        self.check_output('pool2d', {'X': x},
                          attrs={'pooling_type': 'avg',
                                 'global_pooling': True, 'ksize': [1, 1]},
                          expect={'Out': x.mean((2, 3), keepdims=True)})

    def test_grad(self):
        x = rng.randn(1, 2, 4, 4).astype('float32')
        self.check_grad('pool2d', {'X': x},
                        attrs={'pooling_type': 'avg', 'ksize': [2, 2],
                               'strides': [2, 2], 'paddings': [0, 0]})


class TestBatchNorm(OpTest):
    def _inputs(self, c=4):
        x = rng.randn(3, c, 5, 5).astype('float32')
        return {'X': x,
                'Scale': rng.rand(c).astype('float32') + 0.5,
                'Bias': rng.randn(c).astype('float32'),
                'Mean': np.zeros(c, 'float32'),
                'Variance': np.ones(c, 'float32')}

    def test_train_forward(self):
        ins = self._inputs()
        x = ins['X']
        m = x.mean((0, 2, 3))
        v = x.var((0, 2, 3))
        y = (x - m.reshape(1, -1, 1, 1)) / np.sqrt(
            v.reshape(1, -1, 1, 1) + 1e-5)
        y = y * ins['Scale'].reshape(1, -1, 1, 1) + \
            ins['Bias'].reshape(1, -1, 1, 1)
        got = self.run_op('batch_norm', ins,
                          attrs={'is_test': False, 'epsilon': 1e-5,
                                 'momentum': 0.9},
                          out_slots=('Y', 'MeanOut', 'VarianceOut',
                                     'SavedMean', 'SavedVariance'))
        np.testing.assert_allclose(got['Y'], y, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(got['MeanOut'], 0.1 * m, atol=1e-5)

    def test_train_forward_large_mean_no_cancellation(self):
        """f32 one-pass stats about the running-mean shift: variance
        must survive |mean| >> std (the naive E[x^2]-E[x]^2 form
        collapses to 0 -> inv=1/sqrt(eps) and blows up Y)."""
        x = (1e4 + rng.randn(8, 4, 5, 5) * 0.01).astype('float32')
        # COLD START: running mean still 0 — the shift must come from
        # the batch itself, not the (useless) running stats
        ins = {'X': x,
               'Scale': np.ones(4, 'float32'),
               'Bias': np.zeros(4, 'float32'),
               'Mean': np.zeros(4, 'float32'),
               'Variance': np.ones(4, 'float32')}
        got = self.run_op('batch_norm', ins,
                          attrs={'is_test': False, 'epsilon': 1e-5,
                                 'momentum': 0.9},
                          out_slots=('Y', 'SavedMean'))
        y = np.asarray(got['Y'])
        # normalized output has ~unit std; the cancellation bug gives
        # std ~ x.std/sqrt(eps) ~ 3
        assert abs(float(y.std()) - 1.0) < 0.2, y.std()
        np.testing.assert_allclose(got['SavedMean'],
                                   x.transpose(1, 0, 2, 3).reshape(
                                       4, -1).mean(1), rtol=1e-6)

    def test_eval_forward(self):
        ins = self._inputs()
        ins['Mean'] = rng.randn(4).astype('float32') * 0.1
        ins['Variance'] = rng.rand(4).astype('float32') + 0.5
        x = ins['X']
        y = (x - ins['Mean'].reshape(1, -1, 1, 1)) / np.sqrt(
            ins['Variance'].reshape(1, -1, 1, 1) + 1e-5)
        y = y * ins['Scale'].reshape(1, -1, 1, 1) + \
            ins['Bias'].reshape(1, -1, 1, 1)
        got = self.run_op('batch_norm', ins,
                          attrs={'is_test': True, 'epsilon': 1e-5},
                          out_slots=('Y',))
        np.testing.assert_allclose(got['Y'], y, atol=1e-4, rtol=1e-4)


class TestLayerNorm(OpTest):
    def test_forward(self):
        x = rng.randn(4, 10).astype('float32')
        scale = rng.rand(10).astype('float32') + 0.5
        bias = rng.randn(10).astype('float32')
        m = x.mean(-1, keepdims=True)
        v = x.var(-1, keepdims=True)
        y = (x - m) / np.sqrt(v + 1e-5) * scale + bias
        self.check_output('layer_norm',
                          {'X': x, 'Scale': scale, 'Bias': bias},
                          attrs={'epsilon': 1e-5, 'begin_norm_axis': 1},
                          expect={'Y': y}, atol=1e-4, rtol=1e-4,
                          out_slots=['Y'])

    def test_grad(self):
        x = rng.randn(3, 6).astype('float32')
        scale = rng.rand(6).astype('float32') + 0.5
        bias = rng.randn(6).astype('float32')
        self.check_grad('layer_norm',
                        {'X': x, 'Scale': scale, 'Bias': bias},
                        attrs={'epsilon': 1e-5, 'begin_norm_axis': 1},
                        out_slot='Y', atol=2e-2, rtol=2e-2)


class TestDropout(OpTest):
    def test_train_stats(self):
        x = np.ones((100, 100), 'float32')
        got = self.run_op('dropout', {'X': x},
                          attrs={'dropout_prob': 0.3, 'is_test': False,
                                 'dropout_implementation':
                                     'upscale_in_train'})
        keep_rate = (np.asarray(got['Out']) != 0).mean()
        assert abs(keep_rate - 0.7) < 0.03
        # kept values upscaled by 1/0.7
        kept = np.asarray(got['Out'])[np.asarray(got['Out']) != 0]
        np.testing.assert_allclose(kept, 1.0 / 0.7, rtol=1e-5)

    def test_eval_identity(self):
        x = rng.randn(5, 5).astype('float32')
        self.check_output('dropout', {'X': x},
                          attrs={'dropout_prob': 0.3, 'is_test': True,
                                 'dropout_implementation':
                                     'upscale_in_train'},
                          expect={'Out': x})


# -- the dropout op's draw: ops/keep_hash.py's counter hash, keyed by
# (op seed, step) over the element's position --------------------------


def _dropout_lowered(x, rate, op_seed=1000003 * 3 + 17, step=5,
                     impl='upscale_in_train', prefer_test=False):
    """The op's lowering called as the executor calls it -> outs."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry
    ctx = registry.LowerCtx(jnp.int32(step), op_seed,
                            prefer_test=prefer_test)
    return registry.get('dropout').run(
        ctx, {'X': [jnp.asarray(x)]},
        {'dropout_prob': rate, 'is_test': False,
         'dropout_implementation': impl})


def _draw(shape, rate, **kw):
    return np.asarray(_dropout_lowered(
        np.ones(shape, 'float32'), rate, **kw)['Mask'][0]) != 0


@pytest.mark.parametrize('shape', [(64, 128, 768), (4096,), ()])
@pytest.mark.parametrize('rate', [0.1, 0.3, 0.5])
def test_dropout_keep_rate(rate, shape):
    keep = _draw(shape, rate)
    assert keep.shape == shape
    if not shape:       # one element: a bit, drawn anew a step
        bits = [bool(_draw((), rate, step=s)) for s in range(256)]
        assert abs(np.mean(bits) - (1 - rate)) < \
            3 * np.sqrt(rate * (1 - rate) / 256)
        return
    sigma = np.sqrt(rate * (1 - rate) / keep.size)
    assert abs(keep.mean() - (1 - rate)) < 3 * sigma


@pytest.mark.parametrize('rate', [0.1, 0.5])
def test_dropout_draw_has_no_stripe(rate):
    """The pre-mix is (row term) xor (column term): every row and every
    column keeps its share, and neighbours along either axis (and the
    diagonal) are independent."""
    keep = _draw((64, 128, 768), rate).reshape(-1, 768).astype('float64')
    p, var = 1 - rate, rate * (1 - rate)
    for axis, n in ((1, 768), (0, keep.shape[0])):
        z = (keep.mean(axis) - p) / np.sqrt(var / n)
        # the z of 8192 rows / 768 columns: unit variance, no outlier
        assert abs(np.mean(z ** 2) - 1) < 4 * np.sqrt(2.0 / z.size)
        assert np.abs(z).max() < 5
    c = keep - p
    for a, b in ((c[:-1], c[1:]), (c[:, :-1], c[:, 1:]),
                 (c[:-1, :-1], c[1:, 1:]), (c[:-1, 1:], c[1:, :-1]),
                 (c[:-2], c[2:]), (c[:, :-2], c[:, 2:])):
        assert abs((a * b).mean() / var) < 4 / np.sqrt(a.size)


@pytest.mark.parametrize('other', [dict(step=6), dict(step=4),
                                   dict(op_seed=1000003 * 4 + 17),
                                   dict(op_seed=1000003 * 3 + 18)])
def test_dropout_masks_of_two_steps_and_two_ops_are_independent(other):
    p, var = 0.9, 0.09
    a = _draw((64, 128, 768), 0.1) - p
    b = _draw((64, 128, 768), 0.1, **other) - p
    assert abs((a * b).mean() / var) < 4 / np.sqrt(a.size)
    # ... and shifted by one row / one column (a keying that only
    # moved the counter would show here)
    a, b = a.reshape(-1, 768), b.reshape(-1, 768)
    for x, y in ((a[:-1], b[1:]), (a[1:], b[:-1]),
                 (a[:, :-1], b[:, 1:]), (a[:, 1:], b[:, :-1])):
        assert abs((x * y).mean() / var) < 4 / np.sqrt(x.size)


@pytest.mark.parametrize('shape', [(3, 5, 7, 33), (6, 130), (257,), ()])
def test_dropout_bits_are_the_shared_definition(shape):
    """One definition of the draw in the tree: the op's mask is
    keep_hash.keep_nd's, which is the flash kernels' element hash
    (_dropout_keep over _keep_rows / _keep_cols) at head 0, row = the
    flattened index over the leading axes, column = the last axis."""
    import jax.numpy as jnp
    from paddle_tpu.ops import keep_hash, registry
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._dropout_keep is keep_hash._dropout_keep and \
        fa._keep_rows is keep_hash._keep_rows and \
        fa._keep_cols is keep_hash._keep_cols and \
        fa._keep_threshold is keep_hash._keep_threshold
    op_seed, step, rate = 77, 9, 0.3
    seed = registry.LowerCtx(jnp.int32(step), op_seed).draw_seed()
    got = _draw(shape, rate, op_seed=op_seed, step=step)
    np.testing.assert_array_equal(
        got, np.asarray(keep_hash.keep_nd(seed, shape, rate)))
    cols = shape[-1] if shape else 1
    flat = np.arange(max(int(np.prod(shape)), 1), dtype=np.int64)
    want = keep_hash._dropout_keep(
        keep_hash._keep_rows(seed, 0, jnp.asarray(flat // cols,
                                                  jnp.int32)),
        keep_hash._keep_cols(jnp.asarray(flat % cols, jnp.int32)),
        keep_hash._keep_threshold(rate))
    np.testing.assert_array_equal(got, np.asarray(want).reshape(shape))
    if len(shape) == 4:     # the attention form at one head a batch
        b, _, tq, tk = shape
        dense = fa.dropout_keep_dense(seed, 1, 1, b * shape[1] * tq, tk,
                                      rate=rate)
        np.testing.assert_array_equal(
            got, np.asarray(dense).reshape(shape))


@pytest.mark.parametrize('impl', ['upscale_in_train',
                                  'downgrade_in_infer'])
class TestDropoutContract(OpTest):
    def _attrs(self, impl, **more):
        return dict({'dropout_prob': 0.3, 'is_test': False,
                     'dropout_implementation': impl}, **more)

    def test_mask_is_out_nonzero(self, impl):
        x = (rng.rand(33, 65) + 0.5).astype('float32')
        got = self.run_op('dropout', {'X': x}, attrs=self._attrs(impl),
                          out_slots=('Out', 'Mask'))
        out, mask = np.asarray(got['Out']), np.asarray(got['Mask'])
        assert mask.dtype == x.dtype and out.dtype == x.dtype
        np.testing.assert_array_equal(mask, (out != 0).astype(x.dtype))
        scale = 1 / 0.7 if impl == 'upscale_in_train' else 1.0
        np.testing.assert_allclose(out, x * mask * scale, rtol=1e-6)
        assert 0.6 < mask.mean() < 0.8

    def test_grad_is_the_forward_mask(self, impl):
        """The synthesized vjp replays the forward and draws the same
        bits: dX is 0 exactly where Out is, the scale elsewhere."""
        x = (rng.rand(17, 40) + 0.5).astype('float32')
        main, startup, feed, in_vars, out_vars = self._build(
            'dropout', {'X': x}, self._attrs(impl), ('Out', 'Mask'))
        with fluid.program_guard(main, startup):
            loss = fluid.layers.reduce_sum(out_vars['Out'])
            fluid.backward.append_backward(loss)
        gname = main._grad_name_map[in_vars['X'].name]
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            for _ in range(2):      # two steps, two masks, each its own
                out, dx = exe.run(main, feed=feed,
                                  fetch_list=[out_vars['Out'], gname])
                scale = 1 / 0.7 if impl == 'upscale_in_train' else 1.0
                np.testing.assert_allclose(
                    np.asarray(dx),
                    np.where(np.asarray(out) != 0, scale, 0.0),
                    rtol=1e-6)

    def test_fresh_scopes_agree_and_steps_differ(self, impl):
        x = np.ones((64, 64), 'float32')
        main, startup, feed, _, out_vars = self._build(
            'dropout', {'X': x}, self._attrs(impl), ('Out', 'Mask'))
        runs = []
        for _ in range(2):
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                runs.append([np.asarray(exe.run(
                    main, feed=feed, fetch_list=[out_vars['Mask']])[0])
                    for _ in range(2)])
        np.testing.assert_array_equal(runs[0][0], runs[1][0])
        np.testing.assert_array_equal(runs[0][1], runs[1][1])
        assert (runs[0][0] != runs[0][1]).mean() > 0.2

    def test_prefer_test_inference_keeps_the_train_op(self, impl):
        """Shape inference lowers with prefer_test: a train op stays a
        train op there (the op tests its own is_test), same shapes and
        dtypes as the run, and the same bits."""
        from paddle_tpu.ops import registry
        spec = registry.infer_shapes(
            'dropout', {'X': [((-1, 12, 40), 'float32')]},
            self._attrs(impl))
        assert spec['Out'] == [((-1, 12, 40), np.dtype('float32'))]
        assert spec['Mask'] == [((-1, 12, 40), np.dtype('float32'))]
        x = np.ones((4, 12, 40), 'float32')
        train = _dropout_lowered(x, 0.3, impl=impl)
        infer = _dropout_lowered(x, 0.3, impl=impl, prefer_test=True)
        np.testing.assert_array_equal(np.asarray(train['Mask'][0]),
                                      np.asarray(infer['Mask'][0]))
        assert not np.asarray(infer['Mask'][0]).all()


class TestSoftmaxWithCrossEntropy(OpTest):
    def test_forward(self):
        logits = rng.randn(4, 6).astype('float32')
        label = rng.randint(0, 6, (4, 1)).astype('int64')
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(4), label[:, 0]])[:, None]
        got = self.run_op('softmax_with_cross_entropy',
                          {'Logits': logits, 'Label': label},
                          out_slots=('Softmax', 'Loss'))
        np.testing.assert_allclose(got['Softmax'], sm, atol=1e-5,
                                   rtol=1e-4)
        np.testing.assert_allclose(got['Loss'], loss, atol=1e-5,
                                   rtol=1e-4)

    def test_grad(self):
        logits = rng.randn(3, 5).astype('float32')
        label = rng.randint(0, 5, (3, 1)).astype('int64')
        self.check_grad('softmax_with_cross_entropy',
                        {'Logits': logits, 'Label': label},
                        out_slot='Loss', grad_slots=['Logits'])


class TestCrossEntropy(OpTest):
    def test_forward(self):
        probs = rng.dirichlet(np.ones(5), 4).astype('float32')
        label = rng.randint(0, 5, (4, 1)).astype('int64')
        loss = -np.log(probs[np.arange(4), label[:, 0]])[:, None]
        self.check_output('cross_entropy',
                          {'X': probs, 'Label': label},
                          expect={'Y': loss}, out_slots=['Y'],
                          atol=1e-5)


def _outs_and_grad(f, x):
    """(f(x), d x) under cotangents drawn from a fixed seed, one an
    output of the tuple f returns."""
    import jax
    import jax.numpy as jnp
    outs, vjp = jax.vjp(f, jnp.asarray(x))
    r = np.random.RandomState(11)
    cots = tuple(jnp.asarray(r.randn(*o.shape), o.dtype) for o in outs)
    return outs + vjp(cots)


def _lowering(op, ins, attrs, wrt, out_slots):
    """x -> the `out_slots` of a registered lowering fed x as `wrt`."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    def f(x):
        held = {k: [jnp.asarray(v)] for k, v in ins.items()}
        held[wrt] = [x]
        out = registry.get(op).fn(registry.LowerCtx(0), held, attrs)
        return tuple(out[s][0] for s in out_slots)
    return f


def _assert_bit_equal(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, 'float32'),
                                      np.asarray(w, 'float32'))


def _swce_forward_as_it_was(logits, lab, ax, ignore_index, loss_f32):
    """The plain reference: `_swce_fwd_math` as it stood while the
    label's logit was picked from the float32 cast."""
    import jax
    import jax.numpy as jnp
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=ax, keepdims=True)
    lab_safe = jnp.where(lab == ignore_index, 0, lab).astype(jnp.int32)
    picked = jnp.take_along_axis(lf, lab_safe, axis=ax) - lse
    loss = jnp.where(lab != ignore_index, -picked, 0.0)
    softmax = jnp.exp(lf - lse)
    return ((softmax.astype(logits.dtype),
             loss if loss_f32 else loss.astype(logits.dtype)), lse)


def _swce_inputs(dtype, shape, axis):
    import jax.numpy as jnp
    r = np.random.RandomState(len(shape) * 7 + axis % len(shape))
    logits = jnp.asarray(r.randn(*shape) * 4, dtype)
    lab_shape = list(shape)
    lab_shape[axis] = 1
    label = r.randint(0, shape[axis], lab_shape).astype('int64')
    label.flat[1] = -100            # a row that counts for nothing
    return logits, label


@pytest.mark.parametrize('amp_black_out', [False, True])
@pytest.mark.parametrize('shape,axis', [((6, 37), -1), ((3, 5, 37), -1),
                                        ((3, 37, 5), 1)])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_swce_hard_label_is_bit_equal_to_the_pick_after_the_cast(
        dtype, shape, axis, amp_black_out):
    """Picking the label's logit BEFORE the float32 cast moves no value:
    Loss, Softmax and the logits' gradient (the op's own backward rule,
    fed by what each forward saves) are the old forward's bit for bit."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import nn_ops
    logits, label = _swce_inputs(dtype, shape, axis)
    attrs = {'axis': axis, 'ignore_index': -100,
             '__amp_black_out__': amp_black_out}
    got = _outs_and_grad(_lowering(
        'softmax_with_cross_entropy', {'Logits': logits, 'Label': label},
        attrs, 'Logits', ('Softmax', 'Loss')), logits)
    softmax, loss, _ = got
    assert loss.dtype == (jnp.float32 if amp_black_out else logits.dtype)
    assert softmax.dtype == logits.dtype
    assert not np.asarray(loss, 'float32').flat[1]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
    def was(x, lab, ax, ignore_index, loss_f32):
        return _swce_forward_as_it_was(x, lab, ax, ignore_index,
                                       loss_f32)[0]

    def was_fwd(x, lab, ax, ignore_index, loss_f32):
        y, lse = _swce_forward_as_it_was(x, lab, ax, ignore_index, loss_f32)
        return y, (x, lse, lab)

    was.defvjp(was_fwd, nn_ops._swce_bwd_rule)
    _assert_bit_equal(got, _outs_and_grad(
        lambda x: was(x, jnp.asarray(label), axis % len(shape), -100,
                      amp_black_out and dtype == 'bfloat16'), logits))


def _gathers(jaxpr):
    """Every `gather` equation of a jaxpr and of the jaxprs it holds."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'gather':
            yield eqn
        for held in eqn.params.values():
            for sub in held if isinstance(held, (list, tuple)) else [held]:
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    yield from _gathers(sub)


def test_swce_asks_for_no_float32_copy_of_the_logits():
    """The guard on what the lowering ASKS for: a gather's operand is
    written out whole (the TPU compiler fuses no producer into it), so
    a pick from the float32 cast of bf16 [B, T, V] logits costs the
    step that fetches the loss a float32 [B, T, V] buffer: 3.0 GB in
    the BERT cells.  No gather of loss + gradient may read one."""
    import jax
    import jax.numpy as jnp
    logits, label = _swce_inputs('bfloat16', (8, 16, 256), -1)

    def wide(forward):
        traced = jax.make_jaxpr(jax.value_and_grad(
            lambda x: jnp.sum(forward(x).astype(jnp.float32))))(logits)
        return [e for e in _gathers(traced.jaxpr)
                if e.invars[0].aval.shape == logits.shape and
                e.invars[0].aval.dtype == jnp.float32]

    lowered = _lowering(
        'softmax_with_cross_entropy', {'Logits': logits, 'Label': label},
        {'__amp_black_out__': True}, 'Logits', ('Loss',))
    assert not wide(lambda x: lowered(x)[0])
    # the walk does see one where it is asked for
    assert wide(lambda x: _swce_forward_as_it_was(
        x, jnp.asarray(label), 2, -100, True)[0][1])


@pytest.mark.parametrize('op', ['cross_entropy', 'cross_entropy2'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_cross_entropy_hard_label_picks_before_the_logarithm(dtype, op):
    """One logarithm a row, of the picked probability, is the picked
    logarithm of every probability bit for bit, value and gradient."""
    import jax.numpy as jnp
    r = np.random.RandomState(5)
    probs = jnp.asarray(r.dirichlet(np.ones(9), (4, 3)), dtype)
    probs = probs.at[0, 0, 2].set(0.)       # under the clip
    label = r.randint(0, 9, (4, 3, 1)).astype('int64')
    label[0, 0, 0], label[1, 1, 0] = 2, -100
    got = _outs_and_grad(_lowering(
        op, {'X': probs, 'Label': label}, {'ignore_index': -100}, 'X',
        ('Y',)), probs)
    assert not np.asarray(got[0], 'float32')[1, 1, 0]

    def was(x):
        logx = jnp.log(jnp.clip(x, 1e-20, None))
        lab_safe = jnp.where(label == -100, 0, label).astype(jnp.int32)
        picked = jnp.take_along_axis(logx, lab_safe, axis=-1)
        return jnp.where(label == -100, jnp.zeros_like(picked), -picked),

    _assert_bit_equal(got, _outs_and_grad(was, probs))


class TestLookupTable(OpTest):
    def test_forward(self):
        w = rng.randn(10, 4).astype('float32')
        ids = rng.randint(0, 10, (3, 5)).astype('int64')
        self.check_output('lookup_table_v2', {'W': w, 'Ids': ids},
                          expect={'Out': w[ids]})

    def test_padding_idx(self):
        w = rng.randn(10, 4).astype('float32')
        ids = np.array([[0, 2, 0], [1, 0, 3]], 'int64')
        out = w[ids].copy()
        out[ids == 0] = 0
        self.check_output('lookup_table_v2', {'W': w, 'Ids': ids},
                          attrs={'padding_idx': 0}, expect={'Out': out})

    def test_grad_scatter(self):
        """Embedding grad = scatter-add of output grads into rows."""
        w = rng.randn(6, 3).astype('float32')
        ids = np.array([1, 1, 4], 'int64')
        self.check_grad('lookup_table_v2',
                        {'W': w, 'Ids': ids}, grad_slots=['W'])


def _lookup_vjp(op, w, ids, padding_idx, cotangent):
    """(Out, dW) of one of the three lookup ops through its registered
    lowering; lookup_table takes its ids with a trailing unit axis."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry
    fed = jnp.asarray(ids[..., None] if op == 'lookup_table' else ids)

    def lookup(w):
        return registry.get(op).fn(
            registry.LowerCtx(0), {'W': [w], 'Ids': [fed]},
            {'padding_idx': padding_idx})['Out'][0]

    out, vjp = jax.vjp(lookup, w)
    return out, vjp(jnp.asarray(cotangent, w.dtype))[0]


_LOOKUP_OPS = ['lookup_table', 'lookup_table_v2', 'embedding']


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('rank', [1, 2, 3])
@pytest.mark.parametrize('op', _LOOKUP_OPS)
def test_lookup_forward_and_gradient_against_numpy(op, rank, dtype):
    """The three lookup ops share one lowering: rows gathered in the
    table's dtype, padding rows zero, and a gradient that sums the
    cotangents of duplicate ids into their row and gives the padding
    row none."""
    import jax.numpy as jnp
    vocab, width, pad = 11, 4, 3
    r = np.random.RandomState(rank)
    ids = r.randint(0, vocab, (2, 3, 5)[:rank]).astype('int32')
    ids.flat[:3] = (7, 7, pad)      # a duplicate and a padding id
    # small integers: every sum below is exact in bfloat16 too
    w = jnp.asarray(r.randint(-4, 5, (vocab, width)), dtype)
    cot = r.randint(-2, 3, ids.shape + (width,)).astype('float32')
    out, grad = _lookup_vjp(op, w, ids, pad, cot)
    assert out.dtype == w.dtype and grad.dtype == w.dtype
    keep = (ids != pad)[..., None]
    want = np.asarray(w, 'float32')[ids] * keep
    assert np.array_equal(np.asarray(out, 'float32'), want)
    want_grad = np.zeros((vocab, width), 'float32')
    np.add.at(want_grad, ids, cot * keep)
    assert np.array_equal(np.asarray(grad, 'float32'), want_grad)


@pytest.mark.parametrize('op', _LOOKUP_OPS)
def test_lookup_out_of_range_ids_are_jnp_takes_own(op):
    """No clip of the lowering's own: an id outside [-V, V) reads a
    row of NaN and trains nothing (jnp.take's default, where the
    reference operator raises), an id in [-V, 0) counts from the
    end."""
    import jax.numpy as jnp
    vocab, width = 6, 4
    ids = np.array([-vocab - 1, -1, 0, vocab - 1, vocab, vocab + 7],
                   'int32')
    valid = np.array([False, True, True, True, False, False])
    w = jnp.asarray(np.arange(1.0, 1 + vocab * width, dtype='float32')
                    .reshape(vocab, width))
    out, grad = _lookup_vjp(op, w, ids, -1,
                            np.ones((ids.size, width), 'float32'))
    out = np.asarray(out)
    assert np.isnan(out[~valid]).all()
    assert np.array_equal(out[valid], np.asarray(w)[ids[valid]])
    want_grad = np.zeros((vocab, width), 'float32')
    np.add.at(want_grad, ids[valid], 1.0)
    assert np.array_equal(np.asarray(grad), want_grad)


class TestTensorManip(OpTest):
    def test_reshape_transpose_concat(self):
        x = rng.randn(2, 6).astype('float32')
        self.check_output('reshape2', {'X': x}, attrs={'shape': [3, 4]},
                          expect={'Out': x.reshape(3, 4)})
        self.check_output('reshape2', {'X': x}, attrs={'shape': [0, -1]},
                          expect={'Out': x})
        self.check_output('transpose2', {'X': x}, attrs={'axis': [1, 0]},
                          expect={'Out': x.T})
        ys = [('p', rng.randn(2, 3).astype('float32')),
              ('q', rng.randn(2, 2).astype('float32'))]
        self.check_output('concat', {'X': ys}, attrs={'axis': 1},
                          expect={'Out': np.concatenate(
                              [a for _, a in ys], 1)})

    def test_split_sections(self):
        x = rng.randn(2, 10).astype('float32')
        got = self.run_op('split', {'X': x},
                          attrs={'axis': 1, 'sections': [2, -1, 3]},
                          out_slots=('Out',))
        # only first returned through Out[0]; use full runner instead
        # -> validate via direct lowering
        from paddle_tpu.ops import registry
        outs = registry.get('split').fn(
            registry.LowerCtx(0), {'X': [x]},
            {'axis': 1, 'sections': [2, -1, 3]})['Out']
        np.testing.assert_allclose(outs[0], x[:, :2])
        np.testing.assert_allclose(outs[1], x[:, 2:7])
        np.testing.assert_allclose(outs[2], x[:, 7:])

    def test_slice_gather(self):
        x = rng.randn(5, 6).astype('float32')
        self.check_output('slice', {'Input': x},
                          attrs={'axes': [0, 1], 'starts': [1, 2],
                                 'ends': [4, 6]},
                          expect={'Out': x[1:4, 2:6]})
        idx = np.array([3, 0, 1], 'int64')
        self.check_output('gather', {'X': x, 'Index': idx},
                          expect={'Out': x[idx]})

    def test_onehot_cast(self):
        ids = np.array([[1], [3]], 'int64')
        oh = np.zeros((2, 5), 'float32')
        oh[0, 1] = oh[1, 3] = 1
        self.check_output('one_hot', {'X': ids}, attrs={'depth': 5},
                          expect={'Out': oh})
        x = rng.randn(3, 3).astype('float32')
        self.check_output('cast', {'X': x},
                          attrs={'out_dtype': 'int32'},
                          expect={'Out': x.astype(np.int32)})


class TestAccuracyOp(OpTest):
    def test_accuracy(self):
        idx = np.array([[0, 1], [2, 3], [4, 5]], 'int64')
        label = np.array([[1], [0], [4]], 'int64')
        got = self.run_op('accuracy',
                          {'Out': rng.rand(3, 2).astype('float32'),
                           'Indices': idx, 'Label': label},
                          out_slots=('Accuracy',))
        np.testing.assert_allclose(got['Accuracy'], 2.0 / 3.0, rtol=1e-6)


@pytest.mark.parametrize('conv_precision,amp,barriers', [
    ('highest', False, True), ('high', False, True),
    ('default', False, False), ('highest', True, False)])
def test_multi_pass_f32_conv_stands_alone(conv_precision, amp, barriers):
    """The 6- / 3-pass f32 convolution and the backward convolutions jax
    derives from it sit between optimization barriers (the v5e compiler
    does not finish LeNet b512 once a neighbour fuses into one); a
    single-pass or bf16 convolution stays free to fuse."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.flags import get_flag, set_flags
    from paddle_tpu.ops import registry
    x = jnp.asarray(rng.randn(2, 3, 8, 8).astype('float32'))
    w = jnp.asarray(rng.randn(4, 3, 3, 3).astype('float32'))
    attrs = {'strides': [1, 1], 'paddings': [1, 1], '__amp__': amp}

    def loss(x, w):
        out = registry.get('conv2d').fn(
            registry.LowerCtx(0), {'Input': [x], 'Filter': [w]}, attrs)
        return jnp.sum(jax.nn.relu(out['Output'][0]).astype(jnp.float32))

    was = get_flag('FLAGS_conv_precision')
    set_flags({'FLAGS_conv_precision': conv_precision})
    try:
        text = jax.jit(jax.grad(loss, (0, 1))).lower(x, w).as_text()
    finally:
        set_flags({'FLAGS_conv_precision': was})
    assert text.count('stablehlo.convolution') == 3
    # forward inputs and output, and their two cotangent transposes
    assert text.count('optimization_barrier') == (4 if barriers else 0)
