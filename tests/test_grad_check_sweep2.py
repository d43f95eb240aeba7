"""Gradient-check sweep, part 2: the round-3 extension toward full
differentiable-op coverage (reference discipline: OpTest.check_grad
finite differences on every differentiable op, op_test.py:57).

Part 1 (test_grad_check_sweep.py) covers the activation/elementwise/
reduction core; this file adds shape/index manipulation, interpolation,
normalization variants, conv/pool variants, losses, sequence ops under
masks, structured-prediction vjps (CRF, warpctc), roi ops, and the
hand-written flash-attention custom_vjp at multiple shapes/modes.

Inputs live in each op's smooth region (away from kinks) exactly like
part 1."""

import numpy as np
import pytest

from op_test import OpTest

rng = np.random.RandomState(11)


def away(x, bad, margin=0.15):
    x = np.array(x)
    for b in bad:
        close = np.abs(x - b) < margin
        x[close] = b + margin * np.sign(x[close] - b + 1e-8) * 2
    return x


# ---------------------------------------------------------------------------
# single-input ops: op -> (inputs dict builder, attrs, out_slot, kwargs)

SINGLE = {
    'tan': (lambda: {'X': rng.uniform(-1.0, 1.0, (2, 3))}, {}, 'Out', {}),
    'log2': (lambda: {'X': rng.rand(2, 3) + 0.5}, {}, 'Out', {}),
    'log10': (lambda: {'X': rng.rand(2, 3) + 0.5}, {}, 'Out', {}),
    'silu': (lambda: {'X': rng.randn(2, 3)}, {}, 'Out', {}),
    'soft_relu': (lambda: {'X': rng.randn(2, 3)}, {'threshold': 40.0},
                  'Out', {}),
    'soft_shrink': (lambda: {'X': away(rng.randn(2, 3) * 2,
                                       [-0.5, 0.5])},
                    {'lambda': 0.5}, 'Out', {}),
    'cumsum': (lambda: {'X': rng.randn(2, 4)}, {'axis': 1}, 'Out', {}),
    'reduce_max': (lambda: {'X': np.arange(6.).reshape(2, 3) +
                            rng.rand(2, 3) * 0.1},
                   {'dim': [1]}, 'Out', {}),
    'reduce_min': (lambda: {'X': np.arange(6.).reshape(2, 3) +
                            rng.rand(2, 3) * 0.1},
                   {'dim': [1]}, 'Out', {}),
    'expand': (lambda: {'X': rng.randn(2, 3)},
               {'expand_times': [2, 1]}, 'Out', {}),
    'tile': (lambda: {'X': rng.randn(2, 3)},
             {'repeat_times': [1, 2]}, 'Out', {}),
    'reverse': (lambda: {'X': rng.randn(2, 3)}, {'axis': [1]}, 'Out', {}),
    'flip': (lambda: {'X': rng.randn(2, 3)}, {'axis': [0]}, 'Out', {}),
    'roll': (lambda: {'X': rng.randn(2, 4)},
             {'shifts': [1], 'axis': [1]}, 'Out', {}),
    'tril_triu': (lambda: {'X': rng.randn(3, 3)},
                  {'diagonal': 0, 'lower': True}, 'Out', {}),
    'pad2d': (lambda: {'X': rng.randn(1, 2, 3, 3)},
              {'paddings': [1, 1, 1, 1], 'mode': 'constant',
               'pad_value': 0.0}, 'Out', {}),
    'pixel_shuffle': (lambda: {'X': rng.randn(1, 4, 2, 2)},
                      {'upscale_factor': 2}, 'Out', {}),
    'space_to_depth': (lambda: {'X': rng.randn(1, 2, 4, 4)},
                       {'blocksize': 2}, 'Out', {}),
    'shuffle_channel': (lambda: {'X': rng.randn(1, 4, 2, 2)},
                        {'group': 2}, 'Out', {}),
    'unfold': (lambda: {'X': rng.randn(1, 2, 4, 4)},
               {'kernel_sizes': [2, 2], 'strides': [2, 2],
                'paddings': [0, 0, 0, 0], 'dilations': [1, 1]},
               'Y', {}),
    'slice': (lambda: {'Input': rng.randn(3, 4)},
              {'axes': [0, 1], 'starts': [1, 0], 'ends': [3, 3]},
              'Out', {}),
    'strided_slice': (lambda: {'Input': rng.randn(4, 6)},
                      {'axes': [1], 'starts': [0], 'ends': [6],
                       'strides': [2]}, 'Out', {}),
    'crop': (lambda: {'X': rng.randn(3, 4)},
             {'shape': [2, 2], 'offsets': [1, 1]}, 'Out', {}),
    'crop_tensor': (lambda: {'X': rng.randn(3, 4)},
                    {'shape': [2, 2], 'offsets': [0, 1]}, 'Out', {}),
    'label_smooth': (lambda: {'X': rng.rand(2, 5)},
                     {'epsilon': 0.1}, 'Out', {}),
    'temporal_shift': (lambda: {'X': rng.randn(4, 4, 2, 2)},
                       {'seg_num': 2, 'shift_ratio': 0.25}, 'Out', {}),
    'transpose2': (lambda: {'X': rng.randn(2, 3)}, {'axis': [1, 0]},
                   'Out', {}),
    'reshape2': (lambda: {'X': rng.randn(2, 3)}, {'shape': [3, 2]},
                 'Out', {}),
    'squeeze2': (lambda: {'X': rng.randn(2, 1, 3)}, {'axes': [1]},
                 'Out', {}),
    'unsqueeze2': (lambda: {'X': rng.randn(2, 3)}, {'axes': [0]},
                   'Out', {}),
    'flatten2': (lambda: {'X': rng.randn(2, 3, 2)}, {'axis': 1},
                 'Out', {}),
    'flatten_contiguous_range': (lambda: {'X': rng.randn(2, 3, 2)},
                                 {'start_axis': 1, 'stop_axis': 2},
                                 'Out', {}),
    'p_norm': (lambda: {'X': rng.rand(2, 4) + 0.5},
               {'porder': 3.0, 'axis': 1}, 'Out', {}),
    'norm': (lambda: {'X': rng.rand(2, 4) + 0.5}, {'axis': 1},
             'Out', {}),
    'lrn': (lambda: {'X': rng.randn(1, 4, 3, 3)},
            {'n': 3, 'k': 1.0, 'alpha': 1e-2, 'beta': 0.75},
            'Out', {}),
    'maxout': (lambda: {'X': rng.randn(1, 4, 3, 3) +
                        np.arange(4).reshape(1, 4, 1, 1)},
               {'groups': 2}, 'Out', {}),
    'spp': (lambda: {'X': rng.randn(1, 2, 4, 4)},
            {'pyramid_height': 2, 'pooling_type': 'avg'}, 'Out', {}),
    'add_position_encoding': (lambda: {'X': rng.randn(2, 4, 6)},
                              {'alpha': 1.0, 'beta': 1.0}, 'Out', {}),
    'bilinear_interp': (lambda: {'X': rng.randn(1, 2, 4, 4)},
                        {'out_h': 8, 'out_w': 8,
                         'align_corners': False}, 'Out', {}),
    'nearest_interp': (lambda: {'X': rng.randn(1, 2, 4, 4)},
                       {'out_h': 8, 'out_w': 8,
                        'align_corners': False}, 'Out', {}),
    'trilinear_interp': (lambda: {'X': rng.randn(1, 2, 3, 3, 3)},
                         {'out_d': 6, 'out_h': 6, 'out_w': 6,
                          'align_corners': False}, 'Out', {}),
    'mean_iou': None,   # integer semantics
    'square_error_cost': None,  # binary, below
}


@pytest.mark.parametrize('op', sorted(k for k, v in SINGLE.items() if v))
def test_single_grad(op):
    gen, attrs, out_slot, kw = SINGLE[op]
    ins = {k: np.asarray(v, 'float32') for k, v in gen().items()}
    OpTest().check_grad(op, ins, attrs, out_slot=out_slot, **kw)


# ---------------------------------------------------------------------------
# multi-input ops

MULTI = {
    'bmm': (lambda: {'X': rng.randn(2, 3, 4), 'Y': rng.randn(2, 4, 5)},
            {}, 'Out', {}),
    'matmul_v2': (lambda: {'X': rng.randn(2, 3), 'Y': rng.randn(2, 4)},
                  {'trans_x': True}, 'Out', {}),
    'minus': (lambda: {'X': rng.randn(2, 3), 'Y': rng.randn(2, 3)},
              {}, 'Out', {}),
    'elementwise_mod': (lambda: {'X': rng.rand(2, 3) * 3 + 3.2,
                                 'Y': np.full((2, 3), 2.0)},
                        {}, 'Out', {'grad_slots': ['X']}),
    'square_error_cost': (lambda: {'X': rng.randn(2, 3),
                                   'Y': rng.randn(2, 3)}, {}, 'Out', {}),
    'mse_loss': (lambda: {'X': rng.randn(2, 3), 'Y': rng.randn(2, 3)},
                 {}, 'Out', {}),
    'huber_loss': (lambda: {'X': away(rng.randn(4, 1), []),
                            'Y': away(rng.randn(4, 1) * 3, [])},
                   {'delta': 1.0}, 'Out', {}),
    'smooth_l1_loss': (lambda: {'X': rng.randn(3, 4),
                                'Y': rng.randn(3, 4) + 3.0},
                       {'sigma': 1.0}, 'Out', {}),
    'log_loss': (lambda: {'Predicted': rng.uniform(0.2, 0.8, (4, 1)),
                          'Labels': rng.randint(0, 2, (4, 1)).astype(
                              'float32')},
                 {'epsilon': 1e-4}, 'Loss', {'grad_slots': ['Predicted']}),
    'rank_loss': (lambda: {'Label': rng.randint(0, 2, (4, 1)).astype(
                               'float32'),
                           'Left': rng.randn(4, 1),
                           'Right': rng.randn(4, 1)},
                  {}, 'Out', {'grad_slots': ['Left', 'Right'],
                              'stop_gradients': ('Label',)}),
    'margin_rank_loss': (lambda: {'Label': np.ones((4, 1), 'float32'),
                                  'X1': rng.randn(4, 1),
                                  'X2': rng.randn(4, 1) - 3.0},
                         {'margin': 0.1}, 'Out',
                         {'grad_slots': ['X1', 'X2'],
                          'stop_gradients': ('Label',)}),
    'kldiv_loss': (lambda: {'X': np.log(rng.rand(3, 4) + 0.2),
                            'Target': rng.rand(3, 4) + 0.2},
                   {'reduction': 'mean'}, 'Loss',
                   {'grad_slots': ['X']}),
    'sigmoid_cross_entropy_with_logits': (
        lambda: {'X': rng.randn(3, 4),
                 'Label': rng.rand(3, 4)},
        {}, 'Out', {'grad_slots': ['X']}),
    'hinge_loss': (lambda: {'Logits': away(rng.randn(4, 1) * 2, [1, -1],
                                           0.3),
                            'Labels': np.ones((4, 1), 'float32')},
                   {}, 'Loss', {'grad_slots': ['Logits'],
                                'stop_gradients': ('Labels',)}),
    'bpr_loss': (lambda: {'X': rng.rand(3, 4) + 0.5,
                          'Label': rng.randint(0, 4, (3, 1)).astype(
                              'int64')},
                 {}, 'Y', {'grad_slots': ['X']}),
    'cross_entropy': (lambda: {'X': (lambda p: p / p.sum(
                                     1, keepdims=True))(
                                         rng.rand(3, 4) + 0.3),
                               'Label': rng.randint(0, 4, (3, 1)).astype(
                                   'int64')},
                      {'soft_label': False}, 'Y', {'grad_slots': ['X']}),
    'cross_entropy2': (lambda: {'X': (lambda p: p / p.sum(
                                      1, keepdims=True))(
                                          rng.rand(3, 4) + 0.3),
                                'Label': rng.randint(0, 4, (3, 1)).astype(
                                    'int64')},
                       {}, 'Y', {'grad_slots': ['X']}),
    'fsp': (lambda: {'X': rng.randn(1, 2, 3, 3),
                     'Y': rng.randn(1, 3, 3, 3)}, {}, 'Out', {}),
    'conv_shift': (lambda: {'X': rng.randn(2, 5),
                            'Y': rng.randn(2, 3)}, {}, 'Out', {}),
    'pad_constant_like': (lambda: {'X': rng.randn(3, 4),
                                   'Y': rng.randn(2, 3)},
                          {'pad_value': 0.0}, 'Out',
                          {'grad_slots': ['Y']}),
    'bilinear_tensor_product': (
        lambda: {'X': rng.randn(2, 3), 'Y': rng.randn(2, 4),
                 'Weight': rng.randn(5, 3, 4)},
        {}, 'Out', {}),
    'prelu': (lambda: {'X': away(rng.randn(2, 3, 2, 2), [0.0]),
                       'Alpha': rng.rand(1) + 0.1},
              {'mode': 'all'}, 'Out', {}),
    # bilinear sampling's Grid-gradient has kinks where the sample
    # point crosses an integer pixel coordinate (for a 4-wide input,
    # normalized coords -1/3 and 1/3): the numeric gradient straddling
    # a kink is garbage, and whether the shared rng lands near one
    # depends on which tests ran before (pytest -k flake) — keep the
    # draws away from the kinks
    'grid_sampler': (lambda: {'X': rng.randn(1, 2, 4, 4),
                              'Grid': away(rng.uniform(-0.7, 0.7,
                                                       (1, 3, 3, 2)),
                                           [-1.0 / 3, 1.0 / 3],
                                           margin=0.04)},
                     {}, 'Output', {}),
    'kron': None,
    'dist': None,
}


@pytest.mark.parametrize('op', sorted(k for k, v in MULTI.items() if v))
def test_multi_grad(op):
    gen, attrs, out_slot, kw = MULTI[op]
    ins = {}
    for k, v in gen().items():
        v = np.asarray(v)
        ins[k] = v if v.dtype.kind in 'iu' else v.astype('float32')
    OpTest().check_grad(op, ins, attrs, out_slot=out_slot, **kw)


# ---------------------------------------------------------------------------
# normalization variants

def test_group_norm_grad():
    OpTest().check_grad(
        'group_norm',
        {'X': rng.randn(2, 4, 3, 3).astype('float32'),
         'Scale': (rng.rand(4) + 0.5).astype('float32'),
         'Bias': rng.randn(4).astype('float32')},
        {'groups': 2, 'epsilon': 1e-5}, out_slot='Y',
        grad_slots=['X', 'Scale', 'Bias'])


def test_instance_norm_grad():
    OpTest().check_grad(
        'instance_norm',
        {'X': rng.randn(2, 3, 4, 4).astype('float32'),
         'Scale': (rng.rand(3) + 0.5).astype('float32'),
         'Bias': rng.randn(3).astype('float32')},
        {'epsilon': 1e-5}, out_slot='Y',
        grad_slots=['X', 'Scale', 'Bias'])


def test_affine_channel_grad():
    OpTest().check_grad(
        'affine_channel',
        {'X': rng.randn(2, 3, 2, 2).astype('float32'),
         'Scale': (rng.rand(3) + 0.5).astype('float32'),
         'Bias': rng.randn(3).astype('float32')},
        {'data_layout': 'NCHW'}, out_slot='Out')


def test_data_norm_grad():
    OpTest().check_grad(
        'data_norm',
        {'X': rng.randn(4, 3).astype('float32'),
         'BatchSize': np.full(3, 10.0, 'float32'),
         'BatchSum': rng.randn(3).astype('float32'),
         'BatchSquareSum': (np.full(3, 10.0) +
                            rng.rand(3)).astype('float32')},
        {'epsilon': 1e-4}, out_slot='Y', grad_slots=['X'],
        stop_gradients=('BatchSize', 'BatchSum', 'BatchSquareSum'))


# ---------------------------------------------------------------------------
# conv / pool variants

def test_conv2d_transpose_grad():
    OpTest().check_grad(
        'conv2d_transpose',
        {'Input': rng.randn(1, 3, 4, 4).astype('float32'),
         'Filter': rng.randn(3, 2, 3, 3).astype('float32')},
        {'strides': [2, 2], 'paddings': [1, 1], 'dilations': [1, 1],
         'groups': 1}, out_slot='Output')


def test_conv3d_grad():
    OpTest().check_grad(
        'conv3d',
        {'Input': rng.randn(1, 2, 4, 4, 4).astype('float32'),
         'Filter': rng.randn(3, 2, 2, 2, 2).astype('float32')},
        {'strides': [1, 1, 1], 'paddings': [0, 0, 0],
         'dilations': [1, 1, 1], 'groups': 1}, out_slot='Output')


def test_conv3d_transpose_grad():
    OpTest().check_grad(
        'conv3d_transpose',
        {'Input': rng.randn(1, 2, 3, 3, 3).astype('float32'),
         'Filter': rng.randn(2, 2, 2, 2, 2).astype('float32')},
        {'strides': [1, 1, 1], 'paddings': [0, 0, 0],
         'dilations': [1, 1, 1], 'groups': 1}, out_slot='Output')


def test_pool3d_avg_grad():
    OpTest().check_grad(
        'pool3d', {'X': rng.randn(1, 2, 4, 4, 4).astype('float32')},
        {'pooling_type': 'avg', 'ksize': [2, 2, 2],
         'strides': [2, 2, 2], 'paddings': [0, 0, 0]})


def test_max_pool2d_with_index_grad():
    x = rng.randn(1, 2, 4, 4).astype('float32')
    x += np.arange(16, dtype='float32').reshape(1, 1, 4, 4) * 0.05
    OpTest().check_grad(
        'max_pool2d_with_index', {'X': x},
        {'ksize': [2, 2], 'strides': [2, 2], 'paddings': [0, 0]},
        out_slot='Out')


def test_deformable_conv_grad():
    n, cin, h, w = 1, 2, 4, 4
    kh = kw = 3
    OpTest().check_grad(
        'deformable_conv',
        {'Input': rng.randn(n, cin, h, w).astype('float32'),
         'Offset': (rng.randn(n, 2 * kh * kw, h, w) * 0.1).astype(
             'float32'),
         'Mask': rng.uniform(0.3, 0.9, (n, kh * kw, h, w)).astype(
             'float32'),
         'Filter': rng.randn(4, cin, kh, kw).astype('float32')},
        {'strides': [1, 1], 'paddings': [1, 1], 'dilations': [1, 1],
         'groups': 1, 'deformable_groups': 1, 'im2col_step': 1},
        out_slot='Output', grad_slots=['Input', 'Filter'])


# ---------------------------------------------------------------------------
# sequence ops under masks (the LoD surface: X [B,T,D] + Mask [B,T])

def _mask(b, t):
    m = np.zeros((b, t), 'float32')
    lens = rng.randint(1, t + 1, b)
    for i, L in enumerate(lens):
        m[i, :L] = 1.0
    return m


def test_sequence_pool_grads():
    for ptype in ('SUM', 'AVERAGE', 'SQRT', 'MAX'):
        x = rng.randn(3, 5, 4).astype('float32')
        if ptype == 'MAX':
            x += np.arange(5, dtype='float32')[None, :, None] * 0.37
        OpTest().check_grad(
            'sequence_pool',
            {'X': x, 'Mask': _mask(3, 5)},
            {'pooltype': ptype}, out_slot='Out', grad_slots=['X'],
            stop_gradients=('Mask',))


def test_sequence_softmax_grad():
    OpTest().check_grad(
        'sequence_softmax',
        {'X': rng.randn(3, 5).astype('float32'),
         'Mask': _mask(3, 5)}, {}, out_slot='Out', grad_slots=['X'],
        stop_gradients=('Mask',))


def test_sequence_conv_grad():
    OpTest().check_grad(
        'sequence_conv',
        {'X': rng.randn(2, 6, 3).astype('float32'),
         'Filter': rng.randn(9, 4).astype('float32'),
         'Mask': _mask(2, 6)},
        {'contextLength': 3, 'contextStart': -1, 'contextStride': 1},
        out_slot='Out', grad_slots=['X', 'Filter'],
        stop_gradients=('Mask',))


def test_sequence_reverse_grad():
    OpTest().check_grad(
        'sequence_reverse',
        {'X': rng.randn(2, 5, 3).astype('float32'),
         'Mask': _mask(2, 5)}, {}, out_slot='Y', grad_slots=['X'],
        stop_gradients=('Mask',))


def test_row_conv_grad():
    OpTest().check_grad(
        'row_conv',
        {'X': rng.randn(2, 6, 3).astype('float32'),
         'Filter': rng.randn(3, 3).astype('float32')},
        {}, out_slot='Out')


# ---------------------------------------------------------------------------
# structured prediction (hand-written vjps)

def test_linear_chain_crf_grad():
    b, t, n = 2, 4, 3
    OpTest().check_grad(
        'linear_chain_crf',
        {'Emission': rng.randn(b, t, n).astype('float32'),
         'Transition': rng.randn(n + 2, n).astype('float32'),
         'Label': rng.randint(0, n, (b, t, 1)).astype('int64'),
         'Mask': _mask(b, t)},
        {}, out_slot='LogLikelihood',
        grad_slots=['Emission', 'Transition'],
        stop_gradients=('Label', 'Mask'))


def test_warpctc_grad():
    b, t, nc = 2, 6, 4
    logits = rng.randn(b, t, nc).astype('float32')
    label = rng.randint(1, nc, (b, 3)).astype('int64')
    OpTest().check_grad(
        'warpctc',
        {'Logits': logits, 'Label': label},
        {'blank': 0, 'norm_by_times': False},
        out_slot='Loss', grad_slots=['Logits'])


# ---------------------------------------------------------------------------
# roi ops

def _rois():
    # [K, 4] (x1, y1, x2, y2) boxes with batch index slot
    return np.array([[0.5, 0.5, 3.0, 3.0],
                     [1.0, 1.0, 3.5, 3.5]], 'float32')


def test_roi_align_grad():
    OpTest().check_grad(
        'roi_align',
        {'X': rng.randn(1, 2, 6, 6).astype('float32'),
         'ROIs': _rois()},
        {'spatial_scale': 1.0, 'pooled_height': 2, 'pooled_width': 2,
         'sampling_ratio': 2},
        out_slot='Out', grad_slots=['X'], stop_gradients=('ROIs',))


def test_roi_pool_grad():
    x = rng.randn(1, 2, 6, 6).astype('float32')
    x += np.arange(36, dtype='float32').reshape(1, 1, 6, 6) * 0.11
    OpTest().check_grad(
        'roi_pool',
        {'X': x, 'ROIs': _rois()},
        {'spatial_scale': 1.0, 'pooled_height': 2, 'pooled_width': 2},
        out_slot='Out', grad_slots=['X'], stop_gradients=('ROIs',))


def test_psroi_pool_grad():
    OpTest().check_grad(
        'psroi_pool',
        {'X': rng.randn(1, 8, 6, 6).astype('float32'),
         'ROIs': _rois()},
        {'spatial_scale': 1.0, 'pooled_height': 2, 'pooled_width': 2,
         'output_channels': 2},
        out_slot='Out', grad_slots=['X'], stop_gradients=('ROIs',))


def test_sigmoid_focal_loss_grad():
    OpTest().check_grad(
        'sigmoid_focal_loss',
        {'X': rng.randn(4, 3).astype('float32'),
         'Label': rng.randint(0, 4, (4, 1)).astype('int64'),
         'FgNum': np.array([2], 'int32')},
        {'gamma': 2.0, 'alpha': 0.25},
        out_slot='Out', grad_slots=['X'])


# ---------------------------------------------------------------------------
# flash attention custom_vjp: fwd/bwd at multiple shapes, modes, dtypes
# (the hand-written two-pass Pallas backward — VERDICT round-2 item 8)

def _dense_ref(q, k, v, causal, key_bias=None):
    import jax
    import jax.numpy as jnp
    d = q.shape[-1]
    s = jnp.einsum('bthd,bshd->bhts', q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if key_bias is not None:
        s = s + key_bias[:, None, None, :].astype(jnp.float32)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhts,bshd->bthd', p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize('shape,causal,with_bias', [
    ((1, 128, 1, 32), False, False),
    ((2, 128, 2, 64), False, False),
    ((2, 128, 2, 64), True, False),
    ((1, 256, 2, 64), False, True),
    ((1, 256, 1, 128), True, True),
])
def test_flash_attention_grads_match_dense(shape, causal, with_bias):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    b, t, h, d = shape
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)
    bias = jnp.asarray(rng.randn(b, t) * 0.5, jnp.float32) \
        if with_bias else None

    def loss_flash(q, k, v, bias):
        o = fa.flash_attention(q, k, v, causal=causal, key_bias=bias)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v, bias):
        o = _dense_ref(q, k, v, causal, bias)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    args = (q, k, v, bias)
    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    gf = jax.grad(loss_flash, argnums)(*args)
    gd = jax.grad(loss_dense, argnums)(*args)
    for a, b2, name in zip(gf, gd, 'qkvb'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg='d%s %s' % (name, shape))


@pytest.mark.usefixtures('pallas_interpret')
def test_flash_attention_lse_grads():
    """The lse-output variant (ring-attention merge state): both o and
    lse cotangents flow; compare against the jax-native computation of
    (o, lse)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    shape = (1, 128, 2, 64)
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)

    def ref_lse(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum('bthd,bshd->bhts', q, k) / (d ** 0.5)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum('bhts,bshd->bthd', p, v)
        return o, lse

    def loss_flash(q, k, v):
        o, lse = fa.flash_attention(q, k, v, min_seq=0, with_lse=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        o, lse = ref_lse(q, k, v)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b2, name in zip(gf, gd, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg='d' + name)


def test_flash_attention_bf16_grads_finite_and_close():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    shape = (1, 128, 2, 64)
    qf = rng.randn(*shape)
    kf = rng.randn(*shape)
    vf = rng.randn(*shape)

    def loss(att, q, k, v):
        return jnp.sum(att(q, k, v).astype(jnp.float32) ** 2)

    g_bf = jax.grad(lambda q, k, v: loss(fa.flash_attention, q, k, v),
                    (0, 1, 2))(
        jnp.asarray(qf, jnp.bfloat16), jnp.asarray(kf, jnp.bfloat16),
        jnp.asarray(vf, jnp.bfloat16))
    g_f32 = jax.grad(
        lambda q, k, v: loss(
            lambda a, b, c: _dense_ref(a, b, c, False), q, k, v),
        (0, 1, 2))(jnp.asarray(qf, jnp.float32),
                   jnp.asarray(kf, jnp.float32),
                   jnp.asarray(vf, jnp.float32))
    for a, b2, name in zip(g_bf, g_f32, 'qkv'):
        a = np.asarray(a, 'float32')
        b2 = np.asarray(b2)
        assert np.isfinite(a).all()
        # bf16 tolerance: relative error on the grad norm
        denom = np.linalg.norm(b2) + 1e-6
        assert np.linalg.norm(a - b2) / denom < 0.08, \
            (name, np.linalg.norm(a - b2) / denom)
