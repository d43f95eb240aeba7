"""Long-tail layer wrappers closing the API audit gaps
(tools/check_api_coverage.py) — thin builders over already-registered
lowerings, mirroring the reference signatures in
python/paddle/fluid/layers/{nn,detection,loss,tensor}.py.
"""

import numpy as np

from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .. import initializer as init


def _simple(op_type, inputs, attrs=None, dtype=None, out_slot='Out',
            name=None, shape=None):
    helper = LayerHelper(op_type, name=name)
    first = next(iter(inputs.values()))
    first = first[0] if isinstance(first, list) else first
    out = helper.create_variable_for_type_inference(
        dtype or first.dtype)
    helper.append_op(op_type, inputs=inputs, outputs={out_slot: out},
                     attrs=attrs or {}, infer_shape=shape is None)
    if shape is not None:
        out.shape = tuple(shape)
    return out


# ----------------------------- nn.py tail -----------------------------

def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper('instance_norm', name=name)
    c = input.shape[1]
    scale = helper.create_parameter(
        param_attr, [c], input.dtype,
        default_initializer=init.Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype,
                                   is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    saved_mean = helper.create_variable_for_type_inference(input.dtype)
    saved_var = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op('instance_norm',
                     inputs={'X': input, 'Scale': scale, 'Bias': bias},
                     outputs={'Y': out, 'SavedMean': saved_mean,
                              'SavedVariance': saved_var},
                     attrs={'epsilon': epsilon})
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None,
               bias_attr=None, act=None, data_layout='NCHW', name=None):
    helper = LayerHelper('group_norm', name=name)
    c = input.shape[1 if data_layout == 'NCHW' else -1]
    scale = helper.create_parameter(
        param_attr, [c], input.dtype,
        default_initializer=init.Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype,
                                   is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype)
    var = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op('group_norm',
                     inputs={'X': input, 'Scale': scale, 'Bias': bias},
                     outputs={'Y': out, 'Mean': mean, 'Variance': var},
                     attrs={'epsilon': epsilon, 'groups': groups,
                            'data_layout': data_layout})
    return helper.append_activation(out, act)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout='NCHW', in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper('data_norm', name=name)
    c = input.shape[-1]
    batch_size = helper.create_parameter(
        ParamAttr(name=name + '.batch_size' if name else None), [c],
        input.dtype, default_initializer=init.Constant(1e4))
    batch_sum = helper.create_parameter(
        ParamAttr(name=name + '.batch_sum' if name else None), [c],
        input.dtype, default_initializer=init.Constant(0.0))
    batch_square = helper.create_parameter(
        ParamAttr(name=name + '.batch_square_sum' if name else None),
        [c], input.dtype, default_initializer=init.Constant(1e4))
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype)
    scales = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op('data_norm',
                     inputs={'X': input, 'BatchSize': batch_size,
                             'BatchSum': batch_sum,
                             'BatchSquareSum': batch_square},
                     outputs={'Y': out, 'Means': means,
                              'Scales': scales},
                     attrs={'epsilon': epsilon})
    return helper.append_activation(out, act)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper('spectral_norm', name=name)
    h = weight.shape[dim]
    w = int(np.prod(weight.shape)) // h
    u = helper.create_parameter(
        ParamAttr(trainable=False), [h], weight.dtype,
        default_initializer=init.Normal(0.0, 1.0))
    v = helper.create_parameter(
        ParamAttr(trainable=False), [w], weight.dtype,
        default_initializer=init.Normal(0.0, 1.0))
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op('spectral_norm',
                     inputs={'Weight': weight, 'U': u, 'V': v},
                     outputs={'Out': out},
                     attrs={'dim': dim, 'power_iters': power_iters,
                            'eps': eps})
    return out


def maxout(x, groups, name=None, axis=1):
    return _simple('maxout', {'X': x}, {'groups': groups, 'axis': axis},
                   name=name)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    # no shape inference: the dummy batch does not divide seg_num
    return _simple('temporal_shift', {'X': x},
                   {'seg_num': seg_num, 'shift_ratio': shift_ratio},
                   name=name, shape=x.shape)


def pad2d(input, paddings=(0, 0, 0, 0), mode='constant', pad_value=0.0,
          data_format='NCHW', name=None):
    return _simple('pad2d', {'X': input},
                   {'paddings': list(paddings), 'mode': mode,
                    'pad_value': pad_value, 'data_format': data_format},
                   name=name)


def crop(x, shape=None, offsets=None, name=None):
    attrs = {}
    if isinstance(shape, (list, tuple)):
        attrs['shape'] = list(shape)
    if isinstance(offsets, (list, tuple)):
        attrs['offsets'] = list(offsets)
    return _simple('crop', {'X': x}, attrs, name=name)


def crop_tensor(x, shape=None, offsets=None, name=None):
    ins = {'X': x}
    attrs = {}
    from ..framework import Variable
    if isinstance(shape, Variable):
        ins['Shape'] = shape
    elif shape is not None:
        attrs['shape'] = list(shape)
    if isinstance(offsets, Variable):
        ins['Offsets'] = offsets
    elif offsets is not None:
        attrs['offsets'] = list(offsets)
    return _simple('crop_tensor', ins, attrs, name=name)


def expand_as(x, target_tensor, name=None):
    return _simple('expand_as',
                   {'X': x, 'target_tensor': target_tensor}, name=name)


def im2sequence(input, filter_size=1, stride=1, padding=0,
                input_image_size=None, out_stride=1, name=None):
    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]
    attrs = {'kernels': _pair(filter_size), 'strides': _pair(stride),
             'paddings': (_pair(padding) * 2 if
                          len(_pair(padding)) == 2 else list(padding))}
    return _simple('im2sequence', {'X': input}, attrs, name=name)


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    helper = LayerHelper('row_conv', name=name)
    filter_shape = [future_context_size + 1, input.shape[-1]]
    w = helper.create_parameter(param_attr, filter_shape, input.dtype)
    out = _simple('row_conv', {'X': input, 'Filter': w}, name=name)
    return helper.append_activation(out, act)


def short_conv(input, filter_size, gate_in=None, gate_out=None,
               param_attr=None, bias_attr=False, name=None):
    """A causal depthwise convolution over time with one
    ``filter_size``-tap filter a channel (``row_conv``
    looks ahead; this looks back): input [B, T, C] -> out[b, t] =
    sum_j w[:, j] * z[b, t - (filter_size - 1) + j], z zero before the
    sequence starts, so the LAST tap weighs the token itself.  The
    filter is a parameter [C, filter_size].  ``gate_in`` and
    ``gate_out`` ([B, T, C] each) fuse the two multiplicative gates of
    a gated short convolution in: z = input * gate_in, out = gate_out *
    (the filter of z); without them z = input.  ``bias_attr`` (default
    False: none) adds a bias [C] to the filter's output inside the op
    (a Mamba layer's filter)."""
    helper = LayerHelper('short_conv', name=name)
    w = helper.create_parameter(
        param_attr, [int(input.shape[-1]), int(filter_size)], input.dtype)
    ins = {'X': input, 'Filter': w}
    if bias_attr is not False:
        ins['Bias'] = helper.create_parameter(
            bias_attr, [int(input.shape[-1])], input.dtype, is_bias=True)
    if gate_in is not None:
        ins['GateIn'] = gate_in
    if gate_out is not None:
        ins['GateOut'] = gate_out
    return _simple('short_conv', ins, name=name)


def kda_attention(q, k, v, a, beta, name=None):
    """The gated delta rule with a per-channel decay (the linear
    attention of Solar Open 2's ``linear_attn_config`` layers; the op
    ``kda_attention``, ``ops/kda_ops.py``, has the equations): q, k [B,
    T, H, dk], v [B, T, H, dv], ``a`` [B, T, H, dk] the LOG of each key
    channel's decay (<= 0; float32 under AMP), ``beta`` [B, T, H] the
    write strength -> o [B, T, H, dv] in v's dtype, ``o_t = S_t^T q_t``
    of a state ``S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) +
    beta_t k_t v_t^T`` that starts at zero in every sequence.  What
    comes before (projections, filters, the normalisation of q and k)
    and after (the gated norm) is the model's.  Computed in chunks of
    64 tokens (``ops.kda_ops.CHUNK``); T need be no whole number of
    them."""
    return _simple('kda_attention',
                   {'Q': q, 'K': k, 'V': v, 'A': a, 'Beta': beta},
                   dtype=v.dtype, name=name)


def selective_scan(x, delta, a, b, c, d, name=None):
    """The selective state-space scan of a Mamba layer (the op
    ``selective_scan``, ``ops/ssm_ops.py``, has the equations): x [B, T,
    D], ``delta`` [B, T, D] the steps (> 0; float32 under AMP), ``a``
    [D, N] (< 0), ``b`` and ``c`` [B, T, N] the token's write and read
    vectors, ``d`` [D] the skip -> m [B, T, D] in x's dtype, ``m_t = h_t
    c_t + d * x_t`` of a state ``h_t = exp(delta_t a) * h_(t-1) +
    (delta_t x_t) b_t^T`` [D, N] that starts at zero in every sequence.
    What comes before (the projections, the filter, the softplus) and
    after (the gate, W_out) is the model's.  Computed in chunks of 256
    tokens (``ops.ssm_ops.CHUNK``); T need be no whole number of
    them.  Mamba-1's: a decay per channel AND state and 16 states a
    channel, stepped a token at a time; ``ssd_scan`` is Mamba-2's, a
    SCALAR decay a head and a [64, 128] state, in matrix products over
    chunks."""
    return _simple('selective_scan',
                   {'X': x, 'Delta': delta, 'A': a, 'B': b, 'C': c, 'D': d},
                   dtype=x.dtype, name=name)


def ssd_scan(x, delta, a, b, c, d, chunk=128, name=None):
    """Mamba-2's recurrence in its state-space-dual form (the op
    ``ssd_scan``, ``ops/ssd_ops.py``, has the equations): x [B, T, H,
    P] (H heads of P channels), ``delta`` [B, T, H] the steps (> 0;
    float32 under AMP), ``a`` [H] (< 0: ONE decay rate a head), ``b``
    and ``c`` [B, T, G, N] the token's write and read vectors, one pair
    a GROUP of H / G heads, ``d`` [H] the skip -> y [B, T, H, P] in x's
    dtype, ``y_t = S_t c_t + d x_t`` of a state ``S_t = exp(a delta_t)
    S_(t-1) + delta_t x_t b_t^T`` [P, N] a head that starts at zero in
    every sequence.  What comes before (the projection, the filter, the
    softplus) and after (the gated norm, W_out) is the model's.
    Computed as matrix products over chunks of ``chunk`` tokens (the
    model's ``chunk_size``) and one elementwise walk over the chunks'
    states; T need be no whole number of them.  ``selective_scan`` is
    Mamba-1's (a decay per channel and state, no matrix form)."""
    return _simple('ssd_scan',
                   {'X': x, 'Delta': delta, 'A': a, 'B': b, 'C': c, 'D': d},
                   attrs={'chunk': int(chunk)}, dtype=x.dtype, name=name)


def hyper_connection_pre(x, sinkhorn_iters=20, epsilon=1e-6, hc_eps=1e-6,
                         clamp=(-30.0, 30.0), param_attr=None,
                         alpha_attr=None, bias_attr=None, name=None):
    """The read side of a manifold-constrained hyper-connection (the
    op ``hyper_connection_pre``, ``ops/hyper_connection_ops.py``, has
    the equations) around ONE operator: ``x`` [B, T, n, C] is the
    residual stream, n rows a token.  Creates the operator's own float32
    parameters, phi [n C, n^2 + 2 n] (``param_attr``; stored at UNIT
    size, default Normal(0, 1): the op divides by sqrt(n C)), the three scalars
    alpha [3] (``alpha_attr``; default 0.01) and the bias [n^2 + 2 n]
    (``bias_attr``; default zeros), and -> (u [B, T, C], the mix of the
    rows the operator reads, in x's dtype; ``carry``, what
    ``hyper_connection_post`` needs: the token's write weights H_post
    [B, n, T] and its doubly stochastic H_res [B, n, n, T], float32,
    tokens last; err [1], the largest distance of a row or column sum
    of H_res from 1, no gradient).  The maps, and the ``sinkhorn_iters``
    normalisations that make H_res, are float32 under AMP too."""
    n, c = int(x.shape[2]), int(x.shape[3])
    m = n * n + 2 * n
    helper = LayerHelper('hyper_connection_pre', name=name)
    phi = helper.create_parameter(
        param_attr, [n * c, m], 'float32',
        default_initializer=init.Normal(0., 1.))
    alpha = helper.create_parameter(
        alpha_attr, [3], 'float32',
        default_initializer=init.Constant(0.01))
    bias = helper.create_parameter(bias_attr, [m], 'float32', is_bias=True)
    u = helper.create_variable_for_type_inference(x.dtype)
    h_post = helper.create_variable_for_type_inference('float32')
    h_res = helper.create_variable_for_type_inference('float32')
    err = helper.create_variable_for_type_inference('float32',
                                                    stop_gradient=True)
    helper.append_op(
        'hyper_connection_pre',
        inputs={'X': x, 'Phi': phi, 'Alpha': alpha, 'Bias': bias},
        outputs={'U': u, 'HPost': h_post, 'HRes': h_res, 'Err': err},
        attrs={'sinkhorn_iters': int(sinkhorn_iters),
               'epsilon': float(epsilon), 'hc_eps': float(hc_eps),
               'clamp_min': float(clamp[0]), 'clamp_max': float(clamp[1])})
    return u, (h_post, h_res), err


def hyper_connection_post(x, y, carry, name=None):
    """The write side: ``x`` [B, T, n, C] the stream the operator read
    from, ``y`` [B, T, C] what it produced, ``carry`` from
    ``hyper_connection_pre`` -> the new stream H_res x + H_post^T y
    [B, T, n, C] in y's dtype."""
    h_post, h_res = carry
    return _simple('hyper_connection_post',
                   {'X': x, 'Y': y, 'HPost': h_post, 'HRes': h_res},
                   dtype=y.dtype, out_slot='XOut', name=name)


def flash_attention(q, k, v, causal=False, window=0, coarse=None,
                    with_lse=False, block_mask=None, name=None):
    """The ``fused_multihead_attention`` op on heads already split: q
    [B, T, H, D], k [B, Tk, Hkv, D], v [B, Tk, Hkv, Dv] -> [B, T, H,
    Dv] (the flash kernels on a chip from
    ``flash_attention.FLASH_MIN_SEQ`` queries up, the small-keys
    kernels for an unmasked call of ``flash_attention.SMALL_KEYS``
    keys or fewer, the op's dense chain between the two and off a
    chip).  Scores are scaled by 1 / sqrt(D); a
    model whose softmax scale is another (YaRN's ``mscale`` squared,
    ``models/moonlight.py`` ``softmax_scale``) multiplies q by the
    ratio before the call.  ``causal``, ``window``, ``coarse`` =
    (window, chunk) and ``block_mask`` = (block, 'causal' | 'strict')
    are the op's four masks (``ops/pallas/flash_attention.py`` lists
    them in one place); ``with_lse`` also
    returns every row's log-sum-exp [B, T, H], differentiable, for
    ``attention_merge``."""
    helper = LayerHelper('fused_multihead_attention', name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    outputs = {'Out': out}
    attrs = {'causal': bool(causal), 'dropout_rate': 0.0}
    if window:
        attrs['window'] = int(window)
    if coarse:
        attrs['coarse_window'], attrs['coarse_chunk'] = \
            (int(n) for n in coarse)
    if block_mask:
        attrs['block_mask'] = int(block_mask[0])
        attrs['block_relation'] = str(block_mask[1])
    if with_lse:
        attrs['with_lse'] = True
        lse = helper.create_variable_for_type_inference('float32')
        outputs['Lse'] = lse
    helper.append_op('fused_multihead_attention',
                     inputs={'Q': q, 'K': k, 'V': v}, outputs=outputs,
                     attrs=attrs, infer_shape=False)
    out.shape = tuple(q.shape[:3]) + (v.shape[3],)
    if not with_lse:
        return out
    lse.shape = tuple(q.shape[:3])
    return out, lse


def eva_chunk_summary(k, v, chunk_size, phi, mu, name=None):
    """One learned-softmax summary key and value a chunk of
    ``chunk_size`` positions: k [B, T, H, D], v [B, T, H, Dv], phi and
    mu [H, D] (variables; a model makes them parameters) -> (ks [B, T
    / chunk_size, H, D], vs [B, T / chunk_size, H, Dv]); the op
    ``eva_chunk_summary`` has the equations."""
    t = int(k.shape[1])
    if t % int(chunk_size):
        raise ValueError('eva_chunk_summary: %d positions are no whole '
                         'number of %d-position chunks' % (t, chunk_size))
    helper = LayerHelper('eva_chunk_summary', name=name)
    ks = helper.create_variable_for_type_inference(k.dtype)
    vs = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op('eva_chunk_summary',
                     inputs={'K': k, 'V': v, 'Phi': phi, 'Mu': mu},
                     outputs={'KS': ks, 'VS': vs},
                     attrs={'chunk_size': int(chunk_size)})
    return ks, vs


def attention_merge(x1, lse1, x2, lse2, name=None):
    """Two attention results over disjoint key sets joined into the
    one softmax over both by their rows' log-sum-exps: x1, x2 [B, T,
    H, Dv], lse1, lse2 [B, T, H] -> (out [B, T, H, Dv], the second
    set's mean weight [1] over the rows where it is not empty, no
    gradient); the op ``attention_merge`` has the equation."""
    helper = LayerHelper('attention_merge', name=name)
    out = helper.create_variable_for_type_inference(x1.dtype)
    weight = helper.create_variable_for_type_inference(
        'float32', stop_gradient=True)
    helper.append_op('attention_merge',
                     inputs={'X1': x1, 'Lse1': lse1, 'X2': x2,
                             'Lse2': lse2},
                     outputs={'Out': out, 'SecondWeight': weight})
    return out, weight


def _record_remote_weight(values):
    """``Program.watch``'s record for ``eva_attention``: the layers'
    mean remote weights of the last run that fetched, averaged."""
    from .. import monitor
    monitor.set_gauge('eva/remote_weight_mean', float(np.mean(
        [np.asarray(v, np.float64).ravel()[0] for v in values])))


def eva_attention(q, k, v, window_size, chunk_size, phi, mu, name=None):
    """EVA attention as EvaByte ships it (Zheng et al. 2023, "Efficient
    Attention via Control Variates", with a learned feature): q, k, v
    [B, T, H, D] (rotated already; v may be Dv wide), phi and mu [H, D]
    -> [B, T, H, Dv].  Query t sees two kinds of keys in ONE softmax:

    - exactly and causally, the keys of its own window of
      ``window_size`` positions (block-diagonal, not a sliding band);
    - one summary a ``chunk_size``-position chunk
      (``eva_chunk_summary`` of k, v by phi and mu) of every EARLIER
      window, and none of its own.

    Lowered as four ops, each with its gradient: the summaries; the
    local stream, causal flash attention with the windows folded into
    the batch ([B, T, ..] -> [B T / window, window, ..], a reshape);
    the remote stream, the flash kernels under the coarse mask over T
    / chunk keys; ``attention_merge`` of the two by their
    log-sum-exps.  On a chip no [T, T] or [T, T / chunk] tensor reaches
    HBM, forward or backward.  With T <= ``window_size`` there is no
    earlier window: the layer IS causal attention, and phi and mu stay
    unused.  T has to be a whole number of windows (or one shorter
    window) and of chunks, and a window a whole number of chunks:
    anything else is refused here, not padded into the softmax.

    On the runs of the program that fetch, the gauge
    ``eva/remote_weight_mean`` holds the summaries' mean share of the
    softmax (``Program.watch``); ``eva/local_pairs``,
    ``eva/remote_pairs`` and ``eva/chunks`` are set as the remote call
    is lowered."""
    t, heads = int(q.shape[1]), int(q.shape[2])
    window, chunk = int(window_size), int(chunk_size)
    if t <= window:
        return flash_attention(q, k, v, causal=True, name=name)
    if window % chunk or t % window:
        raise ValueError(
            'eva_attention: %d positions in windows of %d over chunks '
            'of %d: the length has to be a whole number of windows and '
            'a window of chunks' % (t, window, chunk))
    from .nn import reshape

    def fold(x):        # windows into the batch
        return reshape(x, [-1, window, int(x.shape[2]), int(x.shape[3])])

    def unfold(x):
        return reshape(x, [-1, t] + [int(n) for n in x.shape[2:]])

    ks, vs = eva_chunk_summary(k, v, chunk, phi, mu)
    local, local_lse = flash_attention(fold(q), fold(k), fold(v),
                                       causal=True, with_lse=True)
    remote, remote_lse = flash_attention(q, ks, vs,
                                         coarse=(window, chunk),
                                         with_lse=True)
    out, weight = attention_merge(unfold(local), unfold(local_lse),
                                  remote, remote_lse, name=name)
    out.block.program.watch([weight.name], _record_remote_weight)
    return out


def block_diffusion_attention(q, k, v, block, name=None):
    """Attention of block-diffusion TRAINING (BD3-LMs, Arriola et al.
    2025; SDAR): q, k, v hold TWO copies of every sequence, one over
    the other along time, [B, 2L, H | Hkv, D]: rows 0 .. L-1 the
    corrupted copy, rows L .. 2L-1 the clean one, both at positions
    0 .. L-1 (rotated already) -> [B, 2L, H, Dv].  q may hold the
    corrupted rows alone, [B, L, H, D], and so does the result then (a
    last layer, whose clean rows nothing reads once their keys and
    values exist).  In blocks of ``block`` positions, in ONE softmax a
    query:

    - corrupted i sees the corrupted keys of its OWN block (both
      directions) and the clean keys of every EARLIER block;
    - clean i sees the clean keys of its own and every earlier block,
      and no corrupted key.

    Lowered as three attention calls and a merge, each with its
    gradient: the clean rows over the clean keys under the block mask
    'causal'; the corrupted rows over the clean keys under 'strict'
    (the first block sees none: out 0, lse -inf); the corrupted rows
    over their own block's corrupted keys with the blocks folded into
    the batch ([B, L, ..] -> [B L / block, block, ..], a reshape; no
    mask); ``attention_merge`` of the last two by their log-sum-exps.
    On a chip the first two run the flash kernels from
    ``flash_attention.FLASH_MIN_SEQ`` queries up and no [L, L] tensor
    reaches HBM, forward or backward; the third runs the small-keys
    kernels (``ops/pallas/small_keys.py``: a ``block`` of
    ``flash_attention.SMALL_KEYS`` or less that divides 128, heads in
    whole lanes, L a multiple of 128), the dense chain where its shape
    fails their gates.  L has to be a whole number of blocks.  As they are lowered the block-mask calls add to
    ``sdar/visible_pairs`` and ``sdar/tiles_visited``."""
    t, block = int(k.shape[1]) // 2, int(block)
    if int(k.shape[1]) != 2 * t or t % block or \
            int(q.shape[1]) not in (t, 2 * t):
        raise ValueError(
            'block_diffusion_attention: %d query and %d key rows in '
            'blocks of %d: the keys are two copies of a whole number of '
            'blocks, the queries both copies or the first'
            % (q.shape[1], k.shape[1], block))
    from .nn import reshape, split
    from .tensor import concat

    def fold(x):        # blocks into the batch
        return reshape(x, [-1, block, int(x.shape[2]), int(x.shape[3])])

    def unfold(x):
        return reshape(x, [-1, t] + [int(n) for n in x.shape[2:]])

    (k_noisy, k_clean), (v_noisy, v_clean) = (
        split(x, 2, dim=1) for x in (k, v))
    q_noisy, q_clean = split(q, 2, dim=1) if int(q.shape[1]) == 2 * t \
        else (q, None)
    own, own_lse = flash_attention(fold(q_noisy), fold(k_noisy),
                                   fold(v_noisy), with_lse=True)
    earlier, earlier_lse = flash_attention(
        q_noisy, k_clean, v_clean, block_mask=(block, 'strict'),
        with_lse=True)
    noisy, _ = attention_merge(unfold(own), unfold(own_lse), earlier,
                               earlier_lse, name=name)
    if q_clean is None:
        return noisy
    clean = flash_attention(q_clean, k_clean, v_clean,
                            block_mask=(block, 'causal'))
    return concat([noisy, clean], axis=1)


def grid_sampler(x, grid, name=None):
    return _simple('grid_sampler', {'X': x, 'Grid': grid}, name=name)


def log_loss(input, label, epsilon=1e-4, name=None):
    return _simple('log_loss',
                   {'Predicted': input, 'Labels': label},
                   {'epsilon': epsilon}, out_slot='Loss', name=name)


def huber_loss(input, label, delta, name=None):
    helper = LayerHelper('huber_loss', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    resid = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op('huber_loss', inputs={'X': input, 'Y': label},
                     outputs={'Out': out, 'Residual': resid},
                     attrs={'delta': delta})
    return out


def kldiv_loss(x, target, reduction='mean', name=None):
    return _simple('kldiv_loss', {'X': x, 'Target': target},
                   {'reduction': reduction}, out_slot='Loss', name=name)


def mse_loss(input, label, name=None):
    return _simple('mse_loss', {'X': input, 'Y': label}, name=name)


def sum(x, name=None):
    xs = x if isinstance(x, (list, tuple)) else [x]
    return _simple('sum', {'X': list(xs)}, name=name)


def shape(input, name=None):
    return _simple('shape', {'Input': input}, dtype='int32', name=name)


def rank(input, name=None):
    return _simple('rank', {'Input': input}, dtype='int32', name=name)


def size(input, name=None):
    return _simple('size', {'Input': input}, dtype='int64', name=name)


def strided_slice(input, axes, starts, ends, strides, name=None):
    return _simple('strided_slice', {'Input': input},
                   {'axes': list(axes), 'starts': list(starts),
                    'ends': list(ends), 'strides': list(strides)},
                   name=name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _simple('reduce_all', {'X': input},
                   {'dim': list(dim) if dim is not None else [],
                    'keep_dim': keep_dim,
                    'reduce_all': dim is None}, name=name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _simple('reduce_any', {'X': input},
                   {'dim': list(dim) if dim is not None else [],
                    'keep_dim': keep_dim,
                    'reduce_all': dim is None}, name=name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    helper = LayerHelper('elementwise_mod', name=name)
    out = _simple('elementwise_mod', {'X': x, 'Y': y}, {'axis': axis},
                  name=name)
    return helper.append_activation(out, act)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    helper = LayerHelper('elementwise_floordiv', name=name)
    out = _simple('elementwise_floordiv', {'X': x, 'Y': y},
                  {'axis': axis}, name=name)
    return helper.append_activation(out, act)


def uniform_random(shape, dtype='float32', min=-1.0, max=1.0, seed=0,
                   name=None):
    helper = LayerHelper('uniform_random', name=name)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op('uniform_random', outputs={'Out': out},
                     attrs={'shape': list(shape), 'dtype': dtype,
                            'min': float(min), 'max': float(max),
                            'seed': seed}, infer_shape=False)
    out.shape = tuple(shape)
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype='float32',
                    name=None):
    helper = LayerHelper('gaussian_random', name=name)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op('gaussian_random', outputs={'Out': out},
                     attrs={'shape': list(shape), 'dtype': dtype,
                            'mean': float(mean), 'std': float(std),
                            'seed': seed}, infer_shape=False)
    out.shape = tuple(shape)
    return out


def uniform_random_batch_size_like(input, shape, dtype='float32',
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    return _simple('uniform_random_batch_size_like', {'Input': input},
                   {'shape': list(shape), 'dtype': dtype,
                    'input_dim_idx': input_dim_idx,
                    'output_dim_idx': output_dim_idx,
                    'min': float(min), 'max': float(max), 'seed': seed},
                   dtype=dtype)


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype='float32'):
    return _simple('gaussian_random_batch_size_like', {'Input': input},
                   {'shape': list(shape), 'dtype': dtype,
                    'input_dim_idx': input_dim_idx,
                    'output_dim_idx': output_dim_idx,
                    'mean': float(mean), 'std': float(std),
                    'seed': seed}, dtype=dtype)


def soft_relu(x, threshold=40.0, name=None):
    return _simple('soft_relu', {'X': x}, {'threshold': threshold},
                   name=name)


def hash(input, hash_size, num_hash=1, name=None):
    return _simple('hash', {'X': input},
                   {'mod_by': hash_size, 'num_hash': num_hash},
                   dtype='int32', name=name)


def unique(x, dtype='int32'):
    helper = LayerHelper('unique')
    out = helper.create_variable_for_type_inference(x.dtype)
    index = helper.create_variable_for_type_inference(dtype)
    helper.append_op('unique', inputs={'X': x},
                     outputs={'Out': out, 'Index': index},
                     infer_shape=False)
    return out, index


def unique_with_counts(x, dtype='int32'):
    helper = LayerHelper('unique_with_counts')
    out = helper.create_variable_for_type_inference(x.dtype)
    index = helper.create_variable_for_type_inference(dtype)
    count = helper.create_variable_for_type_inference(dtype)
    helper.append_op('unique_with_counts', inputs={'X': x},
                     outputs={'Out': out, 'Index': index,
                              'Count': count},
                     infer_shape=False)
    return out, index, count


def scatter_nd(index, updates, shape, name=None):
    return _simple('scatter_nd', {'Index': index, 'Updates': updates},
                   {'shape': list(shape)}, dtype=updates.dtype,
                   name=name)


def similarity_focus(input, axis, indexes, name=None):
    return _simple('similarity_focus', {'X': input},
                   {'axis': axis, 'indexes': list(indexes)}, name=name)


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    return _simple('add_position_encoding', {'X': input},
                   {'alpha': alpha, 'beta': beta}, name=name)


def merge_selected_rows(x, name=None):
    return _simple('merge_selected_rows', {'X': x}, name=name,
                   shape=getattr(x, 'shape', None))


def get_tensor_from_selected_rows(x, name=None):
    return _simple('get_tensor_from_selected_rows', {'X': x}, name=name,
                   shape=getattr(x, 'shape', None))


def continuous_value_model(input, cvm, use_cvm=True):
    return _simple('continuous_value_model',
                   {'X': input, 'CVM': cvm}, {'use_cvm': use_cvm})


def filter_by_instag(ins, ins_tag, filter_tag, is_lod=True):
    helper = LayerHelper('filter_by_instag')
    out = helper.create_variable_for_type_inference(ins.dtype)
    loss_weight = helper.create_variable_for_type_inference('float32')
    index_map = helper.create_variable_for_type_inference('int64')
    helper.append_op('filter_by_instag',
                     inputs={'Ins': ins, 'Ins_tag': ins_tag,
                             'Filter_tag': filter_tag},
                     outputs={'Out': out, 'LossWeight': loss_weight,
                              'IndexMap': index_map},
                     attrs={'is_lod': is_lod}, infer_shape=False)
    return out, loss_weight


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Persistable global step var incremented once per program run
    (reference layers/nn.py autoincreased_step_counter)."""
    helper = LayerHelper('global_step_counter')
    name = counter_name or '@STEP_COUNTER@'
    block = helper.main_program.global_block()
    counter = block._find_var_recursive(name)
    if counter is None:
        counter = block.create_var(name=name, shape=(1,), dtype='int64',
                                   persistable=True)
        sb = helper.startup_program.global_block()
        sb.create_var(name=name, shape=(1,), dtype='int64',
                      persistable=True)
        sb.append_op('fill_constant', outputs={'Out': name},
                     attrs={'shape': [1], 'dtype': 'int64',
                            'value': float(begin - step)})
        block._prepend_op('increment', inputs={'X': counter},
                          outputs={'Out': counter},
                          attrs={'step': float(step)})
        counter.stop_gradient = True
    return counter


def lod_append(x, level):
    """LoD levels are host-side metadata here; appending a level is a
    no-op on the padded dense rendering."""
    return x


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    from . import nn as _nn
    h, w = input.shape[2], input.shape[3]
    short = min(h, w)
    scale = float(out_short_len) / float(short)
    out_shape = [int(round(h * scale)), int(round(w * scale))]
    return _nn.image_resize(input, out_shape=out_shape,
                            resample=resample)


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, name=None,
              rois_num=None):
    ins = {'X': input, 'ROIs': rois}
    if rois_num is not None:
        ins['RoisBatch'] = rois_num
    return _simple('roi_align', ins,
                   {'pooled_height': pooled_height,
                    'pooled_width': pooled_width,
                    'spatial_scale': spatial_scale,
                    'sampling_ratio': sampling_ratio}, name=name)


def prroi_pool(input, rois, spatial_scale=1.0, pooled_height=1,
               pooled_width=1, batch_roi_nums=None, name=None):
    ins = {'X': input, 'ROIs': rois}
    if batch_roi_nums is not None:
        ins['BatchRoINums'] = batch_roi_nums
    return _simple('prroi_pool', ins,
                   {'spatial_scale': spatial_scale,
                    'pooled_height': pooled_height,
                    'pooled_width': pooled_width}, name=name)


def deformable_conv(input, offset, mask, num_filters, filter_size,
                    stride=1, padding=0, dilation=1, groups=1,
                    deformable_groups=1, im2col_step=1, param_attr=None,
                    bias_attr=None, modulated=True, name=None):
    helper = LayerHelper('deformable_conv', name=name)

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]
    c_in = input.shape[1]
    fs = _pair(filter_size)
    w = helper.create_parameter(
        param_attr, [num_filters, c_in // groups] + fs, input.dtype)
    ins = {'Input': input, 'Offset': offset, 'Filter': w}
    if modulated and mask is not None:
        ins['Mask'] = mask
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op('deformable_conv' if modulated else
                     'deformable_conv_v1', inputs=ins,
                     outputs={'Output': out},
                     attrs={'strides': _pair(stride),
                            'paddings': _pair(padding),
                            'dilations': _pair(dilation),
                            'groups': groups,
                            'deformable_groups': deformable_groups,
                            'im2col_step': im2col_step},
                     infer_shape=False)
    if bias_attr is not False:
        out = helper.append_bias_op(out, dim_start=1, dim_end=2,
                                    bias_attr=bias_attr)
    return out


def deformable_roi_pooling(input, rois, trans, no_trans=False,
                           spatial_scale=1.0, group_size=(1, 1),
                           pooled_height=1, pooled_width=1,
                           part_size=None, sample_per_part=1,
                           trans_std=0.1, position_sensitive=False,
                           name=None):
    helper = LayerHelper('deformable_roi_pooling', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    top = helper.create_variable_for_type_inference(input.dtype)
    ins = {'X': input, 'ROIs': rois}
    if not no_trans and trans is not None:
        ins['Trans'] = trans
    helper.append_op('deformable_roi_pooling', inputs=ins,
                     outputs={'Output': out, 'TopCount': top},
                     attrs={'spatial_scale': spatial_scale,
                            'pooled_height': pooled_height,
                            'pooled_width': pooled_width,
                            'trans_std': trans_std},
                     infer_shape=False)
    return out


def adaptive_pool3d(input, pool_size, pool_type='max',
                    require_index=False, name=None):
    return _simple('pool3d', {'X': input},
                   {'pooling_type': pool_type,
                    'ksize': list(pool_size) if isinstance(
                        pool_size, (list, tuple)) else [pool_size] * 3,
                    'adaptive': True}, name=name)


def sampled_softmax_with_cross_entropy(logits, label, num_samples,
                                       num_true=1, remove_accidental_hits=True,
                                       use_customized_samples=False,
                                       customized_samples=None,
                                       customized_probabilities=None,
                                       seed=0):
    """Composite over sample_logits + softmax_with_cross_entropy
    (reference layers/loss.py sampled_softmax_with_cross_entropy)."""
    helper = LayerHelper('sample_logits')
    samples = helper.create_variable_for_type_inference('int64')
    probs = helper.create_variable_for_type_inference(logits.dtype)
    sampled_logits = helper.create_variable_for_type_inference(
        logits.dtype)
    sampled_label = helper.create_variable_for_type_inference('int64')
    helper.append_op('sample_logits',
                     inputs={'Logits': logits, 'Labels': label},
                     outputs={'Samples': samples,
                              'Probabilities': probs,
                              'SampledLogits': sampled_logits,
                              'SampledLabels': sampled_label},
                     attrs={'num_samples': num_samples,
                            'use_customized_samples':
                                use_customized_samples,
                            'remove_accidental_hits':
                                remove_accidental_hits,
                            'seed': seed}, infer_shape=False)
    b = logits.shape[0]
    sampled_logits.shape = (b, num_true + num_samples)
    sampled_label.shape = (b, num_true)
    from . import nn as _nn
    return _nn.softmax_with_cross_entropy(sampled_logits, sampled_label)
