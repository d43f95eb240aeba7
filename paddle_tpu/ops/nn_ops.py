"""NN op lowerings: conv, pool, norm, dropout, losses, metrics.

Reference kernels: operators/conv_cudnn_op.cu, pool_op.*, batch_norm_op.*,
layer_norm_op.*, dropout_op.*, softmax_with_cross_entropy_op.*,
cross_entropy_op.*, metrics/accuracy_op.* — re-designed on
lax.conv_general_dilated / reduce_window so XLA tiles them onto the MXU.
Gradients come from jax.vjp over these lowerings (registry.grad_op_def).
"""

import functools as _functools

import numpy as np
import jax
import jax.numpy as jnp

from . import keep_hash
from . import registry
from .registry import register


def _f32_conv_precision():
    """MXU algorithm for f32 convs, from FLAGS_conv_precision:
    'highest' matches reference fp32 accuracy (6-pass bf16 emulation);
    'high' (3-pass) and 'default' (single-pass bf16) trade accuracy
    for speed."""
    try:
        from ..fluid.flags import get_flag
        name = str(get_flag('FLAGS_conv_precision', 'highest')).lower()
    except Exception:
        name = 'highest'
    return {'highest': jax.lax.Precision.HIGHEST,
            'high': jax.lax.Precision.HIGH,
            'default': jax.lax.Precision.DEFAULT}.get(
        name, jax.lax.Precision.HIGHEST)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v]


def _conv_padding(paddings, algo, ksize, strides, dilations):
    if algo == 'VALID':
        return [(0, 0), (0, 0)]
    if algo == 'SAME':
        return 'SAME'
    p = _pair(paddings)
    if len(p) == 2:
        return [(p[0], p[0]), (p[1], p[1])]
    if len(p) == 4:
        return [(p[0], p[1]), (p[2], p[3])]
    raise ValueError('bad paddings %s' % (paddings,))


@register('conv2d')
def conv2d(ctx, ins, attrs):
    x = ins['Input'][0]
    w = ins['Filter'][0]
    strides = _pair(attrs.get('strides', [1, 1]))
    dilations = _pair(attrs.get('dilations', [1, 1]))
    groups = attrs.get('groups', 1) or 1
    data_format = attrs.get('data_format', 'NCHW')
    if data_format in ('NCHW', 'AnyLayout'):
        dn = ('NCHW', 'OIHW', 'NCHW')
    else:
        # program weights are always OIHW (layer contract); present them
        # to XLA as HWIO for the NHWC path
        dn = ('NHWC', 'HWIO', 'NHWC')
        w = jnp.transpose(w, (2, 3, 1, 0))
    pad = _conv_padding(attrs.get('paddings', [0, 0]),
                        attrs.get('padding_algorithm', 'EXPLICIT'),
                        w.shape[-2:], strides, dilations)
    amp = attrs.get('__amp__') and x.dtype in (jnp.float32, jnp.bfloat16)
    if amp:
        # bf16 in AND out: the MXU accumulates in f32 internally, and the
        # bf16 output propagates through the gray-list tail (batch_norm,
        # relu, add, pool all follow their input dtype) so activations
        # stay bf16 in HBM end-to-end — black-list ops cast up to f32
        # themselves
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    precision = _f32_conv_precision() if x.dtype == jnp.float32 else None
    # the multi-pass (6- / 3-pass bf16) f32 convolution stands alone:
    # once XLA fuses an elementwise neighbour into it (relu-grad and
    # bias-grad producers of the cotangent, an optimizer update
    # consuming the weight gradient) the v5e compiler does not finish
    # LeNet b512 (host memory past 40 GB; any dense optimizer).  The
    # barriers transpose onto the cotangents, so they hold the
    # derived backward convolutions too.
    alone = precision in (jax.lax.Precision.HIGHEST,
                          jax.lax.Precision.HIGH)
    if alone:
        x, w = jax.lax.optimization_barrier((x, w))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=dn, precision=precision,
        preferred_element_type=None if amp else (
            jnp.float32 if x.dtype != jnp.float64 else None))
    if alone:
        out = jax.lax.optimization_barrier(out)
    if not amp:
        out = out.astype(ins['Input'][0].dtype)
    return {'Output': [out]}


@register('depthwise_conv2d')
def depthwise_conv2d(ctx, ins, attrs):
    return conv2d(ctx, ins, attrs)


@register('conv2d_transpose')
def conv2d_transpose(ctx, ins, attrs):
    x = ins['Input'][0]
    w = ins['Filter'][0]  # [in_c, out_c/groups, kh, kw]
    strides = _pair(attrs.get('strides', [1, 1]))
    dilations = _pair(attrs.get('dilations', [1, 1]))
    groups = attrs.get('groups', 1) or 1
    p = _pair(attrs.get('paddings', [0, 0]))
    pad = [(p[0], p[0]), (p[1], p[1])] if len(p) == 2 else [
        (p[0], p[1]), (p[2], p[3])]
    # explicit gradient-of-conv formulation (same as conv3d_transpose):
    # lhs-dilate by stride, pad by (k_eff-1-p), spatially-flipped
    # kernel as OIHW with O=out_c.  (The previous jax.lax.conv_transpose
    # call mis-mapped both the channel slots — it only type-checked for
    # in_c == out_c — and the padding: for k=3 the parity test's p=1
    # coincided with k-1-p and masked it.)
    in_c = x.shape[1]
    out_c_g = w.shape[1]
    k_eff = [(w.shape[2] - 1) * dilations[0] + 1,
             (w.shape[3] - 1) * dilations[1] + 1]
    pad2 = [(k_eff[i] - 1 - pad[i][0], k_eff[i] - 1 - pad[i][1])
            for i in range(2)]
    wf = jnp.flip(w, axis=(2, 3))
    if groups > 1:
        # [in_c, out_c/g, kh, kw] -> [out_c, in_c/g, kh, kw] blockwise
        wf = wf.reshape(groups, in_c // groups, out_c_g,
                        w.shape[2], w.shape[3])
        wf = jnp.swapaxes(wf, 1, 2).reshape(
            groups * out_c_g, in_c // groups, w.shape[2], w.shape[3])
    else:
        wf = jnp.swapaxes(wf, 0, 1)
    out = jax.lax.conv_general_dilated(
        x, wf, window_strides=(1, 1), padding=pad2,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        feature_group_count=groups)
    return {'Output': [out]}


@register('pool2d')
def pool2d(ctx, ins, attrs):
    x = ins['X'][0]
    ptype = attrs.get('pooling_type', 'max')
    ksize = _pair(attrs.get('ksize', [2, 2]))
    strides = _pair(attrs.get('strides', [2, 2]))
    p = _pair(attrs.get('paddings', [0, 0]))
    data_format = attrs.get('data_format', 'NCHW')
    nchw = data_format in ('NCHW', 'AnyLayout')
    hw = (2, 3) if nchw else (1, 2)
    if attrs.get('global_pooling', False) or attrs.get('adaptive', False) \
            and list(attrs.get('ksize')) == [1, 1]:
        if ptype == 'max':
            out = jnp.max(x, axis=hw, keepdims=True)
        else:
            out = jnp.mean(x, axis=hw, keepdims=True)
        return {'Out': [out]}
    if attrs.get('adaptive', False):
        # arbitrary output grid: window i spans [floor(i*H/oh),
        # ceil((i+1)*H/oh)) (reference operators/pool_op.h AdaptStart/
        # AdaptEnd); oh/ow are static so the windows unroll at trace
        # time into oh*ow fused reductions
        oh, ow = ksize
        hdim, wdim = hw
        h_in, w_in = x.shape[hdim], x.shape[wdim]
        red = jnp.max if ptype == 'max' else jnp.mean
        rows = []
        for i in range(oh):
            cols = []
            hs = (i * h_in) // oh
            he = -(-((i + 1) * h_in) // oh)
            for j in range(ow):
                ws = (j * w_in) // ow
                we = -(-((j + 1) * w_in) // ow)
                win = jax.lax.slice_in_dim(
                    jax.lax.slice_in_dim(x, hs, he, axis=hdim),
                    ws, we, axis=wdim)
                cols.append(red(win, axis=(hdim, wdim)))
            rows.append(jnp.stack(cols, axis=-1))
        out = jnp.stack(rows, axis=-2)  # [..., oh, ow] on trailing dims
        if nchw:
            return {'Out': [out]}
        # NHWC: moved pooled dims to the end; restore channel-last
        return {'Out': [jnp.moveaxis(out, 1, -1)]}
    window = [1, 1, 1, 1]
    stride4 = [1, 1, 1, 1]
    pad4 = [(0, 0)] * 4
    for i, d in enumerate(hw):
        window[d] = ksize[i]
        stride4[d] = strides[i]
        pad4[d] = (p[i], p[i]) if len(p) == 2 else (p[2 * i], p[2 * i + 1])
    if attrs.get('padding_algorithm') == 'SAME':
        pad4 = 'SAME'
    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        if all(stride4[d] >= window[d] for d in hw):
            # NON-overlapping windows: shifted-slice rendering reads
            # every element exactly once and its vjp is fused
            # compare-masks instead of select_and_scatter (slow to
            # compile and run); overlapping pools stay on
            # reduce_window — the k^2-slice rendering re-reads the
            # input k^2 times and measured 20% slower on ResNet-50
            out = _max_pool_slices(x, window, stride4, pad4, hw, init)
        else:
            out = jax.lax.reduce_window(x, init, jax.lax.max, window,
                                        stride4, pad4)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, stride4, pad4)
        if attrs.get('exclusive', True) and pad4 != 'SAME' and \
                any(ph != (0, 0) for ph in (pad4 if pad4 != 'SAME' else [])):
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        stride4, pad4)
            out = s / cnt
        else:
            out = s / float(np.prod([window[d] for d in hw]))
    return {'Out': [out]}


def _max_pool_slices(x, window, stride4, pad4, hw, init):
    """Max pooling as an elementwise max over kh*kw strided slices of
    the (init-)padded input.  Identical values to reduce_window(max);
    the backward pass is the jnp.maximum chain's vjp, which XLA fuses
    (reduce_window's vjp is select_and_scatter).  Tie-routing differs
    from select_and_scatter's single winner: each pairwise maximum
    SPLITS the cotangent 0.5/0.5 on an exact tie, so tied positions
    share the gradient (weighted by their depth in the chain) — the
    same freedom the reference's cudnn pooling modes have."""
    d0, d1 = hw
    kh, kw = window[d0], window[d1]
    sh, sw = stride4[d0], stride4[d1]
    h_in, w_in = x.shape[d0], x.shape[d1]
    if pad4 == 'SAME':
        oh = -(-h_in // sh)
        ow = -(-w_in // sw)
        ph_t = max((oh - 1) * sh + kh - h_in, 0)
        pw_t = max((ow - 1) * sw + kw - w_in, 0)
        ph = (ph_t // 2, ph_t - ph_t // 2)
        pw = (pw_t // 2, pw_t - pw_t // 2)
    else:
        ph, pw = pad4[d0], pad4[d1]
        oh = (h_in + ph[0] + ph[1] - kh) // sh + 1
        ow = (w_in + pw[0] + pw[1] - kw) // sw + 1
    pads = [(0, 0)] * x.ndim
    pads[d0], pads[d1] = ph, pw
    xp = jnp.pad(x, pads, constant_values=init)
    out = None
    for i in range(kh):
        for j in range(kw):
            lim = [None] * x.ndim
            start = [0] * x.ndim
            stride = [1] * x.ndim
            start[d0], start[d1] = i, j
            lim[d0] = i + (oh - 1) * sh + 1
            lim[d1] = j + (ow - 1) * sw + 1
            stride[d0], stride[d1] = sh, sw
            sl = jax.lax.slice(
                xp, start,
                [xp.shape[a] if lim[a] is None else lim[a]
                 for a in range(x.ndim)], stride)
            out = sl if out is None else jnp.maximum(out, sl)
    return out


@register('batch_norm', no_grad_out_slots=('MeanOut', 'VarianceOut',
                                           'SavedMean', 'SavedVariance'))
def batch_norm(ctx, ins, attrs):
    """Reference operators/batch_norm_op.cc. In-place running-stat update:
    MeanOut/VarianceOut alias the Mean/Variance input vars in the program."""
    x = ins['X'][0]
    scale = ins['Scale'][0]
    bias = ins['Bias'][0]
    mean = ins['Mean'][0]
    var = ins['Variance'][0]
    eps = attrs.get('epsilon', 1e-5)
    momentum = attrs.get('momentum', 0.9)
    is_test = attrs.get('is_test', False)
    use_global = attrs.get('use_global_stats', False) or is_test
    layout = attrs.get('data_layout', 'NCHW')
    caxis = 1 if layout in ('NCHW', 'AnyLayout') else x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = tuple(x.shape[caxis] if i == caxis else 1
                   for i in range(x.ndim))

    xf = x.astype(jnp.float32)
    if use_global:
        m, v = mean, var
        saved_m, saved_v = mean, var
    else:
        # one-pass statistics: E[x] and E[x^2] reduce in a single fused
        # multi-output pass over x (jnp.mean + jnp.var would read the
        # conv output twice — measurable at 128x56x56x256); dtype picks
        # between the fused raw-sum form and a shift-conditioned form
        cnt = float(np.prod([x.shape[i] for i in red]))
        if x.dtype in (jnp.bfloat16, jnp.float16):
            # half-precision inputs: their own ~8-bit mantissa noise
            # dwarfs any f32 cancellation, and the raw-sum form lets
            # XLA fuse both reductions straight off the conv output
            # (the shifted form costs ~4.5% of ResNet-50 step time)
            shift = None
            s1 = jnp.sum(xf, axis=red)
            s2 = jnp.sum(xf * xf, axis=red)
            m = s1 / cnt
            v = jnp.maximum(s2 / cnt - m * m, 0.0)
        else:
            # f32 inputs: take the second moment about a BATCH-derived
            # per-channel shift (first batch element's mean — one tiny
            # extra reduce) so E[(x-s)^2] - E[x-s]^2 doesn't
            # catastrophically cancel when |mean| >> std; the identity
            # is exact for any shift, the shift only conditions it.
            # Batch-derived (not the running mean) so the very first
            # steps — running mean still 0 — are protected too.
            shift = jax.lax.stop_gradient(jnp.mean(
                jax.lax.slice_in_dim(xf, 0, 1, axis=red[0]),
                axis=red))
            xs = xf - shift.reshape(bshape)
            s1 = jnp.sum(xs, axis=red)
            s2 = jnp.sum(xs * xs, axis=red)
            d = s1 / cnt
            m = shift + d
            v = jnp.maximum(s2 / cnt - d * d, 0.0)
        saved_m, saved_v = m, v
    inv = jax.lax.rsqrt(v.astype(jnp.float32) + eps)
    y = (xf - m.reshape(bshape)) * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    if use_global:
        mean_out, var_out = mean, var
    else:
        n = np.prod([x.shape[i] for i in red])
        unbiased = v * (n / max(n - 1.0, 1.0))
        mean_out = momentum * mean + (1.0 - momentum) * m
        var_out = momentum * var + (1.0 - momentum) * unbiased
    return {'Y': [y.astype(x.dtype)],
            'MeanOut': [mean_out], 'VarianceOut': [var_out],
            'SavedMean': [saved_m], 'SavedVariance': [inv]}


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_core(x2, scale, bias, eps):
    y, _, _, _ = _ln_fwd_math(x2, scale, bias, eps)
    return y


def _ln_row_stats(x2):
    """Per-row (mean, var) in f32.  Half-precision inputs take the
    fused one-pass E[x^2]-m^2 form (their own mantissa noise dwarfs
    the cancellation); f32 inputs use the two-pass centered form —
    E[x^2]-m^2 catastrophically cancels when |mean| >> std (same
    policy as the batch_norm lowering)."""
    xf = x2.astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    if x2.dtype in (jnp.float32, jnp.float64):
        v = jnp.mean(jnp.square(xf - m), axis=1, keepdims=True)
    else:
        v = jnp.maximum(
            jnp.mean(xf * xf, axis=1, keepdims=True) - m * m, 0.0)
    return xf, m, v


def _ln_fwd_math(x2, scale, bias, eps):
    xf, m, v = _ln_row_stats(x2)
    rstd = jax.lax.rsqrt(v + eps)
    xhat = (xf - m) * rstd
    y = xhat
    if scale is not None:
        y = y * scale.astype(jnp.float32)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :]
    return y.astype(x2.dtype), xhat, m, v


def _ln_fwd_rule(x2, scale, bias, eps):
    y, xhat, m, v = _ln_fwd_math(x2, scale, bias, eps)
    # residuals: xhat in the INPUT dtype (bf16 under AMP) + per-row
    # rstd — the lean saved set the analytic backward needs.  Letting
    # jax.vjp differentiate mean/var instead keeps several full f32
    # activation tensors alive per LN: on BERT-large-context that was
    # +2.7 GB/layer of HBM traffic (pre-round reading).
    rstd = jax.lax.rsqrt(v + eps)
    return y, (xhat.astype(x2.dtype), rstd, scale, bias)


def _ln_bwd_rule(eps, res, g):
    xhat_s, rstd, scale, bias = res
    xdt = xhat_s.dtype  # xhat saved in the input dtype
    gf = g.astype(jnp.float32)
    xh = xhat_s.astype(jnp.float32)
    dbias = None if bias is None else jnp.sum(gf, axis=0).astype(
        bias.dtype)
    dscale = None if scale is None else jnp.sum(gf * xh, axis=0).astype(
        scale.dtype)
    gs = gf if scale is None else gf * scale.astype(jnp.float32)[None]
    dx = rstd * (gs - jnp.mean(gs, axis=1, keepdims=True) -
                 xh * jnp.mean(gs * xh, axis=1, keepdims=True))
    return dx.astype(xdt), dscale, dbias


_ln_core.defvjp(_ln_fwd_rule, _ln_bwd_rule)


@register('layer_norm', no_grad_out_slots=('Mean', 'Variance'))
def layer_norm(ctx, ins, attrs):
    x = ins['X'][0]
    eps = attrs.get('epsilon', 1e-5)
    begin = attrs.get('begin_norm_axis', 1)
    shape = x.shape
    lead = int(np.prod(shape[:begin]))
    x2 = x.reshape(lead, -1)
    scale = ins['Scale'][0].reshape(-1) if ins.get('Scale') else None
    bias = ins['Bias'][0].reshape(-1) if ins.get('Bias') else None
    y = _ln_core(x2, scale, bias, float(eps))
    # Mean/Variance side outputs (no-grad): recomputed outside the
    # custom-vjp core; XLA CSE merges them with the core's own stats
    _, m, v = _ln_row_stats(x2)
    return {'Y': [y.reshape(shape)],
            'Mean': [m.reshape(lead)], 'Variance': [v.reshape(lead)]}


@register('rms_norm')
def rms_norm(ctx, ins, attrs):
    """X [..., D], Scale [D] -> Y = x * rsqrt(mean(x^2) + epsilon) *
    scale over the last axis (Zhang & Sennrich 2019; no mean, no bias).
    Statistics and the product in float32, Y in X's dtype: the
    layer_norm policy, so a bf16 stream stays bf16 past the f32 gain."""
    x = ins['X'][0]
    xf = x if x.dtype == jnp.float64 else x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) +
        attrs.get('epsilon', 1e-5))
    gain = ins['Scale'][0].astype(xf.dtype)
    if attrs.get('unit_offset'):    # Scale holds the gain's offset from 1
        gain = 1.0 + gain
    return {'Y': [(y * gain).astype(x.dtype)]}


@register('eva_chunk_summary')
def eva_chunk_summary(ctx, ins, attrs):
    """K [B, T, H, D], V [B, T, H, Dv], Phi [H, D], Mu [H, D] -> KS
    [B, T / c, H, D], VS [B, T / c, H, Dv], c = attrs['chunk_size']:
    one summary key and value a chunk of c positions, pooled by a
    learned softmax.  For chunk n of head h, over its positions j:

        a_j  = softmax_j(K_j . Phi_h)           (no 1/sqrt(D) scale)
        KS_n = sum_j a_j K_j + Mu_h
        VS_n = sum_j a_j V_j

    EVA's control-variate estimate of a chunk's share of the softmax
    (Zheng et al. 2023) with the sampled random feature replaced by a
    learned one, as EvaByte ships it.  An attention call with the
    coarse mask (``fused_multihead_attention``'s ``coarse_window``)
    reads them as keys and values.  Float32 inside whatever K is, KS
    and VS in K's and V's dtype (the rms_norm policy); sums over
    products, no matmul: the op reads K and V once and is bytes-bound.
    The gradient is jax.vjp of this."""
    k, v = ins['K'][0], ins['V'][0]
    chunk = int(attrs['chunk_size'])
    b, t, h, d = k.shape
    if chunk < 1 or t % chunk:
        raise ValueError('eva_chunk_summary: %d positions are no whole '
                         'number of %d-position chunks' % (t, chunk))
    f32 = jnp.float64 if k.dtype == jnp.float64 else jnp.float32
    kc = k.astype(f32).reshape(b, t // chunk, chunk, h, d)
    vc = v.astype(f32).reshape(b, t // chunk, chunk, h, v.shape[3])
    a = jax.nn.softmax(
        jnp.sum(kc * ins['Phi'][0].astype(f32), axis=-1), axis=2)[..., None]
    ks = jnp.sum(a * kc, axis=2) + ins['Mu'][0].astype(f32)
    vs = jnp.sum(a * vc, axis=2)
    return {'KS': [ks.astype(k.dtype)], 'VS': [vs.astype(v.dtype)]}


@register('attention_merge', no_grad_out_slots=('SecondWeight',))
def attention_merge(ctx, ins, attrs):
    """Two attention results over two DISJOINT key sets, each with its
    rows' log-sum-exp, joined into the one softmax over both sets: X1,
    X2 [B, T, H, Dv], Lse1, Lse2 [B, T, H] ->

        Out = sigma(Lse1 - Lse2) X1 + sigma(Lse2 - Lse1) X2

    (the two weights are each set's share of the joint normaliser).
    A row whose second set is empty carries Lse2 = -inf: its weight is
    exactly 0, Out = X1 there, and no cotangent reaches X2 or the
    log-sum-exps through it (the sigmoid's slope at -inf is 0, not
    NaN).  Float32 inside, Out in X1's dtype.  SecondWeight [1] is
    the mean of the second set's weight over the rows where it is not
    empty (0 where every row's is), for a monitor gauge: nothing is
    computed for it where nothing fetches it."""
    x1, x2 = ins['X1'][0], ins['X2'][0]
    lse1, lse2 = ins['Lse1'][0], ins['Lse2'][0]
    f32 = jnp.float64 if x1.dtype == jnp.float64 else jnp.float32
    w2 = jax.nn.sigmoid(lse2.astype(f32) - lse1.astype(f32))
    x1f = x1.astype(f32)
    out = x1f + w2[..., None] * (x2.astype(f32) - x1f)
    has = jnp.isfinite(lse2)
    share = jnp.sum(jnp.where(has, w2, 0.0)) / \
        jnp.maximum(jnp.sum(has), 1).astype(f32)
    return {'Out': [out.astype(x1.dtype)],
            'SecondWeight': [jax.lax.stop_gradient(share).reshape(1)]}


@register('rotary_embedding')
def rotary_embedding(ctx, ins, attrs):
    """Q [B, T, H, D], K [B, T, Hk, D], Positions [B, T] int -> QOut,
    KOut: rotary position embedding (Su et al. 2021) with the
    ROTATE-HALF pairing of HF ``apply_rotary_pos_emb``: over the first
    ``rotary_dim`` features of each head (attrs; default D, the whole
    head), feature i pairs with i + rotary_dim/2, both turned by the
    angle pos * inv_freq[i]; the features past ``rotary_dim`` pass
    through.  The inverse frequencies are the input InvFreq
    [rotary_dim/2] where given (a scaled table: YaRN, NTK), else
    theta^(-2i/rotary_dim); cos and sin are multiplied by
    attrs['attention_factor'] (default 1.0: nothing).  Angles and the
    rotation in float32, outputs in the input dtype.  Hk need not be H
    (one rotary key for all heads: Hk = 1).

    attrs['interleaved'] reads the INPUT's pairs as (2i, 2i + 1)
    instead, as HF ``deepseek_v3``'s ``apply_rotary_pos_emb_interleave``
    does: the rotated features are first brought to [evens | odds] and
    then turned as above, and the output STAYS in that order (q and k
    are permuted alike, so their products do not change)."""
    q, k = ins['Q'][0], ins['K'][0]
    pos = ins['Positions'][0].astype(jnp.float32)
    width = q.shape[-1]
    rotary = int(attrs.get('rotary_dim', 0) or width)
    half = rotary // 2
    if ins.get('InvFreq'):
        inv_freq = ins['InvFreq'][0].astype(jnp.float32)
    else:
        inv_freq = 1.0 / (float(attrs.get('theta', 10000.0)) ** (
            jnp.arange(half, dtype=jnp.float32) / half))
    angle = pos[:, :, None, None] * inv_freq            # [B, T, 1, R/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    factor = float(attrs.get('attention_factor', 1.0))
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor

    interleaved = bool(attrs.get('interleaved', False))

    def rotate(x):
        xf = x.astype(jnp.float32)
        if interleaved:
            x1, x2 = xf[..., 0:rotary:2], xf[..., 1:rotary:2]
        else:
            x1, x2 = xf[..., :half], xf[..., half:rotary]
        parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if rotary < width:
            parts.append(xf[..., rotary:])
        return jnp.concatenate(parts, -1).astype(x.dtype)

    return {'QOut': [rotate(q)], 'KOut': [rotate(k)]}


@register('instance_norm', no_grad_out_slots=('SavedMean', 'SavedVariance'))
def instance_norm(ctx, ins, attrs):
    # stats in f32, output in the input dtype (the layer_norm /
    # batch_norm policy): a bf16 input must not promote the downstream
    # stream to f32 through the f32 Scale param, and bf16 variance is
    # too coarse
    x = ins['X'][0]
    eps = attrs.get('epsilon', 1e-5)
    red = tuple(range(2, x.ndim))
    xf = x if x.dtype == jnp.float64 else x.astype(jnp.float32)
    m = jnp.mean(xf, axis=red, keepdims=True)
    v = jnp.var(xf, axis=red, keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps)
    if 'Scale' in ins and ins['Scale']:
        c = x.shape[1]
        y = y * ins['Scale'][0].astype(xf.dtype).reshape(
            1, c, *([1] * (x.ndim - 2)))
        y = y + ins['Bias'][0].astype(xf.dtype).reshape(
            1, c, *([1] * (x.ndim - 2)))
    return {'Y': [y.astype(x.dtype)],
            'SavedMean': [m.reshape(x.shape[0], x.shape[1])],
            'SavedVariance': [v.reshape(x.shape[0], x.shape[1])]}


@register('group_norm', no_grad_out_slots=('Mean', 'Variance'))
def group_norm(ctx, ins, attrs):
    # stats in f32, output in the input dtype (see instance_norm)
    x = ins['X'][0]
    g = attrs['groups']
    eps = attrs.get('epsilon', 1e-5)
    n, c = x.shape[0], x.shape[1]
    xf = x if x.dtype == jnp.float64 else x.astype(jnp.float32)
    xs = xf.reshape(n, g, c // g, *x.shape[2:])
    red = tuple(range(2, xs.ndim))
    m = jnp.mean(xs, axis=red, keepdims=True)
    v = jnp.var(xs, axis=red, keepdims=True)
    y = ((xs - m) * jax.lax.rsqrt(v + eps)).reshape(x.shape)
    if 'Scale' in ins and ins['Scale']:
        y = y * ins['Scale'][0].astype(xf.dtype).reshape(
            1, c, *([1] * (x.ndim - 2)))
    if 'Bias' in ins and ins['Bias']:
        y = y + ins['Bias'][0].astype(xf.dtype).reshape(
            1, c, *([1] * (x.ndim - 2)))
    return {'Y': [y.astype(x.dtype)], 'Mean': [m.reshape(n, g)],
            'Variance': [v.reshape(n, g)]}


@register('dropout', no_grad_out_slots=('Mask',))
def dropout(ctx, ins, attrs):
    x = ins['X'][0]
    p = attrs.get('dropout_prob', 0.5)
    is_test = attrs.get('is_test', False)
    impl = attrs.get('dropout_implementation', 'downgrade_in_infer')
    if is_test:
        if impl == 'upscale_in_train':
            return {'Out': [x], 'Mask': [jnp.ones_like(x)]}
        return {'Out': [x * (1.0 - p)], 'Mask': [jnp.ones_like(x)]}
    # the counter hash the flash kernels draw from, keyed by (op
    # seed, step) over the element's position: no generator state, so
    # the vjp's replay (and the grad op's, under the parallel runner)
    # draws the same bits and XLA merges the two draws
    from ..fluid import monitor
    monitor.add('dropout/counter_draws', 1)
    registry.trace_sum('dropout/elements', x.size)
    keep = keep_hash.keep_nd(ctx.draw_seed(), x.shape, p)
    mask = keep.astype(x.dtype)
    if impl == 'upscale_in_train':
        out = jnp.where(keep, x / max(1.0 - p, 1e-8), jnp.zeros_like(x))
    else:
        out = x * mask
    return {'Out': [out], 'Mask': [mask]}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _swce_core(logits, lab, ax, ignore_index, loss_f32=False):
    """Hard-label softmax-CE along axis `ax` with an ANALYTIC backward.
    The jax.vjp-synthesized gradient keeps the full f32 log-prob tensor
    as a residual — at BERT's MLM head that is a 3.0 GB [B, T, V] f32
    buffer written+read per step.  The lean saved set is (logits as
    they arrived — usually bf16 under AMP, a buffer that is ALIVE
    anyway as the fc output — plus the per-row f32 logsumexp), and
    backward recomputes the softmax from them:
    dLogits = g_loss * (softmax - onehot) on valid rows, plus the
    softmax-jacobian term for the (normally unused, zero-cotangent)
    Softmax output.  Works on the NATIVE logits shape — flattening to
    [rows, classes] would pin the tensor to the 2-D matmul layout and
    buy a full layout-change copy.  Mirrors the reference's fused
    softmax_with_cross_entropy_grad kernel
    (operators/softmax_with_cross_entropy_op.cu).

    The forward keeps no f32 [.., classes] tensor either: the cast
    feeds only logsumexp's reductions (the compiler fuses it into
    them) and the dead-unless-read Softmax.  The label's logit is
    PICKED from the logits as they arrived and cast after (the same
    number bit for bit): a gather's operand is never fused into, so
    picking from the cast writes all of it out, 3.0 GB in the step
    that fetches the loss.

    `lab` has the logits rank with a size-1 dim at `ax`.

    loss_f32 keeps the Loss output in f32 even for low-precision
    logits (AMP black-list contract): the cast must happen HERE,
    before any dtype round-trip, or the 'f32' loss is a bf16-precision
    value stored in an f32 array."""
    y, _ = _swce_fwd_math(logits, lab, ax, ignore_index, loss_f32)
    return y


def _swce_fwd_math(logits, lab, ax, ignore_index, loss_f32=False):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=ax, keepdims=True)
    lab_safe = jnp.where(lab == ignore_index, 0, lab).astype(jnp.int32)
    # picked BEFORE the cast (_swce_core's docstring says why)
    picked = jnp.take_along_axis(logits, lab_safe, axis=ax).astype(
        jnp.float32) - lse
    valid = lab != ignore_index
    loss = jnp.where(valid, -picked, 0.0)
    softmax = jnp.exp(lf - lse)
    return ((softmax.astype(logits.dtype),
             loss if loss_f32 else loss.astype(logits.dtype)), lse)


def _swce_fwd_rule(logits, lab, ax, ignore_index, loss_f32=False):
    y, lse = _swce_fwd_math(logits, lab, ax, ignore_index, loss_f32)
    return y, (logits, lse, lab)


def _swce_bwd_rule(ax, ignore_index, loss_f32, res, cts):
    logits, lse, lab = res
    g_s, g_l = cts
    p = jnp.exp(logits.astype(jnp.float32) - lse)
    gs = g_s.astype(jnp.float32)
    gl = g_l.astype(jnp.float32)
    lab_safe = jnp.where(lab == ignore_index, 0, lab).astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, p.shape, ax)
    onehot = (iota == lab_safe).astype(jnp.float32)
    d = jnp.where(lab != ignore_index, gl, 0.0) * (p - onehot)
    # Softmax-output term: normally a zero cotangent (the residual is
    # only consumed by the grad op), and XLA folds the constant away
    d = d + p * (gs - jnp.sum(gs * p, axis=ax, keepdims=True))
    return d.astype(logits.dtype), None


_swce_core.defvjp(_swce_fwd_rule, _swce_bwd_rule)


@register('softmax_with_cross_entropy')
def softmax_with_cross_entropy(ctx, ins, attrs):
    logits = ins['Logits'][0]
    label = ins['Label'][0]
    axis = attrs.get('axis', -1)
    soft_label = attrs.get('soft_label', False)
    ignore_index = attrs.get('ignore_index', -100)
    # AMP black-list parity (ADVICE r4): the reference's black rule
    # yields an f32 Loss even from low-precision logits — a tiny
    # per-row tensor, so reported/fetched losses keep f32 precision
    # while the activation-sized Softmax stays in the input dtype
    loss_up = (attrs.get('__amp_black__') or
               attrs.get('__amp_black_out__')) and \
        logits.dtype in (jnp.bfloat16, jnp.float16)
    if not soft_label:
        ax = axis % logits.ndim
        lab = label
        if lab.ndim != logits.ndim:
            lab = jnp.expand_dims(lab, ax)
        softmax, loss = _swce_core(logits, lab, ax, int(ignore_index),
                                   bool(loss_up))
        return {'Softmax': [softmax], 'Loss': [loss]}
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    softmax = jnp.exp(logp)
    loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    return {'Softmax': [softmax.astype(logits.dtype)],
            'Loss': [loss if loss_up else loss.astype(logits.dtype)]}


@register('cross_entropy')
def cross_entropy(ctx, ins, attrs):
    x = ins['X'][0]  # probabilities
    label = ins['Label'][0]
    soft_label = attrs.get('soft_label', False)
    ignore_index = attrs.get('ignore_index', -100)
    if soft_label:
        logx = jnp.log(jnp.clip(x, 1e-20, None))
        loss = -jnp.sum(label * logx, axis=-1, keepdims=True)
    else:
        lab = label
        if lab.ndim == x.ndim and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, -1)
        lab_safe = jnp.where(lab == ignore_index, 0, lab)
        # pick, THEN the logarithm: one value a row, not every class's
        picked = jnp.take_along_axis(
            x, jnp.expand_dims(lab_safe, -1).astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.clip(picked, 1e-20, None))
        loss = jnp.where(jnp.expand_dims(lab, -1) == ignore_index,
                         jnp.zeros_like(loss), loss)
    return {'Y': [loss]}


@register('cross_entropy2', no_grad_out_slots=('XShape', 'MatchX'))
def cross_entropy2(ctx, ins, attrs):
    out = cross_entropy(ctx, ins, attrs)
    return {'Y': out['Y'], 'MatchX': [out['Y'][0]], 'XShape': [out['Y'][0]]}


@register('sigmoid_cross_entropy_with_logits')
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x = ins['X'][0]
    label = ins['Label'][0]
    ignore_index = attrs.get('ignore_index', -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = (label != ignore_index)
    loss = jnp.where(mask, loss, jnp.zeros_like(loss))
    if attrs.get('normalize', False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1.0)
    return {'Out': [loss]}


@register('square_error_cost')
def square_error_cost(ctx, ins, attrs):
    d = ins['X'][0] - ins['Y'][0]
    return {'Out': [d * d]}


@register('huber_loss', no_grad_out_slots=('Residual',))
def huber_loss(ctx, ins, attrs):
    x = ins['X'][0]
    y = ins['Y'][0]
    delta = attrs.get('delta', 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r,
                     delta * (a - 0.5 * delta))
    return {'Out': [loss], 'Residual': [r]}


@register('smooth_l1_loss', no_grad_out_slots=('Diff',))
def smooth_l1_loss(ctx, ins, attrs):
    x = ins['X'][0]
    y = ins['Y'][0]
    sigma = attrs.get('sigma', 1.0)
    s2 = sigma * sigma
    d = x - y
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    return {'Out': [jnp.sum(loss, axis=tuple(range(1, x.ndim)),
                            keepdims=True)],
            'Diff': [d]}


@register('log_loss')
def log_loss(ctx, ins, attrs):
    p = ins['Predicted'][0]
    l = ins['Labels'][0]
    eps = attrs.get('epsilon', 1e-4)
    out = -l * jnp.log(p + eps) - (1 - l) * jnp.log(1 - p + eps)
    return {'Loss': [out]}


@register('kldiv_loss')
def kldiv_loss(ctx, ins, attrs):
    x = ins['X'][0]
    target = ins['Target'][0]
    out = target * (jnp.log(jnp.clip(target, 1e-20, None)) - x)
    out = jnp.where(target > 0, out, jnp.zeros_like(out))
    red = attrs.get('reduction', 'mean')
    if red == 'mean':
        out = jnp.mean(out)
    elif red == 'sum':
        out = jnp.sum(out)
    elif red == 'batchmean':
        out = jnp.sum(out) / x.shape[0]
    return {'Loss': [out]}


@register('mse_loss')
def mse_loss(ctx, ins, attrs):
    d = ins['X'][0] - ins['Y'][0]
    return {'Out': [jnp.mean(d * d)]}


# ---------------------------------------------------------------------------
# metrics (reference operators/metrics/)
# ---------------------------------------------------------------------------


@register('accuracy', no_grad_out_slots=('Accuracy', 'Correct', 'Total'))
def accuracy(ctx, ins, attrs):
    idx = ins['Indices'][0]  # [N, k] from top_k
    label = ins['Label'][0]  # [N, 1]
    if label.ndim == 1:
        label = label[:, None]
    correct_k = jnp.any(idx == label, axis=1)
    num_correct = jnp.sum(correct_k.astype(jnp.float32))
    total = idx.shape[0]
    return {'Accuracy': [num_correct / total],
            'Correct': [num_correct.astype(jnp.int32)],
            'Total': [jnp.asarray(total, jnp.int32)]}


@register('auc', no_grad_out_slots=('AUC', 'StatPosOut', 'StatNegOut'))
def auc(ctx, ins, attrs):
    """Streaming AUC via threshold-bucketed confusion counts
    (reference operators/metrics/auc_op.h)."""
    preds = ins['Predict'][0]  # [N, 2]
    label = ins['Label'][0].reshape(-1)
    stat_pos = ins['StatPos'][0]
    stat_neg = ins['StatNeg'][0]
    num_thresholds = attrs.get('num_thresholds', 4095)
    p = preds[:, 1]
    bucket = jnp.clip((p * num_thresholds).astype(jnp.int32), 0,
                      num_thresholds)
    pos = (label > 0).astype(stat_pos.dtype)
    stat_pos = stat_pos.at[bucket].add(pos)
    stat_neg = stat_neg.at[bucket].add(1 - pos)
    # trapezoid area over descending thresholds
    tp = jnp.cumsum(stat_pos[::-1])
    fp = jnp.cumsum(stat_neg[::-1])
    tot_pos = tp[-1]
    tot_neg = fp[-1]
    tpr = tp / jnp.maximum(tot_pos, 1)
    fpr = fp / jnp.maximum(tot_neg, 1)
    area = jnp.sum((fpr[1:] - fpr[:-1]) * (tpr[1:] + tpr[:-1]) * 0.5)
    return {'AUC': [area], 'StatPosOut': [stat_pos],
            'StatNegOut': [stat_neg]}


# ---------------------------------------------------------------------------
# misc nn
# ---------------------------------------------------------------------------


@register('label_smooth')
def label_smooth(ctx, ins, attrs):
    x = ins['X'][0]
    eps = attrs.get('epsilon', 0.1)
    k = x.shape[-1]
    if 'PriorDist' in ins and ins['PriorDist']:
        prior = ins['PriorDist'][0]
        return {'Out': [(1 - eps) * x + eps * prior]}
    return {'Out': [(1 - eps) * x + eps / k]}


@register('interp_nearest')
@register('nearest_interp')
def nearest_interp(ctx, ins, attrs):
    x = ins['X'][0]
    n, c, h, w = x.shape
    oh = attrs.get('out_h', h)
    ow = attrs.get('out_w', w)
    scale = attrs.get('scale', 0)
    if scale:
        oh, ow = int(h * scale), int(w * scale)
    out = jax.image.resize(x, (n, c, oh, ow), method='nearest')
    return {'Out': [out]}


@register('bilinear_interp')
def bilinear_interp(ctx, ins, attrs):
    x = ins['X'][0]
    n, c, h, w = x.shape
    oh = attrs.get('out_h', h)
    ow = attrs.get('out_w', w)
    scale = attrs.get('scale', 0)
    if scale:
        oh, ow = int(h * scale), int(w * scale)
    out = jax.image.resize(x, (n, c, oh, ow), method='bilinear')
    return {'Out': [out]}


@register('grid_sampler')
def grid_sampler(ctx, ins, attrs):
    """Bilinear sampling at normalized grid coords
    (operators/grid_sampler_op.cc; align_corners semantics):
    X [N,C,H,W], Grid [N,Hg,Wg,2] in [-1,1] -> Out [N,C,Hg,Wg]."""
    x = ins['X'][0]
    grid = ins['Grid'][0]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * 0.5 * (w - 1)   # [N,Hg,Wg]
    gy = (grid[..., 1] + 1.0) * 0.5 * (h - 1)
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(img, yy, xx):
        # img [C,H,W]; out-of-bound neighbors contribute ZERO
        # (reference GetGridPointValue), not the border pixel
        inb = ((yy >= 0) & (yy <= h - 1) &
               (xx >= 0) & (xx <= w - 1))
        yyc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xxc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        return img[:, yyc, xxc] * inb[None].astype(img.dtype)

    def one(img, x0i, y0i, wxi, wyi):
        v00 = gather(img, y0i, x0i)
        v01 = gather(img, y0i, x0i + 1)
        v10 = gather(img, y0i + 1, x0i)
        v11 = gather(img, y0i + 1, x0i + 1)
        return (v00 * (1 - wyi) * (1 - wxi) + v01 * (1 - wyi) * wxi +
                v10 * wyi * (1 - wxi) + v11 * wyi * wxi)

    out = jax.vmap(one)(x, x0.astype(jnp.int32), y0.astype(jnp.int32),
                        wx[:, None], wy[:, None])
    return {'Output': [out], 'Out': [out]}


@register('temporal_shift')
def temporal_shift(ctx, ins, attrs):
    x = ins['X'][0]
    seg = attrs['seg_num']
    ratio = attrs.get('shift_ratio', 0.25)
    nt, c, h, w = x.shape
    n = nt // seg
    xr = x.reshape(n, seg, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    pre = jnp.concatenate([jnp.zeros_like(xr[:, :1, :c1]),
                           xr[:, :-1, :c1]], axis=1)
    post = jnp.concatenate([xr[:, 1:, c1:c2],
                            jnp.zeros_like(xr[:, :1, c1:c2])], axis=1)
    rest = xr[:, :, c2:]
    return {'Out': [jnp.concatenate([pre, post, rest],
                                    axis=2).reshape(nt, c, h, w)]}


# ---------------------------------------------------------------------------
# Parity batch: lrn / indexed pooling / unpool / conv variants
# ---------------------------------------------------------------------------


@register('lrn', no_grad_out_slots=('MidOut',))
def lrn(ctx, ins, attrs):
    """Reference operators/lrn_op.cc: cross-channel local response norm,
    out = x / (k + alpha * sum_{local n channels} x^2) ^ beta."""
    x = ins['X'][0]  # NCHW
    n = attrs.get('n', 5)
    k = attrs.get('k', 1.0)
    alpha = attrs.get('alpha', 1e-4)
    beta = attrs.get('beta', 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {'Out': [x * jnp.power(mid, -beta)], 'MidOut': [mid]}


def _pool_patches(x, ksize, strides, paddings, neg):
    """[N,C,H,W] -> (patches [N,C,OH,OW,kh*kw], flat index [kh*kw] maps).
    Static unroll over the small kernel window."""
    kh, kw = ksize
    sh, sw = strides
    ph, pw = paddings
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=neg)
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    cols, idx = [], []
    for i in range(kh):
        for j in range(kw):
            sl = jax.lax.slice(
                xp, (0, 0, i, j),
                (n, c, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1),
                (1, 1, sh, sw))
            cols.append(sl)
            # global (unpadded) flat h*w index of this tap per output pos
            hh = jnp.arange(oh) * sh + i - ph
            ww = jnp.arange(ow) * sw + j - pw
            idx.append(hh[:, None] * w + ww[None, :])
    return jnp.stack(cols, -1), jnp.stack(idx, -1)  # [...,K],[OH,OW,K]


@register('max_pool2d_with_index', no_grad_out_slots=('Mask',))
def max_pool2d_with_index(ctx, ins, attrs):
    """Reference operators/pool_with_index_op.cc: max pool + argmax
    (flat h*w index) used by unpool."""
    x = ins['X'][0]
    ksize = attrs.get('ksize', [2, 2])
    strides = attrs.get('strides', ksize)
    pads = attrs.get('paddings', [0, 0])
    neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
    patches, fidx = _pool_patches(x, ksize, strides, pads, neg)
    am = jnp.argmax(patches, axis=-1)
    out = jnp.max(patches, axis=-1)
    mask = jnp.take_along_axis(
        jnp.broadcast_to(fidx, am.shape + (fidx.shape[-1],)),
        am[..., None], axis=-1)[..., 0]
    return {'Out': [out], 'Mask': [mask.astype(jnp.int32)]}


@register('max_pool3d_with_index', no_grad_out_slots=('Mask',))
def max_pool3d_with_index(ctx, ins, attrs):
    """3-D variant: unroll over the (small, static) kd*kh*kw window."""
    x = ins['X'][0]  # NCDHW
    kd, kh, kw = attrs.get('ksize', [2, 2, 2])
    strides = attrs.get('strides', [kd, kh, kw])
    pd, ph, pw = attrs.get('paddings', [0, 0, 0])
    sd, sh, sw = strides
    n, c, d, h, w = x.shape
    neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pd, pd), (ph, ph), (pw, pw)),
                 constant_values=neg)
    od = (d + 2 * pd - kd) // sd + 1
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    cols, idx = [], []
    for a in range(kd):
        for i in range(kh):
            for j in range(kw):
                sl = jax.lax.slice(
                    xp, (0, 0, a, i, j),
                    (n, c, a + (od - 1) * sd + 1, i + (oh - 1) * sh + 1,
                     j + (ow - 1) * sw + 1), (1, 1, sd, sh, sw))
                cols.append(sl)
                dd = jnp.arange(od) * sd + a - pd
                hh = jnp.arange(oh) * sh + i - ph
                ww = jnp.arange(ow) * sw + j - pw
                idx.append(dd[:, None, None] * (h * w) +
                           hh[None, :, None] * w + ww[None, None, :])
    patches = jnp.stack(cols, -1)
    fidx = jnp.stack(idx, -1)
    am = jnp.argmax(patches, axis=-1)
    out = jnp.max(patches, axis=-1)
    mask = jnp.take_along_axis(
        jnp.broadcast_to(fidx, am.shape + (fidx.shape[-1],)),
        am[..., None], axis=-1)[..., 0]
    return {'Out': [out], 'Mask': [mask.astype(jnp.int32)]}


@register('unpool')
def unpool(ctx, ins, attrs):
    """Reference operators/unpool_op.cc: scatter pooled values back to
    the argmax positions (indices from max_pool2d_with_index)."""
    x = ins['X'][0]           # [N,C,h,w]
    indices = ins['Indices'][0]
    if attrs.get('unpooled_size'):
        oh, ow = attrs['unpooled_size']
    else:  # reference formula: (in-1)*stride - 2*pad + ksize
        kh, kw = attrs.get('ksize', [2, 2])
        sh, sw = attrs.get('strides', [kh, kw])
        ph, pw = attrs.get('paddings', [0, 0])
        oh = (x.shape[2] - 1) * sh - 2 * ph + kh
        ow = (x.shape[3] - 1) * sw - 2 * pw + kw
    n, c = x.shape[:2]
    vals = x.reshape(n, c, -1)
    idx = indices.reshape(n, c, -1).astype(jnp.int32)
    out = jnp.zeros((n, c, oh * ow), x.dtype)
    out = out.at[jnp.arange(n)[:, None, None],
                 jnp.arange(c)[None, :, None], idx].set(vals)
    return {'Out': [out.reshape(n, c, oh, ow)]}


@register('depthwise_conv2d_transpose')
def depthwise_conv2d_transpose(ctx, ins, attrs):
    """Grouped transpose conv via lhs-dilated conv_general_dilated
    (conv_transpose lacks a groups parameter)."""
    x = ins['Input'][0]
    w = ins['Filter'][0]  # [in_c, 1, kh, kw], groups == in_c
    strides = _pair(attrs.get('strides', [1, 1]))
    dilations = _pair(attrs.get('dilations', [1, 1]))
    p = _pair(attrs.get('paddings', [0, 0]))
    groups = attrs.get('groups', x.shape[1]) or x.shape[1]
    kh = (w.shape[2] - 1) * dilations[0] + 1
    kw = (w.shape[3] - 1) * dilations[1] + 1
    pad = [(kh - 1 - p[0], kh - 1 - p[0]), (kw - 1 - p[1], kw - 1 - p[1])]
    # flip spatially and swap io: [in_c,1,kh,kw] -> OIHW with O=in_c
    wf = jnp.flip(w, axis=(2, 3))
    out = jax.lax.conv_general_dilated(
        x, wf, window_strides=(1, 1), padding=pad,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        feature_group_count=groups)
    return {'Output': [out]}


@register('sync_batch_norm', no_grad_out_slots=('MeanOut', 'VarianceOut',
                                                'SavedMean',
                                                'SavedVariance'))
def sync_batch_norm(ctx, ins, attrs):
    """Reference operators/sync_batch_norm_op.cu (ncclAllReduce of
    partial sums).  TPU-native: psum the per-device moments over the
    data-parallel mesh axis when tracing inside shard_map; identical to
    batch_norm outside one."""
    if attrs.get('is_test', False) or attrs.get('use_global_stats', False):
        return batch_norm(ctx, ins, attrs)   # running stats, no psum
    axis = attrs.get('mesh_axis', 'dp')
    try:
        jax.lax.axis_index(axis)  # raises NameError outside shard_map
    except NameError:
        return batch_norm(ctx, ins, attrs)
    x = ins['X'][0]
    layout = attrs.get('data_layout', 'NCHW')
    caxis = 1 if layout in ('NCHW', 'AnyLayout') else x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != caxis)
    xf = x.astype(jnp.float32)
    n_local = np.prod([x.shape[i] for i in red])
    s1 = jax.lax.psum(jnp.sum(xf, axis=red), axis)
    s2 = jax.lax.psum(jnp.sum(jnp.square(xf), axis=red), axis)
    n = n_local * jax.lax.psum(1, axis)
    m = s1 / n
    v = s2 / n - jnp.square(m)
    eps = attrs.get('epsilon', 1e-5)
    momentum = attrs.get('momentum', 0.9)
    bshape = tuple(x.shape[caxis] if i == caxis else 1
                   for i in range(x.ndim))
    inv = jax.lax.rsqrt(v + eps)
    y = (xf - m.reshape(bshape)) * inv.reshape(bshape)
    y = y * ins['Scale'][0].reshape(bshape) + ins['Bias'][0].reshape(bshape)
    unbiased = v * (n / jnp.maximum(n - 1.0, 1.0))
    mean_out = momentum * ins['Mean'][0] + (1 - momentum) * m
    var_out = momentum * ins['Variance'][0] + (1 - momentum) * unbiased
    return {'Y': [y.astype(x.dtype)], 'MeanOut': [mean_out],
            'VarianceOut': [var_out], 'SavedMean': [m],
            'SavedVariance': [inv]}


@register('row_conv')
def row_conv(ctx, ins, attrs):
    """Reference operators/row_conv_op.cc: lookahead convolution
    (DeepSpeech2) — out[b,t] = sum_{j<ctx} x[b,t+j] * w[j]."""
    x = ins['X'][0]           # [B,T,D]
    w = ins['Filter'][0]      # [future_context, D]
    fc = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, fc - 1), (0, 0)))
    out = sum(xp[:, j:j + t] * w[j] for j in range(fc))
    return {'Out': [out]}


@register('short_conv')
def short_conv(ctx, ins, attrs):
    """A causal depthwise filter over time, row_conv's mirror image:
    X [B, T, C], Filter [C, L] -> Out[b, t] = sum_{j<L} Filter[:, j] *
    z[b, t - (L - 1) + j] with z zero before a sequence's start, so
    Filter[:, L - 1] weighs the token itself, nothing later enters and
    nothing crosses from one sequence of the batch into the next (a
    ``Conv1d(C, C, L, groups=C, padding=L - 1)`` cut to its first T
    outputs), plus Bias [C] where given (a Mamba layer's filter).  The
    two multiplicative gates of a gated short
    convolution (Liquid's LFM2 ``conv`` operator) fuse in where given:
    z = X * GateIn before the filter, Out = GateOut * (filter of z +
    Bias) after it, each [B, T, C]; without them z = X.

    L shifted multiply-adds in float32 whatever X is, Out in X's dtype
    (the rms_norm policy: a bf16 stream stays bf16 past the f32
    filter).  Bytes-bound: every operand is read once and Out written
    once in one fusion; the gradient (jax.vjp of this) is the same
    pattern shifted the other way plus the filter's and the bias's sums
    over B and T."""
    from ..fluid import monitor
    monitor.add('short_conv/calls', 1)
    x = ins['X'][0]
    w = ins['Filter'][0]
    taps = w.shape[1]
    t = x.shape[1]
    f32 = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    z = x.astype(f32)
    if ins.get('GateIn'):
        z = z * ins['GateIn'][0].astype(f32)
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(zp[:, j:j + t] * w[:, j].astype(f32) for j in range(taps))
    if ins.get('Bias'):
        out = out + ins['Bias'][0].astype(f32)
    if ins.get('GateOut'):
        out = out * ins['GateOut'][0].astype(f32)
    return {'Out': [out.astype(x.dtype)]}


@register('conv_shift')
def conv_shift(ctx, ins, attrs):
    """Reference operators/conv_shift_op.cc: circular convolution
    out[b,i] = sum_j x[b, (i + j - m//2) % n] * y[b, j]."""
    x = ins['X'][0]  # [B,N]
    y = ins['Y'][0]  # [B,M], M odd, M <= N
    m = y.shape[1]
    half = m // 2
    out = sum(jnp.roll(x, half - j, axis=1) * y[:, j:j + 1]
              for j in range(m))
    return {'Out': [out]}
