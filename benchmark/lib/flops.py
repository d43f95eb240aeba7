"""Operations and bytes from shapes: the arithmetic every utilization
and roofline number of the benchmark rests on.  Nothing here asks XLA
(its cost analysis cannot see inside a Mosaic custom call and counts
fusion-internal bytes; ``bench.py`` ``_perf_fields`` printed 18.05%
where these functions give ~24%).

Conventions: one multiply-add is 2 FLOPs; a training step is forward +
backward = 3 x forward (each matmul has two gradient matmuls of its
own size); recomputed operations are not counted in a model's FLOPs;
elementwise, normalisation and softmax operations are left out (under
2% at these widths).
"""

TRAIN_OVER_FORWARD = 3


def transformer_encoder_forward_flops_per_token(
        layers, hidden, intermediate, seq_len, head_outputs):
    """Forward FLOPs for one token of a BERT-style encoder with a dense
    output head of ``head_outputs`` columns over every position.

    Per layer: fused QKV projection 2*h*3h, attention output 2*h*h,
    feed-forward 2*h*i + 2*i*h, and attention scores + context
    2*s*h + 2*s*h (every token against all ``seq_len`` keys, all
    heads together).  Embedding lookups are gathers, not FLOPs."""
    per_layer = (2 * hidden * 3 * hidden + 2 * hidden * hidden +
                 4 * hidden * intermediate + 4 * seq_len * hidden)
    return layers * per_layer + 2 * hidden * head_outputs


def conv2d_forward_flops(out_h, out_w, in_ch, out_ch, kernel):
    return 2 * out_h * out_w * in_ch * out_ch * kernel * kernel


def resnet_forward_flops_per_image(stage_blocks, image_hw, classes):
    """Forward FLOPs of a bottleneck ResNet (He et al. 2015, Table 1)
    with the stride on each stage's first 3x3 convolution, as
    PaddlePaddle ``models`` ``image_classification/resnet.py`` and this
    repo's zoo build it.  Convolutions and the classifier only."""
    hw = (image_hw + 1) // 2                      # 7x7 stem, stride 2
    total = conv2d_forward_flops(hw, hw, 3, 64, 7)
    hw = (hw + 1) // 2                            # 3x3 max pool, stride 2
    in_ch = 64
    for stage, count in enumerate(stage_blocks):
        mid = 64 * 2 ** stage
        out_ch = 4 * mid
        for block in range(count):
            stride = 2 if block == 0 and stage != 0 else 1
            out_hw = hw // stride
            total += conv2d_forward_flops(hw, hw, in_ch, mid, 1)
            total += conv2d_forward_flops(out_hw, out_hw, mid, mid, 3)
            total += conv2d_forward_flops(out_hw, out_hw, mid, out_ch, 1)
            if in_ch != out_ch or stride != 1:    # projection shortcut
                total += conv2d_forward_flops(out_hw, out_hw, in_ch,
                                              out_ch, 1)
            in_ch, hw = out_ch, out_hw
    return total + 2 * in_ch * classes


def flash_attention_train_cost(batch, heads, seq_len, head_dim,
                               itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for one layer's forward
    plus backward call at these shapes.

    Forward: QK^T and PV, 2*s*s*d each.  Backward: dV, dP, dQ, dK and
    the recomputation of the scores the algorithm does not store, five
    matmuls of the same size.  Bytes: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv; the
    per-row statistics (f32, s values per head) are under 1%."""
    matmul = 2 * batch * heads * seq_len * seq_len * head_dim
    tensor = batch * heads * seq_len * head_dim * itemsize
    return (2 + 5) * matmul, (4 + 8) * tensor


def roofline_seconds(flops, nbytes, peak_flops, peak_bytes):
    """The least time the chip could take and which side bounds it."""
    compute, memory = flops / peak_flops, nbytes / peak_bytes
    return max(compute, memory), \
        'compute' if compute >= memory else 'memory'
