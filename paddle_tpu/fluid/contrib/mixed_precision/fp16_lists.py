"""AMP op lists. Reference:
python/paddle/fluid/contrib/mixed_precision/fp16_lists.py.

On TPU the low-precision dtype is bfloat16 (MXU-native), so the white list
marks MXU ops; loss-scaling still applies when float16 is forced.
"""

white_list = {
    'conv2d', 'depthwise_conv2d', 'conv2d_transpose', 'matmul',
    'matmul_v2', 'mul', 'bmm',
    # the grouped expert matmuls of a dropless MoE layer
    'moe_experts',
}

black_list = {
    'exp', 'square', 'log', 'mean', 'sum', 'cos_sim',
    'softmax', 'softmax_with_cross_entropy', 'sigmoid_cross_entropy_'
    'with_logits', 'cross_entropy', 'cross_entropy2',
    # the router: f32 logits, softmax over all experts, top-k and the
    # two auxiliary losses (its lowering computes in f32 by itself)
    'moe_route',
}

gray_list = {
    'elementwise_add', 'elementwise_sub', 'elementwise_mul',
    'elementwise_div', 'relu', 'gelu', 'tanh', 'sigmoid', 'pool2d',
    'batch_norm', 'layer_norm', 'dropout', 'reshape2', 'transpose2',
    'concat', 'split', 'slice', 'scale',
    # f32 inside, output in the input's dtype, like layer_norm
    'rms_norm', 'rotary_embedding', 'moe_dispatch', 'moe_combine',
    'short_conv', 'eva_chunk_summary',
    # bf16 q, k, v beside float32 log decays; the solve and the state
    # float32 inside, the output in v's dtype
    'kda_attention',
    # bf16 x, B, C beside float32 steps, decays and skip; the state
    # and every sum float32 inside, the output in x's dtype
    'selective_scan',
    # the same for Mamba-2's chunked form: bf16 x, B, C beside float32
    # steps, decays and skip; the products' operands in x's dtype, the
    # state and every sum float32
    'ssd_scan',
    # a bf16 stream beside float32 maps: r, the projection, the three
    # maps and the Sinkhorn loop float32 inside, U and XOut in the
    # stream's and the operator's dtype
    'hyper_connection_pre', 'hyper_connection_post',
}


class AutoMixedPrecisionLists(object):
    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        self.gray_list = set(gray_list)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
            self.black_list -= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
            self.white_list -= set(custom_black_list)
        # a custom placement overrides gray membership too (the
        # reference's _update_list does the same removal): without
        # this, _mark_amp_ops's gray check shadows an op the user
        # explicitly black/white-listed
        self.gray_list -= set(custom_white_list or ())
        self.gray_list -= set(custom_black_list or ())
        # remembered so _mark_amp_ops can honor an explicit placement
        # even for ops it would normally exempt from harmonization
        self.custom_placed = set(custom_white_list or ()) | \
            set(custom_black_list or ())
