"""Solar Open 2 (upstage, ``model_type: solar_open2``): a routed decoder
whose layers differ by OPERATOR and that carries no position encoding.
``BASE`` is Solar-Open2-250B as published
(https://huggingface.co/upstage/Solar-Open2-250B ``config.json``): 48
layers of hidden 4096; layers 0, 4, 8, ... 44 (``gqa_layers``) mix time
with gated grouped-query softmax attention WITHOUT rotary (64 query
heads over 8 K/V heads of 128, the context times an elementwise
sigmoid gate), the other 36 with the GATED DELTA RULE under a
per-channel decay (64 heads of 128 with a [128, 128] state each, a
4-tap causal filter and SiLU on q, k and v, L2-normalised q and k, a
low-rank decay and a low-rank output gate); every layer has 320 routed
experts of width 1280 (top-8 of sigmoid scores plus a choice bias, the
gates the plain scores over their sum) beside one shared expert; the
head is not tied.

Built from the fluid layer surface like the rest of the zoo:
``layers.kda_attention`` (the recurrence, chunked; ``ops/kda_ops.py``),
``layers.short_conv`` WITHOUT gates for the three filters, the
``fused_multihead_attention`` op with grouped K/V heads,
``layers.rms_norm`` over a head, ``layers.moe(capacity_factor=None,
score_func='sigmoid', score_bias=..., bias_update_rate=...,
experts_held=...)`` for ONE CHIP'S SHARE of the routed experts.  The
head counts of a config are the heads BUILT: a chip that holds a share
of each layer's heads (8 of the 64 query heads with their one K/V
head, 8 of the 64 delta-rule heads) builds its projections at that
width, and what leaves the output projection is its part of the
operator's result.  The log decays stay float32 under bf16 AMP
(``mixed_precision.keep_float32`` on the add that meets ``dt_bias``).
What ``config.json`` does not settle is listed in
``models/reference/solar_open2.py``, the plain reference the tests hold
this to.
"""

import math

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.contrib.mixed_precision import keep_float32
from paddle_tpu.fluid.initializer import Initializer, Normal, Uniform

from . import gpt as _gpt

# softplus(dt_bias) at startup: log-uniform time steps in about
# (0.001, 0.1), softplus(x) ~ exp(x) that far under 0
DT_BIAS_RANGE = (math.log(1e-3), math.log(1e-1))
QK_NORM_EPS = 1e-6


class LogUniform(Initializer):
    """log of Uniform(low, high): ``A_log``'s startup draw, A in (1,
    16)."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def __call__(self, var, block):
        Uniform(self.low, self.high)(var, block)
        return block.append_op('log', inputs={'X': var.name},
                               outputs={'Out': var.name})


class SolarOpen2Config(object):
    def __init__(self, vocab_size=196608, hidden=4096, layers=48,
                 first_layer=0, gqa_layers=None, heads=64, kv_heads=8,
                 head_dim=128, kda_heads=64, kda_head_dim=128,
                 conv_taps=4, neg_eigval=True, expert_hidden=1280,
                 shared_experts=1, experts=320, top_k=8, routed_scale=1.0,
                 renormalize=True, experts_held=None, rms_eps=1e-5,
                 bias_update_rate=0.001, bias_init_std=0.0,
                 init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers                # how many are run
        # the layers run are first_layer .. first_layer + layers - 1 of
        # the MODEL, each with the operator its own index gives it
        self.first_layer = first_layer
        self.gqa_layers = tuple(range(0, 48, 4)) if gqa_layers is None \
            else tuple(gqa_layers)
        # the heads BUILT: the model's, or the share of them held here
        self.heads = heads                  # num_attention_heads
        self.kv_heads = kv_heads            # num_key_value_heads
        self.head_dim = head_dim
        self.kda_heads = kda_heads          # linear_attn_config.num_heads
        self.kda_head_dim = kda_head_dim    # linear_attn_config.head_dim
        self.conv_taps = conv_taps          # short_conv_kernel_size
        self.neg_eigval = neg_eigval        # kda_allow_neg_eigval
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        self.shared_experts = shared_experts
        self.experts = experts              # n_routed_experts
        self.top_k = top_k                  # num_experts_per_tok
        self.routed_scale = routed_scale    # routed_scaling_factor
        self.renormalize = renormalize      # norm_topk_prob
        # (first, count) of the routed experts this chip holds; None:
        # all of them
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        # gamma of b += gamma * sign(mean load - load); 0: a bias that
        # stays as the startup program drew it
        self.bias_update_rate = bias_update_rate
        self.bias_init_std = bias_init_std
        self.init_std = init_std

    def layer_indices(self):
        """The model's own indices of the layers run."""
        return range(self.first_layer, self.first_layer + self.layers)


BASE = SolarOpen2Config()
# one period (softmax, delta, delta, delta), tiny widths; 8 experts top-3
TINY = SolarOpen2Config(
    vocab_size=97, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16,
    kda_heads=3, kda_head_dim=16, expert_hidden=32, experts=8, top_k=3,
    bias_init_std=0.05)


def _normal(cfg):
    return fluid.ParamAttr(initializer=Normal(0., cfg.init_std))


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_normal(cfg))


def _filtered(u, width, cfg):
    """silu(conv4(u W)): a projection, its causal depthwise filter (the
    last tap on the token itself), SiLU."""
    # PyTorch's Conv1d default at a fan-in of `taps`
    bound = cfg.conv_taps ** -0.5
    return layers.silu(layers.short_conv(
        _linear(u, width, cfg), cfg.conv_taps,
        param_attr=fluid.ParamAttr(initializer=Uniform(-bound, bound))))


def _low_rank(u, width, cfg):
    """(u W_down) W_up through a bottleneck of one head's width."""
    return _linear(_linear(u, cfg.kda_head_dim, cfg), width, cfg)


def kda_inputs(u, cfg):
    """Steps 1 to 4 of a delta-rule layer on the normed block input
    ``u`` -> (q, k, v, a, beta) as ``layers.kda_attention`` takes
    them; ``a``, the log of the decay, float32 whatever ``u`` is."""
    h, d = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (layers.reshape(_filtered(u, h * d, cfg), [0, 0, h, d])
               for _ in range(3))
    q = layers.scale(layers.l2_normalize(q, axis=-1, epsilon=QK_NORM_EPS),
                     scale=d ** -0.5)
    k = layers.l2_normalize(k, axis=-1, epsilon=QK_NORM_EPS)
    rate = _low_rank(u, h * d, cfg)
    a_log = layers.create_parameter(
        [h], 'float32', default_initializer=LogUniform(1.0, 16.0))
    dt_bias = layers.create_parameter(
        [h * d], 'float32', default_initializer=Uniform(*DT_BIAS_RANGE))
    rate = layers.softplus(keep_float32(
        layers.elementwise_add(rate, dt_bias)))
    a = layers.elementwise_mul(
        layers.reshape(rate, [0, 0, h, d]),
        layers.scale(layers.exp(a_log), scale=-1.0), axis=2)
    beta = layers.sigmoid(_linear(u, h, cfg))
    if cfg.neg_eigval:
        beta = layers.scale(beta, scale=2.0)
    return q, k, v, a, beta


def kda_operator(u, cfg):
    """The delta-rule layers' operator: the recurrence, an RMSNorm over
    each head (one gain for all), the low-rank sigmoid gate, W_o."""
    h, d = cfg.kda_heads, cfg.kda_head_dim
    o = layers.kda_attention(*kda_inputs(u, cfg))
    o = layers.rms_norm(o, epsilon=cfg.rms_eps)
    gate = layers.sigmoid(layers.reshape(_low_rank(u, h * d, cfg),
                                         [0, 0, h, d]))
    return _linear(layers.reshape(layers.elementwise_mul(o, gate),
                                  [0, 0, h * d]), cfg.hidden, cfg)


def gqa_operator(u, cfg):
    """The ``gqa_layers``' operator: grouped K/V heads, NO position
    encoding, the context times an elementwise sigmoid gate."""
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    q = layers.reshape(_linear(u, h * d, cfg), [0, 0, h, d])
    k = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    v = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    ctx = layers.reshape(layers.flash_attention(q, k, v, causal=True),
                         [0, 0, h * d])
    gate = layers.sigmoid(_linear(u, h * d, cfg))
    return _linear(layers.elementwise_mul(ctx, gate), cfg.hidden, cfg)


def gated_mlp(w, width, cfg):
    """down(silu(gate w) * up w)."""
    gate, up = _linear(w, width, cfg), _linear(w, width, cfg)
    return _linear(layers.elementwise_mul(layers.silu(gate), up),
                   cfg.hidden, cfg)


def decoder_block(x, i, cfg):
    """Layer ``i`` of the MODEL (its operator follows ``i``, wherever
    the run starts)."""
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    op = gqa_operator(u, cfg) if i in cfg.gqa_layers \
        else kda_operator(u, cfg)
    x = layers.elementwise_add(x, op)
    w = layers.rms_norm(x, epsilon=cfg.rms_eps)
    routed, _ = layers.moe(
        w, num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize, gate_scale=cfg.routed_scale,
        experts_held=cfg.experts_held, aux_weight=0.0,
        score_func='sigmoid',
        score_bias=fluid.ParamAttr(
            initializer=Normal(0., cfg.bias_init_std)),
        bias_update_rate=cfg.bias_update_rate)
    x = layers.elementwise_add(
        x, gated_mlp(w, cfg.shared_experts * cfg.expert_hidden, cfg))
    return layers.elementwise_add(x, routed)


def solar_decoder(ids, cfg):
    """-> hidden states after the final norm [B, T, hidden]."""
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(initializer=Normal(0., cfg.init_std)))
    for i in cfg.layer_indices():
        x = decoder_block(x, i, cfg)
    return layers.rms_norm(x, epsilon=cfg.rms_eps)


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``labels`` ([B, seq_len]
    ints; labels are the ids shifted left, -1 where there is no next
    token; no positions: the model has no position encoding) -> (feeds,
    logits, loss): the next-token cross-entropy over the held
    vocabulary rows, averaged over every position but the last.  No
    auxiliary loss: the choice bias is the balancing."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    logits = _linear(solar_decoder(ids, cfg), cfg.vocab_size, cfg)
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(layers.mean(token_loss),
                        scale=seq_len / (seq_len - 1.0))
    return {'ids': ids, 'labels': labels}, logits, loss


def synthetic_batch(cfg, batch, seq_len, rng):
    feed = _gpt.synthetic_batch(cfg, batch, seq_len, rng)
    return {'ids': feed['ids'], 'labels': feed['labels']}
