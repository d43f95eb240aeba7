"""LFM2 (Liquid AI, ``model_type: lfm2_moe``): a routed decoder whose
layers differ by OPERATOR.  ``BASE`` is LFM2-8B-A1B as published
(https://huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``): 24
layers of hidden 2048; 18 of them mix time with a GATED SHORT
CONVOLUTION (a causal depthwise filter of three taps between two
multiplicative gates), 6 with grouped-query causal attention (32 query
heads over 8 K/V heads of 64, an RMSNorm over each head of q and k
before the rotary embedding); layers 0 and 1 a dense gated MLP of width
7168, the 22 after them 32 routed experts of width 1792 (top-4 of
sigmoid scores plus a choice bias, the gates the plain scores divided
by their sum + 1e-6), no shared expert; the head is the embedding.

Built from the fluid layer surface like the rest of the zoo:
``layers.short_conv`` with both gates fused in, the
``fused_multihead_attention`` op with grouped K/V heads,
``layers.rms_norm`` over a head, ``layers.rotary_embedding``,
``layers.moe(capacity_factor=None, score_func='sigmoid', score_bias=...,
bias_update_rate=..., renorm_eps=1e-6, experts_held=...)`` for ONE
CHIP'S SHARE of the routed experts, a ``matmul`` against the embedding
table's held rows as the head.  What ``config.json`` does not settle is
listed in ``models/reference/lfm2.py``, the plain reference the tests
hold this to.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal

from . import gpt as _gpt

CONV, ATTENTION = 'conv', 'full_attention'
# the published pattern: attention at 2, 6, 10, 14, 18, 21
LAYER_TYPES = [ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV
               for i in range(24)]
EMBEDDING = 'lfm2.embed_tokens'


class Lfm2Config(object):
    def __init__(self, vocab_size=65536, hidden=2048, layers=24,
                 heads=32, kv_heads=8, layer_types=None, first_layer=0,
                 dense_layers=2, dense_hidden=7168, expert_hidden=1792,
                 experts=32, top_k=4, routed_scale=1.0, renormalize=True,
                 renorm_eps=1e-6, conv_taps=3, experts_held=None,
                 rms_eps=1e-5, rope_theta=1000000.0,
                 bias_update_rate=0.001, bias_init_std=0.0,
                 init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers                # how many are run
        self.heads = heads
        self.kv_heads = kv_heads
        self.head_dim = hidden // heads
        # the MODEL's pattern and its leading dense layers; the layers
        # run are first_layer .. first_layer + layers - 1 of it, each
        # with the operator and the MLP its own index gives it
        self.layer_types = list(layer_types or LAYER_TYPES)
        self.first_layer = first_layer
        self.dense_layers = dense_layers    # num_dense_layers
        assert first_layer + layers <= len(self.layer_types)
        self.dense_hidden = dense_hidden    # intermediate_size
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        self.experts = experts              # num_experts
        self.top_k = top_k                  # num_experts_per_tok
        self.routed_scale = routed_scale    # routed_scaling_factor
        self.renormalize = renormalize      # norm_topk_prob
        self.renorm_eps = renorm_eps
        self.conv_taps = conv_taps          # conv_L_cache
        # (first, count) of the routed experts this chip holds; None:
        # all of them
        self.experts_held = experts_held
        self.rms_eps = rms_eps              # norm_eps
        self.rope_theta = rope_theta
        # gamma of b += gamma * sign(mean load - load); 0: a bias that
        # stays as the startup program drew it
        self.bias_update_rate = bias_update_rate
        # the choice bias's startup values: Normal(0, this); 0.0 is
        # the published buffer's zeros
        self.bias_init_std = bias_init_std
        self.init_std = init_std

    def layer_indices(self):
        """The model's own indices of the layers run."""
        return range(self.first_layer, self.first_layer + self.layers)


BASE = Lfm2Config()
# the model's layers 1 to 5 (dense conv, then attn, conv, conv, conv
# sparse), tiny widths; 8 experts top-3
TINY = Lfm2Config(
    vocab_size=97, hidden=64, layers=5, heads=4, kv_heads=2,
    first_layer=1, dense_hidden=96, expert_hidden=32, experts=8,
    top_k=3, bias_init_std=0.05)


def _normal(cfg):
    return fluid.ParamAttr(initializer=Normal(0., cfg.init_std))


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_normal(cfg))


def _attend(q, k, v):
    """q [B, T, H, d], k and v [B, T, Hkv, d] -> [B, T, H, d]: causal
    attention, query head i over K/V head i // (H / Hkv)
    (``layers.flash_attention``)."""
    return layers.flash_attention(q, k, v, causal=True)


def short_conv_operator(u, cfg):
    """The ``conv`` layers' operator on the normed block input ``u``:
    [B | C | X] = u W_in, the causal filter of B * X, times C, W_out."""
    gate_in, gate_out, x = layers.split(
        _linear(u, 3 * cfg.hidden, cfg), 3, dim=2)
    mixed = layers.short_conv(x, cfg.conv_taps, gate_in=gate_in,
                              gate_out=gate_out, param_attr=_normal(cfg))
    return _linear(mixed, cfg.hidden, cfg)


def attention_operator(u, pos_ids, cfg):
    """The ``full_attention`` layers' operator: grouped K/V heads, an
    RMSNorm over each head of q and k (one 64-wide gain for q, one for
    k), THEN the rotary embedding."""
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    q = layers.reshape(_linear(u, h * d, cfg), [0, 0, h, d])
    k = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    v = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    q = layers.rms_norm(q, epsilon=cfg.rms_eps)
    k = layers.rms_norm(k, epsilon=cfg.rms_eps)
    q, k = layers.rotary_embedding(q, k, pos_ids, theta=cfg.rope_theta)
    ctx = _attend(q, k, v)
    return _linear(layers.reshape(ctx, [0, 0, h * d]), cfg.hidden, cfg)


def gated_mlp(w, width, cfg):
    """down(silu(gate w) * up w)."""
    gate, up = _linear(w, width, cfg), _linear(w, width, cfg)
    return _linear(layers.elementwise_mul(layers.silu(gate), up),
                   cfg.hidden, cfg)


def decoder_block(x, pos_ids, i, cfg):
    """Layer ``i`` of the MODEL (its operator and MLP kind follow
    ``i``, wherever the run starts)."""
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    if cfg.layer_types[i] == CONV:
        op = short_conv_operator(u, cfg)
    else:
        op = attention_operator(u, pos_ids, cfg)
    x = layers.elementwise_add(x, op)
    w = layers.rms_norm(x, epsilon=cfg.rms_eps)
    if i < cfg.dense_layers:
        return layers.elementwise_add(
            x, gated_mlp(w, cfg.dense_hidden, cfg))
    routed, _ = layers.moe(
        w, num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize, gate_scale=cfg.routed_scale,
        experts_held=cfg.experts_held, aux_weight=0.0,
        score_func='sigmoid', renorm_eps=cfg.renorm_eps,
        score_bias=fluid.ParamAttr(
            initializer=Normal(0., cfg.bias_init_std)),
        bias_update_rate=cfg.bias_update_rate)
    return layers.elementwise_add(x, routed)


def lfm2_decoder(ids, pos_ids, cfg):
    """-> (hidden states after the final norm [B, T, hidden], the
    embedding table)."""
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(name=EMBEDDING,
                                   initializer=Normal(0., cfg.init_std)))
    table = fluid.default_main_program().global_block().var(EMBEDDING)
    for i in cfg.layer_indices():
        x = decoder_block(x, pos_ids, i, cfg)
    return layers.rms_norm(x, epsilon=cfg.rms_eps), table


def build_pretrain(cfg=None, seq_len=8192, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``pos_ids``, ``labels``
    ([B, seq_len] ints; labels are the ids shifted left, -1 where there
    is no next token: ``lm_batch``) -> (feeds, logits, loss): the
    next-token cross-entropy over the held vocabulary rows, averaged
    over every position but the last.  The head is the embedding
    table: its gradient is the lookup's scatter-add plus the head's
    product.  No auxiliary loss: the choice bias is the balancing."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    h, table = lfm2_decoder(ids, pos, cfg)
    logits = layers.matmul(h, table, transpose_y=True)      # tied
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(layers.mean(token_loss),
                        scale=seq_len / (seq_len - 1.0))
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    return feeds, logits, loss


lm_batch = _gpt.lm_batch
synthetic_batch = _gpt.synthetic_batch
