"""Kimi Linear (moonshotai, ``model_type: kimi_linear``): a routed
decoder that mixes time with the GATED DELTA RULE under a per-channel
decay (Kimi Delta Attention, arXiv:2510.26692) in three layers of four
and with LATENT attention WITHOUT any position encoding in the fourth.
``BASE`` is Kimi-Linear-48B-A3B-Instruct as published
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
``config.json``): 27 layers of hidden 2304, numbered from 1 in
``linear_attn_config``; layers 4, 8, ... 24 and 27 (``full_attn_layers``)
expand keys and values from one 512-wide latent a token (32 heads,
192-wide queries and keys over 128-wide values, the last 64 key
features ONE vector shared by all heads, ``mla_use_nope``: nothing is
rotated), the other 20 (``kda_layers``) run the delta rule at 32 heads
of 128 with ``beta = sigmoid`` (no factor 2); layer 1 has a dense gated
MLP of width 9216, every later layer 256 routed experts of width 1024
(top-8 of sigmoid scores plus a choice bias, the gates the plain scores
renormalised and scaled by 2.446) beside one shared expert; the head is
not tied.

No operator of its own: a delta-rule layer is
``models.solar_open2.kda_operator`` with ``neg_eigval`` off, a latent
layer ``models.moonlight.attention`` handed no positions, the MLPs
``models.moonlight.gated_mlp`` / ``sparse_mlp`` (``layers.moe`` for ONE
CHIP'S SHARE of the routed experts).  Every decoder block but the LAST
one run is a ``fluid.backward.recompute_guard`` group: a train step
keeps the [B, T, hidden] stream between two blocks and computes a
block's inside again for its gradient (the delta rule's forward scan
with it; its own chunked backward then walks the chunks in reverse).
The last block's gradient starts where its forward ends, so its inside
is alive at the step's peak grouped or not, and a group would only run
it twice.  The program has
no ``pos_ids`` feed and no ``rotary_embedding`` op: the delta-rule
layers carry order.  What ``config.json`` does not settle is listed in
``models/reference/kimi_linear.py``, the plain reference the tests
hold this to.
"""

import contextlib

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.initializer import Normal

from . import moonlight as _moonlight
from . import solar_open2 as _solar

FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


class KimiLinearConfig(object):
    """What ``solar_open2.kda_operator``, ``moonlight.attention`` and
    ``moonlight.sparse_mlp`` read, under their names."""

    def __init__(self, vocab_size=163840, hidden=2304, layers=27,
                 first_layer=1, full_attn_layers=FULL_ATTN_LAYERS,
                 heads=32, qk_nope=128, qk_rope=64, v_dim=128,
                 kv_rank=512, kda_heads=32, kda_head_dim=128,
                 conv_taps=4, dense_layers=1, dense_hidden=9216,
                 expert_hidden=1024, shared_experts=1, experts=256,
                 top_k=8, routed_scale=2.446, renormalize=True,
                 experts_held=None, rms_eps=1e-5, bias_update_rate=0.001,
                 bias_init_std=0.0, init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers                # how many are run
        # the layers run are first_layer .. first_layer + layers - 1 of
        # the MODEL, numbered from 1 as ``linear_attn_config`` does
        self.first_layer = first_layer
        self.full_attn_layers = tuple(full_attn_layers)
        self.heads = heads                  # num_attention_heads
        self.qk_nope = qk_nope              # qk_nope_head_dim
        self.qk_rope = qk_rope              # qk_rope_head_dim: unrotated
        self.v_dim = v_dim                  # v_head_dim
        self.kv_rank = kv_rank              # kv_lora_rank
        self.q_rank = None                  # q_lora_rank null
        self.yarn = None                    # rope_scaling null
        self.kda_heads = kda_heads          # linear_attn_config.num_heads
        self.kda_head_dim = kda_head_dim    # linear_attn_config.head_dim
        self.conv_taps = conv_taps          # short_conv_kernel_size
        self.neg_eigval = False             # beta = sigmoid, no 2
        self.dense_layers = dense_layers    # first_k_dense_replace
        self.dense_hidden = dense_hidden    # intermediate_size
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        self.shared_experts = shared_experts
        self.experts = experts              # num_experts
        self.top_k = top_k                  # num_experts_per_token
        self.routed_scale = routed_scale    # routed_scaling_factor
        self.renormalize = renormalize      # moe_renormalize
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
        """The model's own (1-based) indices of the layers run."""
        return range(self.first_layer, self.first_layer + self.layers)


BASE = KimiLinearConfig()
# the model's layers 1 to 5 (dense delta, delta, delta, latent, delta),
# tiny widths; 8 experts top-3
TINY = KimiLinearConfig(
    vocab_size=97, hidden=64, layers=5, heads=4, qk_nope=16, qk_rope=8,
    v_dim=12, kv_rank=24, kda_heads=3, kda_head_dim=16, dense_hidden=96,
    expert_hidden=32, experts=8, top_k=3, bias_init_std=0.05)


def decoder_block(x, i, cfg):
    """Layer ``i`` of the MODEL, numbered from 1 (its operator and its
    MLP follow ``i``, wherever the run starts)."""
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    op = _moonlight.attention(u, None, cfg) \
        if i in cfg.full_attn_layers else _solar.kda_operator(u, cfg)
    x = layers.elementwise_add(x, op)
    w = layers.rms_norm(x, epsilon=cfg.rms_eps)
    if i <= cfg.dense_layers:
        return layers.elementwise_add(
            x, _moonlight.gated_mlp(w, cfg.dense_hidden, cfg))
    return _moonlight.sparse_mlp(x, w, cfg)


def build_pretrain(cfg=None, seq_len=8192, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``labels`` ([B, seq_len]
    ints; labels are the ids shifted left, -1 where there is no next
    token; no positions: no position enters the model) -> (feeds,
    logits, loss): the next-token cross-entropy over the held
    vocabulary rows, averaged over every position but the last.  No
    auxiliary loss: the choice bias is the balancing.  Every block
    but the last is a ``recompute_guard`` group."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    x = None
    last = cfg.layer_indices()[-1]
    for i in cfg.layer_indices():
        with recompute_guard() if i < last else contextlib.nullcontext():
            if x is None:
                x = layers.embedding(
                    ids, size=[cfg.vocab_size, cfg.hidden],
                    param_attr=fluid.ParamAttr(
                        initializer=Normal(0., cfg.init_std)))
            x = decoder_block(x, i, cfg)
    h = layers.rms_norm(x, epsilon=cfg.rms_eps)
    logits = _moonlight._linear(h, cfg.vocab_size, cfg)     # not tied
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(layers.mean(token_loss),
                        scale=seq_len / (seq_len - 1.0))
    return {'ids': ids, 'labels': labels}, logits, loss


synthetic_batch = _solar.synthetic_batch
