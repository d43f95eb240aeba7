"""Moonlight (moonshotai, ``model_type: deepseek_v3``): a routed
decoder with latent attention and a bias-corrected sigmoid router.
``BASE`` is Moonlight-16B-A3B as published
(https://huggingface.co/moonshotai/Moonlight-16B-A3B ``config.json``):
27 layers of hidden 2048, 16 heads; keys and values expanded from one
512-wide latent a token, 192-wide queries and keys (128 without
position, 64 rotary) over 128-wide values, the rotary key ONE 64-wide
vector shared by all heads; layer 0 a dense gated MLP of width 11264,
every later layer 64 routed experts of width 1408 (top-6 of sigmoid
scores plus a choice bias, the gates the plain scores renormalised and
scaled by 2.446) beside two shared experts.

Built from the fluid layer surface like the rest of the zoo:
``layers.rotary_embedding(interleaved=True)`` with a one-head key, the
``fused_multihead_attention`` op with V narrower than Q and K,
``layers.moe(capacity_factor=None, score_func='sigmoid',
score_bias=..., bias_update_rate=..., experts_held=...)`` for ONE
CHIP'S SHARE of the routed experts, an untied head over the held rows
of the vocabulary.  What ``config.json`` does not settle is listed in
``models/reference/moonlight.py``, the plain reference the tests hold
this to.

The key handed to the attention op is MATERIALISED at [B, T, 16, 192]:
the shared rotary key is repeated over the heads and joined to each
head's 128 position-free features (``expand`` + ``concat``), and its
gradient is the sum over the heads that ``expand``'s gradient takes.
Reading it through the kernels' index maps instead would save that
copy and sum; what they cost on the chip is in PERF.md (section 6,
PR 32).

``attention`` is the zoo's ONE latent-attention helper
(``models/xing4.py`` calls it too): a config with ``q_rank`` gives the
queries a normed latent of their own (``q = rms_norm(u Wqa) Wqb``),
one with ``yarn`` (a ``rope_scaling`` dict) rotates by YaRN's inverse
frequencies and scales the scores by ``softmax_scale(cfg)``, which is
where the softmax scale comes from: 1 / sqrt(qk width) times
(0.1 mscale_all_dim ln factor + 1)^2.  ``layers.flash_attention``
scales by 1 / sqrt(qk width) itself, so q is multiplied by the rest
before the call.  Moonlight publishes neither key and builds the ops
it always built.  Handed no positions (``models/kimi_linear.py``:
``mla_use_nope``) it rotates nothing and the shared key slice enters
the product as projected.
"""

import math

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal

from . import gpt as _gpt


class MoonlightConfig(object):
    def __init__(self, vocab_size=163840, hidden=2048, layers=27,
                 heads=16, qk_nope=128, qk_rope=64, v_dim=128,
                 kv_rank=512, dense_layers=1, dense_hidden=11264,
                 expert_hidden=1408, shared_experts=2, experts=64,
                 top_k=6, routed_scale=2.446, renormalize=True,
                 experts_held=None, rms_eps=1e-5, rope_theta=50000.0,
                 bias_update_rate=0.001, bias_init_std=0.0,
                 init_std=0.02, q_rank=None, yarn=None):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.qk_nope = qk_nope              # qk_nope_head_dim
        self.qk_rope = qk_rope              # qk_rope_head_dim
        self.v_dim = v_dim                  # v_head_dim
        self.kv_rank = kv_rank              # kv_lora_rank
        self.q_rank = q_rank                # q_lora_rank; None: no latent
        # rope_scaling of type yarn (factor,
        # original_max_position_embeddings, beta_fast, beta_slow,
        # mscale, mscale_all_dim); None: theta's own frequencies
        self.yarn = yarn
        self.dense_layers = dense_layers    # first_k_dense_replace
        self.dense_hidden = dense_hidden    # intermediate_size
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
        self.rope_theta = rope_theta
        # gamma of b += gamma * sign(mean load - load); 0: a bias that
        # stays as the startup program drew it
        self.bias_update_rate = bias_update_rate
        # the choice bias's startup values: Normal(0, this); 0.0 is
        # the published buffer's zeros
        self.bias_init_std = bias_init_std
        self.init_std = init_std


BASE = MoonlightConfig()
# the dense layer and two sparse ones, tiny widths; 8 experts top-3
TINY = MoonlightConfig(
    vocab_size=97, hidden=64, layers=3, heads=4, qk_nope=16, qk_rope=8,
    v_dim=12, kv_rank=24, dense_hidden=96, expert_hidden=32,
    shared_experts=2, experts=8, top_k=3, bias_init_std=0.05)


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(
                         initializer=Normal(0., cfg.init_std)))


def _attend(q, k, v):
    """q, k [B, T, H, dqk], v [B, T, H, dv] -> [B, T, H, dv]: causal
    attention, scores over 1/sqrt(dqk) (``layers.flash_attention``)."""
    return layers.flash_attention(q, k, v, causal=True)


def yarn_mscale(factor, mscale):
    """HF ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_correction(cfg):
    """``yarn_mscale(factor, mscale_all_dim)`` squared under YaRN (HF
    ``DeepseekV3Attention.scaling``), else exactly 1."""
    yarn = cfg.yarn
    if yarn and yarn.get('mscale_all_dim'):
        return yarn_mscale(yarn['factor'], yarn['mscale_all_dim']) ** 2
    return 1.0


def softmax_scale(cfg):
    """What the scores are multiplied by before the softmax."""
    return softmax_correction(cfg) * (cfg.qk_nope + cfg.qk_rope) ** -0.5


def _rotate(q_rope, k_rope, pos_ids, cfg):
    yarn = cfg.yarn
    if not yarn:
        return layers.rotary_embedding(q_rope, k_rope, pos_ids,
                                       theta=cfg.rope_theta,
                                       interleaved=True)
    from .laguna import yarn_inv_freq
    table = layers.assign(yarn_inv_freq(
        cfg.qk_rope, rope_theta=cfg.rope_theta, **yarn))
    # cos and sin times mscale / mscale_all_dim (HF
    # ``_compute_yarn_parameters``'s attention factor)
    factor = yarn_mscale(yarn['factor'], yarn.get('mscale', 1.0)) / \
        yarn_mscale(yarn['factor'], yarn.get('mscale_all_dim') or 0.0)
    return layers.rotary_embedding(
        q_rope, k_rope, pos_ids, theta=cfg.rope_theta, inv_freq=table,
        attention_factor=factor, interleaved=True)


def attention(u, pos_ids, cfg):
    """One layer's latent attention on the normed block input ``u``.
    ``pos_ids`` None: NO position encoding (``mla_use_nope``): the
    queries stay as projected and the 64-wide shared key slice stays
    in the 192-wide product unrotated."""
    h, nope, rope, dv = cfg.heads, cfg.qk_nope, cfg.qk_rope, cfg.v_dim
    q_in = u
    if cfg.q_rank:
        q_in = layers.rms_norm(_linear(u, cfg.q_rank, cfg),
                               epsilon=cfg.rms_eps)
    q = layers.reshape(_linear(q_in, h * (nope + rope), cfg),
                       [0, 0, h, nope + rope])
    if pos_ids is not None:
        q_nope, q_rope = layers.split(q, [nope, rope], dim=3)
    # the latent and the shared (rotary) key, one projection
    latent, k_rope = layers.split(_linear(u, cfg.kv_rank + rope, cfg),
                                  [cfg.kv_rank, rope], dim=2)
    latent = layers.rms_norm(latent, epsilon=cfg.rms_eps)
    kv = layers.reshape(_linear(latent, h * (nope + dv), cfg),
                        [0, 0, h, nope + dv])
    k_nope, v = layers.split(kv, [nope, dv], dim=3)
    k_rope = layers.reshape(k_rope, [0, 0, 1, rope])
    if pos_ids is not None:
        q_rope, k_rope = _rotate(q_rope, k_rope, pos_ids, cfg)
        q = layers.concat([q_nope, q_rope], axis=3)
    if softmax_correction(cfg) != 1.0:
        # the op scales by 1 / sqrt(qk width) itself
        q = layers.scale(q, scale=softmax_correction(cfg))
    k = layers.concat([k_nope, layers.expand(k_rope, [1, 1, h, 1])],
                      axis=3)
    ctx = _attend(q, k, v)
    return _linear(layers.reshape(ctx, [0, 0, h * dv]), cfg.hidden, cfg)


def gated_mlp(w, width, cfg):
    """down(silu(gate w) * up w)."""
    gate, up = _linear(w, width, cfg), _linear(w, width, cfg)
    return _linear(layers.elementwise_mul(layers.silu(gate), up),
                   cfg.hidden, cfg)


def sparse_mlp(x, w, cfg):
    """x + shared(w) + routed(w) of a sparse layer on the normed ``w``:
    ONE CHIP'S SHARE of the routed experts under the bias-picked
    sigmoid router, the shared experts one gated MLP."""
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


def decoder_block(x, pos_ids, i, cfg):
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    x = layers.elementwise_add(x, attention(u, pos_ids, cfg))
    w = layers.rms_norm(x, epsilon=cfg.rms_eps)
    if i < cfg.dense_layers:
        return layers.elementwise_add(
            x, gated_mlp(w, cfg.dense_hidden, cfg))
    return sparse_mlp(x, w, cfg)


def moonlight_decoder(ids, pos_ids, cfg):
    """-> hidden states after the final norm [B, T, hidden]."""
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(initializer=Normal(0., cfg.init_std)))
    for i in range(cfg.layers):
        x = decoder_block(x, pos_ids, i, cfg)
    return layers.rms_norm(x, epsilon=cfg.rms_eps)


def build_pretrain(cfg=None, seq_len=8192, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``pos_ids``, ``labels``
    ([B, seq_len] ints; labels are the ids shifted left, -1 where there
    is no next token: ``lm_batch``) -> (feeds, logits, loss): the
    next-token cross-entropy over the held vocabulary rows, averaged
    over every position but the last.  No auxiliary loss: the
    published config sets ``seq_aux`` and carries no coefficient."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    h = moonlight_decoder(ids, pos, cfg)
    logits = _linear(h, cfg.vocab_size, cfg)        # head not tied
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
