"""Laguna (poolside, ``model_type: laguna``): a routed decoder whose
layers differ.  ``BASE`` is Laguna-S-2.1 as published
(https://huggingface.co/poolside/Laguna-S-2.1 ``config.json``): 48
layers of hidden 3072; sliding-window (512) and full causal attention
mixed 3:1, each kind with its own number of query heads (72 / 48) over
8 K/V heads of 128, its own rotary table (plain theta 10000 over the
whole head / YaRN over half of it), and a per-head sigmoid gate on the
context; layer 0 a dense gated MLP, every later layer 256 routed
experts (top-10, renormalised, scaled by 2.5) beside a shared expert.

Built from the fluid layer surface like the rest of the zoo: the
``fused_multihead_attention`` op with grouped K/V and a ``window``,
``layers.rotary_embedding`` with a rotated width and a table,
``layers.moe(capacity_factor=None, experts_held=...)`` for ONE CHIP'S
SHARE of the routed experts (``cfg.experts_held``; the shared expert
and everything else is computed here for every token), an untied head
over the held rows of the vocabulary.  What ``config.json`` does not
settle is listed in ``models/reference/laguna.py``, the plain
reference the tests hold this to.
"""

import math

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal

from . import gpt as _gpt

FULL, SLIDING = 'full_attention', 'sliding_attention'
DENSE, SPARSE = 'dense', 'sparse'

# rope_parameters.full_attention of the published config
YARN = dict(rope_theta=500000.0, factor=128.0,
            original_max_position_embeddings=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.4852030263919618,
            partial_rotary_factor=0.5)


class LagunaConfig(object):
    def __init__(self, vocab_size=100352, hidden=3072, layers=48,
                 head_dim=128, kv_heads=8, full_heads=48,
                 sliding_heads=72, layer_types=None, mlp_types=None,
                 window=512, dense_hidden=12288, expert_hidden=1024,
                 shared_hidden=1024, experts=256, top_k=10,
                 routed_scale=2.5, renormalize=True, experts_held=None,
                 rms_eps=1e-6, sliding_theta=10000.0, yarn=None,
                 init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers
        self.head_dim = head_dim
        self.kv_heads = kv_heads
        self.heads = {FULL: full_heads, SLIDING: sliding_heads}
        # published pattern: full, then three sliding, repeated; layer
        # 0 dense, the rest sparse
        self.layer_types = list(layer_types) if layer_types else [
            SLIDING if i % 4 else FULL for i in range(layers)]
        self.mlp_types = list(mlp_types) if mlp_types else [
            SPARSE if i else DENSE for i in range(layers)]
        assert len(self.layer_types) == len(self.mlp_types) == layers
        self.window = window
        self.dense_hidden = dense_hidden
        self.expert_hidden = expert_hidden
        self.shared_hidden = shared_hidden
        self.experts = experts
        self.top_k = top_k
        self.routed_scale = routed_scale    # moe_routed_scaling_factor
        self.renormalize = renormalize      # norm_topk_prob
        # (first, count) of the routed experts this chip holds; None:
        # all of them
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.sliding_theta = sliding_theta
        self.yarn = dict(YARN if yarn is None else yarn)
        # every matrix and the embedding; the routed layer's own
        # default is the same Normal(0, 0.02)
        self.init_std = init_std


BASE = LagunaConfig()
# a whole period behind the dense layer, tiny widths; 8 experts top-3
TINY = LagunaConfig(
    vocab_size=97, hidden=64, layers=5, head_dim=16, kv_heads=2,
    full_heads=4, sliding_heads=6, window=8, dense_hidden=96,
    expert_hidden=32, shared_hidden=32, experts=8, top_k=3,
    yarn=dict(YARN, original_max_position_embeddings=16, factor=4.0))


def yarn_inv_freq(dim, rope_theta, factor,
                  original_max_position_embeddings, beta_fast=32.0,
                  beta_slow=1.0, **_):
    """[dim / 2] float32 inverse frequencies of YaRN (Peng et al.
    2023) over ``dim`` rotated features, as HF
    ``modeling_rope_utils._compute_yarn_parameters`` computes them
    (``truncate`` true): frequencies that turn more than ``beta_fast``
    times within the original length are kept, those that turn fewer
    than ``beta_slow`` times are divided by ``factor``, a linear ramp
    between."""
    def correction_dim(rotations):
        return dim * math.log(original_max_position_embeddings / (
            rotations * 2 * math.pi)) / (2 * math.log(rope_theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(rope_theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   np.float32(high - low), 0, 1).astype(np.float32)
    keep = 1 - ramp
    return (1.0 / (np.float32(factor) * pos_freqs) * (1 - keep) +
            1.0 / pos_freqs * keep).astype(np.float32)


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(
                         initializer=Normal(0., cfg.init_std)))


def _attend(q, k, v, window):
    """q [B, T, H, d], k, v [B, T, Hkv, d] -> [B, T, H, d]: causal
    attention banded by ``window`` (``layers.flash_attention``)."""
    return layers.flash_attention(q, k, v, causal=True, window=window)


def attention(u, pos_ids, kind, cfg):
    """One layer's attention on the normed block input ``u``; ``kind``
    picks the head count, the rotary table and the mask."""
    d, heads, kv = cfg.head_dim, cfg.heads[kind], cfg.kv_heads
    q = layers.reshape(_linear(u, heads * d, cfg), [0, 0, heads, d])
    k = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    v = layers.reshape(_linear(u, kv * d, cfg), [0, 0, kv, d])
    if kind == FULL:
        rotary = int(d * cfg.yarn['partial_rotary_factor'])
        table = layers.assign(yarn_inv_freq(rotary, **cfg.yarn))
        q, k = layers.rotary_embedding(
            q, k, pos_ids, rotary_dim=rotary, inv_freq=table,
            attention_factor=cfg.yarn['attention_factor'])
        window = 0
    else:
        q, k = layers.rotary_embedding(q, k, pos_ids,
                                       theta=cfg.sliding_theta)
        window = cfg.window
    ctx = _attend(q, k, v, window)
    # per-head output gate: one sigmoid a head and token, of the same
    # normed input, in float32 whatever the stream is
    gate = layers.sigmoid(layers.cast(_linear(u, heads, cfg), 'float32'))
    ctx = layers.elementwise_mul(ctx, layers.unsqueeze(gate, [3]))
    return _linear(layers.reshape(ctx, [0, 0, heads * d]), cfg.hidden,
                   cfg)


def gated_mlp(w, width, cfg):
    """down(silu(gate w) * up w)."""
    gate, up = _linear(w, width, cfg), _linear(w, width, cfg)
    return _linear(layers.elementwise_mul(layers.silu(gate), up),
                   cfg.hidden, cfg)


def decoder_block(x, pos_ids, i, cfg):
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    x = layers.elementwise_add(
        x, attention(u, pos_ids, cfg.layer_types[i], cfg))
    w = layers.rms_norm(x, epsilon=cfg.rms_eps)
    if cfg.mlp_types[i] == DENSE:
        return layers.elementwise_add(
            x, gated_mlp(w, cfg.dense_hidden, cfg))
    routed, _ = layers.moe(
        w, num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize, gate_scale=cfg.routed_scale,
        experts_held=cfg.experts_held, aux_weight=0.0)
    x = layers.elementwise_add(x, gated_mlp(w, cfg.shared_hidden, cfg))
    return layers.elementwise_add(x, routed)


def laguna_decoder(ids, pos_ids, cfg):
    """-> hidden states after the final norm [B, T, hidden]."""
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(initializer=Normal(0., cfg.init_std)))
    for i in range(cfg.layers):
        x = decoder_block(x, pos_ids, i, cfg)
    return layers.rms_norm(x, epsilon=cfg.rms_eps)


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``pos_ids``, ``labels``
    ([B, seq_len] ints; labels are the ids shifted left, -1 where there
    is no next token: ``lm_batch``) -> (feeds, logits, loss): the
    next-token cross-entropy over the held vocabulary rows, averaged
    over every position but the last.  No auxiliary loss: the
    published config carries no coefficient for one."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    h = laguna_decoder(ids, pos, cfg)
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
