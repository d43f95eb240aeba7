"""OLMoE (Muennighoff et al. 2024): the decoder block today's open
models share (RMSNorm pre-norm, QK-norm, rotary embedding, no bias,
SiLU-gated MLPs) with a routed feed-forward: 64 experts, top-8,
dropless, gates from the softmax over all experts without
renormalisation.  ``BASE`` is OLMoE-1B-7B as published
(allenai/OLMoE-1B-7B-0125-Instruct ``config.json``).

Built from the fluid layer surface like the rest of the zoo.
Attention goes through ``bert.scaled_dot_product_attention``, so the
flash / dense choice at ``flash_min_len``, the kernels and the causal
mask are the encoder stack's and gpt.py's; QK-norm and the rotary
embedding sit between the projections and it.  The routed layer is
``layers.moe(capacity_factor=None)``.  The plain reference
the tests hold this to is ``models/reference/olmoe.py``.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal

from . import bert as _bert
from . import gpt as _gpt


class OlmoeConfig(object):
    def __init__(self, vocab_size=50304, hidden=2048, layers=16,
                 heads=16, expert_hidden=1024, experts=64, top_k=8,
                 max_pos=4096, rms_eps=1e-5, rope_theta=10000.0,
                 renormalize=False, aux_weight=0.01, z_weight=0.001,
                 init_std=0.02, use_flash=True):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.expert_hidden = expert_hidden
        self.experts = experts
        self.top_k = top_k
        self.max_pos = max_pos
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.renormalize = renormalize      # norm_topk_prob
        self.aux_weight = aux_weight        # router_aux_loss_coef
        self.z_weight = z_weight            # the paper's z-loss weight
        self.init_std = init_std
        self.use_flash = use_flash
        self.flash_min_len = 512
        # published: attention_dropout 0.0 and no other dropout
        self.dropout = 0.0
        self.attn_dropout = 0.0


BASE = OlmoeConfig()
TINY = OlmoeConfig(vocab_size=97, hidden=64, layers=2, heads=4,
                   expert_hidden=32, experts=8, top_k=3, max_pos=128)


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(
                         initializer=Normal(0., cfg.init_std)))


def attention(x, pos_ids, cfg, is_test):
    """Causal self-attention with QK-norm over the whole q and k
    projections and a rotary embedding over each whole head."""
    h, heads = cfg.hidden, cfg.heads
    q, k, v = (_linear(x, h, cfg) for _ in range(3))
    q = layers.rms_norm(q, epsilon=cfg.rms_eps)
    k = layers.rms_norm(k, epsilon=cfg.rms_eps)
    q, k, v = (layers.reshape(t, [0, 0, heads, h // heads])
               for t in (q, k, v))
    q, k = layers.rotary_embedding(q, k, pos_ids, theta=cfg.rope_theta)
    ctx = _bert.scaled_dot_product_attention(q, k, v, x, cfg, is_test,
                                             causal=True)
    return _linear(ctx, h, cfg)


def decoder_block(x, pos_ids, cfg, is_test, aux_losses):
    a = attention(layers.rms_norm(x, epsilon=cfg.rms_eps), pos_ids, cfg,
                  is_test)
    x = layers.elementwise_add(x, a)
    m, aux = layers.moe(
        layers.rms_norm(x, epsilon=cfg.rms_eps),
        num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize,
        aux_weight=cfg.aux_weight / cfg.layers,
        z_loss_weight=cfg.z_weight / cfg.layers)
    aux_losses.append(aux)
    return layers.elementwise_add(x, m)


def olmoe_decoder(ids, pos_ids, cfg, is_test=False, aux_losses=None):
    """-> hidden states after the final norm [B, T, hidden]; each
    layer's weighted auxiliary losses are appended to aux_losses."""
    aux_losses = [] if aux_losses is None else aux_losses
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(initializer=Normal(0., cfg.init_std)))
    for _ in range(cfg.layers):
        x = decoder_block(x, pos_ids, cfg, is_test, aux_losses)
    return layers.rms_norm(x, epsilon=cfg.rms_eps)


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``pos_ids``, ``labels``
    ([B, seq_len] ints; labels are the ids shifted left, -1 where there
    is no next token: ``lm_batch``) -> (feeds, logits, loss).  The loss
    is the next-token cross-entropy averaged over every position but
    the last plus the weighted load-balancing and router z losses,
    averaged over the layers; it is the same in a for_test clone (the
    auxiliary terms are part of what the model optimises)."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    aux_losses = []
    h = olmoe_decoder(ids, pos, cfg, is_test, aux_losses)
    logits = _linear(h, cfg.vocab_size, cfg)        # head not tied
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(layers.mean(token_loss),
                        scale=seq_len / (seq_len - 1.0))
    for aux in aux_losses:
        loss = layers.elementwise_add(loss, aux)
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    return feeds, logits, loss


# [B, T] token ids -> the feed dict (positions, ids shifted left as
# labels with -1 at each sequence's end): the GPT family's
lm_batch = _gpt.lm_batch
synthetic_batch = _gpt.synthetic_batch
