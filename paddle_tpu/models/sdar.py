"""SDAR (JetLM, ``model_type: sdar_moe``; arXiv:2510.06303): a routed
decoder trained by BLOCK DIFFUSION (BD3-LMs, arXiv:2503.09573).
``BASE`` is SDAR-30B-A3B-Chat as published
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat ``config.json``): 48
layers of hidden 2048, every one grouped-query attention (32 query
heads over 4 K/V heads of 128, an RMSNorm over each head of q and k
before the rotary embedding at theta 1e6) and 128 routed experts of
width 768 (top-8 of the softmax over all 128, the gates divided by
their sum, no shared expert); an untied head.

What no other model of the zoo does is how it TRAINS.  Every sequence
runs through every layer twice, one copy over the other along time:
rows 0 .. L-1 CORRUPTED (a token replaced by MASK with the probability
t of its block of ``block_length`` positions), rows L .. 2L-1 clean,
both at positions 0 .. L-1.  Every token-wise op (norms, projections,
router, held experts) sees 2L rows at once; only attention tells the
copies apart (``layers.block_diffusion_attention``: a corrupted token
sees its own block's corrupted tokens and every earlier block's clean
ones, a clean token the clean tokens up to its own block).  The head
reads the corrupted copy alone, position i predicts token i (no
shift), and the loss is the cross-entropy at the masked positions
weighted by 1 / t.  The LAST layer run computes of its clean rows only
the keys and values: nothing reads the rest.

The corruption is DATA, drawn outside the program: ``corrupt`` is a
plain numpy function of (ids, seed), and the program feeds on its four
arrays.  No random draw enters the program.

Built from the fluid layer surface like the rest of the zoo:
``layers.rms_norm`` over a head, ``layers.rotary_embedding`` at
repeated integer positions, ``layers.block_diffusion_attention`` (the
flash kernels under the block-relation mask, merged by log-sum-exp),
``layers.moe(capacity_factor=None, renormalize=True, experts_held=...)``
for ONE CHIP'S SHARE of the routed experts, the leading blocks
``recompute_guard`` groups.  What ``config.json`` does not settle
(the block length and the noise schedule first) is listed in
``models/reference/sdar.py``, the plain reference the tests hold this
to.
"""

import contextlib

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.initializer import Constant, Initializer, Normal


class SdarConfig(object):
    def __init__(self, vocab_size=151936, hidden=2048, layers=48,
                 heads=32, kv_heads=4, head_dim=128, expert_hidden=768,
                 experts=128, top_k=8, renormalize=True,
                 experts_held=None, rms_eps=1e-6, rope_theta=1000000.0,
                 block_length=4, t_min=1e-3, recompute_blocks=None,
                 init_std=0.02, embed_std=1.0, qk_gain=3.0):
        # the rows held here; the LAST of them is MASK, data ids are
        # drawn from the rows before it
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers                # how many are run
        self.heads = heads                  # num_attention_heads
        self.kv_heads = kv_heads            # num_key_value_heads
        self.head_dim = head_dim
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        self.experts = experts              # num_experts
        self.top_k = top_k                  # num_experts_per_tok
        self.renormalize = renormalize      # norm_topk_prob
        # (first, count) of the routed experts this chip holds; None:
        # all of them
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        # assumed (config.json gives neither): positions a block, and
        # the least mask probability a block draws
        self.block_length = block_length
        self.t_min = t_min
        # how many leading blocks are recompute groups; None: every
        # block but the last run
        self.recompute_blocks = recompute_blocks
        # assumed, the startup values: every matrix and the MASK row
        # Normal(0, init_std); the DATA rows of the embedding Normal(0,
        # embed_std), so a token's own row leads its stream; the
        # per-head gains of q and k qk_gain each, so a random key's
        # score has a deviation of qk_gain ** 2 and a row's context is
        # a few keys' values, not the mean of thousands
        # (models/reference/sdar.py has the why)
        self.init_std = init_std
        self.embed_std = embed_std
        self.qk_gain = qk_gain

    @property
    def mask_id(self):
        return self.vocab_size - 1


BASE = SdarConfig()
TINY = SdarConfig(vocab_size=97, hidden=64, layers=3, heads=4,
                  kv_heads=2, head_dim=16, expert_hidden=32, experts=8,
                  top_k=3)


def corrupt(ids, seed, cfg):
    """[B, L] token ids -> the feed dict of ``build_pretrain``, a plain
    numpy function of (ids, seed): one t ~ Uniform(t_min, 1) a block of
    ``block_length`` positions, token i replaced by MASK with
    probability t of its block (``noisy_ids``), the clean ``ids``, the
    positions 0 .. L-1 TWICE (``pos_ids`` [B, 2L]: both copies of token
    i stand at i) and the loss weights m_i / t (``weights``, float32:
    0 where the token was kept).  Ints are int32: the executor runs
    with x64 off."""
    ids = np.asarray(ids)
    b, length = ids.shape
    block = cfg.block_length
    if length % block:
        raise ValueError('corrupt: %d tokens are no whole number of '
                         '%d-token blocks' % (length, block))
    rng = np.random.RandomState(seed % 2 ** 32)
    t = np.repeat(rng.uniform(cfg.t_min, 1.0, (b, length // block)),
                  block, axis=1)
    masked = rng.uniform(size=(b, length)) < t
    return {
        'noisy_ids': np.where(masked, cfg.mask_id, ids).astype('int32'),
        'ids': ids.astype('int32'),
        'pos_ids': np.tile(np.arange(length), (b, 2)).astype('int32'),
        'weights': (masked / t).astype('float32'),
    }


def synthetic_batch(cfg, n, seq_len, seed):
    """``n`` sequences of uniform random DATA ids (every row but MASK)
    and their corruption, both from the seed."""
    rng = np.random.RandomState(seed % 2 ** 32)
    return corrupt(rng.randint(0, cfg.mask_id, (n, seq_len)), seed + 1,
                   cfg)


class _EmbeddingRows(Initializer):
    """Normal(0, std) rows; the LAST row, MASK, Normal(0, mask_std)."""

    def __init__(self, std, mask_std):
        self.std, self.mask_std = std, mask_std

    def __call__(self, var, block):
        rows, width = (int(n) for n in var.shape)
        Normal(0., self.std)(var, block)
        row = block.create_var(name=var.name + '.mask_row',
                               shape=[1, width], dtype=var.dtype)
        Normal(0., self.mask_std)(row, block)
        last = block.create_var(name=var.name + '.mask_id', shape=[1],
                                dtype='int32')
        block.append_op('assign_value', outputs={'Out': last.name},
                        attrs={'shape': [1], 'dtype': 'int32',
                               'values': [rows - 1]})
        return block.append_op(
            'scatter', inputs={'X': var.name, 'Ids': last.name,
                               'Updates': row.name},
            outputs={'Out': var.name}, attrs={'overwrite': True})


def _record_masked_share(values):
    """``Program.watch``'s record: the gauge ``sdar/masked_share``, on
    the runs of the program that fetch."""
    from paddle_tpu.fluid import monitor
    monitor.set_gauge('sdar/masked_share',
                      float(np.asarray(values[0]).ravel()[0]))


def _linear(x, size, cfg):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(
                         initializer=Normal(0., cfg.init_std)))


def _heads(x, n, cfg):
    return layers.reshape(x, [0, 0, n, cfg.head_dim])


def _head_norm(x, cfg):
    return layers.rms_norm(x, epsilon=cfg.rms_eps,
                           param_attr=fluid.ParamAttr(
                               initializer=Constant(cfg.qk_gain)))


def attention_operator(u, pos_ids, cfg, last):
    """u [B, 2L, hidden], the normed [corrupted ; clean] stream ->
    a Wo over both copies [B, 2L, hidden], or (``last``) over the
    corrupted copy alone [B, L, hidden], with only the keys and values
    of the clean rows computed.  An RMSNorm over each head of q and k
    (one 128-wide gain each), THEN the rotary embedding."""
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    rows = layers.split(u, 2, dim=1)[0] if last else u
    q = _head_norm(_heads(_linear(rows, h * d, cfg), h, cfg), cfg)
    k = _head_norm(_heads(_linear(u, kv * d, cfg), kv, cfg), cfg)
    v = _heads(_linear(u, kv * d, cfg), kv, cfg)
    if last:    # the corrupted rows' q and k, then the clean rows' k
        k_noisy, k_clean = layers.split(k, 2, dim=1)
        pos_noisy, pos_clean = layers.split(pos_ids, 2, dim=1)
        q, k_noisy = layers.rotary_embedding(q, k_noisy, pos_noisy,
                                             theta=cfg.rope_theta)
        k_clean, _ = layers.rotary_embedding(k_clean, k_clean, pos_clean,
                                             theta=cfg.rope_theta)
        k = layers.concat([k_noisy, k_clean], axis=1)
    else:
        q, k = layers.rotary_embedding(q, k, pos_ids,
                                       theta=cfg.rope_theta)
    ctx = layers.block_diffusion_attention(q, k, v, cfg.block_length)
    return _linear(layers.reshape(ctx, [0, 0, h * d]), cfg.hidden, cfg)


def decoder_block(x, pos_ids, cfg, last=False):
    """One layer over [B, 2L, hidden]; the ``last`` one run returns the
    corrupted copy's stream alone, [B, L, hidden]."""
    op = attention_operator(layers.rms_norm(x, epsilon=cfg.rms_eps),
                            pos_ids, cfg, last)
    if last:
        x = layers.split(x, 2, dim=1)[0]
    x = layers.elementwise_add(x, op)
    routed, _ = layers.moe(
        layers.rms_norm(x, epsilon=cfg.rms_eps),
        num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize, experts_held=cfg.experts_held,
        aux_weight=0.0)
    return layers.elementwise_add(x, routed)


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Block-diffusion training: feeds ``noisy_ids``, ``ids`` ([B,
    seq_len] ints), ``pos_ids`` ([B, 2 seq_len] ints) and ``weights``
    ([B, seq_len] float32), all four from ``corrupt`` -> (feeds, logits
    [B, seq_len, vocab] of the corrupted copy, loss): the mean over
    the DATA tokens of weight x cross-entropy(logits_i, ids_i) over the
    held vocabulary rows; no auxiliary loss.  The first
    ``cfg.recompute_blocks`` blocks (by default every block but the
    last) are ``recompute_guard`` groups.  On the runs that fetch, the gauge
    ``sdar/masked_share`` holds the fed corruption's share of masked
    positions (``Program.watch``)."""
    cfg = cfg or BASE
    noisy = fluid.layers.data('noisy_ids', shape=[seq_len], dtype='int64')
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[2 * seq_len], dtype='int64')
    weights = fluid.layers.data('weights', shape=[seq_len],
                                dtype='float32')
    x = None
    groups = cfg.layers - 1 if cfg.recompute_blocks is None \
        else cfg.recompute_blocks
    for i in range(cfg.layers):
        last = i == cfg.layers - 1
        with recompute_guard() if i < groups else contextlib.nullcontext():
            if x is None:
                x = layers.embedding(
                    layers.concat([noisy, ids], axis=1),
                    size=[cfg.vocab_size, cfg.hidden],
                    param_attr=fluid.ParamAttr(
                        initializer=_EmbeddingRows(cfg.embed_std,
                                                   cfg.init_std)))
            x = decoder_block(x, pos, cfg, last)
    h = layers.rms_norm(x, epsilon=cfg.rms_eps)
    logits = _linear(h, cfg.vocab_size, cfg)        # head not tied
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(ids, [2]))
    loss = layers.mean(layers.elementwise_mul(
        token_loss, layers.unsqueeze(weights, [2])))
    # the fed corruption's share of masked positions, for a gauge
    masked = layers.mean(layers.sign(weights))
    masked.stop_gradient = True
    loss.block.program.watch([masked.name], _record_masked_share)
    feeds = {'noisy_ids': noisy, 'ids': ids, 'pos_ids': pos,
             'weights': weights}
    return feeds, logits, loss
