"""BERT-base encoder for pretraining (BASELINE.json config 2).

Reference workload: fused_attention + layer_norm + adam on the reference's
multihead_matmul fused op (operators/fused/multihead_matmul_op.*).  Built
here with fluid layers; XLA fuses the attention chain, and the pallas
flash-attention kernel (ops/pallas/) replaces the naive chain when
enabled via attrs['__flash__'].
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


class BertConfig(object):
    def __init__(self, vocab_size=30522, hidden=768, layers=12, heads=12,
                 intermediate=3072, max_pos=512, type_vocab=2,
                 dropout=0.1, attn_dropout=None, use_flash=True):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.intermediate = intermediate
        self.max_pos = max_pos
        self.type_vocab = type_vocab
        self.dropout = dropout
        # dropout on the attention probabilities (reference default:
        # dropout inside attention) — runs IN the flash kernels via a
        # counter-hash mask, so the flash path takes it natively
        self.attn_dropout = dropout if attn_dropout is None \
            else attn_dropout
        self.use_flash = use_flash
        # pre-round readings on one v5e-class chip (not measured on
        # current code): the batched
        # round-3 tuned kernels (bf16 MXU dots, 512/1024 blocks —
        # tools/bench_flash.py): flash beats the naive XLA chain from
        # seq 512 up (512: 6.3 vs 8.2 ms; 1024: 11.3 vs 21.5;
        # 2048: 36.7 vs 73.8 fwd+bwd) and only loses in the 256
        # pocket where XLA's fused chain fits VMEM outright
        self.flash_min_len = 512


BASE = BertConfig()
TINY = BertConfig(vocab_size=1000, hidden=64, layers=2, heads=4,
                  intermediate=128, max_pos=128)


def multi_head_attention(x, attn_bias, cfg, is_test, key_bias=None,
                         causal=False):
    """Self-attention: fused QKV projection -> scaled dot product ->
    output projection.  When the config allows it (no attention-probs
    dropout needed) the scaled-dot-product chain runs as ONE Pallas
    flash-attention kernel fwd+bwd (ops/pallas/flash_attention.py) —
    the reference's multihead_matmul fusion
    (operators/fused/multihead_matmul_op.cu), TPU-style.  causal=True
    masks future positions (decoder-only LMs): the flash kernel takes
    it natively, the naive chain adds a causal_mask_like bias."""
    h, heads = cfg.hidden, cfg.heads
    d = h // heads
    qkv = layers.fc(x, size=3 * h, num_flatten_dims=2)
    q, k, v = layers.split(qkv, 3, dim=2)

    if getattr(cfg, 'use_context_parallel', False):
        # sequence/context parallelism: the ring_attention op shards T
        # over the 'sp' mesh axis under CompiledProgram.with_mesh and
        # runs the ppermute K/V ring (dense fallback on one device).
        # The ring carries no attention bias yet — masked BERT inputs
        # must keep the standard path.
        if attn_bias is not None or key_bias is not None:
            raise ValueError(
                'use_context_parallel does not support attention '
                'masks/biases yet: drop the input mask or disable '
                'context parallelism')
        seq = x.shape[1]
        t_dim = seq if seq and seq > 0 else -1
        q3 = layers.reshape(q, [-1, t_dim, heads, d] if t_dim > 0
                            else [0, 0, heads, d])
        k3 = layers.reshape(k, [-1, t_dim, heads, d] if t_dim > 0
                            else [0, 0, heads, d])
        v3 = layers.reshape(v, [-1, t_dim, heads, d] if t_dim > 0
                            else [0, 0, heads, d])
        cp_drop = 0.0 if is_test else float(
            getattr(cfg, 'attn_dropout', cfg.dropout) or 0.0)
        out = layers.context_parallel_attention(
            q3, k3, v3, causal=causal,
            use_flash=getattr(cfg, 'cp_use_flash', False),
            axis=getattr(cfg, 'cp_axis', 'sp'),
            dropout_rate=cp_drop)
        ctx = layers.reshape(out, [0, 0, h])
        return layers.fc(ctx, size=h, num_flatten_dims=2)

    ctx = scaled_dot_product_attention(q, k, v, x, cfg, is_test,
                                       attn_bias=attn_bias,
                                       key_bias=key_bias, causal=causal)
    return layers.fc(ctx, size=h, num_flatten_dims=2)


def scaled_dot_product_attention(q, k, v, x, cfg, is_test,
                                 attn_bias=None, key_bias=None,
                                 causal=False):
    """softmax(q k^T / sqrt(d)) v for every head -> context [B, T, h],
    by the zoo's one flash / dense choice: the
    ``fused_multihead_attention`` op (Pallas flash kernels on a chip)
    from ``cfg.flash_min_len`` up, the ``matmul`` + ``softmax`` chain
    under it.  q, k, v are the projections, [B, T, h] or already split
    into heads [B, T, heads, d] (a decoder that norms and rotates q and
    k per head); ``x`` is the block's input, for its sequence length.
    Shared by the encoder (``multi_head_attention``) and the decoder
    zoo (gpt.py through it, olmoe.py directly)."""
    h, heads = cfg.hidden, cfg.heads
    d = h // heads
    split = len(q.shape) == 4
    seq_len = x.shape[1] if len(x.shape) >= 2 else 0
    use_flash = getattr(cfg, 'use_flash', False) and \
        (seq_len is None or seq_len < 0 or
         seq_len >= getattr(cfg, 'flash_min_len', 1024)) and \
        (attn_bias is None or key_bias is not None)
    # the flash kernel consumes the [B, T] key_bias form only: with a
    # general attn_bias and no key_bias we must keep the naive chain
    # rather than silently dropping the mask.  Attention-prob dropout
    # (the reference BERT default) runs INSIDE the kernels since round
    # 5 — no [T, T] probs ever materialize.
    if use_flash:
        from ..fluid.layer_helper import LayerHelper

        def to_bthd(t):
            return t if split else layers.reshape(t, [0, 0, heads, d])

        q3, k3, v3 = to_bthd(q), to_bthd(k), to_bthd(v)
        helper = LayerHelper('fused_multihead_attention')
        out = helper.create_variable_for_type_inference(x.dtype)
        inputs = {'Q': q3, 'K': k3, 'V': v3}
        if key_bias is not None:
            inputs['KeyBias'] = key_bias
        adrop = 0.0 if is_test else float(
            getattr(cfg, 'attn_dropout', cfg.dropout) or 0.0)
        helper.append_op('fused_multihead_attention', inputs=inputs,
                         outputs={'Out': out},
                         attrs={'causal': bool(causal),
                                'dropout_rate': adrop},
                         infer_shape=False)
        out.shape = tuple(q3.shape)
        return layers.reshape(out, [0, 0, h])

    def to_heads(t):
        if not split:
            t = layers.reshape(t, [0, 0, heads, d])
        return layers.transpose(t, [0, 2, 1, 3])

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    scores = layers.matmul(q, k, transpose_y=True, alpha=d ** -0.5)
    if causal:
        from .transformer import _causal_bias
        scores = layers.elementwise_add(
            scores, _causal_bias(x, x.shape[1] or -1))
    if attn_bias is not None:
        scores = layers.elementwise_add(scores, attn_bias)
    probs = layers.softmax(scores)
    if not is_test and getattr(cfg, 'attn_dropout', cfg.dropout):
        probs = layers.dropout(probs,
                               getattr(cfg, 'attn_dropout', cfg.dropout),
                               is_test=is_test,
                               dropout_implementation='upscale_in_train')
    ctx = layers.matmul(probs, v)
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    return layers.reshape(ctx, [0, 0, h])


def encoder_layer(x, attn_bias, cfg, is_test, key_bias=None):
    attn = multi_head_attention(x, attn_bias, cfg, is_test,
                                key_bias=key_bias)
    if not is_test and cfg.dropout:
        attn = layers.dropout(attn, cfg.dropout, is_test=is_test,
                              dropout_implementation='upscale_in_train')
    x = layers.layer_norm(layers.elementwise_add(x, attn),
                          begin_norm_axis=2)
    ffn = layers.fc(x, size=cfg.intermediate, num_flatten_dims=2,
                    act='gelu')
    ffn = layers.fc(ffn, size=cfg.hidden, num_flatten_dims=2)
    if not is_test and cfg.dropout:
        ffn = layers.dropout(ffn, cfg.dropout, is_test=is_test,
                             dropout_implementation='upscale_in_train')
    return layers.layer_norm(layers.elementwise_add(x, ffn),
                             begin_norm_axis=2)


def bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg,
                 is_test=False):
    emb = layers.embedding(src_ids, size=[cfg.vocab_size, cfg.hidden])
    pos = layers.embedding(pos_ids, size=[cfg.max_pos, cfg.hidden])
    sent = layers.embedding(sent_ids, size=[cfg.type_vocab, cfg.hidden])
    x = layers.elementwise_add(layers.elementwise_add(emb, pos), sent)
    x = layers.layer_norm(x, begin_norm_axis=2)
    if not is_test and cfg.dropout:
        x = layers.dropout(x, cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')
    # [B, T] mask -> additive bias: 0 where attended, -10000 where
    # padded.  The flash path consumes the [B, T] form directly; the
    # naive chain broadcasts the [B, 1, 1, T] form over heads/rows.
    key_bias = layers.scale(input_mask, scale=10000.0, bias=-10000.0)
    bias = layers.unsqueeze(layers.unsqueeze(key_bias, [1]), [1])
    for _ in range(cfg.layers):
        x = encoder_layer(x, bias, cfg, is_test, key_bias=key_bias)
    return x


def build_pretrain(cfg=None, seq_len=128, is_test=False):
    """Masked-LM + next-sentence pretraining heads (reference BERT
    pretraining workload)."""
    cfg = cfg or BASE
    src = fluid.layers.data('src_ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    sent = fluid.layers.data('sent_ids', shape=[seq_len], dtype='int64')
    mask = fluid.layers.data('input_mask', shape=[seq_len],
                             dtype='float32')
    mlm_label = fluid.layers.data('mlm_label', shape=[seq_len],
                                  dtype='int64')
    nsp_label = fluid.layers.data('nsp_label', shape=[1], dtype='int64')

    enc = bert_encoder(src, pos, sent, mask, cfg, is_test)
    # MLM head over all positions (dense path; gather of masked positions
    # is a host-side optimization)
    mlm_logits = layers.fc(enc, size=cfg.vocab_size, num_flatten_dims=2)
    mlm_loss = layers.softmax_with_cross_entropy(
        mlm_logits, layers.unsqueeze(mlm_label, [2]), ignore_index=-1)
    mlm_loss = layers.mean(mlm_loss)
    # NSP head on [CLS] (position 0)
    cls = layers.slice(enc, axes=[1], starts=[0], ends=[1])
    cls = layers.reshape(cls, [0, cfg.hidden])
    nsp_logits = layers.fc(cls, size=2)
    nsp_loss = layers.mean(
        layers.softmax_with_cross_entropy(nsp_logits, nsp_label))
    loss = layers.elementwise_add(mlm_loss, nsp_loss)
    feeds = {'src_ids': src, 'pos_ids': pos, 'sent_ids': sent,
             'input_mask': mask, 'mlm_label': mlm_label,
             'nsp_label': nsp_label}
    return feeds, enc, loss


def synthetic_batch(cfg, batch, seq_len, rng):
    src = rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype('int64')
    pos = np.tile(np.arange(seq_len), (batch, 1)).astype('int64')
    sent = np.zeros((batch, seq_len), 'int64')
    mask = np.ones((batch, seq_len), 'float32')
    mlm = np.where(rng.rand(batch, seq_len) < 0.15,
                   rng.randint(0, cfg.vocab_size, (batch, seq_len)),
                   -1).astype('int64')
    nsp = rng.randint(0, 2, (batch, 1)).astype('int64')
    return {'src_ids': src, 'pos_ids': pos, 'sent_ids': sent,
            'input_mask': mask, 'mlm_label': mlm, 'nsp_label': nsp}
