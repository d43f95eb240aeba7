"""BERT pretraining (Devlin et al. 2018) as a benchmark family: the
program comes from the zoo (``paddle_tpu.models.bert.build_pretrain``,
part of the system under test: the flash / dense choice at
``flash_min_len`` lives there), the batch, the FLOPs and the plain
reference live here.

A configuration file's ``published`` group holds the keys of
google-research/bert's ``bert_config.json``; a traffic file gives
``seq_len``, the share of masked positions, and under ``changed`` the
keys it overrides (a longer position table for long sequences).
"""

import numpy as np

from benchmark.lib import flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative.  Measured on a v5e over 50 runs
# (PR 22): at most 3.8e-7 where attention is the dense chain (the
# program's f32 matmuls run at full precision) and at most 4.7e-5 at
# s2048, where the flash kernel multiplies in bf16 (8 mantissa bits)
# inside an otherwise f32 forward.  The bound is 6x that: bf16 matmuls
# throughout, or a format narrower than bf16 in the kernel, move the
# loss by 1e-3 and more and fail it, as does a wrong gather row or a
# dropped bias (whole units).
REFERENCE_RTOL = 3e-4


def sizes(config, traffic):
    """The published sizes with the traffic's overrides applied."""
    merged = dict(config['published'])
    merged.update(traffic.get('changed', {}))
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import bert
    s = sizes(config, traffic)
    return bert.BertConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['num_hidden_layers'], heads=s['num_attention_heads'],
        intermediate=s['intermediate_size'],
        max_pos=s['max_position_embeddings'],
        type_vocab=s['type_vocab_size'],
        dropout=s['hidden_dropout_prob'],
        attn_dropout=s['attention_probs_dropout_prob'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import bert
    _, _, loss = bert.build_pretrain(_zoo_config(config, traffic),
                                     traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: uniform token ids, no
    padding, ``masked_share`` of the positions carry a label (-1
    elsewhere).  Ints are int32: the executor runs with x64 off."""
    s, t = sizes(config, traffic), traffic['seq_len']
    rng = np.random.RandomState(seed)
    src = rng.randint(0, s['vocab_size'], (n, t))
    masked = rng.rand(n, t) < traffic['masked_share']
    labels = np.where(masked, rng.randint(0, s['vocab_size'], (n, t)), -1)
    return {
        'src_ids': src.astype('int32'),
        'pos_ids': np.tile(np.arange(t, dtype='int32'), (n, 1)),
        'sent_ids': np.zeros((n, t), 'int32'),
        'input_mask': np.ones((n, t), 'float32'),
        'mlm_label': labels.astype('int32'),
        'nsp_label': rng.randint(0, 2, (n, 1)).astype('int32'),
    }


def items_per_sample(config, traffic):
    return traffic['seq_len']


def flops_per_item(config, traffic):
    """Training FLOPs per token: 3 x forward; the masked-LM head runs
    over every position, as the zoo builds it."""
    s = sizes(config, traffic)
    return flops.TRAIN_OVER_FORWARD * \
        flops.transformer_encoder_forward_flops_per_token(
            s['num_hidden_layers'], s['hidden_size'],
            s['intermediate_size'], traffic['seq_len'], s['vocab_size'])


def reference_loss(config, traffic, params, feed):
    """The forward pass and loss in plain jax.numpy, float32, written
    from the paper and the zoo's heads; ``params`` are the program's
    parameters in creation order.  Departures from the published model
    are the configuration's ``assumed.heads``; dropout is off, as in
    the for_test program this is compared with."""
    import jax
    import jax.numpy as jnp
    s = sizes(config, traffic)
    heads = s['num_attention_heads']
    head_dim = s['hidden_size'] // heads
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    def layer_norm(x, gain, bias, eps=1e-5):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * gain + bias

    def token_losses(logits, labels):
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        return jnp.where(labels >= 0, -picked, 0.0)

    with jax.default_matmul_precision('highest'):
        word, position, segment, gain, bias = take(5)
        x = word[feed['src_ids']] + position[feed['pos_ids']] + \
            segment[feed['sent_ids']]
        x = layer_norm(x, gain, bias)
        key_bias = (feed['input_mask'] - 1.0) * 10000.0     # [B, T]
        b, t, h = x.shape
        for _ in range(s['num_hidden_layers']):
            (qkv_w, qkv_b, out_w, out_b, ln1_g, ln1_b,
             up_w, up_b, down_w, down_b, ln2_g, ln2_b) = take(12)
            q, k, v = jnp.split(x @ qkv_w + qkv_b, 3, -1)
            q, k, v = (a.reshape(b, t, heads, head_dim) for a in (q, k, v))
            scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * head_dim ** -0.5
            probs = jax.nn.softmax(
                scores + key_bias[:, None, None, :], -1)
            context = jnp.einsum('bhqk,bkhd->bqhd', probs, v)
            x = layer_norm(x + context.reshape(b, t, h) @ out_w + out_b,
                           ln1_g, ln1_b)
            hidden = jax.nn.gelu(x @ up_w + up_b, approximate=False)
            x = layer_norm(x + hidden @ down_w + down_b, ln2_g, ln2_b)
        mlm_w, mlm_b, nsp_w, nsp_b = take(4)
        mlm = jnp.mean(token_losses(x @ mlm_w + mlm_b,
                                    feed['mlm_label']))
        nsp = jnp.mean(token_losses(x[:, 0] @ nsp_w + nsp_b,
                                    feed['nsp_label'][:, 0]))
        return mlm + nsp
