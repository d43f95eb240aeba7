"""EvaByte (EvaByte/EvaByte, ``model_type: evabyte``, 6.5 B
parameters): a language model over BYTES.  32 identical pre-norm
decoder layers of hidden 4096, 32 heads of 128, a SiLU-gated MLP of
width 11008, no bias; a 320-row vocabulary (256 bytes + specials) and
eight prediction heads, head i at position t predicting byte t + 1 + i.
What sets it apart:

- ``attention_class: eva``: EVA attention (``layers.eva_attention``):
  exact causal attention inside 2048-byte windows joined in ONE
  softmax with a learned summary of every 16-byte chunk of every
  earlier window, from two vectors a head (``adaptive_phi``,
  ``adaptive_mu_k``);
- ``fp32_skip_add``: the residual stream and its two adds a layer stay
  float32 under bf16 AMP (``mixed_precision.keep_float32``: every
  other decoder of the zoo lets the stream follow its bfloat16
  branch), ``fp32_logits``: the heads' products too;
- ``norm_add_unit_offset``: RMSNorm gains stored as offsets from one
  (``layers.rms_norm(unit_offset=True)``).

``BASE`` is the published config.  Built from the fluid layer surface
like the rest of the zoo; the block's projections, rotary embedding
and gated MLP are olmoe.py's and lfm2.py's (``_linear`` and
``gated_mlp`` ARE lfm2.py's).  The plain reference the
tests hold this to is ``models/reference/evabyte.py``, whose docstring
has the equations and what the published config leaves to be assumed.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.contrib.mixed_precision import keep_float32
from paddle_tpu.fluid.initializer import Initializer, Normal

from . import lfm2 as _lfm2


class EvaByteConfig(object):
    def __init__(self, vocab_size=320, hidden=4096, layers=32, heads=32,
                 intermediate=11008, pred_heads=8, window=2048,
                 chunk=16, max_pos=32768, rms_eps=1e-5,
                 rope_theta=100000.0, init_std=0.01275):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers                # num_hidden_layers
        self.heads = heads                  # = num_key_value_heads
        self.intermediate = intermediate
        self.pred_heads = pred_heads        # num_pred_heads
        self.window = window                # window_size
        self.chunk = chunk                  # chunk_size
        self.max_pos = max_pos
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.init_std = init_std

    @property
    def head_dim(self):
        return self.hidden // self.heads


BASE = EvaByteConfig()
TINY = EvaByteConfig(vocab_size=41, hidden=64, layers=2, heads=4,
                     intermediate=96, pred_heads=3, window=32, chunk=4,
                     max_pos=128)


class ClippedNormal(Initializer):
    """Normal(0, 1) clipped to [-1, 1], times ``scale``: the startup
    values of ``adaptive_phi`` and ``adaptive_mu_k`` (assumed: the
    published config has no key for them)."""

    def __init__(self, scale):
        self.scale = float(scale)

    def __call__(self, var, block):
        block.append_op(
            'gaussian_random', outputs={'Out': var.name},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'mean': 0.0, 'std': 1.0})
        block.append_op('clip', inputs={'X': var.name},
                        outputs={'Out': var.name},
                        attrs={'min': -1.0, 'max': 1.0})
        return block.append_op('scale', inputs={'X': var.name},
                               outputs={'Out': var.name},
                               attrs={'scale': self.scale})


# [.., n] -> [.., size] by a bias-free matrix drawn Normal(0, init_std)
_linear = _lfm2._linear


def _norm(x, cfg):
    return layers.rms_norm(x, epsilon=cfg.rms_eps, unit_offset=True)


def attention(u, pos_ids, cfg):
    """EVA attention on the normed block input: rotate-half rotary
    over each whole head of q and k, the two learned vectors a head,
    ``layers.eva_attention``, the output projection."""
    h, heads, d = cfg.hidden, cfg.heads, cfg.head_dim
    q, k, v = (layers.reshape(_linear(u, h, cfg), [0, 0, heads, d])
               for _ in range(3))
    q, k = layers.rotary_embedding(q, k, pos_ids, theta=cfg.rope_theta)
    phi, mu = (layers.create_parameter(
        [heads, d], 'float32',
        default_initializer=ClippedNormal(d ** -0.5)) for _ in range(2))
    ctx = layers.eva_attention(q, k, v, cfg.window, cfg.chunk, phi, mu)
    return _linear(layers.reshape(ctx, [0, 0, h]), h, cfg)


def decoder_block(x, pos_ids, cfg):
    """h = x + A(N1(x)), y = h + M(N2(h)); x, h, y and both adds in
    float32 whatever the branches compute in."""
    x = keep_float32(layers.elementwise_add(
        x, attention(_norm(x, cfg), pos_ids, cfg)))
    return keep_float32(layers.elementwise_add(
        x, _lfm2.gated_mlp(_norm(x, cfg), cfg.intermediate, cfg)))


def evabyte_decoder(ids, pos_ids, cfg):
    """-> hidden states after the final norm [B, T, hidden]."""
    x = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden],
        param_attr=fluid.ParamAttr(initializer=Normal(0., cfg.init_std)))
    for _ in range(cfg.layers):
        x = decoder_block(x, pos_ids, cfg)
    return _norm(x, cfg)


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Multi-byte-prediction pretraining: feeds ``ids``, ``pos_ids``
    ([B, seq_len] ints) and ``labels`` ([B, seq_len, pred_heads]:
    column i is the ids shifted left by 1 + i, -1 where the sequence
    has no such byte: ``lm_batch``) -> (feeds, logits [B, seq_len,
    pred_heads, vocab], loss).  ``pred_heads`` matrices [hidden, vocab]
    over the final norm's output, their products float32; the loss is
    the mean over the heads of each head's cross-entropy averaged over
    the positions that have its label.  ``is_test`` changes nothing:
    the model has no dropout."""
    cfg = cfg or BASE
    if seq_len > cfg.max_pos:
        raise ValueError('%d positions; the model declares %d'
                         % (seq_len, cfg.max_pos))
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len, cfg.pred_heads],
                               dtype='int64')
    z = evabyte_decoder(ids, pos, cfg)
    logits, loss = [], None
    for i in range(cfg.pred_heads):
        logits.append(keep_float32(_linear(z, cfg.vocab_size, cfg)))
        token_loss = layers.softmax_with_cross_entropy(
            logits[-1], layers.slice(labels, [2], [i], [i + 1]),
            ignore_index=-1)
        # the last 1 + i positions carry no label for head i and count
        # 0: the mean over all T is the mean over the rest times
        # (T - 1 - i) / T
        mine = layers.scale(
            layers.mean(token_loss),
            scale=seq_len / (seq_len - 1.0 - i) / cfg.pred_heads)
        loss = mine if loss is None else layers.elementwise_add(loss, mine)
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    return feeds, layers.stack(logits, axis=2), loss


def lm_batch(ids_2d, pred_heads):
    """[B, T] byte ids -> the feed dict: positions, and for each head
    the ids shifted left by 1 + i (-1 past the sequence's end)."""
    ids_2d = np.asarray(ids_2d, 'int64')
    b, t = ids_2d.shape
    labels = np.full((b, t, pred_heads), -1, 'int64')
    for i in range(pred_heads):
        labels[:, :t - 1 - i, i] = ids_2d[:, 1 + i:]
    return {'ids': ids_2d,
            'pos_ids': np.tile(np.arange(t, dtype='int64'), (b, 1)),
            'labels': labels}


def synthetic_batch(cfg, batch, seq_len, rng):
    return lm_batch(rng.randint(0, cfg.vocab_size, (batch, seq_len)),
                    cfg.pred_heads)
