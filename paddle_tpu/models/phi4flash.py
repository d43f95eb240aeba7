"""Phi-4-mini-flash (Microsoft, ``model_type: phi4flash``; the SambaY
decoder-hybrid-decoder, arXiv:2507.06607): a decoder whose layers
differ by OPERATOR by a rule of the layer index, and whose second half
reads ONE layer's keys, values and scan output.  ``BASE`` is
Phi-4-mini-flash-reasoning as published
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
``config.json``): 32 layers of hidden 2560, 40 query / 20 K/V heads of
64, MLP width 10240, ``sliding_window`` 512, ``mb_per_layer`` 2, 200064
rows, the head tied, no position encoding of any kind.

With L layers (a multiple of 4), layer i is (``layer_kinds``):

- i even, i <= L/2: MAMBA (a selective state-space scan between a
  causal 4-tap filter and a SiLU gate); layer L/2 also hands on its scan
  output m, before the gate, as the MEMORY;
- i odd, i < L/2: DIFFERENTIAL attention under the window;
- i = L/2 + 1: differential attention, full causal; its K and V are the
  SHARED ones;
- i even, i >= L/2 + 2: a gated memory unit (GMU) over m;
- i odd, i >= L/2 + 3: differential CROSS-attention, its own Wq and Wo
  over the shared K and V.

Built from the fluid layer surface like the rest of the zoo:
``layers.short_conv`` with the filter's bias fused in,
``layers.selective_scan`` (``ops/ssm_ops.py``), ``layers.layer_norm``
(the block norms are LayerNorms WITH bias), and differential attention
as ONE ``fused_multihead_attention`` call a layer: Q = [the q1 heads,
the q2 heads] (40 heads of 64) over K = [k1, k2] (20) and V = [V, V]
(20 heads of 128, V = [v1 | v2]), so query head j reads K/V head j // 2
as published and the call's first 20 output heads are P(q1, k1) V, its
last 20 P(q2, k2) V.  Each decoder block is a
``fluid.backward.recompute_guard`` group: a train step keeps the [B, T,
hidden] stream between two blocks, m and the shared K and V (which
cross groups: their gradients are sums over their readers), and
computes a block's inside again for its gradient.  Every parameter is
created by NAME (``parameter_specs`` lists them in creation order); what
``config.json`` does not settle is listed in
``models/reference/phi4flash.py``, the plain reference the tests hold
this to.

Under bf16 AMP the steps (``delta``), A, D and the scan's state are
float32; x, B, C, the filters' outputs and the attention operands are
bfloat16.
"""

import contextlib
import math

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.contrib.mixed_precision import keep_float32
from paddle_tpu.fluid.initializer import (Constant, Normal,
                                          NumpyArrayInitializer, Uniform)

from . import gpt as _gpt

MAMBA, WINDOW, FULL, GMU, CROSS = \
    'mamba', 'sliding_attention', 'full_attention', 'gmu', 'cross_attention'
EMBEDDING = 'phi4flash.embed_tokens'


def layer_kinds(layers):
    """[operator kind] by layer index at ``layers`` layers."""
    if layers % 4:
        raise ValueError('SambaY needs a multiple of 4 layers, got %d'
                         % layers)
    half = layers // 2
    return [(MAMBA if i <= half else GMU) if i % 2 == 0 else
            WINDOW if i < half else FULL if i == half + 1 else CROSS
            for i in range(layers)]


def lambda_init(i):
    """lam0 of the differential attention at layer ``i`` of the model
    AS RUN."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


class Phi4FlashConfig(object):
    def __init__(self, vocab_size=200064, hidden=2560, layers=32,
                 heads=40, kv_heads=20, intermediate=10240, window=512,
                 d_state=16, d_conv=4, expand=2, dt_rank=None,
                 ln_eps=1e-5, subln_eps=1e-5, dt_range=(1e-3, 1e-1),
                 lambda_std=0.1, init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.kv_heads = kv_heads
        self.head_dim = hidden // heads
        self.intermediate = intermediate
        self.window = window                # sliding_window
        self.d_state = d_state              # N
        self.d_conv = d_conv
        self.d_inner = expand * hidden
        self.dt_rank = dt_rank or -(-hidden // 16)
        self.ln_eps = ln_eps                # layer_norm_eps
        self.subln_eps = subln_eps
        # softplus(b_dt) at startup: log-uniform steps in this range
        # (softplus(x) ~ exp(x) that far under 0)
        self.dt_range = tuple(dt_range)
        self.lambda_std = lambda_std
        self.init_std = init_std
        if heads % 2 or kv_heads % 2 or heads % kv_heads:
            raise ValueError('differential attention pairs the heads: '
                             '%d over %d' % (heads, kv_heads))
        self.kinds = layer_kinds(layers)


BASE = Phi4FlashConfig()
# every kind at the least depth, tiny widths; a window shorter than the
# tests' sequences
TINY = Phi4FlashConfig(vocab_size=97, hidden=32, layers=8, heads=4,
                       kv_heads=2, intermediate=48, window=5, d_state=4,
                       dt_rank=3)


def _attention_specs(cfg, own_kv):
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    matrix, lam = Normal(0., cfg.init_std), Normal(0., cfg.lambda_std)
    width = (h + 2 * kv) * d if own_kv else h * d
    return [('wqkv' if own_kv else 'wq', [cfg.hidden, width], matrix),
            ('bqkv' if own_kv else 'bq', [width], matrix),
            ('lq1', [d], lam), ('lk1', [d], lam),
            ('lq2', [d], lam), ('lk2', [d], lam),
            ('subln_g', [2 * d], Constant(1.0)),
            ('wo', [h * d, cfg.hidden], matrix),
            ('bo', [cfg.hidden], matrix)]


def operator_specs(cfg, kind):
    """[(what, shape, initializer)] of one layer's operator, in
    creation order."""
    matrix = Normal(0., cfg.init_std)
    inner, n, rank = cfg.d_inner, cfg.d_state, cfg.dt_rank
    if kind == MAMBA:
        # PyTorch's Conv1d default at a fan-in of d_conv, bias too
        bound = cfg.d_conv ** -0.5
        a_log = np.log(np.tile(np.arange(1, n + 1, dtype='float32'),
                               (inner, 1)))
        return [('w_in', [cfg.hidden, 2 * inner], matrix),
                ('conv_w', [inner, cfg.d_conv], Uniform(-bound, bound)),
                ('conv_b', [inner], Uniform(-bound, bound)),
                ('w_x', [inner, rank + 2 * n], matrix),
                ('w_dt', [rank, inner],
                 Uniform(-rank ** -0.5, rank ** -0.5)),
                ('b_dt', [inner],
                 Uniform(*(math.log(v) for v in cfg.dt_range))),
                ('a_log', [inner, n], NumpyArrayInitializer(a_log)),
                ('d', [inner], Constant(1.0)),
                ('w_out', [inner, cfg.hidden], matrix)]
    if kind == GMU:
        return [('w_in', [cfg.hidden, inner], matrix),
                ('w_out', [inner, cfg.hidden], matrix)]
    return _attention_specs(cfg, own_kv=kind != CROSS)


def parameter_specs(cfg):
    """[(name, shape, initializer)] of every parameter in creation
    order: the table, each layer's first norm, operator, second norm
    and MLP, the last norm."""
    matrix = Normal(0., cfg.init_std)

    def norm(prefix):
        return [(prefix + '.g', [cfg.hidden], Constant(1.0)),
                (prefix + '.b', [cfg.hidden], matrix)]

    specs = [(EMBEDDING, [cfg.vocab_size, cfg.hidden], matrix)]
    for i, kind in enumerate(cfg.kinds):
        layer = 'phi4flash.%d.' % i
        specs += norm(layer + 'ln1')
        specs += [(layer + kind + '.' + what, shape, init)
                  for what, shape, init in operator_specs(cfg, kind)]
        specs += norm(layer + 'ln2')
        specs += [(layer + 'mlp.w1',
                   [cfg.hidden, 2 * cfg.intermediate], matrix),
                  (layer + 'mlp.w2', [cfg.intermediate, cfg.hidden],
                   matrix)]
    return specs + norm('phi4flash.ln_f')


def parameter_names(cfg):
    return [name for name, _, _ in parameter_specs(cfg)]


class _Parameters(object):
    """The program's parameters as they are asked for, each checked
    against ``parameter_specs``'s order: ``take`` creates one,
    ``attr`` names one for the layer function that creates it.  ``at``
    is the same under a prefix (one layer's operator)."""

    def __init__(self, cfg, specs=None, prefix=''):
        self.specs = iter(parameter_specs(cfg)) if specs is None else specs
        self.prefix = prefix

    def at(self, prefix):
        return _Parameters(None, self.specs, self.prefix + prefix)

    def _next(self, what):
        name, shape, init = next(self.specs)
        assert name == self.prefix + what, (name, self.prefix + what)
        return name, shape, init

    def take(self, what):
        name, shape, init = self._next(what)
        return layers.create_parameter(shape, 'float32', name=name,
                                       default_initializer=init)

    def attr(self, what):
        name, _, init = self._next(what)
        return fluid.ParamAttr(name=name, initializer=init)


def _linear(x, size, p, weight, bias=None):
    return layers.fc(x, size=size, num_flatten_dims=2,
                     param_attr=p.attr(weight),
                     bias_attr=p.attr(bias) if bias else False)


def _layer_norm(x, p, what, cfg):
    return layers.layer_norm(x, begin_norm_axis=2, epsilon=cfg.ln_eps,
                             param_attr=p.attr(what + '.g'),
                             bias_attr=p.attr(what + '.b'))


def mamba_operator(u, p, cfg):
    """-> (the operator's output [B, T, hidden], the scan's output m
    [B, T, d_inner] before the gate)."""
    n, rank = cfg.d_state, cfg.dt_rank
    x, z = layers.split(_linear(u, 2 * cfg.d_inner, p, 'w_in'), 2, dim=2)
    x = layers.silu(layers.short_conv(
        x, cfg.d_conv, param_attr=p.attr('conv_w'),
        bias_attr=p.attr('conv_b')))
    dt, b, c = layers.split(_linear(x, rank + 2 * n, p, 'w_x'),
                            [rank, n, n], dim=2)
    delta = layers.softplus(keep_float32(layers.elementwise_add(
        _linear(dt, cfg.d_inner, p, 'w_dt'), p.take('b_dt'), axis=2)))
    a = layers.scale(layers.exp(p.take('a_log')), scale=-1.0)
    m = layers.selective_scan(x, delta, a, b, c, p.take('d'))
    y = layers.elementwise_mul(m, layers.silu(z))
    return _linear(y, cfg.hidden, p, 'w_out'), m


def gmu_operator(u, memory, p, cfg):
    gate = layers.silu(_linear(u, cfg.d_inner, p, 'w_in'))
    return _linear(layers.elementwise_mul(memory, gate), cfg.hidden, p,
                   'w_out')


def _pair_major(x, heads, d):
    """[B, T, heads * d] whose heads come in pairs (first, second) ->
    [B, T, heads, d] with every pair's FIRST head before every pair's
    second."""
    x = layers.reshape(x, [0, 0, heads // 2, 2, d])
    return layers.reshape(layers.transpose(x, [0, 1, 3, 2, 4]),
                          [0, 0, heads, d])


def shared_keys_values(k, v, cfg):
    """k, v [B, T, kv_heads * d] as projected -> (K [B, T, kv_heads, d]
    = [k1 heads, k2 heads], V [B, T, kv_heads, 2 d] = [V, V] with V =
    [v1 | v2] a pair): the operands of the one call."""
    d, kv = cfg.head_dim, cfg.kv_heads
    values = layers.reshape(v, [0, 0, kv // 2, 2 * d])
    return (_pair_major(k, kv, d),
            layers.concat([values, values], axis=2))


def differential_attention(q, keys, values, i, window, p, cfg):
    """q [B, T, heads * d] as projected, ``keys`` and ``values`` from
    ``shared_keys_values`` -> [B, T, hidden]: the one call, attn1 - lam
    attn2, the sub-norm over 2 d, (1 - lam0), Wo with its bias."""
    d, h = cfg.head_dim, cfg.heads
    lam0 = lambda_init(i)
    both = layers.flash_attention(_pair_major(q, h, d), keys, values,
                                  causal=True, window=window)
    first, second = layers.split(both, 2, dim=2)    # [B, T, h / 2, 2 d]

    def dot(a, b):
        return layers.exp(layers.reduce_sum(layers.elementwise_mul(a, b)))

    lam = layers.scale(layers.elementwise_sub(
        dot(p.take('lq1'), p.take('lk1')),
        dot(p.take('lq2'), p.take('lk2'))), scale=1.0, bias=lam0)
    o = layers.elementwise_sub(first, layers.elementwise_mul(second, lam))
    o = layers.scale(layers.rms_norm(o, epsilon=cfg.subln_eps,
                                     param_attr=p.attr('subln_g')),
                     scale=1.0 - lam0)
    return _linear(layers.reshape(o, [0, 0, h * d]), cfg.hidden, p, 'wo',
                   'bo')


def attention_operator(u, i, kind, shared, p, cfg):
    """-> (the operator's output, the layer's (K, V) as the one call
    takes them): a cross layer projects q alone and reads ``shared``."""
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    if kind == CROSS:
        q = _linear(u, h * d, p, 'wq', 'bq')
        keys, values = shared
    else:
        q, k, v = layers.split(
            _linear(u, (h + 2 * kv) * d, p, 'wqkv', 'bqkv'),
            [h * d, kv * d, kv * d], dim=2)
        keys, values = shared_keys_values(k, v, cfg)
    out = differential_attention(
        q, keys, values, i, cfg.window if kind == WINDOW else 0, p, cfg)
    return out, (keys, values)


def gated_mlp(w, p, cfg):
    """W2 (up * silu(gate)), [gate | up] = W1 w."""
    gate, up = layers.split(
        _linear(w, 2 * cfg.intermediate, p, 'mlp.w1'), 2, dim=2)
    return _linear(layers.elementwise_mul(up, layers.silu(gate)),
                   cfg.hidden, p, 'mlp.w2')


def decoder_block(x, i, carried, params, cfg):
    """Layer ``i`` on the stream [B, T, hidden]; ``carried`` holds the
    memory and the shared K / V once their layers have run."""
    kind = cfg.kinds[i]
    layer = params.at('phi4flash.%d.' % i)
    operator = layer.at(kind + '.')
    u = _layer_norm(x, layer, 'ln1', cfg)
    if kind == MAMBA:
        op, m = mamba_operator(u, operator, cfg)
        if i == cfg.layers // 2:
            carried['memory'] = m
    elif kind == GMU:
        op = gmu_operator(u, carried['memory'], operator, cfg)
    else:
        op, keys_values = attention_operator(
            u, i, kind, carried.get('shared'), operator, cfg)
        if kind == FULL:
            carried['shared'] = keys_values
    x = layers.elementwise_add(x, op)
    w = _layer_norm(x, layer, 'ln2', cfg)
    return layers.elementwise_add(x, gated_mlp(w, layer, cfg))


def build_pretrain(cfg=None, seq_len=8192, is_test=False, recompute=True):
    """Causal-LM pretraining: feeds ``ids``, ``labels`` ([B, seq_len]
    ints; labels are the ids shifted left, -1 where there is no next
    token; no positions: the model has no position encoding) -> (feeds,
    logits, loss): the next-token cross-entropy over the held
    vocabulary rows, averaged over every position but the last.  The
    head is the embedding table.  ``recompute``: every block a
    ``recompute_guard`` group."""
    cfg = cfg or BASE
    ids = layers.data('ids', shape=[seq_len], dtype='int64')
    labels = layers.data('labels', shape=[seq_len], dtype='int64')
    params = _Parameters(cfg)
    x = None
    carried = {}
    for i in range(cfg.layers):
        with recompute_guard() if recompute else \
                contextlib.nullcontext():
            if x is None:
                x = layers.embedding(
                    ids, size=[cfg.vocab_size, cfg.hidden],
                    param_attr=params.attr(EMBEDDING))
            x = decoder_block(x, i, carried, params, cfg)
    h = _layer_norm(x, params, 'phi4flash.ln_f', cfg)
    assert next(params.specs, None) is None
    table = fluid.default_main_program().global_block().var(EMBEDDING)
    logits = layers.matmul(h, table, transpose_y=True)      # tied
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
