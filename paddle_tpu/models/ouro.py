"""Ouro (ByteDance, ``model_type: ouro``): a LOOPED language model.  The
whole stack of layers is applied ``total_ut_steps`` times with THE SAME
weights; after each pass the shared last norm and head give logits and
a one-output gate gives the probability of stopping there; the
training loss is the expectation of the passes' cross-entropies under
that exit distribution less an entropy bonus.  ``BASE`` is Ouro-2.6B
as published (https://huggingface.co/ByteDance/Ouro-2.6B
``config.json``): 48 layers of hidden 2048, 16 heads of 128 with as
many K/V heads, a SiLU-gated MLP of width 5632, sandwich RMSNorms (one
before and one AFTER each operator), rotary theta 1e6, 49152 rows, the
head not tied, four passes.

Built from the fluid layer surface like the rest of the zoo, and the
first model of it whose graph is not a straight line: the passes are
ONE ``layers.While`` (``max_trip_count = total_ut_steps``) whose
sub-block holds the layers, the last norm, the head, the gate and the
running per-token sums the loss needs.  Every parameter is created
once, before the loop, in the order the plain reference takes them
(``models/reference/ouro.py``, whose docstring has the equations and
what the config leaves to be assumed); the sub-block reads them as the
``while`` op's inputs, and a shared layer's gradient is the sum over
the trips.  ``build_pretrain(..., unrolled=True)`` writes the same
passes as a Python ``for`` over the same parameters: the straight-line
oracle the tests hold the loop to.

Under bf16 AMP the head's product is float32 out of bfloat16 operands
(``mixed_precision.float32_output``) and the gate, its sigmoid and the
exit distribution are float32 (``keep_float32`` on the gate's product;
what follows it meets no bfloat16 operand).
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.contrib.mixed_precision import (float32_output,
                                                       keep_float32)
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.layer_helper import LayerHelper

from . import bert as _bert
from . import gpt as _gpt
from .reference.ouro import LOG_FLOOR


class OuroConfig(object):
    def __init__(self, vocab_size=49152, hidden=2048, layers=48, heads=16,
                 intermediate=5632, steps=4, max_pos=65536, rms_eps=1e-6,
                 rope_theta=1e6, entropy_weight=0.1, init_std=0.02,
                 use_flash=True):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers                # num_hidden_layers
        self.heads = heads                  # = num_key_value_heads
        self.intermediate = intermediate
        self.steps = steps                  # total_ut_steps
        self.max_pos = max_pos
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.entropy_weight = entropy_weight    # beta
        self.init_std = init_std
        self.use_flash = use_flash
        self.flash_min_len = 512
        self.dropout = 0.0
        self.attn_dropout = 0.0


BASE = OuroConfig()
TINY = OuroConfig(vocab_size=97, hidden=64, layers=2, heads=4,
                  intermediate=96, steps=4, max_pos=128)


class Weights(object):
    """The model's parameters, created once and in the reference's
    order: embedding; per layer g1, Wq, Wk, Wv, Wo, g2, g3, Wg, Wu, Wd,
    g4; g_f; W_head; w_g; b_g."""

    def __init__(self, cfg):
        h, m = cfg.hidden, cfg.intermediate
        matrix = Normal(0., cfg.init_std)

        def param(name, shape, init):
            return layers.create_parameter(
                shape, 'float32', name='ouro_' + name,
                default_initializer=init)

        self.embedding = param('embedding', [cfg.vocab_size, h], matrix)
        self.layers = []
        for i in range(cfg.layers):
            shapes = [('g1', [h]), ('wq', [h, h]), ('wk', [h, h]),
                      ('wv', [h, h]), ('wo', [h, h]), ('g2', [h]),
                      ('g3', [h]), ('wg', [h, m]), ('wu', [h, m]),
                      ('wd', [m, h]), ('g4', [h])]
            self.layers.append({
                name: param('l%d_%s' % (i, name), shape,
                            Constant(1.0) if len(shape) == 1 else matrix)
                for name, shape in shapes})
        self.g_f = param('g_f', [h], Constant(1.0))
        self.w_head = param('w_head', [h, cfg.vocab_size], matrix)
        self.w_gate = param('w_gate', [h, 1], matrix)
        self.b_gate = param('b_gate', [1], Constant(0.0))


def _linear(x, w):
    return layers.mul(x, w, x_num_col_dims=2)


def _norm(x, gain, cfg):
    """``layers.rms_norm`` over a gain that exists already."""
    helper = LayerHelper('rms_norm')
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op('rms_norm', inputs={'X': x, 'Scale': gain},
                     outputs={'Y': out}, attrs={'epsilon': cfg.rms_eps})
    return out


def decoder_layer(x, pos_ids, w, cfg, is_test):
    """x + N2(A(N1(x))), then + N4(M(N3(.))): causal attention with a
    rotary embedding over each whole head, the SiLU-gated MLP, a norm
    before and after each.

    The ``recompute_guard`` groups say what a pass keeps for its
    gradient: a scan's residuals are whatever the trace of its body
    names, out of the compiler's reach, and jax's choice for a norm is
    a float32 copy of its input.  Kept, in bfloat16 under AMP: each
    norm's input, q, k, v and the context (the flash kernels' own),
    the two 5632-wide products.  Computed again: the norms and
    silu(gate) * up, a few passes over [T, hidden]; no product is (a
    matmul's output is no input of its gradient)."""
    h, heads = cfg.hidden, cfg.heads
    with recompute_guard():
        u = _norm(x, w['g1'], cfg)
        q, k, v = (_linear(u, w[n]) for n in ('wq', 'wk', 'wv'))
    q, k, v = (layers.reshape(t, [0, 0, heads, h // heads])
               for t in (q, k, v))
    q, k = layers.rotary_embedding(q, k, pos_ids, theta=cfg.rope_theta)
    ctx = _bert.scaled_dot_product_attention(q, k, v, x, cfg, is_test,
                                             causal=True)
    a = _linear(ctx, w['wo'])
    with recompute_guard():
        a = _norm(a, w['g2'], cfg)
    x = layers.elementwise_add(x, a)
    with recompute_guard():
        u = _norm(x, w['g3'], cfg)
        gate, up = _linear(u, w['wg']), _linear(u, w['wu'])
    with recompute_guard():
        m = _linear(layers.elementwise_mul(layers.silu(gate), up),
                    w['wd'])
    with recompute_guard():
        m = _norm(m, w['g4'], cfg)
    return layers.elementwise_add(x, m)


def one_pass(x, pos_ids, labels3, last, state, weights, cfg, is_test):
    """One pass of the stack and its exit -> (h_t, the new state).
    ``last`` is 1.0 on the pass that takes the remaining mass, else 0.0
    ([1] float32); ``state`` the running per-token sums, [B, T, 1]
    float32: survive = prod (1 - lam_j), expected = sum p_t ce_t,
    neg_entropy = sum p_t log p_t, and p_t itself."""
    for w in weights.layers:
        x = decoder_layer(x, pos_ids, w, cfg, is_test)
    with recompute_guard():
        h = _norm(x, weights.g_f, cfg)
    # [B, T, vocab] float32 a pass is what the loss's gradient reads:
    # the backward pass multiplies the head again instead of keeping
    # the passes' logits (3.2 GB of the chip's 16 at the published
    # sizes)
    with recompute_guard():
        logits = float32_output(_linear(h, weights.w_head))
        ce = layers.softmax_with_cross_entropy(logits, labels3,
                                               ignore_index=-1)
    lam = layers.sigmoid(layers.elementwise_add(
        keep_float32(_linear(h, weights.w_gate)), weights.b_gate))
    # p = survive * lam, and all of survive on the last pass
    p = layers.elementwise_mul(state['survive'], layers.elementwise_add(
        lam, layers.elementwise_mul(
            layers.scale(lam, scale=-1.0, bias=1.0), last)))
    return h, {
        'survive': layers.elementwise_mul(
            state['survive'], layers.scale(lam, scale=-1.0, bias=1.0)),
        'expected': layers.elementwise_add(
            state['expected'], layers.elementwise_mul(p, ce)),
        'neg_entropy': layers.elementwise_add(
            state['neg_entropy'], layers.elementwise_mul(
                p, layers.log(layers.clip(p, LOG_FLOOR, 1.0)))),
        'p': p}


STATE = ('survive', 'expected', 'neg_entropy', 'p')


def record_exit(values):
    """``ouro/exit_entropy``: the mean entropy of the exit distribution
    over the batch's positions (nats; ln R at most, 0 when the gate has
    collapsed); ``ouro/exit_mass_last``: the mean probability of
    running all the passes.  Read on the runs that fetch
    (``Program.watch``)."""
    entropy, mass = (float(np.asarray(v).ravel()[0]) for v in values)
    monitor.set_gauge('ouro/exit_entropy', entropy)
    monitor.set_gauge('ouro/exit_mass_last', mass)


def build_pretrain(cfg=None, seq_len=4096, is_test=False, unrolled=False):
    """Looped causal-LM pretraining: feeds ``ids``, ``pos_ids``,
    ``labels`` ([B, seq_len] ints; labels are the ids shifted left, -1
    where there is no next token: ``lm_batch``) -> (feeds, the last
    pass's normed hidden states h_R [B, seq_len, hidden], loss).  The
    loss is the mean over the positions that have a label of
    sum_t p_t ce_t - beta H(p).  ``unrolled`` writes
    the passes as a Python loop over the same parameters instead of one
    ``While``: the same numbers, a program ``steps`` times as long."""
    cfg = cfg or BASE
    if seq_len > cfg.max_pos:
        raise ValueError('%d positions; the model declares %d'
                         % (seq_len, cfg.max_pos))
    ids = layers.data('ids', shape=[seq_len], dtype='int64')
    pos = layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = layers.data('labels', shape=[seq_len], dtype='int64')
    labels3 = layers.unsqueeze(labels, [2])
    weights = Weights(cfg)
    helper = LayerHelper('embedding')
    x = helper.create_variable_for_type_inference('float32')
    helper.append_op('lookup_table_v2',
                     inputs={'W': weights.embedding, 'Ids': ids},
                     outputs={'Out': x}, attrs={'padding_idx': -1})

    def filled(value):
        return layers.fill_constant_batch_size_like(
            ids, [-1, seq_len, 1], 'float32', value)

    state = {'survive': filled(1.0), 'expected': filled(0.0),
             'neg_entropy': filled(0.0), 'p': filled(0.0)}
    if unrolled:
        for t in range(cfg.steps):
            last = layers.fill_constant(
                [1], 'float32', float(t == cfg.steps - 1))
            x, state = one_pass(x, pos, labels3, last, state, weights,
                                cfg, is_test)
    else:
        trip = layers.fill_constant([1], 'int64', 0)
        steps = layers.fill_constant([1], 'int64', cfg.steps)
        final = layers.fill_constant([1], 'int64', cfg.steps - 1)
        going = layers.less_than(trip, steps)
        loop = layers.While(going, max_trip_count=cfg.steps)
        with loop.block():
            last = layers.cast(layers.equal(trip, final), 'float32')
            h, new = one_pass(x, pos, labels3, last, state, weights, cfg,
                              is_test)
            layers.assign(h, x)
            for name in STATE:
                layers.assign(new[name], state[name])
            layers.increment(trip, 1.0)
            layers.less_than(trip, steps, cond=going)
    valid = layers.cast(layers.greater_equal(
        labels3, layers.fill_constant([1], 'int64', 0)), 'float32')
    per_token = layers.elementwise_add(
        state['expected'],
        layers.scale(state['neg_entropy'], scale=cfg.entropy_weight))
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(
        layers.mean(layers.elementwise_mul(per_token, valid)),
        scale=seq_len / (seq_len - 1.0))
    entropy = layers.scale(layers.mean(state['neg_entropy']), scale=-1.0)
    fluid.default_main_program().watch(
        [entropy.name, layers.mean(state['p']).name], record_exit)
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    return feeds, x, loss


lm_batch = _gpt.lm_batch
synthetic_batch = _gpt.synthetic_batch
