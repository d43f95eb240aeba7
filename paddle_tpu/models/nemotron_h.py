"""Nemotron-H (NVIDIA, ``model_type: nemotron_h``; arXiv:2504.03624): a
decoder whose every layer is ONE mixer under one pre-norm, ``x = x +
mixer(rms_norm(x))``, the mixer's KIND read from a pattern string:
``M`` Mamba-2 (state-space duality, arXiv:2405.21060), ``E`` a routed
feed-forward of squared-ReLU experts WITHOUT a gate beside one shared
expert, ``*`` grouped-query attention with NO position encoding.  No
layer pairs an operator with a feed-forward part.  ``BASE`` is
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json``): 52 layers of hidden 2688 (23 ``M``, 23 ``E``, 6
``*``); Mamba-2 at 64 heads of 64 (inner width 4096, NOT ``expand`` x
hidden) with a [64, 128] state a head, the write and read vectors in 8
groups of 8 heads, a 4-tap filter with a bias, chunks of 128; 128
routed experts of width 1856 (top-6 of sigmoid scores plus a choice
bias over ONE group, the gates the plain scores renormalised and
scaled by 2.5) beside a shared expert of width 3712; 32 query heads of
128 over 2 K/V heads; 131072 rows, the head not tied.

Built from the fluid layer surface like the rest of the zoo:
``layers.short_conv`` with the filter's bias fused in,
``layers.ssd_scan`` (``ops/ssd_ops.py``: the recurrence as matrix
products over chunks), ``layers.rms_norm(gain_axes=2)`` for the grouped
norm of ``y * silu(z)``, ``layers.flash_attention`` at 16 queries a K/V
head, ``layers.moe(capacity_factor=None, score_func='sigmoid',
score_bias=..., expert_form='relu2', experts_held=...)`` for ONE CHIP'S
SHARE of the routed experts.  The leading ``recompute_blocks`` layers
are ``fluid.backward.recompute_guard`` groups.  ``parameter_specs``
lists every parameter in creation order; what ``config.json`` does not
settle is listed in ``models/reference/nemotron_h.py``, the plain
reference the tests hold this to.

Under bf16 AMP the steps (``delta``), the decays, the scan's state, the
router, the norms' statistics, the softmax statistics and the
cross-entropy are float32; x, B, C, the filter's output and the
attention operands are bfloat16.
"""

import contextlib
import math

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.contrib.mixed_precision import keep_float32
from paddle_tpu.fluid.initializer import (Constant, Initializer, Normal,
                                          NumpyArrayInitializer, Uniform)

from . import gpt as _gpt

MAMBA, MOE, ATTENTION = 'mamba', 'moe', 'attention'
LETTERS = {'M': MAMBA, 'E': MOE, '*': ATTENTION}
PATTERN = 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'
INIT_STD = 0.02     # every matrix's startup Normal(0, .): the lineage's


def layer_kinds(pattern):
    """``hybrid_override_pattern`` -> [mixer kind] by layer, parsed
    once; a letter this model does not know raises."""
    unknown = sorted(set(pattern) - set(LETTERS))
    if unknown or not pattern:
        raise ValueError(
            'nemotron_h: the pattern %r holds %s; a layer is one of %s'
            % (pattern, ', '.join(repr(c) for c in unknown) or 'no layer',
               ', '.join('%r (%s)' % item for item in LETTERS.items())))
    return [LETTERS[c] for c in pattern]


class NemotronHConfig(object):
    def __init__(self, vocab_size=131072, hidden=2688, pattern=PATTERN,
                 mamba_heads=64, mamba_head_dim=64, groups=8, states=128,
                 conv_kernel=4, chunk=128, heads=32, kv_heads=2,
                 head_dim=128, experts=128, top_k=6, expert_hidden=1856,
                 shared_hidden=3712, routed_scale=2.5, renormalize=True,
                 experts_held=None, rms_eps=1e-5,
                 time_step=(0.001, 0.1, 1e-4), bias_update_rate=0.001,
                 bias_init_std=0.0, embed_std=None,
                 residual_layers=None, recompute_blocks=None):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.pattern = pattern              # the layers run, a letter each
        self.kinds = layer_kinds(pattern)
        self.mamba_heads = mamba_heads      # mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.groups = groups                # n_groups: B / C pairs
        self.states = states                # ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk = chunk                  # chunk_size
        self.heads = heads                  # num_attention_heads
        self.kv_heads = kv_heads            # num_key_value_heads
        self.head_dim = head_dim
        self.experts = experts              # n_routed_experts
        self.top_k = top_k                  # num_experts_per_tok
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        # moe_shared_expert_intermediate_size x n_shared_experts
        self.shared_hidden = shared_hidden
        self.routed_scale = routed_scale    # routed_scaling_factor
        self.renormalize = renormalize      # norm_topk_prob
        # (first, count) of the routed experts this chip holds; None:
        # all of them
        self.experts_held = experts_held
        self.rms_eps = rms_eps              # layer_norm_epsilon
        # time_step_min, time_step_max, time_step_floor: the startup
        # steps softplus(dt_bias) are log-uniform in [min, max], at
        # least floor
        self.time_step = tuple(time_step)
        # gamma of b += gamma * sign(mean load - load); 0: a bias that
        # stays as the startup program drew it
        self.bias_update_rate = bias_update_rate
        self.bias_init_std = bias_init_std
        # the table's rows start Normal(0, this); None: INIT_STD
        self.embed_std = INIT_STD if embed_std is None else embed_std
        # rescale_prenorm_residual: a Mamba-2 mixer's W_out starts at
        # INIT_STD / sqrt(this), the MODEL's depth wherever the run is
        # cut (default: the layers run)
        self.residual_layers = residual_layers or len(self.kinds)
        # how many leading layers are recompute groups; None: every
        # layer but the last run
        self.recompute_blocks = recompute_blocks
        if mamba_heads % groups or heads % kv_heads:
            raise ValueError(
                'nemotron_h: %d Mamba-2 heads in %d groups, %d query '
                'heads over %d K/V heads' % (mamba_heads, groups, heads,
                                             kv_heads))

    @property
    def inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):                     # [x | B | C]
        return self.inner + 2 * self.groups * self.states

    @property
    def experts_here(self):
        return self.experts if self.experts_held is None \
            else self.experts_held[1]


BASE = NemotronHConfig()
# every kind of layer, tiny widths: 8 heads in 2 groups (4 heads a
# group), GQA 4 over 2, 8 experts top-3
TINY = NemotronHConfig(
    vocab_size=97, hidden=32, pattern='ME*ME', mamba_heads=8,
    mamba_head_dim=4, groups=2, states=6, chunk=8, heads=4, kv_heads=2,
    head_dim=8, experts=8, top_k=3, expert_hidden=24, shared_hidden=40,
    bias_init_std=0.05)


class _InverseSoftplusSteps(Initializer):
    """dt_bias: the inverse softplus of steps drawn log-uniform in
    [low, high] and no less than floor, ``dt + log(1 - exp(-dt))``."""

    def __init__(self, low, high, floor):
        self.low, self.high, self.floor = low, high, floor

    def __call__(self, var, block):
        def new(what):
            return block.create_var(name='%s.%s' % (var.name, what),
                                    shape=var.shape, dtype=var.dtype)

        def op(kind, x, out, **attrs):
            block.append_op(kind, inputs={'X': x}, outputs={'Out': out},
                            attrs=attrs)

        steps, rest = new('dt'), new('rest')
        Uniform(math.log(self.low), math.log(self.high))(steps, block)
        dt, rest = steps.name, rest.name
        op('exp', dt, dt)
        op('clip', dt, dt, min=self.floor, max=float(self.high))
        op('scale', dt, rest, scale=-1.0)
        op('exp', rest, rest)
        op('scale', rest, rest, scale=-1.0, bias=1.0)
        op('log', rest, rest)
        return block.append_op(
            'elementwise_add', inputs={'X': dt, 'Y': rest},
            outputs={'Out': var.name}, attrs={'axis': -1})


def mixer_specs(cfg, kind):
    """[(what, shape, initializer)] of one layer's mixer, in creation
    order."""
    matrix = Normal(0., INIT_STD)
    d, h = cfg.hidden, cfg.mamba_heads
    if kind == MAMBA:
        # PyTorch's Conv1d default at a fan-in of conv_kernel, bias too
        bound = cfg.conv_kernel ** -0.5
        return [('w_in', [d, cfg.inner + cfg.conv_dim + h], matrix),
                ('conv_w', [cfg.conv_dim, cfg.conv_kernel],
                 Uniform(-bound, bound)),
                ('conv_b', [cfg.conv_dim], Uniform(-bound, bound)),
                ('dt_bias', [h], _InverseSoftplusSteps(*cfg.time_step)),
                ('a_log', [h], NumpyArrayInitializer(
                    np.log(np.arange(1, h + 1, dtype='float32')))),
                ('d', [h], Constant(1.0)),
                ('norm_g', [cfg.groups, cfg.inner // cfg.groups],
                 Constant(1.0)),
                ('w_out', [cfg.inner, d], Normal(
                    0., INIT_STD / math.sqrt(cfg.residual_layers)))]
    if kind == ATTENTION:
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        return [('wq', [d, q], matrix), ('wk', [d, kv], matrix),
                ('wv', [d, kv], matrix), ('wo', [q, d], matrix)]
    # router, up and down are ``layers.moe``'s own: Normal(0, 0.02)
    here, w, moe = cfg.experts_here, cfg.expert_hidden, Normal(0., INIT_STD)
    return [('router', [d, cfg.experts], moe),
            ('up', [here, d, w], moe), ('down', [here, w, d], moe),
            ('choice_bias', [cfg.experts], Normal(0., cfg.bias_init_std)),
            ('shared_up', [d, cfg.shared_hidden], matrix),
            ('shared_down', [cfg.shared_hidden, d], matrix)]


def parameter_specs(cfg):
    """[(label, shape)] of every parameter in creation order (the
    non-trainable choice biases among them): the table; each layer's
    norm gain, then its mixer's (``mixer_specs``); the last norm's gain;
    the head."""
    specs = [('embedding', [cfg.vocab_size, cfg.hidden])]
    for i, kind in enumerate(cfg.kinds):
        specs.append(('%d.norm_g' % i, [cfg.hidden]))
        specs += [('%d.%s.%s' % (i, kind, what), shape)
                  for what, shape, _ in mixer_specs(cfg, kind)]
    return specs + [('norm_f', [cfg.hidden]),
                    ('head', [cfg.hidden, cfg.vocab_size])]


def _initializers(cfg, kind):
    return {what: init for what, _, init in mixer_specs(cfg, kind)}


def _linear(x, size, init):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(initializer=init))


def _parameter(shape, init):
    return layers.create_parameter(shape, 'float32',
                                   default_initializer=init)


def mamba2_mixer(u, cfg):
    """[z | xBC | dt] = u W_in; xBC through the filter and SiLU; the
    recurrence at float32 steps and decays; the grouped norm of y *
    silu(z); W_out."""
    init = _initializers(cfg, MAMBA)
    h, p, g, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.groups, cfg.states
    z, xbc, dt = layers.split(
        _linear(u, cfg.inner + cfg.conv_dim + h, init['w_in']),
        [cfg.inner, cfg.conv_dim, h], dim=2)
    xbc = layers.silu(layers.short_conv(
        xbc, cfg.conv_kernel,
        param_attr=fluid.ParamAttr(initializer=init['conv_w']),
        bias_attr=fluid.ParamAttr(initializer=init['conv_b'])))
    x, b, c = layers.split(xbc, [cfg.inner, g * n, g * n], dim=2)
    delta = layers.softplus(keep_float32(layers.elementwise_add(
        dt, _parameter([h], init['dt_bias']), axis=2)))
    a = layers.scale(layers.exp(_parameter([h], init['a_log'])),
                     scale=-1.0)
    y = layers.ssd_scan(
        layers.reshape(x, [0, 0, h, p]), delta, a,
        layers.reshape(b, [0, 0, g, n]), layers.reshape(c, [0, 0, g, n]),
        _parameter([h], init['d']), chunk=cfg.chunk)
    gated = layers.elementwise_mul(layers.reshape(y, [0, 0, cfg.inner]),
                                   layers.silu(z))
    normed = layers.rms_norm(
        layers.reshape(gated, [0, 0, g, cfg.inner // g]),
        epsilon=cfg.rms_eps, gain_axes=2,
        param_attr=fluid.ParamAttr(initializer=init['norm_g']))
    return _linear(layers.reshape(normed, [0, 0, cfg.inner]), cfg.hidden,
                   init['w_out'])


def attention_mixer(u, cfg):
    """Causal grouped-query attention, nothing rotated."""
    init = _initializers(cfg, ATTENTION)
    d, h, kv = cfg.head_dim, cfg.heads, cfg.kv_heads
    q = layers.reshape(_linear(u, h * d, init['wq']), [0, 0, h, d])
    k = layers.reshape(_linear(u, kv * d, init['wk']), [0, 0, kv, d])
    v = layers.reshape(_linear(u, kv * d, init['wv']), [0, 0, kv, d])
    ctx = layers.flash_attention(q, k, v, causal=True)
    return _linear(layers.reshape(ctx, [0, 0, h * d]), cfg.hidden,
                   init['wo'])


def relu2_mlp(u, width, cfg, up, down):
    """down(relu(up u)^2)."""
    hidden = layers.relu(_linear(u, width, up))
    return _linear(layers.elementwise_mul(hidden, hidden), cfg.hidden,
                   down)


def moe_mixer(u, cfg):
    """ONE CHIP'S SHARE of the routed sum, plus the shared expert."""
    init = _initializers(cfg, MOE)
    routed, _ = layers.moe(
        u, num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
        capacity_factor=None, top_k=cfg.top_k,
        renormalize=cfg.renormalize, gate_scale=cfg.routed_scale,
        experts_held=cfg.experts_held, aux_weight=0.0,
        score_func='sigmoid', expert_form='relu2',
        score_bias=fluid.ParamAttr(initializer=init['choice_bias']),
        bias_update_rate=cfg.bias_update_rate)
    shared = relu2_mlp(u, cfg.shared_hidden, cfg, init['shared_up'],
                       init['shared_down'])
    return layers.elementwise_add(shared, routed)


def decoder_block(x, kind, cfg):
    u = layers.rms_norm(x, epsilon=cfg.rms_eps)
    mixer = {MAMBA: mamba2_mixer, ATTENTION: attention_mixer,
             MOE: moe_mixer}[kind]
    return layers.elementwise_add(x, mixer(u, cfg))


def build_pretrain(cfg=None, seq_len=8192, is_test=False):
    """Causal-LM pretraining: feeds ``ids``, ``labels`` ([B, seq_len]
    ints; labels are the ids shifted left, -1 where there is no next
    token; no positions: no position enters the model) -> (feeds,
    logits, loss): the next-token cross-entropy over the held
    vocabulary rows, averaged over every position but the last.  No
    auxiliary loss: the choice bias is the balancing.  The first
    ``cfg.recompute_blocks`` layers (by default every layer but the
    last) are ``recompute_guard`` groups."""
    cfg = cfg or BASE
    ids = layers.data('ids', shape=[seq_len], dtype='int64')
    labels = layers.data('labels', shape=[seq_len], dtype='int64')
    matrix = Normal(0., INIT_STD)
    groups = len(cfg.kinds) - 1 if cfg.recompute_blocks is None \
        else cfg.recompute_blocks
    x = None
    for i, kind in enumerate(cfg.kinds):
        with recompute_guard() if i < groups else contextlib.nullcontext():
            if x is None:
                x = layers.embedding(
                    ids, size=[cfg.vocab_size, cfg.hidden],
                    param_attr=fluid.ParamAttr(
                        initializer=Normal(0., cfg.embed_std)))
            x = decoder_block(x, kind, cfg)
    h = layers.rms_norm(x, epsilon=cfg.rms_eps)
    logits = _linear(h, cfg.vocab_size, matrix)             # not tied
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    # the last position of each sequence carries no label and counts
    # 0: the mean over all T is the mean over T - 1 times (T - 1) / T
    loss = layers.scale(layers.mean(token_loss),
                        scale=seq_len / (seq_len - 1.0))
    created = [list(p.shape) for p in
               fluid.default_main_program().all_parameters()]
    assert created == [shape for _, shape in parameter_specs(cfg)], \
        'parameter_specs is out of step with the program'
    return {'ids': ids, 'labels': labels}, logits, loss


def synthetic_batch(cfg, batch, seq_len, rng):
    feed = _gpt.synthetic_batch(cfg, batch, seq_len, rng)
    return {'ids': feed['ids'], 'labels': feed['labels']}
