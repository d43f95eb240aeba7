"""Xing4.0 (XingChen-AGI, ``model_type: xing4_0``): a routed decoder
whose residual is FOUR streams a token, mixed around every operator by
a per-token matrix that Sinkhorn normalisations bring to the doubly
stochastic ones (manifold-constrained hyper-connections, "mHC",
arXiv:2512.24880), with Moonlight's latent attention under a LOW-RANK
QUERY and YaRN, and a multi-token-prediction module that shares the
embedding, the final norm and the head.  ``BASE`` is Xing4.0-29B-A4B as
published
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B ``config.json``):
40 layers of hidden 3584, 32 heads; queries from a 768-wide normed
latent, keys and values from a 512-wide one, 192-wide queries and keys
(128 without position, 64 rotary, the rotary key shared by all heads)
over 128-wide values, YaRN factor 64 over 4096 positions; layers 0-1 a
dense gated MLP of width 9216, every later layer 64 routed experts of
width 1024 (top-4 of sigmoid scores plus a choice bias, the gates
renormalised and scaled by 2) beside one shared expert; ``hc_mult`` 4
streams, 20 Sinkhorn iterations; one prediction module; 131072 rows,
the head not tied.

Built from the fluid layer surface like the rest of the zoo:
``layers.hyper_connection_pre`` / ``_post`` around each operator (the
ops of ``ops/hyper_connection_ops.py``, float32 maps over a stream in
the program's type), ``models.moonlight.attention`` (the zoo's one
latent-attention helper) and ``gated_mlp``, ``layers.moe`` for ONE
CHIP'S SHARE of the routed experts.  Each decoder block, the module's
too, is a ``fluid.backward.recompute_guard`` group: a train step keeps
the [B, T, 4, hidden] stream between two blocks and computes a block's
inside again for its gradient.  The embedding, the final norm's gain
and the head are created ONCE, by name, and read twice in one program
(the embedding by ``ids`` and by the next tokens, the norm and head by
the main stack and by the module): their gradients are the sums of the
two uses.  What ``config.json`` does not settle is listed in
``models/reference/xing4.py``, the plain reference the tests hold this
to.

Under bf16 AMP the hyper-connection ops are cast by no list: r, the
three maps and the Sinkhorn loop are float32 inside them, the stream
bfloat16 from the first write-back on.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.fluid.backward import recompute_guard
from paddle_tpu.fluid.initializer import (Constant, Normal,
                                          NumpyArrayInitializer)
from paddle_tpu.fluid.layer_helper import LayerHelper

from . import moonlight as _moonlight

YARN = dict(factor=64.0, original_max_position_embeddings=4096,
            beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


class Xing4Config(object):
    def __init__(self, vocab_size=131072, hidden=3584, layers=40,
                 heads=32, qk_nope=128, qk_rope=64, v_dim=128,
                 kv_rank=512, q_rank=768, dense_layers=2,
                 dense_hidden=9216, expert_hidden=1024, shared_experts=1,
                 experts=64, top_k=4, routed_scale=2.0, renormalize=True,
                 experts_held=None, rms_eps=1e-6, rope_theta=10000.0,
                 yarn=YARN, hc_mult=4, hc_iters=20, hc_eps=1e-6,
                 hc_clamp=(-30.0, 30.0), hc_alpha_init=0.5,
                 hc_phi_std=1.0, hc_pre_init=1.0, hc_post_init=1.0,
                 hc_res_init=(1.5, 1.0), mtp_layers=1,
                 mtp_weight=0.3, bias_update_rate=0.001,
                 bias_init_std=0.0, init_std=0.02):
        self.vocab_size = vocab_size        # the rows held here
        self.hidden = hidden
        self.layers = layers                # the main stack's
        self.heads = heads
        self.qk_nope = qk_nope
        self.qk_rope = qk_rope
        self.v_dim = v_dim
        self.kv_rank = kv_rank              # kv_lora_rank
        self.q_rank = q_rank                # q_lora_rank
        self.dense_layers = dense_layers    # first_k_dense_replace
        self.dense_hidden = dense_hidden    # intermediate_size
        self.expert_hidden = expert_hidden  # moe_intermediate_size
        self.shared_experts = shared_experts
        self.experts = experts              # n_routed_experts
        self.top_k = top_k
        self.routed_scale = routed_scale
        self.renormalize = renormalize
        self.experts_held = experts_held    # (first, count); None: all
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.yarn = dict(yarn) if yarn else None    # rope_scaling
        self.hc_mult = hc_mult              # n, the rows of the stream
        self.hc_iters = hc_iters            # hc_sinkhorn_iters
        self.hc_eps = hc_eps
        self.hc_clamp = tuple(hc_clamp)     # mhc_h_res_clamp_min / _max
        # startup values of an operator's maps (``startup_bias`` has
        # b's): alpha's three scalars; the PARAMETER phi Normal(0,
        # this): it is stored at unit size and the op divides by
        # sqrt(n hidden), so at 1.0 a logit's dynamic part is alpha x
        # Normal(0, 1) a token
        self.hc_alpha_init = hc_alpha_init
        self.hc_phi_std = hc_phi_std
        self.hc_pre_init = hc_pre_init
        self.hc_post_init = hc_post_init
        self.hc_res_init = tuple(hc_res_init)   # (diagonal, above it)
        self.mtp_layers = mtp_layers        # num_nextn_predict_layers
        self.mtp_weight = mtp_weight        # lambda of L_main + lambda L_mtp
        self.bias_update_rate = bias_update_rate
        self.bias_init_std = bias_init_std
        self.init_std = init_std
        if mtp_layers not in (0, 1):
            raise ValueError('mtp_layers is 0 or 1, got %r' % (mtp_layers,))


BASE = Xing4Config()
# one dense layer and two sparse ones + the module, tiny widths; 8
# experts top-3; YaRN over 16 original positions
TINY = Xing4Config(
    vocab_size=97, hidden=64, layers=3, heads=4, qk_nope=16, qk_rope=8,
    v_dim=12, kv_rank=24, q_rank=20, dense_layers=1, dense_hidden=96,
    expert_hidden=32, experts=8, top_k=3, bias_init_std=0.05,
    yarn=dict(YARN, original_max_position_embeddings=16, factor=4.0))


def _param(name, shape, init):
    return layers.create_parameter(shape, 'float32', name='xing4_' + name,
                                   default_initializer=init)


def _norm(x, gain, cfg):
    """``layers.rms_norm`` over a gain that exists already."""
    helper = LayerHelper('rms_norm')
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op('rms_norm', inputs={'X': x, 'Scale': gain},
                     outputs={'Y': out}, attrs={'epsilon': cfg.rms_eps})
    return out


def _lookup(table, ids):
    helper = LayerHelper('embedding')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op('lookup_table_v2', inputs={'W': table, 'Ids': ids},
                     outputs={'Out': out}, attrs={'padding_idx': -1})
    return out


def startup_bias(cfg, k):
    """b's startup values for the step's ``k``-th operator,
    [n^2 + 2 n] float32: [H_pre | H_post | R~ row-major].  H_pre:
    +``hc_pre_init`` on row k mod n and minus it on the others (an
    operator reads mostly one row, the rows in turn, as
    Hyper-Connections start).  H_post: +``hc_post_init`` on rows k and
    k + 1 mod n and minus it on the other two (the operator writes
    mostly into two rows, so the rows of the stream DIFFER from the
    first operator on: rows that stay alike are left alike by every
    doubly stochastic H_res, and no loss would see that map).  R~:
    ``hc_res_init`` = (d, t), d on the diagonal and t above it: a row
    keeps most of itself and takes more from the rows after it than
    from those before."""
    n = cfg.hc_mult
    bias = np.zeros((n * n + 2 * n,), 'float32')
    bias[:n] = -cfg.hc_pre_init
    bias[k % n] = cfg.hc_pre_init
    bias[n:2 * n] = -cfg.hc_post_init
    bias[n + k % n] = bias[n + (k + 1) % n] = cfg.hc_post_init
    d, t = cfg.hc_res_init
    bias[2 * n:] = (d * np.eye(n) + t * np.triu(np.ones((n, n)), 1)).ravel()
    return bias


def hyper_connected(x, operator, cfg, errs):
    """x [B, T, n, hidden] -> H_res x + H_post^T operator(H_pre x)
    with the operator's own maps; ``errs`` collects H_res's distance
    from the doubly stochastic matrices (one an operator so far: its
    length is this operator's index)."""
    res = startup_bias(cfg, len(errs))
    u, carry, err = layers.hyper_connection_pre(
        x, sinkhorn_iters=cfg.hc_iters, epsilon=cfg.rms_eps,
        hc_eps=cfg.hc_eps, clamp=cfg.hc_clamp,
        param_attr=fluid.ParamAttr(
            initializer=Normal(0., cfg.hc_phi_std)),
        alpha_attr=fluid.ParamAttr(
            initializer=Constant(cfg.hc_alpha_init)),
        bias_attr=fluid.ParamAttr(
            initializer=NumpyArrayInitializer(res)))
    errs.append(err)
    return layers.hyper_connection_post(x, operator(u), carry)


def decoder_block(x, pos_ids, dense, cfg, errs):
    """One layer on the stream [B, T, n, hidden]."""
    x = hyper_connected(
        x, lambda u: _moonlight.attention(
            layers.rms_norm(u, epsilon=cfg.rms_eps), pos_ids, cfg),
        cfg, errs)
    if dense:
        return hyper_connected(
            x, lambda u: _moonlight.gated_mlp(
                layers.rms_norm(u, epsilon=cfg.rms_eps), cfg.dense_hidden,
                cfg), cfg, errs)

    def experts(u):
        w = layers.rms_norm(u, epsilon=cfg.rms_eps)
        routed, _ = layers.moe(
            w, num_experts=cfg.experts, hidden_size=cfg.expert_hidden,
            capacity_factor=None, top_k=cfg.top_k,
            renormalize=cfg.renormalize, gate_scale=cfg.routed_scale,
            experts_held=cfg.experts_held, aux_weight=0.0,
            score_func='sigmoid',
            score_bias=fluid.ParamAttr(
                initializer=Normal(0., cfg.bias_init_std)),
            bias_update_rate=cfg.bias_update_rate)
        shared = _moonlight.gated_mlp(
            w, cfg.shared_experts * cfg.expert_hidden, cfg)
        return layers.elementwise_add(shared, routed)

    return hyper_connected(x, experts, cfg, errs)


def _expand(h, cfg):
    """[B, T, hidden] -> the stream [B, T, n, hidden], n copies."""
    return layers.expand(layers.unsqueeze(h, [2]), [1, 1, cfg.hc_mult, 1])


def record(cfg):
    """``Program.watch``'s record on the runs that fetch: gauge
    ``mhc/stochastic_err`` (the largest |row or column sum of H_res -
    1| over layers and tokens) and, with a module, ``mtp/loss`` and
    ``mtp/loss_share`` = lambda L_mtp / L."""
    def read(values):
        values = [float(np.asarray(v).ravel()[0]) for v in values]
        if cfg.mtp_layers:
            module, total = values[-2:]
            values = values[:-2]
            monitor.set_gauge('mtp/loss', module)
            monitor.set_gauge(
                'mtp/loss_share',
                cfg.mtp_weight * module / total if total else 0.0)
        monitor.set_gauge('mhc/stochastic_err', max(values))
    return read


def _cross_entropy(logits, labels, positions, seq_len):
    """Mean over the ``positions`` of each sequence that carry a label
    (the others hold -1 and count 0)."""
    token_loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    return layers.scale(layers.mean(token_loss),
                        scale=seq_len / float(positions))


def build_pretrain(cfg=None, seq_len=4096, is_test=False):
    """Causal-LM pretraining with the prediction module: feeds ``ids``,
    ``pos_ids``, ``labels`` (the ids shifted left by one, -1 where
    there is no next token) and, with a module,
    ``labels_mtp`` (shifted by two, -1 at the last two positions:
    ``mtp_batch``) -> (feeds, (main logits, module logits or None),
    loss = L_main + mtp_weight x L_mtp): cross-entropies over the held
    vocabulary rows, L_main averaged over every position but the last,
    L_mtp over every position but the last two.  The module's input
    token at position i is ``labels[i]`` (t_{i+1}; row 0 where there is
    none: the causal mask keeps that position from every one that
    carries a module loss)."""
    cfg = cfg or BASE
    ids = layers.data('ids', shape=[seq_len], dtype='int64')
    pos = layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = layers.data('labels', shape=[seq_len], dtype='int64')
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    matrix = Normal(0., cfg.init_std)
    embedding = _param('embedding', [cfg.vocab_size, cfg.hidden], matrix)
    errs = []
    x = None
    for i in range(cfg.layers):
        with recompute_guard():
            if x is None:
                x = _expand(_lookup(embedding, ids), cfg)
            x = decoder_block(x, pos, i < cfg.dense_layers, cfg, errs)
    g_final = _param('g_final', [cfg.hidden], Constant(1.0))
    w_head = _param('w_head', [cfg.hidden, cfg.vocab_size], matrix)

    def head(stream):
        """-> (the stream summed over n, logits): the shared norm and
        head."""
        h = layers.reduce_sum(stream, dim=2)
        return h, layers.mul(_norm(h, g_final, cfg), w_head,
                             x_num_col_dims=2)

    h, logits = head(x)
    loss = main_loss = _cross_entropy(logits, labels, seq_len - 1, seq_len)
    module_logits, watched = None, []
    if cfg.mtp_layers:
        labels_mtp = layers.data('labels_mtp', shape=[seq_len],
                                 dtype='int64')
        feeds['labels_mtp'] = labels_mtp
        next_ids = layers.elementwise_max(
            labels, layers.fill_constant([1], 'int64', 0))
        with recompute_guard():
            joined = _moonlight._linear(layers.concat(
                [layers.rms_norm(_lookup(embedding, next_ids),
                                 epsilon=cfg.rms_eps),
                 layers.rms_norm(h, epsilon=cfg.rms_eps)], axis=2),
                cfg.hidden, cfg)
            x = decoder_block(_expand(joined, cfg), pos, False, cfg, errs)
        _, module_logits = head(x)
        module_loss = _cross_entropy(module_logits, labels_mtp,
                                     seq_len - 2, seq_len)
        loss = layers.elementwise_add(
            main_loss, layers.scale(module_loss, scale=cfg.mtp_weight))
        watched = [module_loss.name, loss.name]
    fluid.default_main_program().watch(
        [e.name for e in errs] + watched, record(cfg))
    return feeds, (logits, module_logits), loss


def mtp_batch(ids):
    """ids [B, T] ints -> the feeds of ``build_pretrain``: ids,
    positions, the labels shifted by one and by two (-1 where the
    sequence has no such token)."""
    ids = np.asarray(ids)
    b, t = ids.shape
    labels = np.full((b, t), -1, ids.dtype)
    labels[:, :-1] = ids[:, 1:]
    labels_mtp = np.full((b, t), -1, ids.dtype)
    labels_mtp[:, :-2] = ids[:, 2:]
    return {'ids': ids, 'pos_ids': np.tile(np.arange(t, dtype=ids.dtype),
                                           (b, 1)),
            'labels': labels, 'labels_mtp': labels_mtp}
