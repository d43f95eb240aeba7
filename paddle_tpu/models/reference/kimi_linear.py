"""Kimi-Linear-48B-A3B-Instruct (moonshotai, ``model_type:
kimi_linear``) in plain ``jax.numpy``, float32,
``default_matmul_precision('highest')``: forward, loss and, through
``jax.grad``, gradients.  No kernel, no chunk, no sort: the delta
rule's state is carried TOKEN BY TOKEN by a ``lax.scan``
(``reference/solar_open2.py`` ``kda_operator``, the same equations at
other sizes), the latent layers use dense [T, T] masks one head at a
time, the experts are a Python loop (``reference/moonlight.py``
``routed_share``).  It imports nothing of the zoo or of fluid.

One layer, on the block input ``x`` [B, T, 2304] (RMSNorm eps 1e-5, a
gain each, no bias anywhere); layers are numbered from 1, as
``linear_attn_config`` numbers them:

    u = rms_norm(x, g_op);  h = x + Op(u)
    w = rms_norm(h, g_ffn); y = h + FF(w)

``Op`` of the ``kda_layers`` (20 of 27): the gated delta rule with a
per-channel decay at 32 heads of d = 128, as Solar Open 2's delta-rule
layers but for beta:

    q, k, v = silu(conv4(u W))            causal depthwise 4-tap filters
    q_h = q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2;  k_h likewise without d
    a_t = -exp(A_log_h) * softplus((u Wf_down) Wf_up + dt_bias)   in R^128
    beta_t = sigmoid(u Wb)_h              NO factor 2 (the config has no
                                          ``kda_allow_neg_eigval`` key)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t
    Op = concat_h(rms_norm(o_h, g_o) * sigmoid(((u Wg_down) Wg_up)_h)) Wo

``Op`` of the ``full_attn_layers`` (4, 8, ... 24, 27): latent attention
WITHOUT position encoding (``mla_use_nope`` true, ``rope_scaling`` and
``q_lora_rank`` null; ``rope_theta`` is read by no layer):

    q = u Wq                              [T, 32, 192]
    [c | k_r] = u Wkva                    512 + 64 wide
    [k_nope | v] = rms_norm(c, g_latent) Wkvb     [T, 32, 128 + 128]
    k_h = [k_nope_h | k_r]                k_r ONE 64-wide vector for all
                                          heads, as projected: nothing
                                          is rotated, on either side
    scores q_h . k_h / sqrt(192), causal, softmax in float32, o_h = p_h v_h
    Op = concat_h(o_h) Wo                 Wo [32 x 128, 2304]

``FF`` of layer 1 (``first_k_dense_replace`` 1): down(silu(gate w) *
up w) of width 9216.  Of layers 2 to 27, the router in float32:

    s = sigmoid(w Wr)                     over 256 experts
    chosen = top-8 of (s + b)             b: 256 floats a layer, a buffer
                                          (``num_expert_group`` =
                                          ``topk_group`` = 1: the group
                                          step is the identity)
    g_i = s_i / (sum over the chosen of s_j + 1e-20) x 2.446
    FF = shared(w) + sum over the chosen i of g_i E_i(w)
    E_i and the one shared expert: gated MLPs of width 1024

    logits = rms_norm(x, g_final) W_head  (head not tied)

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end).

THE BIAS takes no gradient and after each train step moves by
``reference/moonlight.py`` ``bias_update``: b += gamma * sign(mean load
- load).

THE SHARE.  ``experts_held = (first, count)`` gives this copy the
routed experts first .. first + count - 1; the router and its bias stay
256 wide and pick top-8 of all; what the absent experts would have
added is left out, and that partial result goes on to the next layer.
The vocabulary slice is simply a smaller vocabulary.  All heads of both
layer kinds are here.  The layers run are ``first_layer .. first_layer
+ layers - 1`` of the model, each with the operator and the MLP its own
index gives it.

What ``config.json`` (the catalog's row) does not settle, as this file
and ``paddle_tpu/models/kimi_linear.py`` read it (``assumed`` in the
benchmark's configuration file gives the reasons): the delta-rule
layer's details that ``linear_attn_config`` only sizes (Solar Open 2's
reading of the same published mechanism); that the 64-wide key slice
stays in the product unrotated under ``mla_use_nope``; the router's
choice bias and how it moves; a last norm.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.kimi_linear.build_pretrain`` creates its TRAINABLE
parameters: embedding; per layer g_op, then Wq, filter_q [C, 4], Wk,
filter_k, Wv, filter_v, Wf_down, Wf_up, A_log [H], dt_bias [H x 128],
Wb, g_o [128], Wg_down, Wg_up, Wo (delta rule) or Wq, Wkva, g_latent,
Wkvb, Wo (latent); g_ffn, then gate, up, down (layer 1) or Wr, gate
[count, D, W], up, down [count, W, D], shared gate, shared up, shared
down; g_final; W_head.  ``biases``: one [256] array a sparse layer.
"""

import jax
import jax.numpy as jnp

from .moonlight import gated_mlp, rms_norm, routed_share
from .solar_open2 import REMAT_BLOCK, kda_operator


def nope_attention(u, wq, wkva, g_latent, wkvb, wo, sizes, remat=False):
    """Latent attention with no position encoding."""
    b, t, _ = u.shape
    heads, nope, rope, dv, rank = (sizes[n] for n in (
        'heads', 'qk_nope', 'qk_rope', 'v_dim', 'kv_rank'))
    q = (u @ wq).reshape(b, t, heads, nope + rope)
    kva = u @ wkva
    latent = rms_norm(kva[..., :rank], g_latent, sizes['rms_eps'])
    kv = (latent @ wkvb).reshape(b, t, heads, nope + dv)
    k_shared = kva[..., rank:]                          # [B, T, rope]
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = (nope + rope) ** -0.5

    def one_head(qh, kn, v):
        """qh [B, T, nope + rope], kn [B, T, nope], v [B, T, dv]."""
        kh = jnp.concatenate([kn, k_shared], -1)
        scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * scale
        probs = jax.nn.softmax(
            jnp.where(visible, scores, -jnp.inf).astype(jnp.float32),
            -1).astype(qh.dtype)
        return jnp.einsum('bqk,bkd->bqd', probs, v)

    if remat:
        one_head = jax.checkpoint(one_head)
    context = jnp.stack(
        [one_head(q[:, :, h], kv[:, :, h, :nope], kv[:, :, h, nope:])
         for h in range(heads)], 2)
    return context.reshape(b, t, heads * dv) @ wo


def forward(params, biases, ids, *, sizes, dtype=jnp.float32,
            remat=False, chosen=None):
    """-> (logits [B, T, V], [expert loads [E] per sparse layer]).
    ``sizes``: ``sizes_of``'s dict.  ``dtype`` other than float32
    computes EVERYTHING in it, the decays, the state and the router
    too: the deliberately cruder model a tolerance has to tell from
    this one.  ``remat`` keeps no [T, T] scores for a gradient and
    steps the recurrence in checkpointed blocks.  ``chosen``: one [S,
    k] array of expert ids a sparse layer, to route by instead of this
    model's own choice."""
    params = iter([jnp.asarray(p, dtype) for p in params])
    biases = iter([jnp.asarray(b, dtype) for b in biases])
    indices = range(sizes['first_layer'],
                    sizes['first_layer'] + sizes['layers'])
    sparse = [i for i in indices if i > sizes['dense_layers']]
    chosen = iter(chosen if chosen is not None else [None] * len(sparse))
    kda_sizes = dict(kda_head_dim=sizes['kda_head_dim'], neg_eigval=False,
                     rms_eps=sizes['rms_eps'])

    def take(n):
        return [next(params) for _ in range(n)]

    eps = sizes['rms_eps']
    loads = []
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[ids]
        b, t, width = x.shape
        for i in indices:
            (g_op,) = take(1)
            u = rms_norm(x, g_op, eps)
            if i in sizes['full_attn_layers']:
                x = x + nope_attention(u, *take(5), sizes, remat)
            else:
                x = x + kda_operator(
                    u, *take(15), kda_sizes,
                    REMAT_BLOCK if remat and t % REMAT_BLOCK == 0 else None)
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn, eps)
            if i <= sizes['dense_layers']:
                x = x + gated_mlp(w, *take(3))
                continue
            wr, gate, up, down = take(4)
            routed, load = routed_share(
                w.reshape(b * t, width), wr, next(biases), gate, up,
                down, sizes['top_k'], sizes['routed_scale'],
                sizes['experts_held'], next(chosen))
            x = x + gated_mlp(w, *take(3)) + routed.reshape(b, t, width)
            loads.append(load)
        g_final, head = take(2)
        logits = rms_norm(x, g_final, eps) @ head
    assert next(params, None) is None and next(biases, None) is None
    return logits, loads


def loss(params, biases, ids, labels, *, sizes, dtype=jnp.float32,
         remat=False, chosen=None):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, _ = forward(params, biases, ids, sizes=sizes, dtype=dtype,
                        remat=remat, chosen=chosen)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_and_grads(params, biases, ids, labels, *, sizes, remat=False,
                   chosen=None):
    """(loss, [d loss / d param] in ``params`` order); the biases are
    held fixed."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, biases, ids, labels,
                                    sizes=sizes, remat=remat,
                                    chosen=chosen)


def sizes_of(cfg):
    """The ``sizes`` dict of a ``paddle_tpu.models.kimi_linear.
    KimiLinearConfig`` (plain attribute reads: this module imports
    nothing of the zoo)."""
    return dict(layers=cfg.layers, first_layer=cfg.first_layer,
                full_attn_layers=tuple(cfg.full_attn_layers),
                dense_layers=cfg.dense_layers, heads=cfg.heads,
                qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
                v_dim=cfg.v_dim, kv_rank=cfg.kv_rank,
                kda_head_dim=cfg.kda_head_dim, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps)
