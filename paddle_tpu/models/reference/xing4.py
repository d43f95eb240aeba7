"""Xing4.0-29B-A4B (XingChen-AGI, ``model_type: xing4_0``) in plain
``jax.numpy``: the causal-LM forward pass with its multi-token-
prediction module, the training loss and, by ``jax.grad``, its
gradients.  Float32 throughout under
``jax.default_matmul_precision('highest')``, dense [T, T] masks, a
Python loop over the heads, one over the experts and one over the
Sinkhorn normalisations, no kernel, no sort, no cache, nothing imported
from ``paddle_tpu.ops`` or ``paddle_tpu.parallel``.

Written from the catalog row's ``config.json`` and the equations the
issue states (PR 54).  What it shares with Moonlight (the K/V latent,
the one rotary key for all heads, the sigmoid router whose bias picks,
the held share of the experts) is ``models/reference/moonlight.py``'s,
imported; RMSNorm eps 1e-6, no bias anywhere.

THE STREAM (manifold-constrained hyper-connections, arXiv:2512.24880):
the residual is X [n = hc_mult = 4, C] a token, and each operator F
(attention; MLP or experts) has parameters of its own, phi
[n C, n^2 + 2 n], alpha [3], b [n^2 + 2 n]:

    r       = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     (no gain)
    phi     = Phi / sqrt(n C)     (the parameter holds phi at unit size)
    [p~ | q~ | R~] = alpha_0 (r phi_pre) + b_pre | alpha_1 (r phi_post) +
                     b_post | alpha_2 mat(r phi_res) + b_res   (row-major)
    H_pre   = sigmoid(p~)        H_post = 2 sigmoid(q~)
    M_0     = exp(clamp(R~, -30, 30));  hc_sinkhorn_iters = 20 times:
              M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    u       = H_pre X      y = F(rms_norm(u, g))     X' = M X + H_post^T y

ATTENTION is Moonlight's with a LOW-RANK QUERY and YaRN:

    q = rms_norm(u Wqa, g_q) Wqb            [T, 32, 192] = [nope 128 | rope 64]
    rotary on q_rope and on the one shared key with YaRN's inverse
      frequencies (HF ``_compute_yarn_parameters``: theta 10000, factor
      64 over 4096 original positions, beta 32 / 1), interleaved pairs;
      cos and sin times mscale / mscale_all_dim = 1
    scores q_h . k_h x (0.1 ln 64 + 1)^2 / sqrt(192)   (HF deepseek_v3:
      ``yarn_get_mscale(factor, mscale_all_dim)`` squared)

LAYERS: ``dense_layers`` leading layers with a gated MLP of width 9216,
then sparse ones: s = sigmoid(w Wg) over all 64, the choice the 4
largest of s + b, the gates s_e / (sum over the 4 + 1e-20) x 2; y =
shared(w) + sum over the chosen experts HELD HERE of gate_e expert_e(w)
(width 1024; one shared expert of width 1024).

THE PREDICTION MODULE (DeepSeek-V3, arXiv:2412.19437 section 2.2,
depth 1), after the main stack, with h_i the stream summed over n
BEFORE the final norm:

    h'_i   = [rms_norm(emb(t_{i+1}), g_e) ; rms_norm(h_i, g_h)] W_eh
    X      = h' repeated n times -> ONE more sparse layer (its own
             parameters, hyper-connections like the others) -> summed
    logits = rms_norm(. , g_final) W_head      the SHARED norm and head
    L_mtp  = cross-entropy against t_{i+2}     (T - 2 positions)

    loss = L_main + mtp_weight x L_mtp,  L_main over T - 1 positions.

What ``config.json`` does not settle, as this file and
``paddle_tpu/models/xing4.py`` read it (``assumed`` in the benchmark's
configuration file gives the reasons):

- the first stream is the embedding repeated n times and the last is
  SUMMED over n before the final norm (Hyper-Connections,
  arXiv:2409.19606);
- r's eps is ``rms_norm_eps``; phi, alpha and b's startup values are
  the caller's (a checkpoint's are trained);
- the module's placement, that its layer carries hyper-connections,
  that its rotary positions are the main stack's, the order
  [embedding ; hidden] of W_eh's input, mtp_weight 0.3;
- ``rope_interleave`` true, bias update rate 0.001, the latent norm's
  and the query norm's eps ``rms_norm_eps``, no auxiliary loss: as
  Moonlight's.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.xing4.build_pretrain`` creates its TRAINABLE
parameters: embedding; per layer phi_a, alpha_a, b_a, g_in, Wqa, g_q,
Wqb, Wkva, g_latent, Wkvb, Wo, phi_m, alpha_m, b_m, g_post, then for a
dense layer gate, up, down and for a sparse one Wg, gate [count, D, H],
up, down [count, H, D], shared gate, shared up, shared down; g_final;
W_head; then, with a module, g_e, g_h, W_eh and one sparse layer's.
``biases``: one [64] array a sparse layer, the module's last.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import yarn_inv_freq
from .moonlight import gated_mlp, rms_norm, routed_share

LAYER_PARAMS_ATTENTION = 11     # phi, alpha, b, g_in, the seven of MLA
LAYER_PARAMS_DENSE = 4 + 3
LAYER_PARAMS_SPARSE = 4 + 7


def softmax_scale(sizes):
    """(0.1 mscale_all_dim ln factor + 1)^2 / sqrt(qk width); without
    rotary scaling 1 / sqrt(qk width)."""
    scale = (sizes['qk_nope'] + sizes['qk_rope']) ** -0.5
    yarn = sizes.get('yarn')
    if yarn and yarn.get('mscale_all_dim'):
        m = 0.1 * yarn['mscale_all_dim'] * math.log(yarn['factor']) + 1.0
        scale *= m * m
    return scale


def inverse_frequencies(sizes):
    """[qk_rope / 2] float32: YaRN's table, or theta's own."""
    rope, yarn = sizes['qk_rope'], sizes.get('yarn')
    if yarn:
        return yarn_inv_freq(
            rope, sizes['rope_theta'], yarn['factor'],
            yarn['original_max_position_embeddings'], yarn['beta_fast'],
            yarn['beta_slow'])
    half = rope // 2
    return 1.0 / (np.float32(sizes['rope_theta']) ** (
        np.arange(half, dtype=np.float32) / np.float32(half)))


def rope_interleaved(x, positions, inv_freq):
    """x [B, T, H, R], positions [B, T]: the input's pairs (2i, 2i+1)
    turned by pos * inv_freq[i]; the output in [evens | odds] order."""
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
        x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], -1)


def attention(u, positions, wqa, g_q, wqb, wkva, g_latent, wkvb, wo,
              sizes, remat=False):
    b, t, _ = u.shape
    heads, nope, rope, dv, rank = (sizes[n] for n in (
        'heads', 'qk_nope', 'qk_rope', 'v_dim', 'kv_rank'))
    eps = sizes['rms_eps']
    q = (rms_norm(u @ wqa, g_q, eps) @ wqb).reshape(
        b, t, heads, nope + rope)
    kva = u @ wkva
    kv = (rms_norm(kva[..., :rank], g_latent, eps) @ wkvb).reshape(
        b, t, heads, nope + dv)
    table = inverse_frequencies(sizes)
    q_rope = rope_interleaved(q[..., nope:], positions, table)
    k_rope = rope_interleaved(kva[..., rank:][:, :, None, :], positions,
                              table)[:, :, 0]               # [B, T, R]
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = softmax_scale(sizes)

    def one_head(qn, qr, kn, v):
        scores = (jnp.einsum('bqd,bkd->bqk', qn, kn) +
                  jnp.einsum('bqd,bkd->bqk', qr, k_rope)) * scale
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum('bqk,bkd->bqd', probs, v)

    if remat:
        one_head = jax.checkpoint(one_head)
    context = jnp.stack(
        [one_head(q[:, :, h, :nope], q_rope[:, :, h], kv[:, :, h, :nope],
                  kv[:, :, h, nope:]) for h in range(heads)], 2)
    return context.reshape(b, t, heads * dv) @ wo


def hyper_maps(x, phi, alpha, b, sizes, iters=None):
    """x [B, T, n, C] -> (H_pre [B, T, n], H_post [B, T, n], H_res
    [B, T, n, n]); ``iters`` other than the model's is for the tests
    that hold a tolerance to a skipped normalisation."""
    n = x.shape[2]
    flat = x.reshape(x.shape[:2] + (-1,))
    r = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), -1, keepdims=True) + sizes['rms_eps'])
    proj = (r @ phi) / math.sqrt(flat.shape[-1])
    pre = alpha[0] * proj[..., :n] + b[:n]
    post = alpha[1] * proj[..., n:2 * n] + b[n:2 * n]
    res = (alpha[2] * proj[..., 2 * n:] + b[2 * n:]).reshape(
        x.shape[:2] + (n, n))
    m = jnp.exp(jnp.clip(res, *sizes['hc_clamp']))
    for _ in range(sizes['hc_iters'] if iters is None else iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + sizes['hc_eps'])
        m = m / (jnp.sum(m, -2, keepdims=True) + sizes['hc_eps'])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


def hyper_connected(x, phi, alpha, b, sizes, operator):
    """X' = H_res X + H_post^T operator(H_pre X)."""
    h_pre, h_post, h_res = hyper_maps(x, phi, alpha, b, sizes)
    y = operator(jnp.einsum('btn,btnc->btc', h_pre, x))
    return jnp.einsum('btij,btjc->btic', h_res, x) + \
        h_post[..., None] * y[:, :, None, :]


def decoder_layer(x, positions, take, dense, bias, sizes, remat, chosen):
    """One layer on the stream -> (stream, the router's load or
    None)."""
    eps = sizes['rms_eps']
    phi, alpha, b, g_in, *mla = take(LAYER_PARAMS_ATTENTION)
    x = hyper_connected(
        x, phi, alpha, b, sizes, lambda u: attention(
            rms_norm(u, g_in, eps), positions, *mla, sizes, remat))
    phi, alpha, b, g_post = take(4)
    if dense:
        gate, up, down = take(3)
        return hyper_connected(
            x, phi, alpha, b, sizes, lambda u: gated_mlp(
                rms_norm(u, g_post, eps), gate, up, down)), None
    wg, gate, up, down, s_gate, s_up, s_down = take(7)
    loads = []

    def experts(u):
        w = rms_norm(u, g_post, eps)
        routed, load = routed_share(
            w.reshape(-1, w.shape[-1]), wg, bias, gate, up, down,
            sizes['top_k'], sizes['routed_scale'], sizes['experts_held'],
            chosen)
        loads.append(load)
        return gated_mlp(w, s_gate, s_up, s_down) + routed.reshape(w.shape)

    x = hyper_connected(x, phi, alpha, b, sizes, experts)
    return x, loads[0]


def forward(params, biases, ids, positions, next_ids=None, *, sizes,
            dtype=jnp.float32, remat=False, chosen=None,
            module_copies=None):
    """-> (main logits [B, T, V], module logits [B, T, V] or None,
    [expert loads [E] per sparse layer]).  ``next_ids`` [B, T]: the
    token after each position (anything where there is none); needed
    with ``sizes['mtp_layers']`` = 1.  ``sizes``: ``sizes_of`` takes
    them from an ``Xing4Config``.  ``dtype`` other than float32
    computes EVERYTHING in it, the maps and the Sinkhorn loop too: the
    deliberately cruder model a tolerance has to tell from this one.
    ``remat``, ``chosen``: as ``reference.moonlight.forward``.
    ``module_copies``: (embedding, g_final, W_head) for the module to
    read INSTEAD of the shared ones (the tests' way of telling the
    gradients of a shared parameter's two uses apart)."""
    params = iter([jnp.asarray(p, dtype) for p in params])
    biases = iter([jnp.asarray(b, dtype) for b in biases])
    sparse = sizes['layers'] - sizes['dense_layers'] + sizes['mtp_layers']
    chosen = iter(chosen if chosen is not None else [None] * sparse)

    def take(n):
        return [next(params) for _ in range(n)]

    eps, n = sizes['rms_eps'], sizes['hc_mult']
    loads = []

    def expand(h):
        return jnp.repeat(h[:, :, None, :], n, 2)

    def layer(x, dense):
        x, load = decoder_layer(
            x, positions, take, dense, None if dense else next(biases),
            sizes, remat, None if dense else next(chosen))
        if load is not None:
            loads.append(load)
        return x

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = expand(embedding[ids])
        for i in range(sizes['layers']):
            x = layer(x, i < sizes['dense_layers'])
        h = jnp.sum(x, 2)
        g_final, head = take(2)
        logits = rms_norm(h, g_final, eps) @ head
        module_logits = None
        if sizes['mtp_layers']:
            g_e, g_h, w_eh = take(3)
            if module_copies is not None:
                embedding, g_final, head = (
                    jnp.asarray(p, dtype) for p in module_copies)
            joined = jnp.concatenate(
                [rms_norm(embedding[next_ids], g_e, eps),
                 rms_norm(h, g_h, eps)], -1) @ w_eh
            x = layer(expand(joined), False)
            module_logits = rms_norm(jnp.sum(x, 2), g_final, eps) @ head
    assert next(params, None) is None and next(biases, None) is None
    return logits, module_logits, loads


def cross_entropy(logits, labels):
    """Mean over the positions whose label is >= 0."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def losses(params, biases, ids, positions, labels, labels_mtp, *, sizes,
           dtype=jnp.float32, remat=False, chosen=None,
           module_copies=None):
    """-> (loss, L_main, L_mtp).  ``labels`` are the ids shifted left
    by one and ``labels_mtp`` by two, -1 where there is no such token;
    the module's input token is ``labels`` (0 where it is -1: that
    position carries no module loss and, the mask being causal, reaches
    no position that does)."""
    logits, module_logits, _ = forward(
        params, biases, ids, positions, jnp.maximum(labels, 0),
        sizes=sizes, dtype=dtype, remat=remat, chosen=chosen,
        module_copies=module_copies)
    main = cross_entropy(logits, labels)
    if module_logits is None:
        return main, main, jnp.zeros_like(main)
    module = cross_entropy(module_logits, labels_mtp)
    return main + sizes['mtp_weight'] * module, main, module


def loss(params, biases, ids, positions, labels, labels_mtp, **kw):
    return losses(params, biases, ids, positions, labels, labels_mtp,
                  **kw)[0]


def loss_and_grads(params, biases, ids, positions, labels, labels_mtp, *,
                   sizes, remat=False, chosen=None):
    """(loss, [d loss / d param] in ``params`` order); the biases are
    held fixed."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(
        params, biases, ids, positions, labels, labels_mtp, sizes=sizes,
        remat=remat, chosen=chosen)


def sizes_of(cfg):
    """The ``sizes`` dict of a ``paddle_tpu.models.xing4.Xing4Config``
    (plain attribute reads: this module imports nothing of the zoo)."""
    return dict(layers=cfg.layers, dense_layers=cfg.dense_layers,
                heads=cfg.heads, qk_nope=cfg.qk_nope,
                qk_rope=cfg.qk_rope, v_dim=cfg.v_dim,
                kv_rank=cfg.kv_rank, q_rank=cfg.q_rank, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, rms_eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta,
                yarn=dict(cfg.yarn) if cfg.yarn else None,
                hc_mult=cfg.hc_mult, hc_iters=cfg.hc_iters,
                hc_eps=cfg.hc_eps, hc_clamp=tuple(cfg.hc_clamp),
                mtp_layers=cfg.mtp_layers, mtp_weight=cfg.mtp_weight)
