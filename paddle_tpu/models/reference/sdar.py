"""SDAR's block-diffusion training loss in plain jax.numpy: float32 at
highest matmul precision, ONE dense [2L, 2L] boolean mask built from
the four lines below, one head at a time (``lax.map``) so that 8192 x
8192 fits, a Python loop over the held experts; no kernel, no merge by
log-sum-exp, no sort.  What ``models/sdar.py`` and the benchmark family
(``benchmark/families/sdar.py``, which keeps its own copy) are held to.

The layer, as ``config.json`` (``sdar_moe``) gives it, numbered from 0:

    u = rms_norm(x) * g1                              (rms_norm_eps 1e-6)
    q = u Wq -> [T, 32, 128]; k = u Wk, v = u Wv -> [T, 4, 128]  (no bias)
    q = rms_norm(q over its 128) * gq;  k = rms_norm(k over its 128) * gk
    q, k = rotate-half rotary at theta 1e6 over all 128, at position p
    a = softmax(q k^T / sqrt(128) + mask) v, 8 query heads a K/V head
    x = x + a Wo
    w = rms_norm(x) * g2
    s = softmax(w Wr) over all 128 experts;  top-8;
    gates = s_chosen / sum(s_chosen)                      (norm_topk_prob)
    x = x + sum over the chosen experts e HELD HERE of
            gate_e * (silu(w G_e) * (w U_e)) D_e    (width 768, no shared)

then a last RMSNorm and an untied head.

The objective (block diffusion: BD3-LMs, arXiv:2503.09573, as SDAR,
arXiv:2510.06303, adapts a trained autoregressive model to it), for L
tokens x_0 .. x_(L-1) in blocks of B (block of token i: i // B):

    t_b ~ Uniform(t_min, 1), one a block;  m_i ~ Bernoulli(t_(i // B))
    z_i = MASK if m_i else x_i
    input = [emb(z_0 .. z_(L-1)) ; emb(x_0 .. x_(L-1))]    2L positions;
            the position of BOTH copies of token i is i
    visible(query, key):
        corrupted i -> corrupted j  iff  j // B == i // B
        corrupted i -> clean j      iff  j // B <  i // B
        clean i     -> clean j      iff  j // B <= i // B
        clean i     -> corrupted j  never
    logits_i = head(rms_norm(h_i of the corrupted copy))      no shift
    loss = (1 / L) * sum_i  m_i / t_(i // B) * cross_entropy(logits_i, x_i)

z, x, the positions and the weights m / t are FED (``models.sdar.
corrupt``): the reference and the program see the same corruption.

ASSUMED, because ``config.json`` does not settle it (the catalog lists
the first two under ``not_given``), none changing a published shape:

- ``block_length`` 4: the family's released chat models and their
  generation script, as remembered (no network here); the mask and
  every count take B as a number, so a corrected value is one key;
- one t a block, the linear schedule (mask probability t, weight 1 / t),
  ``t_min`` 1e-3;
- no shift of the logits: position i of the corrupted copy predicts
  token i;
- the per-head QK-norm before the rotary embedding: the config's keys
  are ``qwen3_moe``'s, whose layer has it, and there is no key for it;
- the MASK id: the LAST row of the held vocabulary slice (data ids are
  drawn from the rows before it);
- no auxiliary balance loss and no router z-loss in the training loss;
- a last RMSNorm with a gain before the untied head;
- the startup values, which stand in for a TRAINED model's (the job is
  continued training): every matrix and the MASK row Normal(0, 0.02),
  the norms' gains 1; the embedding's DATA rows Normal(0, 1) (PaLM's),
  so a token's own row leads its stream through the layers; the
  per-head gains of q and k 3 each, so a random key's score has a
  deviation of 9 and a row's context is a few keys' values.  With 0.02
  and 1 throughout, attention is the plain mean of thousands of keys,
  the same for every row; from the second layer on the streams are 0.8
  alike, every row of a layer picks the same eight experts and the
  work of a chip that holds 16 of 128 follows the seed (PERF.md,
  section 6, PR 63);
- AdamW at 1e-5, a continued-training rate: at a from-scratch peak of
  4e-4 with no warm-up, Adam's first steps move every weight by the
  rate whatever its gradient, and on one fixed corruption the streams
  collapse and the held experts draw every row inside ten steps.

``params`` are the program's parameters in creation order: embedding;
per layer g1, Wq, gq [128], Wk, gk [128], Wv, Wo, g2, router, gate
[E_held, D, W], up, down; final-norm gain; head.  The LAST layer's
clean rows give keys and values only (nothing reads the rest), so the
stream is the corrupted copy's from that layer's ``a Wo`` on.
"""

import jax
import jax.numpy as jnp
import numpy as np

PER_LAYER = 12


def visible(length, block):
    """The [2L, 2L] boolean mask of the four lines above: rows and
    columns 0 .. L-1 the corrupted copy, L .. 2L-1 the clean one."""
    i = np.arange(2 * length)
    copy, blk = i >= length, (i % length) // block
    q_clean, k_clean = copy[:, None], copy[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return np.where(
        q_clean, k_clean & (k_blk <= q_blk),
        np.where(k_clean, k_blk < q_blk, k_blk == q_blk))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x [B, T, H, d], positions [B, T]: rotate-half pairing."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos.astype(x.dtype) + rotated * sin.astype(x.dtype)


def attention(u, positions, mask, wq, gq, wk, gk, wv, wo, head_dim, eps,
              theta, remat=False):
    """u [B, 2L, D] -> a Wo [B, 2L, D] under ``mask`` [2L, 2L].
    ``remat``: a head's [2L, 2L] scores are computed again for its
    gradient, not kept (8192 x 8192 x 32 heads do not fit)."""
    b, t, _ = u.shape
    q = rms_norm((u @ wq).reshape(b, t, -1, head_dim), gq, eps)
    k = rms_norm((u @ wk).reshape(b, t, -1, head_dim), gk, eps)
    v = (u @ wv).reshape(b, t, -1, head_dim)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    group = q.shape[2] // k.shape[2]

    def one_head(args):
        qh, kh, vh = args                       # [B, 2L, d] each
        scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(
            mask, scores, -jnp.inf).astype(jnp.float32), -1)
        return jnp.einsum('bqk,bkd->bqd', probs.astype(qh.dtype), vh)

    context = jax.lax.map(jax.checkpoint(one_head) if remat else one_head, (
        jnp.moveaxis(q, 2, 0),
        jnp.repeat(jnp.moveaxis(k, 2, 0), group, axis=0),
        jnp.repeat(jnp.moveaxis(v, 2, 0), group, axis=0)))
    return jnp.moveaxis(context, 0, 2).reshape(b, t, -1) @ wo


def routed(w, router, gate, up, down, top_k, first, renormalize=True):
    """w [S, D] -> the part of the routed sum that the experts first ..
    first + E_held - 1 give, the router over ALL experts."""
    scores = jax.nn.softmax((w @ router).astype(jnp.float32), -1)
    weight, chosen = jax.lax.top_k(scores, top_k)
    if renormalize:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    out = jnp.zeros_like(w)
    for e in range(gate.shape[0]):              # the experts held
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0), -1)
        out = out + share[:, None].astype(w.dtype) * (
            (jax.nn.silu(w @ gate[e]) * (w @ up[e])) @ down[e])
    return out


def forward(params, feed, *, layers, head_dim, top_k, block, first=0,
            eps=1e-6, theta=1e6, renormalize=True, dtype=jnp.float32,
            mask=None, remat=False):
    """-> the corrupted copy's logits [B, L, V] on one fed corruption
    (``models.sdar.corrupt``'s four arrays).  ``dtype`` other than
    float32 computes EVERYTHING in it: the deliberately cruder model a
    tolerance has to tell from this one.  ``mask``: another [2L, 2L]
    mask than ``visible``'s (the tests' mutations)."""
    params = [jnp.asarray(p, dtype) for p in params]
    assert len(params) == 3 + PER_LAYER * layers, len(params)
    length = feed['ids'].shape[1]
    mask = jnp.asarray(visible(length, block) if mask is None else mask)
    positions = feed['pos_ids']
    with jax.default_matmul_precision('highest'):
        x = params[0][jnp.concatenate([feed['noisy_ids'], feed['ids']], 1)]
        for i in range(layers):
            (g1, wq, gq, wk, gk, wv, wo, g2, router, gate, up,
             down) = params[1 + PER_LAYER * i:1 + PER_LAYER * (i + 1)]
            x = x + attention(rms_norm(x, g1, eps), positions, mask, wq,
                              gq, wk, gk, wv, wo, head_dim, eps, theta,
                              remat)
            if i == layers - 1:     # nothing reads the clean rows now
                x = x[:, :length]
            b, t, width = x.shape
            w = rms_norm(x, g2, eps).reshape(b * t, width)
            x = x + routed(w, router, gate, up, down, top_k, first,
                           renormalize).reshape(b, t, width)
        return rms_norm(x, params[-2], eps) @ params[-1]


def weighted_cross_entropy(logits, labels, weights):
    """(1 / L) sum_i weight_i x cross_entropy(logits_i, label_i), the
    mean over the sequences with it."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -jnp.mean(weights * picked)


def loss(params, feed, **sizes):
    """The training loss: position i of the corrupted copy predicts
    token i, weighted by m_i / t."""
    return weighted_cross_entropy(forward(params, feed, **sizes),
                                  feed['ids'], feed['weights'])


def loss_and_grads(params, feed, **sizes):
    """(loss, [d loss / d param] in ``params`` order)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, feed, **sizes)
