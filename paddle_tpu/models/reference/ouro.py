"""Ouro (ByteDance, ``model_type: ouro``; the LoopLM of "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) in plain
``jax.numpy``: the looped forward pass, its expected-exit training loss
and, by ``jax.grad``, its gradients.  Float32 under
``jax.default_matmul_precision('highest')``, no kernel, no scan, no
cache, nothing imported from ``paddle_tpu.ops``: the loop over the
passes is a Python ``for`` over the SAME arrays.

    x_0 = E[ids]
    for t = 1 .. R (R = total_ut_steps):            the same weights
        x = x_{t-1}
        for l = 1 .. L:
            u = N1_l(x); q, k, v = u Wq_l, u Wk_l, u Wv_l
            q, k = rope(q), rope(k)     per head, ROTATE-HALF: feature
                i with i + d/2, angle = pos * theta^(-2i/d)
            a = causal_softmax(q k^T / sqrt(d)) v
            x = x + N2_l(a Wo_l)        sandwich: a norm AFTER the
                                        operator too
            u = N3_l(x)
            x = x + N4_l((silu(u Wg_l) * (u Wu_l)) Wd_l)
        h_t = N_f(x);  x_t = h_t        the last norm closes every pass
                                        and feeds the next
        z_t = h_t W_head                float32
        ce_t = -log softmax(z_t)[label]         per token
        lam_t = sigmoid(h_t . w_g + b_g)        one gate for all passes
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j) (1 < t < R);
    p_R = prod_{j<R}(1 - lam_j)
    loss = mean over the tokens that have a label of
           [ sum_t p_t ce_t  -  beta * H(p) ],
           H(p) = -sum_t p_t log max(p_t, 1e-30)

RMSNorm ``x * rsqrt(mean(x^2) + eps) * g`` everywhere
(``labels[t] = ids[t + 1]``, -1 at a sequence's end).

What the published ``config.json`` does not settle, each as the
benchmark's configuration file lists it under ``assumed``: that
``N_f``'s output is what the next pass reads; the four-norm layer; the
gate's input (``h_t``) and its bias; ``beta`` (the paper's first-stage
0.1); the floor inside the logarithm, which changes no value where
p > 0.  Left out on purpose: the paper's second training stage (a
separate adaptive-exit loss for the gate: a recipe, not the
architecture) and early exit at inference (``early_exit_threshold``).

``params`` is the flat list of arrays in the order
``paddle_tpu.models.ouro.build_pretrain`` creates its parameters:
embedding; per layer g1, Wq, Wk, Wv, Wo, g2, g3, Wg, Wu, Wd, g4;
g_f; W_head; w_g [D, 1]; b_g [1].  Each layer appears ONCE however
many passes run.

``without`` names parts to leave out, for the tests that show each
part moves the result: ``post_norms`` (N2 and N4), ``norm_between``
(the next pass reads x, not N_f(x)), ``gate`` (lam = 1/2 everywhere),
``entropy`` (beta = 0).
"""

import jax
import jax.numpy as jnp
import numpy as np

PER_LAYER = 11
LOG_FLOOR = 1e-30


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def rotate(x, positions, theta):
    """[B, T, H, d]: rotate-half, feature i with i + d/2, turned by
    pos * theta^(-2i / d); the angles in float32 whatever x is."""
    d = x.shape[-1]
    inv_freq = 1.0 / (np.float32(theta) ** (
        np.arange(d // 2, dtype=np.float32) / np.float32(d // 2)))
    angle = positions.astype(jnp.float32)[:, :, None, None] * \
        jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
        x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, block=None):
    """softmax(q k^T / sqrt(d)) v under the causal mask, [B, T, H, d]
    -> [B, T, H * d]; the softmax in float32.  ``block`` queries at a
    time (``lax.map``) where [H, T, T] scores do not fit."""
    b, t, heads, d = q.shape
    block = min(block or t, t)
    kpos = jnp.arange(t)

    def one_block(args):
        qb, qpos = args
        scores = jnp.einsum('bqhd,bkhd->bhqk', qb, k) * d ** -0.5
        visible = kpos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(
            visible, scores, -jnp.inf).astype(jnp.float32), -1).astype(
                qb.dtype)
        return jnp.einsum('bhqk,bkhd->bqhd', probs, v)

    out = jax.lax.map(one_block, (
        jnp.moveaxis(q.reshape(b, t // block, block, heads, d), 1, 0),
        jnp.arange(t).reshape(t // block, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads * d)


def exit_distribution(lams):
    """[R] gate probabilities lam_t (arrays of one shape) -> the R exit
    probabilities: lam_t times the mass that survived the earlier
    passes; the last pass takes all that is left."""
    survive, out = 1.0, []
    for lam in lams[:-1]:
        out.append(lam * survive)
        survive = survive * (1.0 - lam)
    return out + [survive * jnp.ones_like(lams[-1])]


def forward(params, ids, pos_ids, labels, layers, heads, steps=4,
            eps=1e-6, theta=1e6, beta=0.1, dtype=jnp.float32, block=None,
            remat=False, without=()):
    """-> (loss, per-token exit probabilities [R, B, T], per-token
    cross-entropies [R, B, T]).  ``dtype`` other than float32 computes
    everything but the logits, the gate and the loss in it;
    ``remat`` recomputes each layer in the backward pass (published
    widths on one chip)."""
    params = [jnp.asarray(p) for p in params]
    embedding = params[0].astype(dtype)
    stack = [[w.astype(dtype) for w in
              params[1 + i * PER_LAYER:1 + (i + 1) * PER_LAYER]]
             for i in range(layers)]
    g_f, w_head, w_g, b_g = params[1 + layers * PER_LAYER:]
    g_f, w_head = g_f.astype(dtype), w_head.astype(dtype)
    post = 'post_norms' not in without

    def layer(x, weights):
        g1, wq, wk, wv, wo, g2, g3, wg, wu, wd, g4 = weights
        b, t, _ = x.shape
        d = wq.shape[-1] // heads
        u = rms_norm(x, g1, eps)
        q = rotate((u @ wq).reshape(b, t, heads, d), pos_ids, theta)
        k = rotate((u @ wk).reshape(b, t, heads, d), pos_ids, theta)
        v = (u @ wv).reshape(b, t, heads, d)
        a = causal_attention(q, k, v, block) @ wo
        x = x + (rms_norm(a, g2, eps) if post else a)
        u = rms_norm(x, g3, eps)
        m = (jax.nn.silu(u @ wg) * (u @ wu)) @ wd
        return x + (rms_norm(m, g4, eps) if post else m)

    if remat:
        layer = jax.checkpoint(layer)
    valid = labels >= 0

    def exit_of(h):
        logp = jax.nn.log_softmax((h @ w_head).astype(jnp.float32), -1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        ce = jnp.where(valid, -picked, 0.0)
        lam = jax.nn.sigmoid(
            (h.astype(jnp.float32) @ w_g)[..., 0] + b_g[0])
        if 'gate' in without:
            lam = jnp.full_like(lam, 0.5)
        return ce, lam

    if remat:
        exit_of = jax.checkpoint(exit_of)

    with jax.default_matmul_precision('highest'):
        x = embedding[ids]
        ces, lams = [], []
        for _ in range(steps):
            for weights in stack:
                x = layer(x, weights)
            h = rms_norm(x, g_f, eps)
            if 'norm_between' not in without:
                x = h
            ce, lam = exit_of(h)
            ces.append(ce)
            lams.append(lam)
        ps = exit_distribution(lams)
        expected = sum(p * ce for p, ce in zip(ps, ces))
        neg_entropy = sum(p * jnp.log(jnp.maximum(p, LOG_FLOOR))
                          for p in ps)
        if 'entropy' in without:
            beta = 0.0
        per_token = expected + beta * neg_entropy
        loss = jnp.sum(jnp.where(valid, per_token, 0.0)) / jnp.sum(valid)
    return loss, jnp.stack(ps), jnp.stack(ces)


def loss_fn(params, ids, pos_ids, labels, **kw):
    return forward(params, ids, pos_ids, labels, **kw)[0]


def loss_and_grads(params, ids, pos_ids, labels, **kw):
    """-> (loss, [d loss / d param] in ``params``' order): a shared
    layer's gradient is the sum over the passes, which ``jax.grad``
    gives for an array used R times."""
    params = [jnp.asarray(p) for p in params]
    return jax.value_and_grad(loss_fn)(params, ids, pos_ids, labels, **kw)
