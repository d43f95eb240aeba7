"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060) in plain
``jax.numpy``: the causal-LM forward pass, its training loss and, by
``jax.grad``, its gradients.  Float32 throughout under
``jax.default_matmul_precision('highest')`` (on a TPU a float32 matmul
is otherwise bfloat16 passes), no kernel, no sort, no cache, nothing
imported from ``paddle_tpu.ops`` or ``paddle_tpu.parallel``.

Written from the equations of HF ``modeling_olmoe.py``
(``OlmoeDecoderLayer``, ``OlmoeAttention``, ``OlmoeSparseMoeBlock``,
``load_balancing_loss_func``):

    h   = embed[ids]
    per layer:
      a = rms_norm(h, g_in)
      q = rms_norm(a Wq, g_q);  k = rms_norm(a Wk, g_k);  v = a Wv
          (QK-norm over the WHOLE projection, before the head split)
      q, k = rope(q), rope(k)   per head, ROTATE-HALF pairing: feature
          i pairs with i + d/2, angle = pos * theta^(-2i/d)
      h = h + causal_softmax(q k^T / sqrt(d)) v Wo
      m = rms_norm(h, g_post)
      p = softmax(m Wr) over all E experts, in float32
      w, e = top_k(p)           NOT renormalised (norm_topk_prob false)
      h = h + sum_j w_j * down_{e_j}(silu(gate_{e_j} m) * up_{e_j} m)
    logits = rms_norm(h, g_final) W_head        (head not tied)

Loss: next-token cross-entropy, mean over every position but the last
of each sequence (``labels[t] = ids[t + 1]``, -1 at the end), plus
``aux_weight`` x the load-balancing loss plus ``z_weight`` x the
router z-loss, both averaged over the layers.

Departures from the HF code, each on purpose:

- each token's experts are computed by a masked loop over ALL experts
  (every expert on every token, times the token's gate or 0): no
  ``index_add``, no sort; E/k times the FLOPs, the same numbers;
- the load-balancing loss is taken per layer and averaged; HF
  concatenates the layers' router outputs first, which is the same
  number at one layer (the benchmark's cut) and differs by the
  between-layer covariance of load and probability otherwise;
- the router z-loss is the paper's (section 3, weight 0.001), which
  the HF inference code does not carry;
- no attention mask beside the causal one, no dropout
  (``attention_dropout`` 0.0), ``clip_qkv`` null as published.

``params`` is the flat list of arrays in the order
``paddle_tpu.models.olmoe.build_pretrain`` creates its parameters:
embedding; per layer g_in, Wq, Wk, Wv, g_q, g_k, Wo, g_post, Wr,
gate [E, D, H], up [E, D, H], down [E, H, D]; g_final; W_head.
"""

import jax
import jax.numpy as jnp

PER_LAYER = 12


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


def attention(a, positions, wq, wk, wv, gq, gk, wo, heads, eps, theta):
    b, t, h = a.shape
    d = h // heads
    q = rms_norm(a @ wq, gq, eps).reshape(b, t, heads, d)
    k = rms_norm(a @ wk, gk, eps).reshape(b, t, heads, d)
    v = (a @ wv).reshape(b, t, heads, d)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    return jnp.einsum('bhqk,bkhd->bqhd', probs, v).reshape(b, t, h) @ wo


def sparse_moe(m, wr, gate, up, down, top_k):
    """m [S, D] -> (out [S, D], balance loss, z-loss, load [E])."""
    n_experts = wr.shape[-1]
    logits = m @ wr
    probs = jax.nn.softmax(logits, -1)
    weight, chosen = jax.lax.top_k(probs, top_k)

    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        y = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return out + share[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (jnp.arange(n_experts), gate, up, down))
    picked = jnp.sum(jax.nn.one_hot(chosen, n_experts), 1)      # [S, E]
    balance = n_experts * jnp.sum(jnp.mean(picked, 0) *
                                  jnp.mean(probs, 0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, balance, z, jnp.sum(picked, 0)


def forward(params, ids, positions, *, layers, heads, top_k,
            eps=1e-5, theta=10000.0, dtype=jnp.float32):
    """-> (logits [B, T, V], [balance loss per layer], [z-loss per
    layer], [expert loads per layer]).  ``dtype`` other than float32
    computes EVERYTHING in it (parameters, router, norms): the
    deliberately cruder model a tolerance has to tell from this one."""
    params = [jnp.asarray(p, dtype) for p in params]
    assert len(params) == 3 + PER_LAYER * layers, len(params)
    with jax.default_matmul_precision('highest'):
        h = params[0][ids]
        b, t, width = h.shape
        balances, zs, loads = [], [], []
        for i in range(layers):
            (g_in, wq, wk, wv, gq, gk, wo, g_post, wr, gate, up,
             down) = params[1 + PER_LAYER * i:1 + PER_LAYER * (i + 1)]
            h = h + attention(rms_norm(h, g_in, eps), positions, wq, wk,
                              wv, gq, gk, wo, heads, eps, theta)
            m = rms_norm(h, g_post, eps).reshape(b * t, width)
            out, balance, z, load = sparse_moe(m, wr, gate, up, down,
                                               top_k)
            h = h + out.reshape(b, t, width)
            balances.append(balance)
            zs.append(z)
            loads.append(load)
        logits = rms_norm(h, params[-2], eps) @ params[-1]
    return logits, balances, zs, loads


def loss(params, ids, positions, labels, *, layers, heads, top_k,
         eps=1e-5, theta=10000.0, aux_weight=0.01, z_weight=0.001,
         dtype=jnp.float32):
    """The training loss; ``labels`` are the ids shifted left with -1
    where there is no next token."""
    logits, balances, zs, _ = forward(
        params, ids, positions, layers=layers, heads=heads, top_k=top_k,
        eps=eps, theta=theta, dtype=dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    lm = -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
    return lm + (aux_weight * sum(balances) + z_weight * sum(zs)
                 ).astype(jnp.float32) / layers


def loss_and_grads(params, ids, positions, labels, **sizes):
    """(loss, [d loss / d param] in ``params`` order)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    return jax.value_and_grad(loss)(params, ids, positions, labels,
                                    **sizes)
