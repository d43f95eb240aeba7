"""OLMoE causal-LM pretraining (Muennighoff et al. 2024) as a
benchmark family: the program comes from the zoo
(``paddle_tpu.models.olmoe.build_pretrain``, part of the system under
test: QK-norm, rotary embedding, the flash / dense choice, dropless
top-k routing with grouped expert matmuls); the batch, the FLOPs and
the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); a traffic file gives ``seq_len`` and may override keys
under ``changed``.
"""

import numpy as np

from benchmark.lib import decoder_flops, flops

# the paper's router z-loss weight; config.json carries only the
# load-balancing coefficient (HF's default 0.01)
AUX_WEIGHT, Z_WEIGHT = 0.01, 0.001

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative.  Measured on a v5e at published
# widths, one 4096-token sequence (PR 25, my chip runs): at most
# 1.7e-7 (two units in the last place of a float32 near ln 50304 =
# 10.8) on 112 of 113 pairs of weights and batch, since the flash
# kernels multiply f32 operands at full precision (before that: up to
# 7.8e-5), and 3.87e-6 on one, where one token of the 4096 whose 8th
# and 9th router probabilities lay 6.4e-7 apart (relative) picked the
# other expert and its own loss moved by 0.18.  Such a token is about
# one run in a hundred and no defect, so the bound is 2.6 times that
# reading.  The same reference computed in bfloat16 throughout reads
# 8.4e-7 to 1.9e-4 from float32 over 96 batches, median 4.2e-5 (the
# roundings partly cancel over 4096 tokens): over the bound on 81 of
# the 96.  A dropped z-loss (1.6e-3 of the loss) or load-balancing
# loss (7e-3), a renormalised gate, a wrong rotary pairing or one
# bfloat16 product in the attention kernels (up to 7.8e-5) fail it
# too.  `chip_smoke.py --phase olmoe` prints all three readings.
REFERENCE_RTOL = 1e-5


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied."""
    merged = {k: v for k, v in config.items()
              if not isinstance(v, (dict, list))}
    merged.update(traffic.get('changed', {}))
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import olmoe
    s = sizes(config, traffic)
    return olmoe.OlmoeConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['num_hidden_layers'], heads=s['num_attention_heads'],
        expert_hidden=s['intermediate_size'], experts=s['num_experts'],
        top_k=s['num_experts_per_tok'],
        max_pos=s['max_position_embeddings'],
        rms_eps=s['rms_norm_eps'], rope_theta=float(s['rope_theta']),
        renormalize=s['norm_topk_prob'], aux_weight=AUX_WEIGHT,
        z_weight=Z_WEIGHT)


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import olmoe
    _, _, loss = olmoe.build_pretrain(_zoo_config(config, traffic),
                                      traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: uniform token ids, the
    labels the ids shifted left (-1 where there is no next token).
    Ints are int32: the executor runs with x64 off."""
    t = traffic['seq_len']
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, sizes(config, traffic)['vocab_size'], (n, t))
    labels = np.full((n, t), -1)
    labels[:, :-1] = ids[:, 1:]
    return {'ids': ids.astype('int32'),
            'pos_ids': np.tile(np.arange(t, dtype='int32'), (n, 1)),
            'labels': labels.astype('int32')}


def items_per_sample(config, traffic):
    return traffic['seq_len']


def flops_per_item(config, traffic):
    """Training FLOPs per token: 3 x forward, the ACTIVE experts only,
    the causal half of the attention square, the head over every
    position."""
    s = sizes(config, traffic)
    return flops.TRAIN_OVER_FORWARD * \
        decoder_flops.routed_decoder_forward_flops_per_token(
            s['num_hidden_layers'], s['hidden_size'],
            s['intermediate_size'], s['num_experts'],
            s['num_experts_per_tok'], traffic['seq_len'],
            s['vocab_size'])


def reference_loss(config, traffic, params, feed):
    """The forward pass and loss in plain jax.numpy, float32, written
    from the equations of HF ``modeling_olmoe.py`` (the benchmark's own
    copy of ``paddle_tpu/models/reference/olmoe.py``; its docstring
    lists the departures).  ``params`` are the program's parameters in
    creation order: embedding; per layer input-norm gain, Wq, Wk, Wv,
    q-norm gain, k-norm gain, Wo, post-attention-norm gain, router,
    gate [E, D, H], up [E, D, H], down [E, H, D]; final-norm gain;
    head.  QK-norm over the whole projection; rotate-half rotary
    pairing; gates from the softmax over all experts, not
    renormalised; every expert computed on every token and masked (no
    sort)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config, traffic)
    heads, top_k = s['num_attention_heads'], s['num_experts_per_tok']
    n_experts, eps = s['num_experts'], s['rms_norm_eps']
    theta = float(s['rope_theta'])
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    def rms_norm(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def rope(x, positions):
        half = x.shape[-1] // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
        return x * cos + jnp.concatenate(
            [-x[..., half:], x[..., :half]], -1) * sin

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, h = x.shape
        d = h // heads
        causal = jnp.tril(jnp.ones((t, t), bool))
        aux = 0.0
        for _ in range(s['num_hidden_layers']):
            (g_in, wq, wk, wv, g_q, g_k, wo, g_post, router, gate, up,
             down) = take(12)
            a = rms_norm(x, g_in)
            q = rms_norm(a @ wq, g_q).reshape(b, t, heads, d)
            k = rms_norm(a @ wk, g_k).reshape(b, t, heads, d)
            v = (a @ wv).reshape(b, t, heads, d)
            q, k = rope(q, feed['pos_ids']), rope(k, feed['pos_ids'])
            scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * d ** -0.5
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), -1)
            context = jnp.einsum('bhqk,bkhd->bqhd', probs, v)
            x = x + context.reshape(b, t, h) @ wo

            m = rms_norm(x, g_post).reshape(b * t, h)
            logits = m @ router
            route = jax.nn.softmax(logits, -1)
            weight, chosen = jax.lax.top_k(route, top_k)

            def one_expert(out, expert):
                e, w_gate, w_up, w_down = expert
                share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
                y = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
                return out + share[:, None] * y, None

            routed, _ = jax.lax.scan(
                one_expert, jnp.zeros_like(m),
                (jnp.arange(n_experts), gate, up, down))
            x = x + routed.reshape(b, t, h)
            picked = jnp.sum(jax.nn.one_hot(chosen, n_experts), 1)
            balance = n_experts * jnp.sum(jnp.mean(picked, 0) *
                                          jnp.mean(route, 0))
            z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
            aux = aux + (AUX_WEIGHT * balance + Z_WEIGHT * z) / \
                s['num_hidden_layers']
        g_final, head = take(2)
        logp = jax.nn.log_softmax(rms_norm(x, g_final) @ head, -1)
        labels = feed['labels']
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        valid = labels >= 0
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / \
            jnp.sum(valid) + aux
