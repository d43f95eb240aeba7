"""Laguna causal-LM pretraining (poolside Laguna-S-2.1) as a benchmark
family: the program comes from the zoo
(``paddle_tpu.models.laguna.build_pretrain``, part of the system under
test: sliding-window and full attention mixed, a head count per layer
kind over grouped K/V heads, partial rotary with a YaRN table,
per-head gates, one chip's share of the routed experts beside a shared
expert); the batch, the FLOPs and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); the per-layer lists stay whole and the first
``num_hidden_layers`` entries are run; ``num_experts`` counts the
experts HELD here (``experts_held`` says which), ``num_experts_published``
what the router spans.  A traffic file gives ``seq_len`` and may
override keys under ``changed``.
"""

import math

import numpy as np

from benchmark.lib import flops, laguna_flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative; the two readings it lies
# between are in PERF.md section 6 (PR 30) and `chip_smoke.py --phase
# laguna` prints both.  Measured on a v5e at published widths, one
# 4096-token sequence (my chip runs, PR 30): the program reads at most
# a few units in the last place of a float32 near ln 12544 = 9.44
# (flash kernels, grouped matmuls and the reference all multiply f32
# operands at full precision) unless a token's 10th and 11th router
# probabilities nearly tie and it picks the other expert in the
# program than in the reference, which moves its own loss and the mean
# by 1e-6 to 1e-5 (OLMoE's known exception; with top-10 of 256 over
# four routed layers it has four times the layers to bite in, but only
# the 8 held of 256 experts change the result).  The same reference in
# bfloat16 throughout reads 1e-5 to 1e-3 from float32.  A dropped gate,
# a wrong K/V group, window edge, rotated width, YaRN ramp, scaling
# factor or held range fail it by orders of magnitude.
REFERENCE_RTOL = 1e-5

FULL = 'full_attention'


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys (the per-layer lists
    and the rotary parameters among them) with the traffic's overrides
    applied."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import laguna
    s = sizes(config, traffic)
    n = s['num_hidden_layers']
    heads = dict(zip(s['layer_types'],
                     s['num_attention_heads_per_layer']))
    yarn = {k: v for k, v in s['rope_parameters'][FULL].items()
            if k != 'rope_type'}
    return laguna.LagunaConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'], layers=n,
        head_dim=s['head_dim'], kv_heads=s['num_key_value_heads'],
        full_heads=heads[FULL], sliding_heads=heads['sliding_attention'],
        layer_types=s['layer_types'][:n],
        mlp_types=s['mlp_layer_types'][:n], window=s['sliding_window'],
        dense_hidden=s['intermediate_size'],
        expert_hidden=s['moe_intermediate_size'],
        shared_hidden=s['shared_expert_intermediate_size'],
        experts=s['num_experts_published'],
        top_k=s['num_experts_per_tok'],
        routed_scale=s['moe_routed_scaling_factor'],
        renormalize=s['norm_topk_prob'],
        experts_held=tuple(s['experts_held']),
        rms_eps=s['rms_norm_eps'],
        sliding_theta=float(
            s['rope_parameters']['sliding_attention']['rope_theta']),
        yarn=yarn)


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import laguna
    _, _, loss = laguna.build_pretrain(_zoo_config(config, traffic),
                                       traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: token ids uniform over
    the held vocabulary rows, the labels the ids shifted left (-1 where
    there is no next token).  Ints are int32: the executor runs with
    x64 off."""
    t = traffic['seq_len']
    rng = np.random.RandomState(seed % 2 ** 32)
    ids = rng.randint(0, sizes(config, traffic)['vocab_size'], (n, t))
    labels = np.full((n, t), -1)
    labels[:, :-1] = ids[:, 1:]
    return {'ids': ids.astype('int32'),
            'pos_ids': np.tile(np.arange(t, dtype='int32'), (n, 1)),
            'labels': labels.astype('int32')}


def items_per_sample(config, traffic):
    return traffic['seq_len']


def flops_per_item(config, traffic):
    """Training FLOPs per token: 3 x forward; the band on sliding
    layers and the causal half on full ones, the projections at each
    layer's own head count, the router, the shared expert, and the
    routed experts at the EXPECTED rows held here (10 x 8 / 256 expert
    MLPs a token: ``benchmark/lib/laguna_flops.py``)."""
    return flops.TRAIN_OVER_FORWARD * \
        laguna_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def _yarn_inv_freq(dim, y):
    """HF ``_compute_yarn_parameters``'s inverse frequencies, [dim/2]."""
    base, original = y['rope_theta'], \
        y['original_max_position_embeddings']

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(y['beta_fast'])), 0)
    high = min(math.ceil(correction_dim(y['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   np.float32(high - low), 0, 1).astype(np.float32)
    return (1.0 / (np.float32(y['factor']) * pos_freqs) * ramp +
            1.0 / pos_freqs * (1 - ramp)).astype(np.float32)


def reference_loss(config, traffic, params, feed, dtype=None):
    """The forward pass and loss in plain jax.numpy, float32 at highest
    matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/laguna.py``; its docstring has the
    equations and what the config leaves to be assumed), given the same
    share: the held experts, the vocabulary slice.  Dense [T, T] masks,
    a Python loop over the held experts, no kernel, no sort.  Computed
    in blocks so that it fits beside the program's state: attention one
    K/V group at a time (``lax.map``: H / 8 query heads' [T, T] scores
    alive, not all H).  ``params`` are the program's parameters in
    creation order: embedding; per layer input-norm gain, Wq, Wk, Wv,
    Wg, Wo, post-attention-norm gain, then gate, up, down (dense) or
    router, gate [8, D, H], up, down, shared gate, shared up, shared
    down (sparse); final-norm gain; head.  ``dtype`` other than float32
    computes everything in it (``chip_smoke.py --phase laguna``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    d, kv = s['head_dim'], s['num_key_value_heads']
    eps, top_k = s['rms_norm_eps'], s['num_experts_per_tok']
    scale, first = s['moe_routed_scaling_factor'], s['experts_held'][0]
    window = s['sliding_window']
    rope = s['rope_parameters']
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def rotate(x, positions, inv_freq, factor):
        half = inv_freq.shape[0]
        angle = positions.astype(jnp.float32)[:, :, None, None] * \
            jnp.asarray(inv_freq)
        cos = (jnp.concatenate([jnp.cos(angle)] * 2, -1) *
               factor).astype(x.dtype)
        sin = (jnp.concatenate([jnp.sin(angle)] * 2, -1) *
               factor).astype(x.dtype)
        turned, rest = x[..., :2 * half], x[..., 2 * half:]
        turned = turned * cos + jnp.concatenate(
            [-turned[..., half:], turned[..., :half]], -1) * sin
        return jnp.concatenate([turned, rest], -1)

    def mlp(w, gate, up, down):
        return (jax.nn.silu(w @ gate) * (w @ up)) @ down

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, h = x.shape
        i = jnp.arange(t)[:, None]
        j = jnp.arange(t)[None, :]
        for kind, heads, kind_mlp in laguna_flops.layers_of(s):
            g_in, wq, wk, wv, wg, wo, g_post = take(7)
            u = rms_norm(x, g_in)
            group = heads // kv
            # [B, T, kv, group, d]: query head n = kv head n // group
            q = (u @ wq).reshape(b, t, heads, d)
            k = (u @ wk).reshape(b, t, kv, d)
            v = (u @ wv).reshape(b, t, kv, d)
            if kind == FULL:
                y = rope[FULL]
                table = _yarn_inv_freq(
                    int(d * y['partial_rotary_factor']), y)
                factor, visible = y['attention_factor'], j <= i
            else:
                table = np.float32(
                    rope['sliding_attention']['rope_theta']) ** (
                    -np.arange(d // 2, dtype=np.float32) /
                    np.float32(d // 2))
                factor, visible = 1.0, (j <= i) & (i - j < window)
            q = rotate(q, feed['pos_ids'], table, factor)
            k = rotate(k, feed['pos_ids'], table, factor)

            def one_group(qkv):
                qg, kg, vg = qkv    # [B, T, group, d], [B, T, d] x 2
                scores = jnp.einsum('bqgd,bkd->bgqk', qg, kg) * d ** -0.5
                probs = jax.nn.softmax(
                    jnp.where(visible, scores, -jnp.inf), -1)
                return jnp.einsum('bgqk,bkd->bqgd', probs, vg)

            context = jax.lax.map(one_group, (
                jnp.moveaxis(q.reshape(b, t, kv, group, d), 2, 0),
                jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
            context = jnp.moveaxis(context, 0, 2).reshape(b, t, heads, d)
            gate = jax.nn.sigmoid(u @ wg)
            x = x + (context * gate[..., None]).reshape(
                b, t, heads * d) @ wo

            w = rms_norm(x, g_post)
            if kind_mlp == 'dense':
                x = x + mlp(w, *take(3))
                continue
            router, e_gate, e_up, e_down, s_gate, s_up, s_down = take(7)
            flat = w.reshape(b * t, h)
            route = jax.nn.softmax(flat @ router, -1)
            weight, chosen = jax.lax.top_k(route, top_k)
            weight = weight / jnp.sum(weight, -1, keepdims=True) * scale
            routed = jnp.zeros_like(flat)
            for e in range(e_gate.shape[0]):        # the experts held
                share = jnp.sum(
                    jnp.where(chosen == first + e, weight, 0), -1)
                routed = routed + share[:, None].astype(flat.dtype) * \
                    mlp(flat, e_gate[e], e_up[e], e_down[e])
            x = x + mlp(w, s_gate, s_up, s_down) + routed.reshape(b, t, h)
        g_final, head = take(2)
        logp = jax.nn.log_softmax(
            (rms_norm(x, g_final) @ head).astype(jnp.float32), -1)
        labels = feed['labels']
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        valid = labels >= 0
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
