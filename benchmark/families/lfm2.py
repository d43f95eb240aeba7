"""LFM2 causal-LM pretraining (LiquidAI LFM2-8B-A1B, ``model_type:
lfm2_moe``) as a benchmark family: the program comes from the zoo
(``paddle_tpu.models.lfm2.build_pretrain``, part of the system under
test: gated short convolutions in three layers of four, grouped-query
causal attention at head width 64 with per-head QK-norm in the fourth,
a sigmoid router whose bias picks top-4 of 32, one chip's share of the
routed experts, the head tied to the embedding); the batch, the FLOPs
and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``num_experts`` counts the experts HELD here
(``experts_held`` says which), ``num_experts_published`` what the
router and its bias span; ``first_layer`` which of the model's layers
the run starts at (``layer_types`` stays the model's whole pattern);
``assumed`` holds what ``config.json`` does not settle, three numbers
among it (``renorm_eps``, ``bias_update_rate``, ``bias_init_std``).  A
traffic file gives ``seq_len`` and may override keys under ``changed``.
"""

import numpy as np

from benchmark.lib import flops, lfm2_flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative; the two readings it lies
# between are in PERF.md section 6 (PR 36) and `chip_smoke.py --phase
# lfm2` prints both (my chip runs, PR 36: published widths, the cell's
# five layers, one 8192-token sequence).  Every product on both sides
# is full float32 (the flash kernels' too); what differs is the order
# of float32 sums and the tokens whose 4th and 5th BIASED scores nearly
# tie, which pick the other expert in the program than in the reference
# and move the mean over 8191 targets by about 1e-6 each (the routed
# families' known exception; here four routed layers of 32 scores):
# over 12 batches the program read 0 to 1.61e-6, median 0 (1.89e-6 at
# seven layers; the cell's own checks over 23 runs at most 1.51e-6).
# The same reference in bfloat16 throughout reads 1.51e-6 to 1.02e-4
# over those batches, quartiles 8.35e-6 / 1.05e-5 / 3.96e-5, NOT
# correct under this limit on 7 of the 12: like the other routed
# families' it cannot refuse every bfloat16 batch, and it stands 6.2
# times over the program's largest reading and just under the bfloat16
# median.  The taps in the other order, the gates swapped, a head of
# its own or a wrong held range fail it by orders of magnitude, rotary
# before the QK-norm by 6 times (benchmark/tests/test_rehearsal_lfm2.py).
REFERENCE_RTOL = 1e-5


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, and what the shared readers and FLOP counts take
    from a family whose layers differ: ``layer_types`` cut to the
    layers run (``layer_types_published`` keeps the model's whole
    pattern), a query-head count and an MLP kind for each of them, the
    head width."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    merged['layer_types_published'] = merged['layer_types']
    run = lfm2_flops.layers_run(merged)
    merged['layer_types'] = [kind for _, kind, _ in run]
    merged['mlp_layer_types'] = [mlp for _, _, mlp in run]
    merged['num_attention_heads_per_layer'] = \
        [merged['num_attention_heads']] * len(run)
    merged['head_dim'] = merged['hidden_size'] // \
        merged['num_attention_heads']
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import lfm2
    s = sizes(config, traffic)
    assumed = config['assumed']
    return lfm2.Lfm2Config(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['num_hidden_layers'], heads=s['num_attention_heads'],
        kv_heads=s['num_key_value_heads'],
        layer_types=s['layer_types_published'],
        first_layer=s['first_layer'], dense_layers=s['num_dense_layers'],
        dense_hidden=s['intermediate_size'],
        expert_hidden=s['moe_intermediate_size'],
        experts=s['num_experts_published'],
        top_k=s['num_experts_per_tok'],
        routed_scale=float(s['routed_scaling_factor']),
        renormalize=s['norm_topk_prob'],
        renorm_eps=assumed['renorm_eps']['value'],
        conv_taps=s['conv_L_cache'],
        experts_held=tuple(s['experts_held']),
        rms_eps=s['norm_eps'], rope_theta=float(s['rope_theta']),
        bias_update_rate=assumed['bias_update_rate']['value'],
        bias_init_std=assumed['bias_init_std']['value'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import lfm2
    _, _, loss = lfm2.build_pretrain(_zoo_config(config, traffic),
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
    """Training FLOPs per token: 3 x forward; each layer's operator
    (the short convolution's two projections, or q / k / v / o and the
    scores over the causal half), the router, the dense MLP or the
    routed experts at the EXPECTED rows held here (4 x 8 / 32 = one
    expert MLP a token), the tied head
    (``benchmark/lib/lfm2_flops.py``)."""
    return flops.TRAIN_OVER_FORWARD * \
        lfm2_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed, dtype=None):
    """The forward pass and loss in plain jax.numpy, float32 at highest
    matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/lfm2.py``; its docstring has the
    equations and what the config leaves to be assumed), given the same
    share: the layers run, the held experts, the vocabulary slice.  The
    convolution a sum over taps of shifted arrays, dense [T, T] masks,
    a Python loop over the held experts, no kernel, no sort.  Computed
    in blocks so that it fits beside the program's state: attention one
    query head at a time (``lax.map``: one head's [T, T] scores alive,
    not all 32).  ``params`` are the program's parameters in creation
    order, the non-trainable choice biases among them: embedding; per
    layer operator-norm gain, then W_in, filter [C, L], W_out (conv) or
    Wq, Wk, Wv, q gain, k gain, Wo (attention), ffn-norm gain, then
    gate, up, down (dense) or router, gate [8, D, H], up, down, choice
    bias [32] (sparse); final-norm gain; the head is the embedding.
    ``dtype`` other than float32 computes everything in it
    (``chip_smoke.py --phase lfm2``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    heads, kv_heads, d = (s['num_attention_heads'],
                          s['num_key_value_heads'], s['head_dim'])
    eps, top_k, taps = s['norm_eps'], s['num_experts_per_tok'], \
        s['conv_L_cache']
    first = s['experts_held'][0]
    renorm_eps = config['assumed']['renorm_eps']['value']
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def rotate(x, positions):
        """[B, T, H, d]: rotate-half, feature i with i + d/2, turned by
        pos * theta^(-2i / d)."""
        inv_freq = 1.0 / (np.float32(s['rope_theta']) ** (
            np.arange(d // 2, dtype=np.float32) / np.float32(d // 2)))
        angle = positions.astype(jnp.float32)[:, :, None, None] * \
            jnp.asarray(inv_freq)
        cos, sin = jnp.cos(angle).astype(x.dtype), \
            jnp.sin(angle).astype(x.dtype)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def mlp(w, gate, up, down):
        return (jax.nn.silu(w @ gate) * (w @ up)) @ down

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, h = x.shape
        visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        for kind, ffn in zip(s['layer_types'], s['mlp_layer_types']):
            (g_op,) = take(1)
            u = rms_norm(x, g_op)
            if kind == lfm2_flops.CONV:
                w_in, w, w_out = take(3)
                bcx = u @ w_in
                z = bcx[..., :h] * bcx[..., 2 * h:]         # B * X
                c = jnp.zeros_like(z)
                for j in range(taps):       # tap j looks taps-1-j back
                    back = taps - 1 - j
                    c = c + w[:, j] * jnp.concatenate(
                        [jnp.zeros_like(z[:, :back]), z[:, :t - back]], 1)
                x = x + (bcx[..., h:2 * h] * c) @ w_out     # C * c
            else:
                wq, wk, wv, g_q, g_k, wo = take(6)
                q = rotate(rms_norm((u @ wq).reshape(b, t, heads, d),
                                    g_q), feed['pos_ids'])
                k = rotate(rms_norm((u @ wk).reshape(b, t, kv_heads, d),
                                    g_k), feed['pos_ids'])
                v = (u @ wv).reshape(b, t, kv_heads, d)
                group = heads // kv_heads

                def one_head(args):
                    qh, i = args            # [B, T, d], the query head
                    kh = jnp.take(k, i // group, axis=2)
                    vh = jnp.take(v, i // group, axis=2)
                    scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * \
                        d ** -0.5
                    probs = jax.nn.softmax(jnp.where(
                        visible, scores, -jnp.inf).astype(jnp.float32),
                        -1).astype(qh.dtype)
                    return jnp.einsum('bqk,bkd->bqd', probs, vh)

                context = jax.lax.map(
                    one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
                x = x + jnp.moveaxis(context, 0, 2).reshape(
                    b, t, heads * d) @ wo
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn)
            if ffn == 'dense':
                x = x + mlp(w, *take(3))
                continue
            router, e_gate, e_up, e_down, bias = take(5)
            flat = w.reshape(b * t, h)
            scores = jax.nn.sigmoid(flat @ router)
            _, chosen = jax.lax.top_k(scores + bias, top_k)
            picked = jnp.take_along_axis(scores, chosen, -1)
            weight = picked / (jnp.sum(picked, -1, keepdims=True) +
                               renorm_eps) * s['routed_scaling_factor']
            routed = jnp.zeros_like(flat)
            for e in range(e_gate.shape[0]):        # the experts held
                share = jnp.sum(
                    jnp.where(chosen == first + e, weight, 0), -1)
                routed = routed + share[:, None].astype(flat.dtype) * \
                    mlp(flat, e_gate[e], e_up[e], e_down[e])
            x = x + routed.reshape(b, t, h)
        (g_final,) = take(1)
        logp = jax.nn.log_softmax(
            (rms_norm(x, g_final) @ embedding.T).astype(jnp.float32), -1)
        labels = feed['labels']
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        valid = labels >= 0
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
