"""Solar Open 2 causal-LM pretraining (upstage Solar-Open2-250B,
``model_type: solar_open2``) as a benchmark family: the program comes
from the zoo (``paddle_tpu.models.solar_open2.build_pretrain``, part of
the system under test: the gated delta rule with a per-channel decay in
three layers of four, gated grouped-query softmax attention without
any position encoding in the fourth, a sigmoid router whose bias picks
top-8 of 320, one chip's share of the routed experts beside a shared
one, one chip's share of every layer's HEADS); the batch, the FLOPs
and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``n_routed_experts`` counts the experts HELD here
(``experts_held`` says which), ``n_routed_experts_published`` what the
router and its bias span; ``num_attention_heads``,
``num_key_value_heads`` and ``linear_attn_config.num_heads`` count the
heads held of ``head_shards`` shares; ``assumed`` holds what
``config.json`` does not settle, two numbers among it
(``bias_update_rate``, ``bias_init_std``).  A traffic file gives
``seq_len`` and may override keys under ``changed``.
"""

import numpy as np

from benchmark.lib import flops, solar_flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative; the two readings it lies
# between are in PERF.md section 6 (PR 46) and `chip_smoke.py --phase
# solar` prints both (my chip runs, PR 46: published widths, the
# cell's four layers and shares, one 4096-token sequence).  Every
# product on both sides is full float32 (the flash kernels' and the
# chunked recurrence's too); what differs is the order of float32 sums
# (the recurrence in chunks of 64 against a token at a time: 5e-6 of
# the op's output at 32768 positions) and the tokens whose 8th and 9th
# BIASED scores nearly tie, which pick the other expert in the program
# than in the reference (the routed families' known exception; here
# four routed layers of 320 scores, and a changed choice moves the
# loss only where one of the 8 held experts is in it): over 12 batches
# the program read 0 to 2.35e-6, median 8.7e-8 (the two largest,
# 2.35e-6 and 1.83e-6, with two experts' loads off in one layer and
# 8.7e-8 from the reference routed by the program's own choice; the
# cell's own checks over 31 runs at most 1.75e-7; 48 more batches over
# four more draws of the weights at most 7.86e-7).  The same reference
# in bfloat16 throughout reads 9.61e-6 to 1.47e-4 over those 12
# batches, quartiles 4.10e-5 / 6.19e-5 / 8.09e-5: NOT correct under
# this limit on every one, which `chip_smoke.py --phase solar` checks.
# Over the 48 further batches it reads 1.40e-6, 5.52e-6, 7.32e-6,
# 9.96e-6 and from 1.25e-5 up (quartiles 3.59e-5 / 6.72e-5 / 1.16e-4):
# its error is a signed sum that can land near zero, so ONE bfloat16
# batch in 60 reads under the program's own largest and no limit
# refuses them all (the issue asks for "most"); 1e-5 would pass five.
# The limit stands 2.1 times over the program's largest reading of 91;
# a run in which more tokens change a held expert at a near-tie than
# in any of those (each moves the loss by one to two millionths) would
# be refused.  Beta without its 2, the decay per head instead of per
# channel, the taps in the other order, the gate before the norm or a
# wrong held range fail it by orders of magnitude
# (benchmark/tests/test_rehearsal_solar.py).
REFERENCE_RTOL = 5e-6


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, and what the shared readers and FLOP counts take
    from a family whose layers differ: a kind (``layer_types``), a
    query-head count and an MLP kind for each layer run."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    merged['layer_types'] = solar_flops.layers_run(merged)
    merged['mlp_layer_types'] = ['sparse'] * len(merged['layer_types'])
    merged['num_attention_heads_per_layer'] = \
        [merged['num_attention_heads']] * len(merged['layer_types'])
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import solar_open2
    s = sizes(config, traffic)
    linear, assumed = s['linear_attn_config'], config['assumed']
    return solar_open2.SolarOpen2Config(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['num_hidden_layers'], first_layer=s['first_layer'],
        gqa_layers=s['gqa_layers'], heads=s['num_attention_heads'],
        kv_heads=s['num_key_value_heads'], head_dim=s['head_dim'],
        kda_heads=linear['num_heads'], kda_head_dim=linear['head_dim'],
        conv_taps=linear['short_conv_kernel_size'],
        neg_eigval=s['kda_allow_neg_eigval'],
        expert_hidden=s['moe_intermediate_size'],
        shared_experts=s['n_shared_experts'],
        experts=s['n_routed_experts_published'],
        top_k=s['num_experts_per_tok'],
        routed_scale=float(s['routed_scaling_factor']),
        renormalize=s['norm_topk_prob'],
        experts_held=tuple(s['experts_held']),
        rms_eps=s['rms_norm_eps'],
        bias_update_rate=assumed['bias_update_rate']['value'],
        bias_init_std=assumed['bias_init_std']['value'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import solar_open2
    _, _, loss = solar_open2.build_pretrain(_zoo_config(config, traffic),
                                            traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: token ids uniform over
    the held vocabulary rows, the labels the ids shifted left (-1 where
    there is no next token); no positions (the model has no position
    encoding).  Ints are int32: the executor runs with x64 off."""
    t = traffic['seq_len']
    rng = np.random.RandomState(seed % 2 ** 32)
    ids = rng.randint(0, sizes(config, traffic)['vocab_size'], (n, t))
    labels = np.full((n, t), -1)
    labels[:, :-1] = ids[:, 1:]
    return {'ids': ids.astype('int32'), 'labels': labels.astype('int32')}


def items_per_sample(config, traffic):
    return traffic['seq_len']


def flops_per_item(config, traffic):
    """Training FLOPs per token: 3 x forward; each layer's operator at
    the heads held (the softmax layer's scores over the causal half,
    the delta rule in chunked form at a nominal chunk of 64), the
    router, the shared expert, the routed experts at the EXPECTED rows
    held here, the head (``benchmark/lib/solar_flops.py``)."""
    return flops.TRAIN_OVER_FORWARD * \
        solar_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed, dtype=None):
    """The forward pass and loss in plain jax.numpy, float32 at highest
    matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/solar_open2.py``; its docstring has
    the equations and what the config leaves to be assumed), given the
    same share: the layers run, the held heads, the held experts, the
    vocabulary slice.  The delta rule's state stepped TOKEN BY TOKEN by
    a ``lax.scan``, the filters a sum over taps of shifted arrays,
    dense [T, T] masks one query head at a time (``lax.map``), a Python
    loop over the held experts, no kernel, no chunk, no sort.
    ``params`` are the program's parameters in creation order, the
    non-trainable choice biases among them: embedding; per layer
    operator-norm gain, then Wq, Wk, Wv, Wgate, Wo (softmax) or Wq,
    filter_q [C, 4], Wk, filter_k, Wv, filter_v, Wf_down, Wf_up, A_log
    [H], dt_bias [H x 128], Wb, o-norm gain [128], Wg_down, Wg_up, Wo
    (delta rule); ffn-norm gain, router, gate [8, D, W], up, down,
    choice bias [320], shared gate, up, down; final-norm gain; head.
    ``dtype`` other than float32 computes everything in it
    (``chip_smoke.py --phase solar``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    d, eps, top_k = s['head_dim'], s['rms_norm_eps'], \
        s['num_experts_per_tok']
    kda_d = s['linear_attn_config']['head_dim']
    first = s['experts_held'][0]
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def mlp(w, gate, up, down):
        return (jax.nn.silu(w @ gate) * (w @ up)) @ down

    def filtered(z, w):             # tap j looks taps-1-j back
        taps, t = w.shape[1], z.shape[1]
        c = jnp.zeros_like(z)
        for j in range(taps):
            back = taps - 1 - j
            c = c + w[:, j] * jnp.concatenate(
                [jnp.zeros_like(z[:, :back]), z[:, :t - back]], 1)
        return jax.nn.silu(c)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) +
                            1e-6)

    def delta_rule(u):
        (wq, fq, wk, fk, wv, fv, wf_down, wf_up, a_log, dt_bias, wb,
         g_o, wg_down, wg_up, wo) = take(15)
        b, t, _ = u.shape
        h = wq.shape[1] // kda_d
        q, k, v = (filtered(u @ w, f).reshape(b, t, h, kda_d)
                   for w, f in ((wq, fq), (wk, fk), (wv, fv)))
        q, k = unit(q) * kda_d ** -0.5, unit(k)
        a = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (u @ wf_down) @ wf_up + dt_bias).reshape(b, t, h, kda_d)
        beta = jax.nn.sigmoid(u @ wb) * (
            2.0 if s['kda_allow_neg_eigval'] else 1.0)

        def token(state, x):
            q_t, k_t, v_t, a_t, beta_t = x
            state = jnp.exp(a_t)[..., None] * state
            u_t = beta_t[..., None] * (
                v_t - jnp.einsum('bhkv,bhk->bhv', state, k_t))
            state = state + k_t[..., None] * u_t[..., None, :]
            return state, jnp.einsum('bhkv,bhk->bhv', state, q_t)

        _, o = jax.lax.scan(
            token, jnp.zeros((b, h, kda_d, kda_d), dtype),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta)))
        o = rms_norm(jnp.moveaxis(o, 0, 1), g_o)
        gate = jax.nn.sigmoid((u @ wg_down) @ wg_up).reshape(o.shape)
        return (o * gate).reshape(b, t, h * kda_d) @ wo

    def softmax_attention(u):
        wq, wk, wv, wgate, wo = take(5)
        b, t, _ = u.shape
        heads, kv_heads = wq.shape[1] // d, wk.shape[1] // d
        q = (u @ wq).reshape(b, t, heads, d)
        k = (u @ wk).reshape(b, t, kv_heads, d)
        v = (u @ wv).reshape(b, t, kv_heads, d)
        group = heads // kv_heads
        visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

        def one_head(args):
            qh, i = args                # [B, T, d], the query head
            kh = jnp.take(k, i // group, axis=2)
            vh = jnp.take(v, i // group, axis=2)
            scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(
                visible, scores, -jnp.inf).astype(jnp.float32),
                -1).astype(qh.dtype)
            return jnp.einsum('bqk,bkd->bqd', probs, vh)

        context = jax.lax.map(
            one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
        context = jnp.moveaxis(context, 0, 2).reshape(b, t, heads * d)
        return (context * jax.nn.sigmoid(u @ wgate)) @ wo

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, h = x.shape
        for kind in s['layer_types']:
            (g_op,) = take(1)
            u = rms_norm(x, g_op)
            x = x + (softmax_attention(u) if kind == solar_flops.GQA
                     else delta_rule(u))
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn)
            router, e_gate, e_up, e_down, bias = take(5)
            flat = w.reshape(b * t, h)
            scores = jax.nn.sigmoid(flat @ router)
            _, chosen = jax.lax.top_k(scores + bias, top_k)
            picked = jnp.take_along_axis(scores, chosen, -1)
            weight = picked / (jnp.sum(picked, -1, keepdims=True) +
                               1e-20) * s['routed_scaling_factor']
            routed = jnp.zeros_like(flat)
            for e in range(e_gate.shape[0]):        # the experts held
                share = jnp.sum(
                    jnp.where(chosen == first + e, weight, 0), -1)
                routed = routed + share[:, None].astype(flat.dtype) * \
                    mlp(flat, e_gate[e], e_up[e], e_down[e])
            x = x + mlp(w, *take(3)) + routed.reshape(b, t, h)
        g_final, head = take(2)
        logp = jax.nn.log_softmax(
            (rms_norm(x, g_final) @ head).astype(jnp.float32), -1)
        labels = feed['labels']
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        valid = labels >= 0
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
