"""Kimi Linear causal-LM pretraining (moonshotai
Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``) as a
benchmark family: the program comes from the zoo
(``paddle_tpu.models.kimi_linear.build_pretrain``, part of the system
under test: the gated delta rule with a per-channel decay at ALL 32 of
its heads in three layers of four, latent attention without any
position encoding in the fourth, a sigmoid router whose bias picks
top-8 of 256, one chip's share of the routed experts beside a shared
one, every decoder block but the last a recompute group); the batch,
the FLOPs and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``num_experts`` counts the experts HELD here
(``experts_held`` says which), ``num_experts_published`` what the
router and its bias span; ``num_hidden_layers`` counts the layers run,
from ``first_layer`` of the model, numbered from 1 as
``linear_attn_config`` numbers them; ``assumed`` holds what
``config.json`` does not settle, two numbers among it
(``bias_update_rate``, ``bias_init_std``).  A traffic file gives
``seq_len`` and may override keys under ``changed``.
"""

import numpy as np

from benchmark.lib import flops, kimi_linear_flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative; the two readings it lies
# between are in PERF.md section 6 (PR 60) and `chip_smoke.py --phase
# kimi` prints both (my chip runs, PR 60: published widths, the cell's
# five layers and shares, one 8192-token sequence).  Every product on
# both sides is full float32 (the flash kernels' and the chunked
# recurrence's too); what differs is the order of float32 sums (the
# recurrence in chunks of 64 against a token at a time) and the tokens
# whose 8th and 9th BIASED scores nearly tie, which pick the other
# expert in the program than in the reference (the routed families'
# known exception; here four routed layers of 256 scores over 8192
# tokens: 10, 22, 35 and 42 experts a layer differ in load by one or
# two rows, and a changed choice moves the loss only where one of the
# 8 held experts is in it).  Over 22 readings (12 batches of the smoke
# phase, 10 seeds of the cell's own check) the program read 0 to
# 1.93e-6, median 4.6e-7 (the two largest 1.93e-6 and 1.38e-6; the
# train loss 0.0 from the reference routed by the program's own
# choice).  The same reference in bfloat16 throughout reads 7.33e-6 to
# 1.01e-4 over the 12 batches, quartiles 1.32e-5 / 3.89e-5 / 7.74e-5:
# NOT correct under this limit on every one, which the smoke phase
# checks ("most").  The limit stands 2.6 times over the program's
# largest reading and 1.5 times under the bfloat16 reference's
# smallest; a run in which more tokens change a held expert at a
# near-tie than in any of those would be refused.  Beta with Solar's 2,
# the decay per head, the taps in the other order, the shared key
# slice dropped or rotated, a wrong held range, no choice bias or a
# dropped 2.446 fail it by orders of magnitude at the tiny preset
# (benchmark/tests/test_rehearsal_kimi_linear.py).
REFERENCE_RTOL = 5e-6


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, and what the shared readers and FLOP counts take
    from a family whose layers differ: an operator kind
    (``layer_types``) and an MLP kind (``mlp_layer_types``) for each
    layer run.  ``num_hidden_layers`` answers the LATENT layers run
    (``mla_flash_roofline.py`` multiplies ONE layer's flash calls by
    that key) and ``layers_held`` keeps the file's count of layers, as
    ``families/ouro.py`` does for ``causal_flash_roofline``."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    merged['layers_held'] = merged['num_hidden_layers']
    kinds = kimi_linear_flops.layers_run(merged)
    merged['layer_types'] = [op for op, _ in kinds]
    merged['mlp_layer_types'] = [mlp for _, mlp in kinds]
    merged['num_hidden_layers'] = merged['layer_types'].count(
        kimi_linear_flops.LATENT)
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import kimi_linear
    s = sizes(config, traffic)
    linear, assumed = s['linear_attn_config'], config['assumed']
    assert s['mla_use_nope'] and s['q_lora_rank'] is None and \
        s['rope_scaling'] is None
    return kimi_linear.KimiLinearConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['layers_held'], first_layer=s['first_layer'],
        full_attn_layers=linear['full_attn_layers'],
        heads=s['num_attention_heads'], qk_nope=s['qk_nope_head_dim'],
        qk_rope=s['qk_rope_head_dim'], v_dim=s['v_head_dim'],
        kv_rank=s['kv_lora_rank'], kda_heads=linear['num_heads'],
        kda_head_dim=linear['head_dim'],
        conv_taps=linear['short_conv_kernel_size'],
        dense_layers=s['first_k_dense_replace'],
        dense_hidden=s['intermediate_size'],
        expert_hidden=s['moe_intermediate_size'],
        shared_experts=s['num_shared_experts'],
        experts=s['num_experts_published'],
        top_k=s['num_experts_per_token'],
        routed_scale=float(s['routed_scaling_factor']),
        renormalize=s['moe_renormalize'],
        experts_held=tuple(s['experts_held']),
        rms_eps=s['rms_norm_eps'],
        bias_update_rate=assumed['bias_update_rate']['value'],
        bias_init_std=assumed['bias_init_std']['value'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import kimi_linear
    _, _, loss = kimi_linear.build_pretrain(_zoo_config(config, traffic),
                                            traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: token ids uniform over
    the held vocabulary rows, the labels the ids shifted left (-1 where
    there is no next token); no positions (no position enters the
    model).  Ints are int32: the executor runs with x64 off."""
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
    all its heads (the delta rule in chunked form at a nominal chunk of
    64, the latent layer's scores over the causal half), the dense MLP
    or the router, the shared expert and the routed experts at the
    EXPECTED rows held here, the head
    (``benchmark/lib/kimi_linear_flops.py``); no recomputed forward."""
    return flops.TRAIN_OVER_FORWARD * \
        kimi_linear_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed, dtype=None):
    """The forward pass and loss in plain jax.numpy, float32 at highest
    matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/kimi_linear.py``; its docstring has
    the equations and what the config leaves to be assumed), given the
    same share: the layers run, the held experts, the vocabulary slice.
    The delta rule's state stepped TOKEN BY TOKEN by a ``lax.scan``,
    the filters a sum over taps of shifted arrays, dense [T, T] masks
    one head at a time (``lax.map``), a Python loop over the held
    experts, no kernel, no chunk, no sort.  ``params`` are the
    program's parameters in creation order, the non-trainable choice
    biases among them: embedding; per layer operator-norm gain, then
    Wq, filter_q [C, 4], Wk, filter_k, Wv, filter_v, Wf_down, Wf_up,
    A_log [H], dt_bias [H x 128], Wb, o-norm gain [128], Wg_down,
    Wg_up, Wo (delta rule) or Wq, Wkva, latent-norm gain [512], Wkvb,
    Wo (latent); ffn-norm gain, then gate, up, down (the dense layer)
    or router, gate [8, D, W], up, down, choice bias [256], shared
    gate, up, down; final-norm gain; head.  ``dtype`` other than
    float32 computes everything in it (``chip_smoke.py --phase
    kimi``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    eps, top_k = s['rms_norm_eps'], s['num_experts_per_token']
    kda_d = s['linear_attn_config']['head_dim']
    nope, rope, dv, rank = (s[n] for n in (
        'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
        'kv_lora_rank'))
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
        beta = jax.nn.sigmoid(u @ wb)           # no factor 2

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

    def latent_attention(u):
        wq, wkva, g_latent, wkvb, wo = take(5)
        b, t, _ = u.shape
        heads = wq.shape[1] // (nope + rope)
        q = (u @ wq).reshape(b, t, heads, nope + rope)
        kva = u @ wkva
        kv = (rms_norm(kva[..., :rank], g_latent) @ wkvb).reshape(
            b, t, heads, nope + dv)
        shared = kva[..., rank:]        # one key slice, NOT rotated
        visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

        def one_head(args):
            qh, kvh = args              # [B, T, 192], [B, T, 128 + 128]
            kh = jnp.concatenate([kvh[..., :nope], shared], -1)
            scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * \
                (nope + rope) ** -0.5
            probs = jax.nn.softmax(jnp.where(
                visible, scores, -jnp.inf).astype(jnp.float32),
                -1).astype(qh.dtype)
            return jnp.einsum('bqk,bkd->bqd', probs, kvh[..., nope:])

        context = jax.lax.map(
            one_head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
        return jnp.moveaxis(context, 0, 2).reshape(b, t, heads * dv) @ wo

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, h = x.shape
        for kind, mlp_kind in zip(s['layer_types'], s['mlp_layer_types']):
            (g_op,) = take(1)
            u = rms_norm(x, g_op)
            x = x + (latent_attention(u)
                     if kind == kimi_linear_flops.LATENT
                     else delta_rule(u))
            (g_ffn,) = take(1)
            w = rms_norm(x, g_ffn)
            if mlp_kind == 'dense':
                x = x + mlp(w, *take(3))
                continue
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
