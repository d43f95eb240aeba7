"""EvaByte multi-byte-prediction pretraining (EvaByte/EvaByte,
``model_type: evabyte``, ``attention_class: eva``) as a benchmark
family: the program comes from the zoo
(``paddle_tpu.models.evabyte.build_pretrain``, part of the system under
test: EVA attention, exact and causal inside 2048-byte windows and
joined in one softmax with learned 16-byte chunk summaries of every
earlier window; eight next-byte heads over 320 rows; a float32
residual stream under bf16 AMP); the batch, the FLOPs and the plain
reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``assumed`` holds what ``config.json`` does not settle,
each with its reason.  A traffic file gives ``seq_len`` and may
override keys under ``changed``.
"""

import numpy as np

from benchmark.lib import evabyte_flops, flops

# loss of the f32 for_test program on the chip against the f32
# 'highest' reference below, relative; `chip_smoke.py --phase evabyte`
# prints both readings it lies between (my chip runs, PR 38: published
# widths, the cell's four layers, one 4096-byte sequence; PERF.md
# section 6).  Every product on both sides is full float32 (the flash
# kernels' too) and no top-k tie exists here, so what differs is the
# order of float32 sums alone: over six batches the program read 0 to
# 2.34e-7, median 0 (the cell's own checks 0 to 1.56e-7 on every
# run); the same reference in bfloat16 throughout read 4.76e-6 to
# 1.88e-5, median 1.05e-5, NOT correct on any.  The limit has four
# times of room on both sides and sits an order under the routed
# families' 1e-5 to 2e-5.  The remote stream, mu, phi, a head of the
# eight, the window, the chunk and the gain's unit offset each fail it
# by orders of magnitude (benchmark/tests/test_rehearsal_evabyte.py).
REFERENCE_RTOL = 1e-6


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, and the head width."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    merged['head_dim'] = merged['hidden_size'] // \
        merged['num_attention_heads']
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import evabyte
    s = sizes(config, traffic)
    return evabyte.EvaByteConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['num_hidden_layers'], heads=s['num_attention_heads'],
        intermediate=s['intermediate_size'],
        pred_heads=s['num_pred_heads'], window=s['window_size'],
        chunk=s['chunk_size'], max_pos=s['max_position_embeddings'],
        rms_eps=s['rms_norm_eps'], rope_theta=float(s['rope_theta']),
        init_std=s['init_std'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import evabyte
    _, _, loss = evabyte.build_pretrain(_zoo_config(config, traffic),
                                        traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: byte ids uniform over
    all the vocabulary's rows; column i of the labels is the ids
    shifted left by 1 + i (-1 where the sequence has no such byte).
    Ints are int32: the executor runs with x64 off."""
    t = traffic['seq_len']
    s = sizes(config, traffic)
    rng = np.random.RandomState(seed % 2 ** 32)
    ids = rng.randint(0, s['vocab_size'], (n, t))
    labels = np.full((n, t, s['num_pred_heads']), -1)
    for i in range(s['num_pred_heads']):
        labels[:, :t - 1 - i, i] = ids[:, 1 + i:]
    return {'ids': ids.astype('int32'),
            'pos_ids': np.tile(np.arange(t, dtype='int32'), (n, 1)),
            'labels': labels.astype('int32')}


def items_per_sample(config, traffic):
    return traffic['seq_len']


def flops_per_item(config, traffic):
    """Training FLOPs per byte: 3 x forward; each layer's four
    projections, the scores and context over the VISIBLE pairs of both
    attention streams, the gated MLP, and the eight heads
    (``benchmark/lib/evabyte_flops.py``)."""
    return flops.TRAIN_OVER_FORWARD * \
        evabyte_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed, dtype=None):
    """The forward pass and loss in plain jax.numpy, float32 at highest
    matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/evabyte.py``; its docstring has the
    equations and what the config leaves to be assumed).  No kernel, no
    windows folded into the batch, no log-sum-exp merge: ONE softmax
    over the [T + T / chunk] keys of both kinds under the mask built
    whole, a block of 512 queries at a time (``lax.map``) so that it
    fits beside the program's state.  ``params`` are the program's
    parameters in creation order: embedding; per layer g1, Wq, Wk, Wv,
    phi [H, d], mu [H, d], Wo, g2, Wg, Wu, Wd; g_last; W_0 .. W_7.
    ``dtype`` other than float32 computes everything but the logits in
    it (``chip_smoke.py --phase evabyte``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    heads, d, eps = s['num_attention_heads'], s['head_dim'], \
        s['rms_norm_eps']
    window, chunk = s['window_size'], s['chunk_size']
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, g):         # norm_add_unit_offset: the gain is 1 + g
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1.0 + g)

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

    def attend(q, k, v, phi, mu):
        b, t = q.shape[:2]
        kc = k.reshape(b, t // chunk, chunk, heads, d)
        vc = v.reshape(b, t // chunk, chunk, heads, d)
        a = jax.nn.softmax(jnp.einsum('bnchd,hd->bnch', kc, phi).astype(
            jnp.float32), axis=2).astype(k.dtype)
        keys = jnp.concatenate(
            [k, jnp.einsum('bnch,bnchd->bnhd', a, kc) + mu], 1)
        values = jnp.concatenate(
            [v, jnp.einsum('bnch,bnchd->bnhd', a, vc)], 1)
        kpos, cpos = jnp.arange(t), jnp.arange(t // chunk)
        block = min(512, t)

        def one_block(args):
            qb, qpos = args
            scores = jnp.einsum('bqhd,bkhd->bhqk', qb, keys) * d ** -0.5
            mine = qpos[:, None] // window
            visible = jnp.concatenate([
                (kpos[None, :] // window == mine) &
                (kpos[None, :] <= qpos[:, None]),
                cpos[None, :] < (window // chunk) * mine], 1)
            probs = jax.nn.softmax(jnp.where(
                visible, scores, -jnp.inf).astype(jnp.float32),
                -1).astype(qb.dtype)
            return jnp.einsum('bhqk,bkhd->bqhd', probs, values)

        out = jax.lax.map(one_block, (
            jnp.moveaxis(q.reshape(b, t // block, block, heads, d), 1, 0),
            jnp.arange(t).reshape(t // block, block)))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, heads * d)

    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        b, t, _ = x.shape
        for _ in range(s['num_hidden_layers']):
            g1, wq, wk, wv, phi, mu, wo, g2, wg, wu, wd = take(11)
            u = rms_norm(x, g1)
            q = rotate((u @ wq).reshape(b, t, heads, d), feed['pos_ids'])
            k = rotate((u @ wk).reshape(b, t, heads, d), feed['pos_ids'])
            v = (u @ wv).reshape(b, t, heads, d)
            x = x + attend(q, k, v, phi, mu) @ wo
            u = rms_norm(x, g2)
            x = x + (jax.nn.silu(u @ wg) * (u @ wu)) @ wd
        (g_last,) = take(1)
        z = rms_norm(x, g_last)
        labels = feed['labels']
        total = 0.0
        for i, w in enumerate(take(s['num_pred_heads'])):
            logp = jax.nn.log_softmax((z @ w).astype(jnp.float32), -1)
            mine = labels[..., i]
            picked = jnp.take_along_axis(
                logp, jnp.maximum(mine, 0)[..., None], -1)[..., 0]
            valid = mine >= 0
            total = total - jnp.sum(jnp.where(valid, picked, 0.0)) / \
                jnp.sum(valid)
        return total / s['num_pred_heads']
