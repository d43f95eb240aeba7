"""Ouro looped-LM pretraining (ByteDance/Ouro-2.6B, ``model_type:
ouro``) as a benchmark family: the program comes from the zoo
(``paddle_tpu.models.ouro.build_pretrain``, part of the system under
test: the layer stack applied ``total_ut_steps`` times with the same
weights as ONE ``layers.While`` sub-block, an exit gate a pass, the
expected loss over the exits less an entropy bonus); the batch, the
FLOPs and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``assumed`` holds what ``config.json`` does not settle,
each with its reason.  A traffic file gives ``seq_len`` and may
override keys under ``changed``.
"""

import numpy as np

from benchmark.lib import flops, ouro_flops

# loss of the f32 for_test program (the ``lax.while_loop`` lowering of
# the loop) on the chip against the f32 'highest' reference below,
# relative; `chip_smoke.py --phase ouro` prints both readings it lies
# between (my chip runs, PR 49: published widths, the cell's four
# layers and four passes, one 4096-token sequence; PERF.md section 6).
# Every product on both sides is full float32 (the flash kernels' too)
# and nothing here picks a top-k, so what differs is the order of
# float32 sums alone: over the phase's eight batches the program read
# 0 to 2.57e-7, median 8.59e-8 (the cell's own checks 8.59e-8 to
# 1.72e-7 on nine runs); the same reference in bfloat16 throughout
# read 2.92e-6 to 2.32e-5, median 1.04e-5, NOT correct on any.  The
# limit has 3.9 times of room under it and 2.9 over.  The
# post-operator norms, the norm between passes, the gate, the entropy
# term and a pass fewer each fail it by orders of magnitude
# (benchmark/tests/test_rehearsal_ouro.py).
REFERENCE_RTOL = 1e-6
# log of the exit distribution is taken of max(p, this): no value
# changes where p > 0 (paddle_tpu/models/reference/ouro.py)
LOG_FLOOR = 1e-30


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, plus ``layers_held`` (the file's
    ``num_hidden_layers``: the layers whose weights are here) and
    ``num_hidden_layers`` REPLACED by the layer applications a step,
    ``layers_held`` x ``total_ut_steps``: what the readers that
    multiply one layer's kernel calls by the layers of a step
    (``causal_flash_roofline``) have to count in a stack that runs
    several times."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    merged['layers_held'] = merged['num_hidden_layers']
    merged['num_hidden_layers'] = merged['layers_held'] * \
        merged['total_ut_steps']
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import ouro
    s = sizes(config, traffic)
    assert s['head_dim'] * s['num_attention_heads'] == s['hidden_size']
    assert s['num_key_value_heads'] == s['num_attention_heads']
    return ouro.OuroConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        layers=s['layers_held'], heads=s['num_attention_heads'],
        intermediate=s['intermediate_size'], steps=s['total_ut_steps'],
        max_pos=s['max_position_embeddings'], rms_eps=s['rms_norm_eps'],
        rope_theta=float(s['rope_theta']),
        entropy_weight=s['entropy_weight'],
        init_std=s['initializer_range'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import ouro
    _, _, loss = ouro.build_pretrain(_zoo_config(config, traffic),
                                     traffic['seq_len'])
    return loss


def batch(config, traffic, n, seed):
    """``n`` synthetic sequences from the seed: ids uniform over all
    the vocabulary's rows, labels the ids shifted left by one (-1 at a
    sequence's end).  Ints are int32: the executor runs with x64 off."""
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
    """Training FLOPs per token: 3 x forward; ``total_ut_steps`` passes
    of each layer's seven products and its scores and context over the
    VISIBLE causal pairs, as many products of the head, the gate
    (``benchmark/lib/ouro_flops.py``).  A forward the gradient would
    replay is not in it."""
    return flops.TRAIN_OVER_FORWARD * ouro_flops.forward_flops_per_token(
        sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed, dtype=None,
                   without=()):
    """The looped forward pass and its loss in plain jax.numpy, float32
    at highest matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/ouro.py``; its docstring has the
    equations and what the config leaves to be assumed).  No kernel,
    no scan: a Python loop over the passes on the same arrays, the
    causal softmax under the mask built whole, a block of 512 queries
    at a time (``lax.map``) so that it fits beside the program's state.
    ``params`` are the program's parameters in creation order:
    embedding; per layer g1, Wq, Wk, Wv, Wo, g2, g3, Wg, Wu, Wd, g4;
    g_f; W_head; w_g [D, 1]; b_g [1].  ``dtype`` other than float32
    computes everything but the logits, the gate and the loss in it
    (``chip_smoke.py --phase ouro``).  ``without`` leaves a part out
    (``post_norms``, ``norm_between``, ``gate``, ``entropy``): the
    rehearsal shows each moves the loss."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    heads, d, eps = s['num_attention_heads'], s['head_dim'], \
        s['rms_norm_eps']
    params = iter(params)

    def take(n, dtype=dtype):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    def rotate(x, positions):
        inv_freq = 1.0 / (np.float32(s['rope_theta']) ** (
            np.arange(d // 2, dtype=np.float32) / np.float32(d // 2)))
        angle = positions.astype(jnp.float32)[:, :, None, None] * \
            jnp.asarray(inv_freq)
        cos, sin = jnp.cos(angle).astype(x.dtype), \
            jnp.sin(angle).astype(x.dtype)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def attend(q, k, v):
        b, t = q.shape[:2]
        kpos = jnp.arange(t)
        block = min(512, t)

        def one_block(args):
            qb, qpos = args
            scores = jnp.einsum('bqhd,bkhd->bhqk', qb, k) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(
                kpos[None, :] <= qpos[:, None], scores,
                -jnp.inf).astype(jnp.float32), -1).astype(qb.dtype)
            return jnp.einsum('bhqk,bkhd->bqhd', probs, v)

        out = jax.lax.map(one_block, (
            jnp.moveaxis(q.reshape(b, t // block, block, heads, d), 1, 0),
            jnp.arange(t).reshape(t // block, block)))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, heads * d)

    post = 'post_norms' not in without
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        stack = [take(11) for _ in range(s['layers_held'])]
        g_f, w_head = take(2)
        w_gate, b_gate = take(2, jnp.float32)
        x = embedding[feed['ids']]
        b, t, _ = x.shape
        labels = feed['labels']
        valid = labels >= 0
        survive = jnp.ones((b, t), jnp.float32)
        expected = neg_entropy = jnp.zeros((b, t), jnp.float32)
        for step in range(s['total_ut_steps']):
            for g1, wq, wk, wv, wo, g2, g3, wg, wu, wd, g4 in stack:
                u = rms_norm(x, g1)
                q = rotate((u @ wq).reshape(b, t, heads, d),
                           feed['pos_ids'])
                k = rotate((u @ wk).reshape(b, t, heads, d),
                           feed['pos_ids'])
                a = attend(q, k, (u @ wv).reshape(b, t, heads, d)) @ wo
                x = x + (rms_norm(a, g2) if post else a)
                u = rms_norm(x, g3)
                m = (jax.nn.silu(u @ wg) * (u @ wu)) @ wd
                x = x + (rms_norm(m, g4) if post else m)
            h = rms_norm(x, g_f)
            if 'norm_between' not in without:
                x = h
            logp = jax.nn.log_softmax((h @ w_head).astype(jnp.float32),
                                      -1)
            picked = jnp.take_along_axis(
                logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
            lam = jax.nn.sigmoid(
                (h.astype(jnp.float32) @ w_gate)[..., 0] + b_gate[0])
            if 'gate' in without:
                lam = jnp.full_like(lam, 0.5)
            # the last pass takes the mass that is left
            p = survive * (1.0 if step == s['total_ut_steps'] - 1
                           else lam)
            survive = survive * (1.0 - lam)
            expected = expected + p * jnp.where(valid, -picked, 0.0)
            neg_entropy = neg_entropy + p * jnp.log(
                jnp.maximum(p, LOG_FLOOR))
        beta = 0.0 if 'entropy' in without else s['entropy_weight']
        per_token = expected + beta * neg_entropy
        return jnp.sum(jnp.where(valid, per_token, 0.0)) / jnp.sum(valid)
