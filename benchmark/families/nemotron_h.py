"""Nemotron-H causal-LM pretraining (NVIDIA
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``) as a
benchmark family: the program comes from the zoo
(``paddle_tpu.models.nemotron_h.build_pretrain``, part of the system
under test: a layer that is ONE mixer under one pre-norm, its kind read
from the pattern string; Mamba-2's recurrence as matrix products over
128-token chunks, ``ssd_scan``; squared-ReLU experts without a gate,
one chip's share of 128 under a sigmoid router whose bias picks top-6,
beside a shared expert; grouped-query attention at 16 queries a K/V
head with no position encoding; the leading layers recompute groups);
the batch, the FLOPs and the plain reference live here.

A configuration file holds the keys of the model's ``config.json`` at
its top level as they are run (``published`` keeps the catalog's row
verbatim); ``n_routed_experts`` counts the experts HELD here
(``experts_held`` says which), ``n_routed_experts_published`` what the
router and its bias span; ``num_hidden_layers`` counts the layers run,
the first letters of ``hybrid_override_pattern`` (the file keeps the
model's whole pattern, as ``lfm2``'s keeps ``layer_types``; ``sizes``
cuts it); ``assumed`` holds
what ``config.json`` does not settle, four numbers among it
(``bias_update_rate``, ``bias_init_std``, ``embed_std``,
``recompute_groups``).  A
traffic file gives ``seq_len`` and may override keys under
``changed``.
"""

import numpy as np

from benchmark.lib import flops, nemotron_h_flops

# THE TOLERANCE of the harness's reference check (the f32 for_test
# program's loss on the chip against the f32 'highest' reference below,
# relative) is set for EACH comparison from what the reference itself
# cannot decide, as Xing4's family does, and the number the harness is
# handed is the MIDDLE of what the reference can decide.  Every product
# on both sides is full float32 (the flash kernels' and the chunked
# scan's too); what differs is the order of float32 sums (the
# recurrence in chunks of 128 against a token at a time), which moves
# the loss by its last places, UNLESS a token's 6th and 7th BIASED
# scores lie closer than the two sides' scores agree and the program
# picks the other expert than the reference (a changed choice moves
# the loss only where one of the 8 held experts is in the pair, by a
# gate of 2.5 x score, and what follows reads it through four Mamba-2
# states and the attention layer).  So the reference runs its forward
# pass twice, the second time with every choice within TIE_MARGIN taken
# the other way, and finds the span its own loss can take
# (`reference_readings`: `low` <= 0 <= `high`, every undecided token's
# own position by its sign, all other positions as one signed sum).
# The harness compares ONE number with ONE relative tolerance, so
# `reference_loss` answers the middle of [loss + low, loss + high] and
# REFERENCE_RTOL becomes half the span plus BASE_RTOL (`allowed`):
# half as wide as a limit of BASE_RTOL + |moved| about the reference's
# own choice, and a reading on the far side of the reference from the
# undecided choice is refused.  Where the reference finds no choice
# within the margin, the number is its loss and the limit BASE_RTOL.
# The rule reads the reference's score margins alone: no batch by name,
# nothing of the program's.  `reference_loss` is traced under the
# harness's jit, so the readings reach the host through a callback that
# sets REFERENCE_RTOL before the harness reads it (it reads it after
# the reference has run: benchmark/run.py `reference_check`).
#
# THE READINGS the numbers lie between (my chip runs, PR 65,
# `chiprun_out/pr65e/`: `chip_smoke.py --phase nemotron_h`, which puts
# program and control through THIS comparison batch by batch, on 12
# batches, and the same over 24 more with the margin swept from 1e-7 to
# 1e-4; published widths, the cell's nine layers and shares, one
# 8192-token sequence; PERF.md section 6 has every batch):
# BASE_RTOL 6e-7: where no choice moved, the program read 0 to 1.86e-7
# from the reference on 30 of the 36 batches (0, 1 or 2 last places of
# a float32 near 10.2): 3.2 times under the base; the control, the same
# reference in bfloat16 throughout, read 7.4e-7 to 9.6e-5 (median
# 2.2e-5), its smallest 1.2 times over the base.
# TIE_MARGIN 3e-6: on the other 6 batches the program took ONE choice
# the other way and read 9.3e-7 to 5.68e-6; each of the six lay within
# 1e-6 of a tie (three within 3e-7), and the reference's second pass
# moved its own loss to within 1.5e-7 of the program's: the margin
# stands three times over the farthest, and finds 3 to 17 undecided
# choices a batch (32,768 choices), about one in eight with a held
# expert in its pair, so the tolerance is the base (to 2%) on 14 of the
# 36 batches and 6.9e-7 to 5.98e-6 on the others.
# WHAT THE LIMIT REFUSES: the program lay at 0 to 0.83 of its batch's
# tolerance (at an END of the span where the span is one-sided, which
# reads half-span / (half-span + base): room is the base, not the
# ratio).  The control is refused on all 12 batches of the smoke phase
# (6.0 to 65 times its batch's tolerance), which the phase requires, and
# on 23 of the other 24; on one it read -7.4e-7, eight last places of
# the loss, inside a span of +-4.5e-7 plus the base.  A bfloat16
# reading is a signed sum about 3e-5 wide that lands that near zero
# once in some forty batches; no limit on one number that lets
# float32's own two places through with room refuses that batch, and
# this one would have taken it with the span's 4.5e-7 or without
# (7.4e-7 against 6e-7 is 1.2 times: no room either way).  The taps in
# the other order, one decay for all heads, B and C of the other group,
# no skip, a wrong held range, no choice bias, a dropped 2.5 or another
# top-k miss it by orders of magnitude at the tiny preset
# (benchmark/tests/test_rehearsal_nemotron_h.py).
BASE_RTOL = 6e-7
TIE_MARGIN = 3e-6
REFERENCE_RTOL = BASE_RTOL      # of the LAST comparison: `_allow`


def sizes(config, traffic):
    """The sizes as run: the file's top-level keys with the traffic's
    overrides applied, and what the shared readers and FLOP counts take
    from a family whose layers differ: a kind for each layer run
    (``layer_types``; ``full_attention`` is what
    ``gqa_causal_flash_roofline`` looks for), the query heads of each
    (``num_attention_heads_per_layer``) and ``mlp_layer_types`` (a
    layer here has no second part: ``laguna_flops.layers_of`` zips the
    three).  ``hybrid_override_pattern`` is cut to its first
    ``num_hidden_layers`` letters (``hybrid_override_pattern_published``
    keeps the whole).  ``num_hidden_layers`` stays the layers run, which is what
    that reader slices the lists by; ``layers_held`` says the same
    under the name the other decoders' families use."""
    merged = {k: v for k, v in config.items()
              if k not in ('published', 'reduced', 'assumed',
                           'optimizer', 'amp')}
    merged.update(traffic.get('changed', {}))
    whole = merged['hybrid_override_pattern']
    assert len(whole) >= merged['num_hidden_layers'], whole
    merged['hybrid_override_pattern_published'] = whole
    merged['hybrid_override_pattern'] = whole[:merged['num_hidden_layers']]
    kinds = nemotron_h_flops.layer_kinds(merged['hybrid_override_pattern'])
    merged['layers_held'] = len(kinds)
    merged['layer_types'] = kinds
    merged['num_attention_heads_per_layer'] = \
        [merged['num_attention_heads']] * len(kinds)
    merged['mlp_layer_types'] = ['none'] * len(kinds)
    return merged


def _zoo_config(config, traffic):
    from paddle_tpu.models import nemotron_h
    s = sizes(config, traffic)
    assumed = config['assumed']
    assert s['n_group'] == s['topk_group'] == 1 and s['use_conv_bias'] \
        and s['mlp_hidden_act'] == 'relu2' and not s['mamba_proj_bias'] \
        and not s['attention_bias'] and not s['mlp_bias'] \
        and not s['tie_word_embeddings'] and s['rescale_prenorm_residual']
    return nemotron_h.NemotronHConfig(
        vocab_size=s['vocab_size'], hidden=s['hidden_size'],
        pattern=s['hybrid_override_pattern'],
        mamba_heads=s['mamba_num_heads'],
        mamba_head_dim=s['mamba_head_dim'], groups=s['n_groups'],
        states=s['ssm_state_size'], conv_kernel=s['conv_kernel'],
        chunk=s['chunk_size'], heads=s['num_attention_heads'],
        kv_heads=s['num_key_value_heads'], head_dim=s['head_dim'],
        experts=s['n_routed_experts_published'],
        top_k=s['num_experts_per_tok'],
        expert_hidden=s['moe_intermediate_size'],
        shared_hidden=s['n_shared_experts'] *
        s['moe_shared_expert_intermediate_size'],
        routed_scale=float(s['routed_scaling_factor']),
        renormalize=s['norm_topk_prob'],
        experts_held=tuple(s['experts_held']),
        rms_eps=s['layer_norm_epsilon'],
        time_step=(s['time_step_min'], s['time_step_max'],
                   s['time_step_floor']),
        bias_update_rate=assumed['bias_update_rate']['value'],
        bias_init_std=assumed['bias_init_std']['value'],
        embed_std=assumed['embed_std']['value'],
        residual_layers=config['published']['num_hidden_layers'],
        recompute_blocks=assumed['recompute_groups']['value'])


def build(config, traffic):
    """The zoo's pretraining graph inside the current program guard ->
    the loss variable."""
    from paddle_tpu.models import nemotron_h
    _, _, loss = nemotron_h.build_pretrain(_zoo_config(config, traffic),
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
    """Training FLOPs per token: 3 x forward; each layer's one mixer
    (the Mamba-2 recurrence in chunked form at the published chunk, the
    attention layer's scores over the causal half, the router, the
    shared expert and the routed experts at the EXPECTED rows held
    here), the head (``benchmark/lib/nemotron_h_flops.py``); no
    recomputed forward."""
    return flops.TRAIN_OVER_FORWARD * \
        nemotron_h_flops.forward_flops_per_token(
            sizes(config, traffic), traffic['seq_len'])


def reference_loss(config, traffic, params, feed):
    """What the harness compares the for_test program's loss with: the
    middle of the span the plain reference's loss can take over its own
    undecided choices (its loss where there is none), after
    ``REFERENCE_RTOL`` has been set for THIS comparison to half that
    span plus ``BASE_RTOL`` (``reference_readings``, ``allowed``; the
    comment at ``REFERENCE_RTOL`` says why).  Traced under ``jax.jit``,
    so the readings reach the host through a callback that hands the
    number back: the result waits for it."""
    import jax
    from jax.experimental import io_callback
    loss, low, high, _ = reference_readings(config, traffic, params, feed)
    return io_callback(_allow, jax.ShapeDtypeStruct((), loss.dtype),
                       loss, low, high, ordered=True)


def _allow(loss, low, high):
    global REFERENCE_RTOL
    middle, REFERENCE_RTOL = allowed(float(loss), float(low), float(high))
    return middle


def allowed(loss, low, high):
    """-> (the number one comparison is made with, its relative
    tolerance): the float32 nearest the middle of [loss + low, loss +
    high], and the least tolerance about THAT number which reaches
    ``BASE_RTOL`` of the loss past both ends."""
    middle = np.float32(loss + 0.5 * (low + high))
    reach = max(loss + high - float(middle), float(middle) - loss - low)
    return middle, (reach + BASE_RTOL * abs(loss)) / abs(float(middle))


def reference_readings(config, traffic, params, feed, dtype=None,
                       tie_margin=None):
    """-> (loss, low, high, undecided): the forward pass and loss in
    plain jax.numpy; ``undecided`` counts the choices of the routed
    layers whose last chosen and first unchosen BIASED scores lie
    within ``tie_margin`` (default ``TIE_MARGIN``), and [``low``,
    ``high``] (``low`` <= 0 <= ``high``) is what the loss can move by
    with such tokens given the other expert: every undecided token's
    OWN position (its share of the loss then - its share as chosen)
    goes to the end its sign points to (a choice moves its own
    position's cross-entropy most, and the program may have taken any
    subset of them), all other positions as ONE signed sum (what
    reaches them through the states and attention, as it enters the
    loss).  0 and 0 where there is none: the second pass is the
    first.  Float32 at
    highest matmul precision (the benchmark's own copy of
    ``paddle_tpu/models/reference/nemotron_h.py``; its docstring has
    the equations and what the config leaves to be assumed), given the
    same share: the layers run, the held experts, the vocabulary slice.
    The Mamba-2 state stepped TOKEN BY TOKEN by a ``lax.scan`` ([64,
    128] a head), the filter a sum over taps of shifted arrays, one
    dense [T, T] causal mask a head (``lax.map``), a Python loop over
    the held experts; no kernel, no chunk, no sort.  ``params`` are the
    program's parameters in creation order, the non-trainable choice
    biases among them: embedding; per layer the norm's gain, then ``M``:
    W_in, filter [6144, 4], filter bias, dt_bias [64], A_log [64], D
    [64], the gated norm's gain [8, 512], W_out; ``E``: router, up [8,
    D, W], down, choice bias [128], shared up, shared down; ``*``: Wq,
    Wk, Wv, Wo; the last norm's gain; the head.  ``dtype`` other than
    float32 computes everything in it (``chip_smoke.py --phase
    nemotron_h``: the control, whose first answer alone is read)."""
    import jax
    import jax.numpy as jnp
    if tie_margin is None:
        tie_margin = TIE_MARGIN
    # the two passes are one loop's body: compiled once, run twice
    shares, ties = jax.lax.map(
        lambda other: _forward(config, traffic, params, feed, dtype,
                               tie_margin, other),
        jnp.asarray([False, True]))
    count = jnp.sum(feed['labels'] >= 0)
    own, change = ties[0] > 0, shares[1] - shares[0]
    mine, rest = jnp.where(own, change, 0.0), \
        jnp.sum(jnp.where(own, 0.0, change))
    low = jnp.sum(jnp.minimum(mine, 0.0)) + jnp.minimum(rest, 0.0)
    high = jnp.sum(jnp.maximum(mine, 0.0)) + jnp.maximum(rest, 0.0)
    return jnp.sum(shares[0]) / count, low / count, high / count, \
        jnp.sum(ties[0])


def _forward(config, traffic, params, feed, dtype, tie_margin, other):
    """-> (every position's cross-entropy [B, T], 0 where there is no
    label: their sum over the labelled count is the loss; in how many
    routed layers the position's choice is undecided [B, T]).
    ``other``: an undecided token takes the first unchosen expert in
    place of the last chosen."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    s = sizes(config, traffic)
    eps, top_k = s['layer_norm_epsilon'], s['num_experts_per_tok']
    d = s['head_dim']
    first = s['experts_held'][0]
    params = iter(params)
    undecided = []

    def take(n):
        return [jnp.asarray(next(params), dtype) for _ in range(n)]

    def rms_norm(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def relu2_mlp(u, up, down):
        return jnp.square(jax.nn.relu(u @ up)) @ down

    def filtered(z, w, bias):       # tap j looks taps-1-j back
        taps, t = w.shape[1], z.shape[1]
        c = jnp.zeros_like(z) + bias
        for j in range(taps):
            back = taps - 1 - j
            c = c + w[:, j] * jnp.concatenate(
                [jnp.zeros_like(z[:, :back]), z[:, :t - back]], 1)
        return jax.nn.silu(c)

    def mamba2(u):
        w_in, conv_w, conv_b, dt_bias, a_log, skip, norm_g, w_out = take(8)
        b, t, _ = u.shape
        heads, groups, inner = dt_bias.shape[0], norm_g.shape[0], \
            norm_g.size
        states = (conv_w.shape[0] - inner) // (2 * groups)
        z, xbc, dt = jnp.split(u @ w_in,
                               [inner, inner + conv_w.shape[0]], -1)
        x, bm, cm = jnp.split(filtered(xbc, conv_w, conv_b),
                              [inner, inner + groups * states], -1)
        delta = jax.nn.softplus(dt + dt_bias)
        a = -jnp.exp(a_log)
        x = x.reshape(b, t, heads, inner // heads)

        def token(state, item):
            x_t, delta_t, b_t, c_t = item
            b_t, c_t = (jnp.repeat(v.reshape(b, groups, states),
                                   heads // groups, axis=1)
                        for v in (b_t, c_t))
            state = jnp.exp(delta_t * a)[..., None, None] * state + \
                (delta_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            return state, jnp.einsum('bhpn,bhn->bhp', state, c_t) + \
                skip[:, None] * x_t

        _, y = jax.lax.scan(
            token, jnp.zeros((b, heads, inner // heads, states), dtype),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, bm, cm)))
        gated = (jnp.moveaxis(y, 0, 1).reshape(b, t, inner) *
                 jax.nn.silu(z)).reshape(b, t, groups, inner // groups)
        return rms_norm(gated, norm_g).reshape(b, t, inner) @ w_out

    def attention(u):
        wq, wk, wv, wo = take(4)
        b, t, _ = u.shape
        q, k, v = ((u @ w).reshape(b, t, -1, d) for w in (wq, wk, wv))
        per_kv = q.shape[2] // k.shape[2]
        visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

        def one_head(args):
            qh, kh, vh = args
            scores = jnp.einsum('bqd,bkd->bqk', qh, kh) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(
                visible, scores, -jnp.inf).astype(jnp.float32),
                -1).astype(qh.dtype)
            return jnp.einsum('bqk,bkd->bqd', probs, vh)

        context = jax.lax.map(one_head, (
            jnp.moveaxis(q, 2, 0),
            jnp.repeat(jnp.moveaxis(k, 2, 0), per_kv, axis=0),
            jnp.repeat(jnp.moveaxis(v, 2, 0), per_kv, axis=0)))
        return jnp.moveaxis(context, 0, 2).reshape(b, t, -1) @ wo

    def routed_and_shared(u):
        router, up, down, bias, shared_up, shared_down = take(6)
        b, t, h = u.shape
        flat = u.reshape(b * t, h)
        scores = jax.nn.sigmoid((flat @ router).astype(jnp.float32))
        best, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                     top_k + 1)
        tie = best[:, top_k - 1] - best[:, top_k] < tie_margin
        undecided.append(tie.reshape(b, t).astype(jnp.int32))
        chosen = jnp.where(
            (tie & other)[:, None],
            chosen[:, jnp.asarray([*range(top_k - 1), top_k])],
            chosen[:, :top_k])
        picked = jnp.take_along_axis(scores, chosen, -1)
        weight = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * \
            s['routed_scaling_factor']
        routed = jnp.zeros_like(flat)
        for e in range(up.shape[0]):            # the experts held
            share = jnp.sum(jnp.where(chosen == first + e, weight, 0), -1)
            routed = routed + share[:, None].astype(flat.dtype) * \
                relu2_mlp(flat, up[e], down[e])
        return relu2_mlp(u, shared_up, shared_down) + \
            routed.reshape(b, t, h)

    mixers = {nemotron_h_flops.MAMBA: mamba2,
              nemotron_h_flops.FULL: attention,
              nemotron_h_flops.MOE: routed_and_shared}
    with jax.default_matmul_precision('highest'):
        (embedding,) = take(1)
        x = embedding[feed['ids']]
        for kind in s['layer_types']:
            (gain,) = take(1)
            x = x + mixers[kind](rms_norm(x, gain))
        g_final, head = take(2)
        logp = jax.nn.log_softmax(
            (rms_norm(x, g_final) @ head).astype(jnp.float32), -1)
        labels = feed['labels']
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    assert next(params, None) is None
    return jnp.where(labels >= 0, -picked, 0.0), sum(undecided)
