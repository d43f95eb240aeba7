"""Operations and bytes from shapes for the Solar Open 2 family
(``solar_open2``: layers that differ by OPERATOR, gated grouped-query
softmax attention without rotary or the gated delta rule with a
per-channel decay, every one under a routed-plus-shared MLP), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward, elementwise work (the 4-tap filters, the gates, the
decays' exponentials), norms, softmaxes and the sort are left out.

The delta rule is counted in its CHUNKED form at a nominal chunk of 64
tokens, whatever implements it: the count is a function of the shapes,
so a kernel, another chunk size or another way through the triangular
system is judged on the same yardstick."""

from benchmark.lib.laguna_flops import (gated_mlp_forward_flops_per_token,
                                        visible_pairs)

GQA, KDA = 'full_attention', 'kda'
NOMINAL_CHUNK = 64


def layers_run(sizes):
    """[operator kind] of the layers run: ``num_hidden_layers`` of the
    model's from ``first_layer`` on, softmax where ``gqa_layers`` says
    so."""
    first = sizes.get('first_layer', 0)
    return [GQA if i in sizes['gqa_layers'] else KDA
            for i in range(first, first + sizes['num_hidden_layers'])]


def kda_chunk_forward_flops(head_dim, chunk=NOMINAL_CHUNK):
    """Forward FLOPs of ONE chunk of one head of the gated delta rule
    in chunked form, keys and values ``head_dim`` wide: the two score
    matrices A = K K^T and B = Q K^T over the causal half of the chunk
    (chunk x (chunk + 1) / 2 pairs, ``head_dim`` multiply-adds each,
    the per-channel decay folded into the operands); the unit
    lower-triangular system of A applied to [K | V] by forward
    substitution (chunk^2 / 2 rows of 2 x head_dim multiply-adds); the
    three products with the [head_dim, head_dim] state (W_k S, Q S,
    K^T U: chunk x head_dim^2 multiply-adds each) and B U over the
    causal half."""
    pairs = chunk * (chunk + 1) // 2
    scores = 2 * 2 * pairs * head_dim
    solve = 2 * (chunk * chunk // 2) * 2 * head_dim
    state = 3 * 2 * chunk * head_dim * head_dim
    inside = 2 * pairs * head_dim
    return scores + solve + state + inside


def operator_forward_flops_per_token(sizes, kind, seq_len):
    """One layer's operator for one token at the heads HELD.  Softmax:
    q, the gate and the output projection at the query heads, k and v
    at the K/V heads, scores and context against the keys the causal
    mask leaves visible (on average over a sequence's positions).
    Delta rule: q, k, v and the output projection at its heads, the
    two low-rank gates (down to one head's width, up to all heads),
    beta's [hidden, heads] map, and the recurrence in chunked form."""
    hidden = sizes['hidden_size']
    if kind == GQA:
        heads, kv = sizes['num_attention_heads'], \
            sizes['num_key_value_heads']
        d = sizes['head_dim']
        keys = visible_pairs(seq_len) / seq_len
        return 2 * hidden * (3 * heads + 2 * kv) * d + \
            2 * 2 * heads * d * keys
    linear = sizes['linear_attn_config']
    heads, d = linear['num_heads'], linear['head_dim']
    low_rank = 2 * (hidden * d + d * heads * d)
    return (2 * hidden * 4 * heads * d + 2 * low_rank +
            2 * hidden * heads +
            heads * kda_chunk_forward_flops(d) / NOMINAL_CHUNK)


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run.
    ``sizes``: ``families/solar_open2.py`` ``sizes``.  Per layer its
    operator, the router over all ``n_routed_experts_published``
    experts, the shared expert, and the routed experts at the EXPECTED
    rows held here: of a token's ``num_experts_per_tok`` choices the
    share ``n_routed_experts`` (held) / ``n_routed_experts_published``
    lands on an expert this chip holds when the routing is even (8 x 8
    / 320 = a fifth of an expert MLP a token).  The untied head over
    the held vocabulary rows, every position."""
    hidden, width = sizes['hidden_size'], sizes['moe_intermediate_size']
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['n_routed_experts'] / sizes['n_routed_experts_published']
    mlp = (2 * hidden * sizes['n_routed_experts_published'] +
           gated_mlp_forward_flops_per_token(
               hidden, sizes['n_shared_experts'] * width) +
           held_per_token * gated_mlp_forward_flops_per_token(
               hidden, width))
    return sum(operator_forward_flops_per_token(sizes, kind, seq_len) + mlp
               for kind in layers_run(sizes)) + \
        2 * hidden * sizes['vocab_size']


def kda_train_cost(batch, seq_len, heads, head_dim, chunk=NOMINAL_CHUNK,
                   itemsize=2):
    """(FLOPs, bytes) ONE delta-rule layer's recurrence needs for its
    forward plus backward pass, from its shapes and a nominal chunk.

    FLOPs: 3 x the chunked forward (``kda_chunk_forward_flops``) of
    every chunk and head; a recomputed forward is not counted.
    Bytes, every operand read or written ONCE each way: forward reads
    q, k, v (``itemsize`` an element), the log decays (float32, as
    wide as k) and beta, writes o and the [head_dim, head_dim] float32
    state at each chunk's boundary; backward reads q, k, v, a, beta,
    o's cotangent and the boundary states and writes dq, dk, dv, da
    (float32) and dbeta."""
    chunks = batch * heads * -(-seq_len // chunk)
    tensor = batch * seq_len * heads * head_dim
    betas = batch * seq_len * heads * itemsize
    states = chunks * head_dim * head_dim * 4
    flops = 3 * chunks * kda_chunk_forward_flops(head_dim, chunk)
    forward = 4 * tensor * itemsize + 4 * tensor + betas + states
    backward = 7 * tensor * itemsize + 2 * 4 * tensor + 2 * betas + states
    return flops, forward + backward
