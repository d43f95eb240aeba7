"""Operations and bytes from shapes for the ``nemotron_h`` family (a
layer is ONE mixer, its kind read from a pattern string: Mamba-2,
a routed feed-forward of ungated squared-ReLU experts beside a shared
one, or grouped-query attention without position encoding), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward, a recomputed forward is not counted, elementwise work (the
4-tap filter, the gates, the decays' exponentials, norms, softmaxes,
the sort) is left out of a model's FLOPs.

The Mamba-2 recurrence is counted in its CHUNKED form at the published
chunk (``chunk_size`` 128), the form the model is defined to run in:
the products of ``ssd_chunk_forward_flops``.  Its hand count of bytes
(``ssd_train_cost``) is from SHAPES alone, whatever implements the
op."""

from benchmark.lib.laguna_flops import visible_pairs

MAMBA, MOE, FULL = 'mamba', 'moe', 'full_attention'
LETTERS = {'M': MAMBA, 'E': MOE, '*': FULL}


def layer_kinds(pattern):
    """``hybrid_override_pattern`` -> [mixer kind] by layer, under the
    names the shared readers know (``full_attention``:
    ``gqa_causal_flash_roofline``'s)."""
    return [LETTERS[c] for c in pattern]


def chunk_pairs(seq_len, chunk):
    """(t, s) with s <= t inside one chunk, over a sequence's chunks."""
    whole, tail = divmod(seq_len, chunk)
    return whole * chunk * (chunk + 1) // 2 + tail * (tail + 1) // 2


def ssd_chunk_forward_flops(seq_len, heads, head_dim, groups, states,
                            chunk):
    """One sequence's recurrence, forward, as matrix products over
    chunks: the scores C_t . B_s of the pairs s <= t inside a chunk (a
    GROUP's, ``states`` wide), those pairs' weights times delta_s x_s
    (a head's, ``head_dim`` wide), each token's write into the chunk's
    state and its read of the state at the chunk's start (``head_dim``
    x ``states`` a head each)."""
    pairs = chunk_pairs(seq_len, chunk)
    return (2 * pairs * (groups * states + heads * head_dim) +
            2 * 2 * seq_len * heads * head_dim * states)


def ssd_train_cost(batch, seq_len, heads, head_dim, groups, states, chunk,
                   itemsize=2):
    """(FLOPs, bytes) ONE layer's ``ssd_scan`` needs for its forward
    plus backward pass, from its shapes.

    FLOPs: 3 x the chunked form's forward.
    Bytes, every operand read or written ONCE each way: forward reads
    x, B, C (``itemsize`` an element) and delta (float32), writes y and
    the [heads, head_dim, states] float32 state at each chunk's
    boundary; backward reads x, delta, B, C, y's cotangent and the
    boundary states and writes the five gradients dx, ddelta (float32),
    dB, dC and (a head's scalars) dA, dD."""
    flops = 3 * batch * ssd_chunk_forward_flops(
        seq_len, heads, head_dim, groups, states, chunk)
    tokens = batch * seq_len
    wide = tokens * heads * head_dim         # x, y and their cotangents
    narrow = tokens * groups * states        # B or C
    steps = tokens * heads * 4               # delta, float32
    boundary = batch * -(-seq_len // chunk) * heads * head_dim * states * 4
    forward = (2 * wide + 2 * narrow) * itemsize + steps + boundary
    backward = ((3 * wide + 4 * narrow) * itemsize + 2 * steps + boundary +
                2 * heads * 4)
    return flops, forward + backward


def mixer_parameters(sizes):
    """{kind: parameters of ONE layer's mixer that a matmul reads},
    ``expert`` (one routed expert's), ``head`` (the held rows'): what
    6 x N counts."""
    hidden = sizes['hidden_size']
    inner = sizes['mamba_num_heads'] * sizes['mamba_head_dim']
    bc = 2 * sizes['n_groups'] * sizes['ssm_state_size']
    q = sizes['num_attention_heads'] * sizes['head_dim']
    kv = sizes['num_key_value_heads'] * sizes['head_dim']
    return {
        MAMBA: hidden * (2 * inner + bc + sizes['mamba_num_heads']) +
        inner * hidden,
        FULL: 2 * hidden * q + 2 * hidden * kv,
        # the router and the shared expert: what every chip computes
        MOE: hidden * sizes['n_routed_experts_published'] +
        2 * hidden * sizes['n_shared_experts'] *
        sizes['moe_shared_expert_intermediate_size'],
        'expert': 2 * hidden * sizes['moe_intermediate_size'],
        'head': hidden * sizes['vocab_size']}


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run
    (``families/nemotron_h.py`` ``sizes``): 2 x the parameters every
    matmul reads; a Mamba-2 layer's recurrence in chunked form; an
    attention layer's scores and context over the causal half; a routed
    layer's experts at the EXPECTED rows held here (of a token's
    ``num_experts_per_tok`` choices the share ``n_routed_experts``
    (held) / ``n_routed_experts_published`` lands on an expert this
    chip holds when the routing is even: 6 x 8 / 128 = 0.375 of an
    expert a token); the untied head over the held rows."""
    count = mixer_parameters(sizes)
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['n_routed_experts'] / sizes['n_routed_experts_published']
    total = 2 * count['head']
    for kind in sizes['layer_types']:
        total += 2 * count[kind]
        if kind == MAMBA:
            total += ssd_chunk_forward_flops(
                seq_len, sizes['mamba_num_heads'], sizes['mamba_head_dim'],
                sizes['n_groups'], sizes['ssm_state_size'],
                sizes['chunk_size']) / seq_len
        elif kind == FULL:
            total += 2 * 2 * sizes['num_attention_heads'] * \
                sizes['head_dim'] * visible_pairs(seq_len) / seq_len
        else:
            total += 2 * held_per_token * count['expert']
    return total


def parameter_count(sizes, pattern=None, experts=None, vocab=None):
    """Every parameter of the model at ``pattern``'s layers, ``experts``
    routed experts a layer and ``vocab`` rows (default: as run): the
    matmuls' plus the filters, biases, decays, skips and gains."""
    pattern = pattern or sizes['hybrid_override_pattern']
    experts = sizes['n_routed_experts'] if experts is None else experts
    vocab = vocab or sizes['vocab_size']
    hidden, heads = sizes['hidden_size'], sizes['mamba_num_heads']
    inner = heads * sizes['mamba_head_dim']
    conv = inner + 2 * sizes['n_groups'] * sizes['ssm_state_size']
    count = mixer_parameters(sizes)
    small = {MAMBA: conv * (sizes['conv_kernel'] + 1) + 3 * heads + inner,
             FULL: 0, MOE: 0}
    total = 2 * hidden * vocab + hidden     # table, head, the last gain
    for kind in layer_kinds(pattern):
        total += count[kind] + small[kind] + hidden
        if kind == MOE:
            total += experts * count['expert']
    return total
