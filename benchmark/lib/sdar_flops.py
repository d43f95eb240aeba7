"""Operations and bytes from shapes for the SDAR family (``sdar``: a
routed decoder trained by block diffusion, every sequence run as a
corrupted and a clean copy under a relation between blocks), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward (a recompute group's second forward is not counted),
elementwise work, norms, the rotary embedding, softmaxes and the sort
are left out.  What is counted is what the loss DEPENDS on: both copies
through every layer but the last, where the clean copy gives keys and
values and nothing else; attention by the (query, key) pairs the mask
lets through, exactly; the held experts at their EXPECTED rows; the
head over the corrupted copy alone."""

from benchmark.lib.laguna_flops import gated_mlp_forward_flops_per_token


def visible_pairs(seq_len, block):
    """(query, key) pairs a head, of one sequence of ``seq_len`` data
    tokens in blocks of ``block`` -> (clean over clean, corrupted over
    clean, corrupted over its own block): L (L + B) / 2, L (L - B) / 2,
    L B."""
    return (seq_len * (seq_len + block) // 2,
            seq_len * (seq_len - block) // 2, seq_len * block)


def row_forward_flops(sizes):
    """One position's token-wise products in one layer -> (q and output
    projections, k and v projections, router + expected held experts)."""
    hidden, d = sizes['hidden_size'], sizes['head_dim']
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['num_experts'] / sizes['num_experts_published']
    return (2 * 2 * hidden * sizes['num_attention_heads'] * d,
            2 * 2 * hidden * sizes['num_key_value_heads'] * d,
            2 * hidden * sizes['num_experts_published'] +
            held_per_token * gated_mlp_forward_flops_per_token(
                hidden, sizes['moe_intermediate_size']))


def forward_flops_per_sequence(sizes, seq_len):
    """Forward FLOPs of one sequence of ``seq_len`` data tokens through
    the decoder as it is run (``families/sdar.py`` ``sizes``)."""
    layers, block = sizes['num_hidden_layers'], sizes['block_length']
    q_o, k_v, mlp = row_forward_flops(sizes)
    clean, earlier, own = visible_pairs(seq_len, block)
    pair = 2 * 2 * sizes['num_attention_heads'] * sizes['head_dim']
    whole = 2 * seq_len * (q_o + k_v + mlp) + \
        pair * (clean + earlier + own)
    last = seq_len * (q_o + k_v + mlp) + seq_len * k_v + \
        pair * (earlier + own)
    return (layers - 1) * whole + last + \
        seq_len * 2 * sizes['hidden_size'] * sizes['vocab_size']


def block_flash_train_cost(sizes, batch, seq_len, itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for a step's calls
    under the block-relation mask, forward plus backward: clean over
    clean and corrupted over clean in every layer but the last, the
    latter alone there.  FLOPs: seven matmuls of 2 * head_dim a VISIBLE
    (query, key) pair and query head (``laguna_flops.
    grouped_flash_train_cost``'s factor): a function of the mask, so
    the same work whatever walks it.  Bytes: six passes over each
    call's [B, L, H, d] queries and six over its [B, L, Hkv, d] keys."""
    layers, d = sizes['num_hidden_layers'], sizes['head_dim']
    heads, kv_heads = (sizes['num_attention_heads'],
                       sizes['num_key_value_heads'])
    clean, earlier, _ = visible_pairs(seq_len, sizes['block_length'])
    pairs = (layers - 1) * clean + layers * earlier
    calls = 2 * layers - 1
    return (7 * 2 * batch * heads * pairs * d,
            calls * 6 * (heads + kv_heads) * batch * seq_len * d *
            itemsize)
