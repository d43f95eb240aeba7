"""Operations from shapes for the Ouro family (a stack of dense decoder
layers applied ``total_ut_steps`` times with the same weights, the
head and a one-output exit gate after every pass), by ``flops.py``'s
conventions: one multiply-add is 2 FLOPs, training is 3 x forward,
elementwise work, norms, the rotary embedding and softmaxes are left
out.  Only VISIBLE (query, key) pairs count, as the other families
count the causal half.  Every pass is counted ONCE: a forward that the
loop's gradient ran again would be recomputation, which a model's
FLOPs never hold, so ``mfu`` cannot gain from it."""


def causal_pairs(seq_len):
    """(query, key) pairs of causal attention in one sequence and head:
    each query against the keys up to its own position."""
    return seq_len * (seq_len + 1) // 2


def layer_pass_flops_per_token(sizes, seq_len):
    """One application of one layer to one token: q, k, v and the
    output projection, scores and context over the visible pairs (on
    average over a sequence's positions), the gated MLP's three
    products."""
    hidden, heads = sizes['hidden_size'], sizes['num_attention_heads']
    return (4 * 2 * hidden * hidden +
            2 * 2 * heads * sizes['head_dim'] *
            causal_pairs(seq_len) / seq_len +
            3 * 2 * hidden * sizes['intermediate_size'])


def exit_flops_per_token(sizes):
    """What closes one pass for one token: the head over the whole
    vocabulary and the gate's one output."""
    return 2 * sizes['hidden_size'] * (sizes['vocab_size'] + 1)


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the looped decoder as it is run.
    ``sizes``: ``families/ouro.py`` ``sizes`` (``layers_held`` layers,
    ``total_ut_steps`` passes)."""
    return sizes['total_ut_steps'] * (
        sizes['layers_held'] * layer_pass_flops_per_token(sizes, seq_len)
        + exit_flops_per_token(sizes))
