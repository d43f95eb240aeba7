"""Operations and bytes from shapes for the LFM2 family (``lfm2_moe``:
layers that differ by OPERATOR, a gated short convolution or grouped
causal attention at head width 64, under a dense or a routed MLP, the
head tied to the embedding), by ``flops.py``'s conventions: one
multiply-add is 2 FLOPs, training is 3 x forward, elementwise work
(the convolution's taps and gates among it), norms, the rotary
embedding, softmaxes and the sort are left out."""

from benchmark.lib.laguna_flops import (gated_mlp_forward_flops_per_token,
                                        visible_pairs)

CONV, ATTENTION = 'conv', 'full_attention'


def layers_run(sizes):
    """[(the model's layer index, operator kind, 'dense' | 'sparse')]
    of the layers run: ``num_hidden_layers`` of the published pattern
    from ``first_layer`` on."""
    first = sizes.get('first_layer', 0)
    return [(i, sizes['layer_types_published'][i],
             'dense' if i < sizes['num_dense_layers'] else 'sparse')
            for i in range(first, first + sizes['num_hidden_layers'])]


def operator_forward_flops_per_token(sizes, kind, seq_len):
    """One layer's operator for one token.  ``conv``: ``W_in`` [hidden,
    3 x hidden] and ``W_out`` [hidden, hidden] (the filter's three taps
    and the two gates are elementwise and not counted).  Attention: q
    and the output projection at all query heads, k and v at the K/V
    heads, scores and context against the keys the causal mask leaves
    visible (on average over a sequence's positions), every query
    head."""
    hidden = sizes['hidden_size']
    if kind == CONV:
        return 2 * (3 * hidden * hidden + hidden * hidden)
    heads, kv = sizes['num_attention_heads'], sizes['num_key_value_heads']
    d = hidden // heads
    keys = visible_pairs(seq_len) / seq_len
    return 2 * 2 * hidden * (heads + kv) * d + 2 * 2 * heads * d * keys


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run.
    ``sizes``: ``families/lfm2.py`` ``sizes``.  Per layer its operator;
    a dense layer the MLP of ``intermediate_size``; a sparse layer the
    router over all ``num_experts_published`` experts and the routed
    experts at the EXPECTED rows held here: of a token's
    ``num_experts_per_tok`` choices the share ``num_experts`` (held) /
    ``num_experts_published`` lands on an expert this chip holds when
    the routing is even (4 x 8 / 32 = one expert MLP a token), and the
    rest is not computed here.  The tied head over the held vocabulary
    rows, every position."""
    hidden = sizes['hidden_size']
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['num_experts'] / sizes['num_experts_published']
    total = 0.0
    for _, kind, mlp in layers_run(sizes):
        total += operator_forward_flops_per_token(sizes, kind, seq_len)
        if mlp == 'dense':
            total += gated_mlp_forward_flops_per_token(
                hidden, sizes['intermediate_size'])
        else:
            total += 2 * hidden * sizes['num_experts_published'] + \
                held_per_token * gated_mlp_forward_flops_per_token(
                    hidden, sizes['moe_intermediate_size'])
    return total + 2 * hidden * sizes['vocab_size']


def short_conv_train_cost(batch, seq_len, channels, taps, itemsize=2):
    """(FLOPs, bytes) one gated short convolution needs for its forward
    plus backward pass, as ONE pass over its operands each way (what a
    kernel that keeps the ``taps - 1`` earlier rows on chip would
    move).

    Bytes: forward reads B, C, X and writes the output: four [B, T, C]
    passes; backward reads the output's cotangent, B, C, X and writes
    dB, dC, dX: seven; the filter and its gradient ([C, taps] float32)
    are nothing beside them.  FLOPs an element: forward the first gate,
    ``taps`` multiply-adds and the second gate, 2 x taps + 2; backward
    the filter again for dC, the transposed filter for dz, the
    filter's own gradient and the gates' products, 6 x taps + 4.  At
    the cell's shape that is 50 FLOPs over 22 bytes an element: the
    bytes bound it on any chip of today."""
    elements = batch * seq_len * channels
    return (elements * (8 * taps + 6), 11 * elements * itemsize)
