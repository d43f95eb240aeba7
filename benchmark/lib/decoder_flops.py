"""Operations and bytes from shapes for the routed decoder family
(OLMoE), by ``flops.py``'s conventions: one multiply-add is 2 FLOPs,
training is 3 x forward, elementwise work, norms, the rotary
embedding, softmaxes and the sort are left out."""


def routed_decoder_forward_flops_per_token(
        layers, hidden, expert_hidden, experts, top_k, seq_len, vocab):
    """Forward FLOPs for one token of a decoder with causal attention
    and a routed gated MLP, and an untied output head over every
    position.

    Per layer: q, k, v and output projections 4 * 2*h*h; causal scores
    + context 2*s*h (each token against the s/2 keys before it on
    average, two matmuls, all heads together: half the square); router
    2*h*E; the ``top_k`` ACTIVE experts' gate, up and down matrices
    top_k * 3 * 2*h*H.  Head 2*h*V.  The embedding lookup is a gather."""
    per_layer = (8 * hidden * hidden + 2 * seq_len * hidden +
                 2 * hidden * experts +
                 top_k * 6 * hidden * expert_hidden)
    return layers * per_layer + 2 * hidden * vocab


def grouped_gated_mlp_train_cost(rows, hidden, expert_hidden, experts,
                                 itemsize=2):
    """(FLOPs, bytes) one layer's grouped gate / up / down matmuls need
    for forward plus backward over ``rows`` = S*k routed rows.

    FLOPs: three matrices of 2*rows*h*H, each with two gradient
    matmuls of its own size.  Bytes, in the compute dtype: each of the
    three weight sets [E, h, H] read in the forward, read again for the
    input gradient and its gradient written (3 passes); the gathered
    rows [rows, h] read by gate and up and once more for their weight
    gradients, the rows' gradient written, the expert output written
    and its gradient read (6 passes of rows*h); the two [rows, H]
    intermediates and the hidden product written and read back, and
    their gradients (12 passes of rows*H)."""
    flops = 3 * 3 * 2 * rows * hidden * expert_hidden
    nbytes = itemsize * (3 * 3 * experts * hidden * expert_hidden +
                         6 * rows * hidden + 12 * rows * expert_hidden)
    return flops, nbytes
