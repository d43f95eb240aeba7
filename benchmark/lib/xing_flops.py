"""Operations and bytes from shapes for the Xing4.0 family
(``xing4_0``: Moonlight's latent attention under a low-rank query, a
dense or a routed-plus-shared MLP, a residual of ``hc_mult`` streams
mixed around every operator by manifold-constrained hyper-connections,
one multi-token-prediction module that shares the embedding and the
head), by ``flops.py``'s conventions: one multiply-add is 2 FLOPs,
training is 3 x forward, recomputed operations are not counted,
elementwise work (the read-out and write-back of the streams, the
Sinkhorn normalisations, norms, rotary, softmaxes, the sort) is left
out.

The hyper-connection is counted by what a FUSED implementation has to
move, whatever implements it (XLA fusions today, a kernel tomorrow):
the count is a function of the shapes."""

from benchmark.lib.laguna_flops import (gated_mlp_forward_flops_per_token,
                                        visible_pairs)


def maps_columns(n):
    """n^2 + 2 n: H_res, H_pre and H_post's logits."""
    return n * n + 2 * n


def maps_forward_flops_per_token(sizes):
    """ONE operator's projection r phi: [n hidden] x [n hidden, n^2 +
    2 n]."""
    n = sizes['hc_mult']
    return 2 * n * sizes['hidden_size'] * maps_columns(n)


def attention_forward_flops_per_token(sizes, seq_len):
    """One layer's latent attention for one token: the query's latent
    [hidden, q_rank] and its expansion [q_rank, heads x (nope + rope)],
    the K/V latent and rotary key [hidden, rank + rope], the expansion
    [rank, heads x (nope + v)], the output projection [heads x v,
    hidden]; scores over nope + rope and the context over v features
    against the keys the causal mask leaves visible (on average over
    the positions of a sequence), every head."""
    hidden, heads = sizes['hidden_size'], sizes['num_attention_heads']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    v, rank, q_rank = sizes['v_head_dim'], sizes['kv_lora_rank'], \
        sizes['q_lora_rank']
    projections = 2 * (hidden * q_rank + q_rank * heads * qk +
                       hidden * (rank + sizes['qk_rope_head_dim']) +
                       rank * heads * (sizes['qk_nope_head_dim'] + v) +
                       heads * v * hidden)
    keys = visible_pairs(seq_len) / seq_len
    return projections + 2 * heads * (qk + v) * keys


def sparse_mlp_forward_flops_per_token(sizes):
    """The router over all ``n_routed_experts_published`` experts, the
    shared expert, and the routed experts at the EXPECTED rows held
    here (4 x 8 / 64 = 0.5 of an expert MLP a token)."""
    hidden, width = sizes['hidden_size'], sizes['moe_intermediate_size']
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['n_routed_experts'] / sizes['n_routed_experts_published']
    return (2 * hidden * sizes['n_routed_experts_published'] +
            gated_mlp_forward_flops_per_token(
                hidden, sizes['n_shared_experts'] * width) +
            held_per_token * gated_mlp_forward_flops_per_token(
                hidden, width))


def forward_parts_per_token(sizes, seq_len):
    """{part: forward FLOPs a token} of the step as it is run.
    ``sizes``: ``families/xing4.py`` ``sizes`` (``layers_held`` layers
    of the main stack, ``first_k_dense_replace`` of them dense, and
    ``num_nextn_predict_layers`` modules of one more sparse layer).
    ``main`` the stack with its maps and ONE head product; ``module``
    the prediction module: its layer with its maps, W_eh [2 hidden,
    hidden] and the SECOND product of the shared head."""
    hidden = sizes['hidden_size']
    layers, modules = sizes['layers_held'], \
        sizes['num_nextn_predict_layers']
    dense = min(sizes['first_k_dense_replace'], layers)
    attention = attention_forward_flops_per_token(sizes, seq_len) + \
        2 * maps_forward_flops_per_token(sizes)
    head = 2 * hidden * sizes['vocab_size']
    sparse = sparse_mlp_forward_flops_per_token(sizes)
    return {
        'main': layers * attention +
        dense * gated_mlp_forward_flops_per_token(
            hidden, sizes['intermediate_size']) +
        (layers - dense) * sparse + head,
        'module': modules * (attention + sparse + 2 * 2 * hidden * hidden
                             + head)}


def forward_flops_per_token(sizes, seq_len):
    return sum(forward_parts_per_token(sizes, seq_len).values())


def operators(sizes):
    """Hyper-connected operators a step: two a layer, the module's
    layer too."""
    return 2 * (sizes['layers_held'] + sizes['num_nextn_predict_layers'])


def mhc_train_cost(tokens, n, hidden):
    """(FLOPs, bytes) ONE hyper-connected operator's read-out and
    write-back need for a train step over ``tokens`` tokens of a
    bfloat16 stream (the program's type under AMP): forward and
    backward, and like ``flops.py``'s counts NO forward run again for a
    gradient (``mhc_forward_share`` says what that weighs).

    Bytes, in elements of the stream a token: FORWARD (3 n + 2) C: X
    read once for the maps and the read-out, u written; X and y read,
    X' written.  BACKWARD (5 n + 3) C: the write-back's reads dX', X
    and y and writes dy; the read-out's reads dX', X and du and writes
    dX (the operator's own backward lies between the two, so dX' and X
    are read twice).  The maps themselves (n^2 + 2 n float32 a token,
    written and read a pass) and phi (read once a pass) are counted
    beside them.  FLOPs: the projection r phi, [tokens, n C] x [n C,
    n^2 + 2 n], once forward and twice backward."""
    m = maps_columns(n)
    stream = tokens * hidden * 2 * ((3 * n + 2) + (5 * n + 3))
    maps = tokens * m * 4 * 2 * 3
    phi = n * hidden * m * 4 * 3
    return 3 * tokens * 2 * n * hidden * m, stream + maps + phi


def mhc_forward_share(n):
    """The share of a train step's stream elements that ONE MORE
    forward would add to ``mhc_train_cost``'s: what a block that is a
    recompute group pays again, (3 n + 2) over (3 n + 2) + (5 n + 3)."""
    return (3 * n + 2) / float((3 * n + 2) + (5 * n + 3))
