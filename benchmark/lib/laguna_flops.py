"""Operations and bytes from shapes for the Laguna family (layers that
differ: full or sliding-window attention with a head count of their
own over grouped K/V heads, a dense or a routed-plus-shared MLP), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward, elementwise work, norms, the rotary embedding, softmaxes
and the sort are left out."""


def visible_pairs(seq_len, window=0):
    """(query, key) pairs a causal mask leaves in one sequence: each
    query sees the keys up to its own position, and with a window only
    the last ``window`` of them."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_forward_flops_per_token(hidden, heads, kv_heads, head_dim,
                                      seq_len, window=0):
    """One layer's attention for one token: the q and output
    projections at THIS layer's head count, k and v at the K/V heads',
    the per-head gate's [hidden, heads] map, and scores + context
    against the keys the mask leaves visible (on average over the
    positions of a sequence), two matmuls, every query head."""
    projections = (2 * 2 * hidden * heads * head_dim +
                   2 * 2 * hidden * kv_heads * head_dim +
                   2 * hidden * heads)
    keys = visible_pairs(seq_len, window) / seq_len
    return projections + 2 * 2 * heads * head_dim * keys


def gated_mlp_forward_flops_per_token(hidden, width):
    """gate, up and down matrices of one gated MLP."""
    return 3 * 2 * hidden * width


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run.
    ``sizes``: the configuration file's top-level keys
    (``families/laguna.py`` ``sizes``).  Per layer the attention of its
    kind; layer 0 the dense MLP; every sparse layer the router over
    all ``num_experts_published`` experts, the shared expert, and the
    routed experts at the EXPECTED rows held here: of a token's
    ``num_experts_per_tok`` choices, the share ``num_experts`` (held)
    / ``num_experts_published`` lands on an expert this chip holds
    when the routing is even, and the rest is not computed here.  The
    untied head over the held vocabulary rows, every position."""
    hidden = sizes['hidden_size']
    total = 0.0
    for kind, heads, mlp in layers_of(sizes):
        total += attention_forward_flops_per_token(
            hidden, heads, sizes['num_key_value_heads'],
            sizes['head_dim'], seq_len,
            sizes['sliding_window'] if kind == 'sliding_attention' else 0)
        if mlp == 'dense':
            total += gated_mlp_forward_flops_per_token(
                hidden, sizes['intermediate_size'])
            continue
        held_per_token = sizes['num_experts_per_tok'] * \
            sizes['num_experts'] / sizes['num_experts_published']
        total += (2 * hidden * sizes['num_experts_published'] +
                  gated_mlp_forward_flops_per_token(
                      hidden, sizes['shared_expert_intermediate_size']) +
                  held_per_token * gated_mlp_forward_flops_per_token(
                      hidden, sizes['moe_intermediate_size']))
    return total + 2 * hidden * sizes['vocab_size']


def layers_of(sizes):
    """[(attention kind, query heads, mlp kind)] of the layers run: the
    first ``num_hidden_layers`` entries of the published lists."""
    n = sizes['num_hidden_layers']
    return list(zip(sizes['layer_types'][:n],
                    sizes['num_attention_heads_per_layer'][:n],
                    sizes['mlp_layer_types'][:n]))


def grouped_flash_train_cost(batch, heads, kv_heads, seq_len, head_dim,
                             window=0, itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for one layer's forward
    plus backward calls with a causal mask, banded by ``window``, and
    ``kv_heads`` K/V heads under ``heads`` query heads.

    FLOPs: forward q k^T and p v; backward dV, dP, dQ, dK and the
    recomputed scores: seven matmuls of 2 * head_dim a visible (query,
    key) pair and query head; pairs outside the mask are not counted
    (the kernels skip whole blocks of them; what they compute and mask
    inside the edge blocks is their cost, not the algorithm's).
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v,
    o, do and writes dq, dk, dv: six passes over a [B, T, H, d] tensor
    and six over a [B, T, Hkv, d] one (each K/V head read once, not
    once a query head)."""
    pairs = batch * heads * visible_pairs(seq_len, window)
    tensor = batch * seq_len * head_dim * itemsize
    return 7 * 2 * pairs * head_dim, 6 * (heads + kv_heads) * tensor
