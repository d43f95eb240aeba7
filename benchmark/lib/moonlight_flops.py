"""Operations and bytes from shapes for the Moonlight family
(``deepseek_v3``: latent attention whose queries and keys are wider
than its values, a dense or a routed-plus-shared MLP), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward, elementwise work, norms, the rotary embedding, softmaxes
and the sort are left out."""

from benchmark.lib.laguna_flops import (gated_mlp_forward_flops_per_token,
                                        visible_pairs)


def attention_forward_flops_per_token(sizes, seq_len):
    """One layer's latent attention for one token: the query
    projection [hidden, heads x (nope + rope)], the latent and rotary
    key [hidden, rank + rope], the expansion [rank, heads x (nope +
    v)], the output projection [heads x v, hidden]; scores over nope +
    rope and the context over v features against the keys the causal
    mask leaves visible (on average over the positions of a
    sequence), every head."""
    hidden, heads = sizes['hidden_size'], sizes['num_attention_heads']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    v, rank = sizes['v_head_dim'], sizes['kv_lora_rank']
    projections = 2 * (hidden * heads * qk +
                       hidden * (rank + sizes['qk_rope_head_dim']) +
                       rank * heads * (sizes['qk_nope_head_dim'] + v) +
                       heads * v * hidden)
    keys = visible_pairs(seq_len) / seq_len
    return projections + 2 * heads * (qk + v) * keys


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run.
    ``sizes``: the configuration file's top-level keys
    (``families/moonlight.py`` ``sizes``).  Per layer the attention;
    the first ``first_k_dense_replace`` layers the dense MLP; every
    later layer the router over all ``n_routed_experts_published``
    experts, the shared experts (one MLP of ``n_shared_experts`` x
    ``moe_intermediate_size``), and the routed experts at the EXPECTED
    rows held here: of a token's ``num_experts_per_tok`` choices the
    share ``n_routed_experts`` (held) / ``n_routed_experts_published``
    lands on an expert this chip holds when the routing is even (0.75
    of an expert MLP a token), and the rest is not computed here.  The
    untied head over the held vocabulary rows, every position."""
    hidden = sizes['hidden_size']
    width = sizes['moe_intermediate_size']
    layers = sizes['num_hidden_layers']
    dense = min(sizes['first_k_dense_replace'], layers)
    held_per_token = sizes['num_experts_per_tok'] * \
        sizes['n_routed_experts'] / sizes['n_routed_experts_published']
    sparse = (2 * hidden * sizes['n_routed_experts_published'] +
              gated_mlp_forward_flops_per_token(
                  hidden, sizes['n_shared_experts'] * width) +
              held_per_token * gated_mlp_forward_flops_per_token(
                  hidden, width))
    return (layers * attention_forward_flops_per_token(sizes, seq_len) +
            dense * gated_mlp_forward_flops_per_token(
                hidden, sizes['intermediate_size']) +
            (layers - dense) * sparse +
            2 * hidden * sizes['vocab_size'])


def latent_flash_train_cost(batch, heads, seq_len, qk_dim, v_dim,
                            itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for one layer's forward
    plus backward calls with a causal mask, queries and keys ``qk_dim``
    wide over values ``v_dim`` wide.

    FLOPs a visible (query, key) pair and head: forward q k^T over
    qk_dim and p v over v_dim, 2 x (qk_dim + v_dim); backward the
    recomputed scores, dQ and dK over qk_dim and dV and dP over v_dim,
    2 x (3 x qk_dim + 2 x v_dim).  Pairs above the diagonal are not
    counted (the kernels skip whole blocks of them; what they compute
    and mask inside the diagonal's blocks is their cost, not the
    algorithm's), and nothing is counted at a padded width.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v,
    o, do and writes dq, dk, dv: six passes over a [B, T, H, qk_dim]
    tensor (q, k twice each, dq, dk) and six over a [B, T, H, v_dim]
    one (v and o twice each, do, dv).  The key is counted as the
    kernels are handed it, a full [B, T, H, qk_dim] tensor."""
    pairs = batch * heads * visible_pairs(seq_len)
    rows = batch * heads * seq_len * itemsize
    return (2 * pairs * (4 * qk_dim + 3 * v_dim),
            6 * rows * (qk_dim + v_dim))
