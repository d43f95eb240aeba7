"""Operations from shapes for the Kimi Linear family (``kimi_linear``:
layers that differ by OPERATOR, the gated delta rule with a per-channel
decay or latent attention without position encoding, under a dense or a
routed-plus-shared MLP), by ``flops.py``'s conventions: one
multiply-add is 2 FLOPs, training is 3 x forward (a recompute group's
second forward is not counted), elementwise work (the 4-tap filters,
the gates, the decays' exponentials), norms, softmaxes and the sort are
left out.  Both operators are counted by the functions of the families
that brought them: the delta rule in its CHUNKED form at a nominal
chunk of 64 (``solar_flops``, resting on ``kda_chunk_forward_flops``),
the latent layer as Moonlight's with nothing rotated (``moonlight_flops``;
its flash calls' roofline is ``latent_flash_train_cost``'s)."""

from benchmark.lib import moonlight_flops, solar_flops
from benchmark.lib.laguna_flops import gated_mlp_forward_flops_per_token

LATENT, KDA = solar_flops.GQA, solar_flops.KDA


def layers_run(sizes):
    """[(operator kind, MLP kind)] of the layers run: ``layers_held``
    of the model's from ``first_layer`` on, numbered from 1 as
    ``linear_attn_config`` numbers them; latent attention where
    ``full_attn_layers`` says so, a dense MLP in the first
    ``first_k_dense_replace`` layers."""
    first = sizes['first_layer']
    full = sizes['linear_attn_config']['full_attn_layers']
    return [(LATENT if i in full else KDA,
             'dense' if i <= sizes['first_k_dense_replace'] else 'sparse')
            for i in range(first, first + sizes['layers_held'])]


def operator_forward_flops_per_token(sizes, kind, seq_len):
    """One layer's operator for one token, every head here."""
    if kind == KDA:
        return solar_flops.operator_forward_flops_per_token(
            sizes, KDA, seq_len)
    return moonlight_flops.attention_forward_flops_per_token(
        sizes, seq_len)


def mlp_forward_flops_per_token(sizes, kind):
    """Dense: one gated MLP of ``intermediate_size``.  Sparse: the
    router over all ``num_experts_published`` experts, the shared
    expert, and the routed experts at the EXPECTED rows held here: of a
    token's ``num_experts_per_token`` choices the share ``num_experts``
    (held) / ``num_experts_published`` lands on an expert this chip
    holds when the routing is even (8 x 8 / 256 = a quarter of an
    expert MLP a token)."""
    hidden, width = sizes['hidden_size'], sizes['moe_intermediate_size']
    if kind == 'dense':
        return gated_mlp_forward_flops_per_token(
            hidden, sizes['intermediate_size'])
    held_per_token = sizes['num_experts_per_token'] * \
        sizes['num_experts'] / sizes['num_experts_published']
    return (2 * hidden * sizes['num_experts_published'] +
            gated_mlp_forward_flops_per_token(
                hidden, sizes['num_shared_experts'] * width) +
            held_per_token * gated_mlp_forward_flops_per_token(
                hidden, width))


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run
    (``families/kimi_linear.py`` ``sizes``): each layer's operator and
    MLP, and the untied head over the held vocabulary rows, every
    position."""
    return sum(operator_forward_flops_per_token(sizes, op, seq_len) +
               mlp_forward_flops_per_token(sizes, mlp)
               for op, mlp in layers_run(sizes)) + \
        2 * sizes['hidden_size'] * sizes['vocab_size']
