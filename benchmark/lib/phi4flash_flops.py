"""Operations and bytes from shapes for the ``phi4flash`` family
(SambaY: layers that differ by OPERATOR by a rule of the layer index,
a selective state-space scan, differential attention under a window or
full, a gated memory unit and a cross-attention that read ONE layer's
scan output and keys / values; every layer under a dense gated MLP), by
``flops.py``'s conventions: one multiply-add is 2 FLOPs, training is
3 x forward, a recomputed forward is not counted, elementwise work
(the scan's recurrence, the 4-tap filters, gates, norms, softmaxes) is
left out of a model's FLOPs.

The scan and the differential flash calls have hand counts of their
own, from SHAPES alone, whatever implements them."""

from benchmark.lib.laguna_flops import (gated_mlp_forward_flops_per_token,
                                        visible_pairs)

MAMBA, WINDOW, FULL, GMU, CROSS = \
    'mamba', 'sliding_attention', 'full_attention', 'gmu', 'cross_attention'
# the boundary states of the hand count: one a NOMINAL_CHUNK tokens
NOMINAL_CHUNK = 256


def layer_kinds(layers):
    """The model's own rule at ``layers`` layers (a multiple of 4) ->
    [operator kind] by layer index: even layers up to the middle are
    Mamba (the middle one hands on its scan output), odd ones before it
    windowed attention, middle + 1 full attention (its K / V are the
    shared ones), then gated memory units (even) and cross-attention
    (odd)."""
    if layers % 4:
        raise ValueError('%d layers are no multiple of 4' % layers)
    half = layers // 2
    kinds = []
    for i in range(layers):
        if i % 2 == 0:
            kinds.append(MAMBA if i <= half else GMU)
        elif i < half:
            kinds.append(WINDOW)
        else:
            kinds.append(FULL if i == half + 1 else CROSS)
    return kinds


def matmul_parameters(sizes):
    """{kind: parameters of ONE layer's operator that a matmul reads},
    plus ``mlp`` (one layer's) and ``head`` (the tied table's held
    rows): what 6 x N counts."""
    hidden, inner = sizes['hidden_size'], sizes['mamba_d_inner']
    heads, kv, d = sizes['num_attention_heads'], \
        sizes['num_key_value_heads'], sizes['head_dim']
    states, rank = sizes['mamba_d_state'], sizes['mamba_dt_rank']
    own_kv = hidden * (heads + 2 * kv) * d + heads * d * hidden
    return {
        MAMBA: (hidden * 2 * inner + inner * (rank + 2 * states) +
                rank * inner + inner * hidden),
        WINDOW: own_kv, FULL: own_kv,
        CROSS: 2 * hidden * heads * d,
        GMU: 2 * hidden * inner,
        'mlp': 3 * hidden * sizes['intermediate_size'],
        'head': hidden * sizes['vocab_size']}


def attention_pairs_flops(sizes, kind, seq_len):
    """Forward FLOPs a token of one attention layer's scores and
    context: differential attention is two softmaxes a head PAIR over
    values twice as wide as the keys, so every one of the
    ``num_attention_heads`` 64-wide query heads scores its visible keys
    (2 x head_dim a pair) and weighs 2 x head_dim-wide values (2 x 2 x
    head_dim a pair); the pairs inside the band or the causal half, on
    average over a sequence's positions."""
    window = sizes['sliding_window'] if kind == WINDOW else 0
    d = sizes['head_dim']
    return sizes['num_attention_heads'] * 2 * (d + 2 * d) * \
        visible_pairs(seq_len, window) / seq_len


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run
    (``sizes``: ``families/phi4flash.py`` ``sizes``): 2 x the
    parameters every matmul reads, the attention layers' scores and
    context, the tied head over the held rows."""
    count = matmul_parameters(sizes)
    total = 2 * count['head']
    assert 2 * count['mlp'] == gated_mlp_forward_flops_per_token(
        sizes['hidden_size'], sizes['intermediate_size'])
    for kind in sizes['layer_types']:
        total += 2 * (count[kind] + count['mlp'])
        if kind in (WINDOW, FULL, CROSS):
            total += attention_pairs_flops(sizes, kind, seq_len)
    return total


def parameter_count(sizes, layers=None, vocab=None):
    """Every parameter of the model at ``layers`` layers and ``vocab``
    rows (default: as run): the matmuls' plus the filters, biases,
    decays, skips, lambdas and norms."""
    layers = layers or sizes['num_hidden_layers']
    vocab = vocab or sizes['vocab_size']
    hidden, inner = sizes['hidden_size'], sizes['mamba_d_inner']
    heads, kv, d = sizes['num_attention_heads'], \
        sizes['num_key_value_heads'], sizes['head_dim']
    count = matmul_parameters(sizes)
    diff = 4 * d + 2 * d                    # four lambdas, the sub-norm
    small = {
        MAMBA: inner * (sizes['mamba_d_conv'] + 1) + inner +
        inner * sizes['mamba_d_state'] + inner,
        WINDOW: (heads + 2 * kv) * d + hidden + diff,
        FULL: (heads + 2 * kv) * d + hidden + diff,
        CROSS: heads * d + hidden + diff,
        GMU: 0}
    total = hidden * vocab + 2 * hidden     # the table, the last norm
    for kind in layer_kinds(layers):
        total += count[kind] + small[kind] + count['mlp'] + 4 * hidden
    return total


def scan_train_cost(batch, seq_len, channels, states, itemsize=2,
                    chunk=NOMINAL_CHUNK):
    """(FLOPs, bytes) ONE layer's selective scan needs for its forward
    plus backward pass, from its shapes.

    FLOPs: 3 x the recurrence's forward, a token, channel and state:
    delta A, its exponential, the decay times the state, (delta x) B,
    their sum, the product with C and the sum over states (7), and a
    token and channel delta x and the skip (3).
    Bytes, every operand read or written ONCE each way: forward reads
    x, B, C (``itemsize`` an element) and delta (float32), writes m and
    the [channels, states] float32 state at each chunk's boundary;
    backward reads x, delta, B, C, m's cotangent and the boundary
    states and writes dx, ddelta (float32), dB, dC, dA and dD
    (float32)."""
    tokens = batch * seq_len
    flops = 3 * tokens * channels * (7 * states + 3)
    wide, narrow = tokens * channels, tokens * states
    boundary = batch * -(-seq_len // chunk) * channels * states * 4
    forward = wide * (2 * itemsize + 4) + 2 * narrow * itemsize + boundary
    backward = (wide * (3 * itemsize + 2 * 4) + 4 * narrow * itemsize +
                boundary + channels * (states + 1) * 4)
    return flops, forward + backward


def diff_flash_train_cost(batch, heads, kv_heads, seq_len, head_dim,
                          window=0, itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for one differential
    layer's forward plus backward calls: ``heads`` query heads
    ``head_dim`` wide over ``kv_heads`` keys as wide and values TWICE as
    wide, a causal mask banded by ``window``.

    FLOPs a visible (query, key) pair and query head, as
    ``moonlight_flops.latent_flash_train_cost`` counts two widths:
    forward 2 x (qk + v), backward 2 x (3 x qk + 2 x v).
    Bytes, every distinct value once a pass: q read twice and dq
    written (three passes at head_dim a query head), o written, read
    and do read (three at 2 x head_dim); k twice and dk (three at
    head_dim a K / V head), v twice and dv (three at 2 x head_dim a
    head PAIR: the two keys of a pair weigh the same values); each K / V
    head read once, not once a query head."""
    v_dim = 2 * head_dim
    pairs = batch * heads * visible_pairs(seq_len, window)
    rows = batch * seq_len * itemsize
    q_side = 3 * rows * heads * (head_dim + v_dim)
    kv_side = 3 * rows * (kv_heads * head_dim + kv_heads // 2 * v_dim)
    return 2 * pairs * (4 * head_dim + 3 * v_dim), q_side + kv_side
