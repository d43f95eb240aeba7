"""Operations and bytes from shapes for the EvaByte family (EVA
attention: exact causal attention inside windows joined in one softmax
with chunk summaries of every earlier window; several prediction
heads over a small vocabulary), by ``flops.py``'s conventions: one
multiply-add is 2 FLOPs, training is 3 x forward, elementwise work
(the chunk pooling among it), norms, the rotary embedding and
softmaxes are left out of a model's FLOPs.  Only VISIBLE (query, key)
pairs count, as the other families count the causal half."""


def windows_of(seq_len, window):
    """The lengths of a sequence's windows: whole ones and, where the
    length is no multiple, a shorter last one."""
    return [min(window, seq_len - start)
            for start in range(0, seq_len, window)]


def local_pairs(seq_len, window):
    """(query, key) pairs of the exact stream in one sequence and
    head: each query against the keys of its own window up to its own
    position."""
    return sum(n * (n + 1) // 2 for n in windows_of(seq_len, window))


def remote_pairs(seq_len, window, chunk):
    """(query, summary) pairs of the remote stream in one sequence and
    head: each query of window w against the ``window / chunk``
    summaries of each of the w windows before it."""
    return sum(n * w * (window // chunk)
               for w, n in enumerate(windows_of(seq_len, window)))


def forward_flops_per_token(sizes, seq_len):
    """Forward FLOPs for one token of the decoder as it is run.
    ``sizes``: ``families/evabyte.py`` ``sizes``.  Per layer q, k, v
    and the output projection, scores and context over the visible
    pairs of both streams (on average over a sequence's positions),
    the gated MLP; the ``num_pred_heads`` heads over the vocabulary,
    every position."""
    hidden, heads = sizes['hidden_size'], sizes['num_attention_heads']
    d = hidden // heads
    pairs = (local_pairs(seq_len, sizes['window_size']) +
             remote_pairs(seq_len, sizes['window_size'],
                          sizes['chunk_size'])) / seq_len
    per_layer = (4 * 2 * hidden * hidden + 2 * 2 * heads * d * pairs +
                 3 * 2 * hidden * sizes['intermediate_size'])
    return sizes['num_hidden_layers'] * per_layer + \
        sizes['num_pred_heads'] * 2 * hidden * sizes['vocab_size']


def local_train_cost(batch, heads, seq_len, head_dim, window,
                     itemsize=2):
    """(FLOPs, bytes) the flash algorithm needs for one layer's local
    stream, forward plus backward.  FLOPs: forward q k^T and p v;
    backward dV, dP, dQ, dK and the recomputed scores: seven matmuls
    of 2 * head_dim a visible pair and head.  Bytes: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv: twelve passes over a [B, T, H, d] tensor (the per-row
    statistics are under 1%)."""
    pairs = batch * heads * local_pairs(seq_len, window)
    tensor = batch * heads * seq_len * head_dim * itemsize
    return 7 * 2 * pairs * head_dim, 12 * tensor


def remote_train_cost(batch, heads, seq_len, head_dim, window, chunk,
                      itemsize=2):
    """(FLOPs, bytes) of one layer's remote stream, forward plus
    backward: the same seven matmuls over the visible (query, summary)
    pairs only.  Bytes: q, o, do read and dq written at [B, T, H, d]
    (forward q, o; backward q, o, do, dq: six passes), the summaries
    k~, v~ read ONCE a call and their gradients written ([B, T /
    chunk, H, d]: forward 2, backward 2 + 2)."""
    pairs = batch * heads * remote_pairs(seq_len, window, chunk)
    tensor = batch * heads * seq_len * head_dim * itemsize
    return 7 * 2 * pairs * head_dim, 6 * tensor + 6 * tensor // chunk


def chunk_summary_train_cost(batch, heads, seq_len, head_dim, chunk,
                             itemsize=2):
    """(FLOPs, bytes) of one layer's pooling pass, forward plus
    backward, as ONE pass over its operands each way.  Bytes: forward
    reads k, v and writes k~, v~ (2 + 2 / chunk tensors); backward
    reads k, v and the summaries' cotangents and writes dk, dv (4 + 2 /
    chunk).  FLOPs an element of k or v: forward the logit's product
    and the weighted sum, 2 + 2 for k and 2 for v; backward about twice
    that: 18 a (position, head, feature) of the pair.  Bytes bound it
    on any chip of today."""
    elements = batch * heads * seq_len * head_dim
    return 18 * elements, (6 * elements + 4 * elements // chunk) * itemsize
