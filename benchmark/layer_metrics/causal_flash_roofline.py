"""The causal flash-attention kernels' share of their roofline: as
``flash_roofline``, with the FLOPs of the causal HALF of the square
(each query against the keys up to its own position; the kernels skip
the blocks above the diagonal, so counting the full square would
credit work that is not done).  Bytes are the full q, k, v, o and
gradient tensors either way.

The calls are the Mosaic custom calls whose framework name holds the
``fused_multihead_attention`` scope.  Nothing where the trace names no
such call or the configuration has no attention heads."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

FLASH_OP = r'fused_multihead_attention'


def read(trace, run):
    from benchmark.lib import flops, peaks
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    traced_ns = trace.first.matching_ns(FLASH_OP, MOSAIC)
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    if not traced_ns or 'num_attention_heads' not in sizes:
        return None
    heads = sizes['num_attention_heads']
    square_flops, layer_bytes = flops.flash_attention_train_cost(
        cell.traffic['batch_per_chip'], heads, cell.traffic['seq_len'],
        sizes['hidden_size'] // heads)
    layers = sizes['num_hidden_layers']
    least_s, bound_by = flops.roofline_seconds(
        layers * square_flops / 2, layers * layer_bytes,
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['causal_flash_roofline'] = \
        'the causal flash calls are %s-bound at these shapes' % bound_by
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)
