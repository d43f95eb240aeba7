"""The flash-attention kernels' share of their roofline: the least
time the chip could take for one step's forward + backward calls
(``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)`` from the shapes,
``benchmark/lib/flops.py``) over the time the trace gives them.

The calls are found by the framework name the trace carries for each
Mosaic custom call: the executor lowers every fluid op inside a named
scope that ends in the op's type, and flash attention is the
``fused_multihead_attention`` op and its gradient.  Returns nothing
where the trace names no such call (the step ran dense attention, or
the names are gone) or the configuration is not a multi-head encoder.
"""

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
    layer_flops, layer_bytes = flops.flash_attention_train_cost(
        cell.traffic['batch_per_chip'], heads, cell.traffic['seq_len'],
        sizes['hidden_size'] // heads)
    layers = sizes['num_hidden_layers']
    least_s, bound_by = flops.roofline_seconds(
        layers * layer_flops, layers * layer_bytes,
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['flash_roofline'] = \
        'the flash calls are %s-bound at these shapes' % bound_by
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)
