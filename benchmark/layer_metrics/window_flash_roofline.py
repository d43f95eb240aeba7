"""The banded (sliding-window) flash-attention calls' share of their
roofline: the least time the chip could take for one step's forward +
backward calls of the sliding layers (``benchmark/lib/laguna_flops.py``
``grouped_flash_train_cost``: FLOPs of the (query, key) pairs INSIDE
the band only, bytes with each of the 8 K/V heads read once) over the
time the trace gives them.

The calls are the Mosaic custom calls the compiler names after the
scope a windowed call is lowered in, ``window<n>`` (``ops/fused_ops.py``;
forward and both backward kernels alike).  A query block computes every
key block it touches whole, so with blocks as wide as the band about
half of what the kernels compute lies outside it: the share has that
ceiling before anything else costs.  Nothing where the trace names no
such call or the configuration has no sliding layers."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

WINDOWED = r'^window\d+'
KIND = 'sliding_attention'


def layers_cost(run, kind):
    """(FLOPs, bytes) of a step's flash calls in the layers of this
    kind, or None where the configuration has none."""
    from benchmark.lib import laguna_flops
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    if 'layer_types' not in sizes:
        return None
    total = [0, 0]
    for layer_kind, heads, _ in laguna_flops.layers_of(sizes):
        if layer_kind != kind:
            continue
        cost = laguna_flops.grouped_flash_train_cost(
            cell.traffic['batch_per_chip'], heads,
            sizes['num_key_value_heads'], cell.traffic['seq_len'],
            sizes['head_dim'],
            sizes['sliding_window'] if kind == KIND else 0)
        total = [a + b for a, b in zip(total, cost)]
    return total if total[0] else None


def share(trace, run, pattern, kind, note):
    from benchmark.lib import flops, peaks
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    traced_ns = trace.first.matching_ns(pattern, MOSAIC)
    cost = layers_cost(run, kind) if traced_ns else None
    if cost is None:
        return None
    least_s, bound_by = flops.roofline_seconds(
        *cost, *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})[note] = (
        'the %s layers\' flash calls take %.3f ms a step and are '
        '%s-bound at these shapes (%.1f GFLOP, %.1f MB)'
        % (kind, trace.per_step_ms(traced_ns), bound_by, cost[0] / 1e9,
           cost[1] / 1e6))
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)


def read(trace, run):
    return share(trace, run, WINDOWED, KIND, 'window_flash_roofline')
