"""The block-mask flash calls' share of their roofline: the FLOPs of
the VISIBLE (query, key) pairs of the two flash-side parts of block
diffusion's attention (clean over clean, corrupted over clean: L^2
pairs a head in a layer that runs both, L (L - B) / 2 in the last),
forward and backward by ``causal_flash_roofline``'s factor, and the
bytes of each call's q, k, v, o and gradients
(``benchmark/lib/sdar_flops.py`` ``block_flash_train_cost``), against
``benchmark/lib/peaks.py``, over the time of the Mosaic custom calls
named after the ``block<n>_<relation>`` scopes.  The count is a
function of the MASK, not of the tiles a kernel walks, so it reads the
same work whatever implements it; the time holds a recompute group's
second forward call, which the count does not.  Nothing where the
trace names no such call."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

BLOCK_MASK = r'^block\d+_'


def read(trace, run):
    from benchmark.lib import flops, peaks, sdar_flops
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    traced_ns = trace.first.matching_ns(BLOCK_MASK, MOSAIC)
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    if not traced_ns or 'block_length' not in sizes:
        return None
    cost = sdar_flops.block_flash_train_cost(
        sizes, cell.traffic['batch_per_chip'], cell.traffic['seq_len'])
    least_s, bound_by = flops.roofline_seconds(
        *cost, *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['bd_flash_roofline'] = (
        'the block-mask flash calls take %.3f ms a step and are '
        '%s-bound at these shapes (%.1f GFLOP, %.1f MB)'
        % (trace.per_step_ms(traced_ns), bound_by, cost[0] / 1e9,
           cost[1] / 1e6))
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)
