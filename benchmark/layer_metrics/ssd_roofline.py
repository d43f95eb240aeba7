"""The chunked state-space scan's share of its roofline, chip 0: the
least time the chip could take for one step's forward + backward
recurrences of the Mamba-2 layers, by a hand count from their SHAPES
(``benchmark/lib/nemotron_h_flops.py`` ``ssd_train_cost``: x, delta, B,
C read and y written once forward; those and y's cotangent read and the
five gradients written once backward; the [heads, head_dim, states]
float32 state at each chunk's boundary each way; the chunked form's
FLOPs at the published chunk), over the time the trace gives the
``ssd_scan`` op and its gradient (``ssd_ms``'s).  The count never looks
at what implements the op: XLA's lowering of the chunks' products
today, a kernel tomorrow, on one yardstick; a forward that a recompute
group runs again is in the time and not in the count.  Nothing where no
such instruction ran or the configuration has no such layer."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.layer_metrics import ssd_ms
    from benchmark.lib import flops, nemotron_h_flops, peaks
    ms = ssd_ms.read(trace, run)
    if not ms:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    layers = sum(kind == nemotron_h_flops.MAMBA
                 for kind in sizes.get('layer_types', ()))
    if not layers or 'ssm_state_size' not in sizes:
        return None
    one = nemotron_h_flops.ssd_train_cost(
        cell.traffic['batch_per_chip'], cell.traffic['seq_len'],
        sizes['mamba_num_heads'], sizes['mamba_head_dim'],
        sizes['n_groups'], sizes['ssm_state_size'], sizes['chunk_size'])
    least_s, bound_by = flops.roofline_seconds(
        layers * one[0], layers * one[1],
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['ssd_roofline'] = (
        'the %d Mamba-2 layers\' scans take %.3f ms a step and are '
        '%s-bound by the hand count (%.2f GFLOP, %.1f MB a step at '
        'chunks of %d)'
        % (layers, ms, bound_by, layers * one[0] / 1e9,
           layers * one[1] / 1e6, sizes['chunk_size']))
    return 100.0 * least_s / (ms / 1e3)
