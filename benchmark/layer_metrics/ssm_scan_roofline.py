"""The selective scan's share of its roofline, chip 0: the least time
the chip could take for one step's forward + backward recurrences of
the Mamba layers, by a hand count from their SHAPES
(``benchmark/lib/phi4flash_flops.py`` ``scan_train_cost``: x, delta, B,
C read and m written once forward; those and m's cotangent read and dx,
ddelta, dB, dC, dA, dD written once backward; the [channels, states]
float32 state at each nominal 256-token chunk's boundary each way; the
recurrence's FLOPs), over the time the trace gives the
``selective_scan`` op and its gradient (``ssm_scan_ms``'s).  The count
never looks at what implements the op: two nested ``lax.scan``s today,
a kernel tomorrow, on one yardstick; a forward that a recompute group
runs again is in the time and not in the count.  Nothing where no such
instruction ran or the configuration has no such layer."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.layer_metrics import ssm_scan_ms
    from benchmark.lib import flops, peaks, phi4flash_flops
    ms = ssm_scan_ms.read(trace, run)
    if not ms:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    layers = sum(kind == phi4flash_flops.MAMBA
                 for kind in sizes.get('layer_types', ()))
    if not layers or 'mamba_d_inner' not in sizes:
        return None
    one = phi4flash_flops.scan_train_cost(
        cell.traffic['batch_per_chip'], cell.traffic['seq_len'],
        sizes['mamba_d_inner'], sizes['mamba_d_state'])
    least_s, bound_by = flops.roofline_seconds(
        layers * one[0], layers * one[1],
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['ssm_scan_roofline'] = (
        'the %d Mamba layers\' scans take %.3f ms a step and are '
        '%s-bound by the hand count (%.2f GFLOP, %.1f MB a step at a '
        'nominal chunk of %d)'
        % (layers, ms, bound_by, layers * one[0] / 1e9,
           layers * one[1] / 1e6, phi4flash_flops.NOMINAL_CHUNK))
    return 100.0 * least_s / (ms / 1e3)
