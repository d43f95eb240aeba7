"""The hyper-connections' share of their roofline, chip 0: the least
time the chip could take for one step's read-outs and write-backs of
the residual streams around every operator, by a hand count from their
SHAPES (``benchmark/lib/xing_flops.py`` ``mhc_train_cost``: a fused
implementation moves (3 n + 2) C elements of the stream a token
forward and (5 n + 3) C backward, beside the float32 maps and phi; the
FLOPs of the projection r phi; like ``mfu``'s count, no forward that a
recompute group runs again), over the time the trace gives the two ops
and their gradients (``mhc_ms``'s, recomputation included: time spent
on it lowers the share, as it lowers ``mfu``).  It is bound by BYTES.
The count never looks at what implements the ops or at what the
program recomputes: XLA fusions today, a kernel or another recompute
policy tomorrow, on one yardstick.  Nothing where no such instruction ran or the
configuration has no streams."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.layer_metrics import mhc_ms
    from benchmark.lib import flops, peaks, xing_flops
    ms = mhc_ms.read(trace, run)
    if not ms:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    if not sizes.get('hc_mult'):
        return None
    count = xing_flops.operators(sizes)
    one = xing_flops.mhc_train_cost(
        cell.traffic['batch_per_chip'] * cell.traffic['seq_len'],
        sizes['hc_mult'], sizes['hidden_size'])
    least_s, bound_by = flops.roofline_seconds(
        count * one[0], count * one[1],
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['mhc_roofline'] = (
        'the %d hyper-connected operators take %.3f ms a step and are '
        '%s-bound by the hand count (%.2f GFLOP, %.1f MB a step; least '
        '%.3f ms)' % (count, ms, bound_by, count * one[0] / 1e9,
                      count * one[1] / 1e6, least_s * 1e3))
    return 100.0 * least_s / (ms / 1e3)
