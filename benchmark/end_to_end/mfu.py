"""Model FLOP/s utilization: throughput x training FLOPs per item (from
the configuration's shapes, by the family's function; 3 x forward,
recomputation not counted) over chips x the published bf16 peak of the
device kind.  Per-chip utilization on a cell of several chips."""

UNIT = '%'


def read(run):
    from benchmark.lib import peaks
    if not run.get('window_seconds'):
        return None
    cell = run['cell']
    peak_flops, _ = peaks.chip_peak(run['device_kind'])
    rate = run['items_done'] / run['window_seconds']
    return 100.0 * rate * cell.family.flops_per_item(
        cell.config, cell.traffic) / (cell.chips * peak_flops)
