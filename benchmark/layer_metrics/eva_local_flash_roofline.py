"""The local stream's flash calls' share of their roofline: the least
time the chip could take for one step's forward + backward calls of
EVA attention's exact stream (``benchmark/lib/evabyte_flops.py``
``local_train_cost``: the FLOPs of the causal half of each window's
square, every head; q, k, v, o and their gradients moved once) over
the time the trace gives them.

The calls are the Mosaic custom calls named after the
``fused_multihead_attention`` scope itself (windows folded into the
batch: plain causal calls at ``window_size`` keys); the remote
stream's are named ``remote`` and read by ``eva_remote_flash_roofline``
(the same reader with the other cost).  Nothing where the trace names
no such call or the configuration has no windows."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

LOCAL = r'fused_multihead_attention'


def share(trace, run, pattern, cost_of, note):
    """100 x roofline seconds of ``cost_of(batch, heads, seq_len,
    head_dim, window, chunk)`` a layer over the traced seconds of the
    Mosaic calls matching ``pattern``."""
    from benchmark.lib import flops, peaks
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    traced_ns = trace.first.matching_ns(pattern, MOSAIC)
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    if not traced_ns or 'window_size' not in sizes:
        return None
    layers = sizes['num_hidden_layers']
    cost = [layers * n for n in cost_of(
        cell.traffic['batch_per_chip'], sizes['num_attention_heads'],
        cell.traffic['seq_len'], sizes['head_dim'],
        sizes['window_size'], sizes['chunk_size'])]
    least_s, bound_by = flops.roofline_seconds(
        *cost, *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})[note] = (
        'these calls take %.3f ms a step and are %s-bound at these '
        'shapes (%.2f GFLOP, %.1f MB a step)'
        % (trace.per_step_ms(traced_ns), bound_by, cost[0] / 1e9,
           cost[1] / 1e6))
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)


def read(trace, run):
    from benchmark.lib import evabyte_flops

    def cost(batch, heads, seq_len, head_dim, window, chunk):
        return evabyte_flops.local_train_cost(batch, heads, seq_len,
                                              head_dim, window)

    return share(trace, run, LOCAL, cost, 'eva_local_flash_roofline')
