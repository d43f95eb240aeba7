"""The differential-attention layers' flash calls' share of their
roofline: the least time the chip could take for one step's forward +
backward calls (``benchmark/lib/phi4flash_flops.py``
``diff_flash_train_cost``: 40 query heads 64 wide over 20 keys as wide
and values 128 wide; per (query, key) pair and head 2 x (64 + 128)
FLOPs forward and 2 x (3 x 64 + 2 x 128) backward, the pairs INSIDE the
512-key band for the windowed layers and the causal half for the full
and the cross layers; bytes with every distinct K / V value read once a
pass) over the time the trace gives them.

The calls are the Mosaic custom calls the compiler names after the
innermost scope such a call is lowered in, ``qk64v128``
(``ops/fused_ops.py``: a windowed call opens ``window512`` and then
``qk64v128``, so windowed, full and cross calls are all named by their
widths; forward and both backward kernels alike).  A recompute group's
second forward call is in the time and not in the count.
``mla_flash_roofline`` reckons one K / V head a query head and no band,
and is not declared for this family.  Nothing where the trace names no
such call or the configuration has no differential attention."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

TWO_WIDTHS = r'^qk\d+v\d+'


def read(trace, run):
    from benchmark.lib import flops, peaks, phi4flash_flops
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    traced_ns = trace.first.matching_ns(TWO_WIDTHS, MOSAIC)
    if not traced_ns or not sizes.get('differential_attention'):
        return None
    cost = [0, 0]
    for kind in sizes['layer_types']:
        if kind not in (phi4flash_flops.WINDOW, phi4flash_flops.FULL,
                        phi4flash_flops.CROSS):
            continue
        one = phi4flash_flops.diff_flash_train_cost(
            cell.traffic['batch_per_chip'], sizes['num_attention_heads'],
            sizes['num_key_value_heads'], cell.traffic['seq_len'],
            sizes['head_dim'],
            sizes['sliding_window'] if kind == phi4flash_flops.WINDOW
            else 0)
        cost = [a + b for a, b in zip(cost, one)]
    least_s, bound_by = flops.roofline_seconds(
        *cost, *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['diff_flash_roofline'] = (
        'the differential layers\' flash calls take %.3f ms a step and '
        'are %s-bound at these shapes (%.1f GFLOP, %.1f MB)'
        % (trace.per_step_ms(traced_ns), bound_by, cost[0] / 1e9,
           cost[1] / 1e6))
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)
