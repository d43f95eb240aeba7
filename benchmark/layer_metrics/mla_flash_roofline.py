"""The latent-attention layers' flash calls' share of their roofline:
the least time the chip could take for one step's forward + backward
calls at a query / key width that differs from the value width
(``benchmark/lib/moonlight_flops.py`` ``latent_flash_train_cost``: per
(query, key) pair of the causal half and head 2 x (qk + v) FLOPs
forward and 2 x (3 x qk + 2 x v) backward, bytes of six passes over
the q / k-sized and six over the v / o-sized tensors) over the time the
trace gives them.

The calls are the Mosaic custom calls the compiler names after the
scope such a call is lowered in, ``qk<D>v<Dv>`` (``ops/fused_ops.py``;
forward and both backward kernels alike).  ``causal_flash_roofline``
and ``gqa_causal_flash_roofline`` reckon one width for all seven
products and are not declared for this family.  Nothing where the
trace names no such call or the configuration has no such widths."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

TWO_WIDTHS = r'^qk\d+v\d+'


def read(trace, run):
    from benchmark.lib import flops, moonlight_flops, peaks
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    traced_ns = trace.first.matching_ns(TWO_WIDTHS, MOSAIC)
    if not traced_ns or 'v_head_dim' not in sizes:
        return None
    one = moonlight_flops.latent_flash_train_cost(
        cell.traffic['batch_per_chip'], sizes['num_attention_heads'],
        cell.traffic['seq_len'],
        sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim'],
        sizes['v_head_dim'])
    cost = [sizes['num_hidden_layers'] * n for n in one]
    least_s, bound_by = flops.roofline_seconds(
        *cost, *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['mla_flash_roofline'] = (
        'the latent layers\' flash calls take %.3f ms a step and are '
        '%s-bound at these shapes (%.1f GFLOP, %.1f MB)'
        % (trace.per_step_ms(traced_ns), bound_by, cost[0] / 1e9,
           cost[1] / 1e6))
    return 100.0 * least_s / (traced_ns / 1e9 / trace.steps)
