"""The gated short convolutions' share of their roofline, chip 0: over
the executed HLO instructions under a ``short_conv`` op or its
gradient, the least time the chip could take for the bytes each moves
at its own boundary (``fluid.profiler.cost_tables()``, the rule in
``fluid/profiler.py``; the op holds no matmul, so its FLOPs count
nothing against 197 TFLOP/s) over their innermost traced time.  Where
one of them is a custom call (a kernel: the cost table knows its
boundary and not what it reads), the hand count of
``benchmark/lib/lfm2_flops.py`` ``short_conv_train_cost`` (eleven
passes over a [tokens, channels] tensor a layer, forward and backward)
stands for all of them.  What XLA fuses INTO a neighbour that holds a
matmul reads under that matmul's op, here as in ``short_conv_ms``.
Nothing where no such instruction ran."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import flops, lfm2_flops, peaks, scope_cost, \
        scope_time
    got = scope_cost.measured(trace, run)
    if got is None:
        return None
    rows = [r for r in got['rows']
            if r.scope and scope_time.op_type(r.scope) == 'short_conv'
            and r.cost is not None and r.cost.kind != 'collective']
    ns = sum(r.ns for r in rows)
    if not ns:
        return None
    peak = peaks.chip_peak(run['device_kind'])
    if any(r.cost.flops is None for r in rows):
        cell = run['cell']
        sizes = cell.family.sizes(cell.config, cell.traffic)
        layers = sum(kind == lfm2_flops.CONV
                     for kind in sizes['layer_types'])
        one = lfm2_flops.short_conv_train_cost(
            cell.traffic['batch_per_chip'], cell.traffic['seq_len'],
            sizes['hidden_size'], sizes['conv_L_cache'])
        least = trace.steps * flops.roofline_seconds(
            layers * one[0], layers * one[1], *peak)[0]
        how = 'the hand count (a custom call ran)'
    else:
        least = sum(scope_cost.least_seconds(r, peak)[0] for r in rows)
        how = "the cost table's bytes"
    run.setdefault('notes', {})['short_conv_roofline'] = (
        'short_conv and its gradient: %d instructions, %.3f ms a step, '
        '%.1f MB a step at their boundaries; roofline seconds from %s'
        % (len(rows), trace.per_step_ms(ns),
           sum(r.calls * r.cost.bytes for r in rows) / 1e6 / trace.steps,
           how))
    return 100.0 * least / (ns / 1e9)
