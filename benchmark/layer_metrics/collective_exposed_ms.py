"""Collective time no other op hides, per step, on the first chip."""

LAYER = 'parallel runner'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib.trace_reduce import COLLECTIVE
    if trace is None or not trace.first.kind_intervals[COLLECTIVE]:
        return None
    return trace.per_step_ms(trace.first.exposed_collective_ns())
