"""Device time per step in ops that are neither Mosaic custom calls
nor collectives: what the op lowerings hand to XLA."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib.trace_reduce import OTHER
    if trace is None:
        return None
    return trace.per_step_ms(trace.first.kind_ns(OTHER))
