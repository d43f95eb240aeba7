"""Device time per step in Mosaic custom calls, all Pallas kernels
together; 0 where the step holds none."""

LAYER = 'kernels'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    return trace.per_step_ms(trace.first.kind_ns(MOSAIC))
