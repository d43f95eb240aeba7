"""Device time per step, chip 0, in the REMOTE stream's kernel calls
alone: the Mosaic custom calls the compiler names after the scope a
coarse-mask call is lowered in, ``remote`` (``ops/fused_ops.py``;
forward and backward kernels alike).  Nothing where the trace names no
such call."""

LAYER = 'kernels'
UNIT = 'ms/step'
MOVES = 'throughput'

REMOTE = r'^remote'


def read(trace, run):
    from benchmark.lib.trace_reduce import MOSAIC
    if trace is None:
        return None
    ns = trace.first.matching_ns(REMOTE, MOSAIC)
    return trace.per_step_ms(ns) if ns else None
