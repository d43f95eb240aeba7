"""Device time per step, chip 0, in the ``short_conv`` op and its
gradient (``benchmark/lib/scope_time.py``): the causal three-tap
depthwise filter between its two gates, forward and backward, of every
``conv`` layer.  Nothing where the program holds no such op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'short_conv'


def read(trace, run):
    from benchmark.lib import scope_time
    got = scope_time.measured(trace, run)
    if got is None:
        return None
    ns = sum(ns for scope, ns in got['by_scope'].items()
             if scope and belongs(scope_time.op_type(scope)))
    return trace.per_step_ms(ns) if ns else None
