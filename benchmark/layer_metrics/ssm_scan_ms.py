"""Device time per step, chip 0, in the ``selective_scan`` op and its
gradient (``benchmark/lib/scope_time.py``): the selective state-space
recurrence of every Mamba layer, forward (twice where a recompute group
runs it again) and the reverse walk with each chunk's forward once
more; the projections, the filter and the gates around it read under
their own ops.  Nothing where the program holds no such op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'selective_scan'


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs) or None
