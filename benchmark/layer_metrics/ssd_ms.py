"""Device time per step, chip 0, in the ``ssd_scan`` op and its gradient
(``benchmark/lib/scope_time.py``): Mamba-2's recurrence of every ``M``
layer as matrix products over chunks, forward (twice where a recompute
group runs it again) and the backward that computes every chunk's
inside again; the projections, the filter, the gated norm around it
read under their own ops.  Nothing where the program holds no such
op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'ssd_scan'


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs) or None
