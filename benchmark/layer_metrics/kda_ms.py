"""Device time per step, chip 0, in the ``kda_attention`` op and its
gradient (``benchmark/lib/scope_time.py``): the gated delta rule's
recurrence with its per-channel decay, forward and backward, of every
delta-rule layer (what is batched over all chunks, the scan over the
chunks, the reverse walk); the projections, filters and gates around it
read under their own ops.  Nothing where the program holds no such
op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'kda_attention'


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs) or None
