"""Device time per step, chip 0, in what surrounds the expert matmuls
of the routed layers (``benchmark/lib/scope_time.py``): ``moe_route``
(f32 router logits, softmax over all experts, top-k, the two auxiliary
losses), ``moe_dispatch`` (sort of the (token, expert) pairs, row
gather) and ``moe_combine`` (the weighted sum back), forward and
backward together."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['moe_route', 'moe_dispatch', 'moe_combine'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
