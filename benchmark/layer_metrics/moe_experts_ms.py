"""Device time per step, chip 0, in the ``moe_experts`` op and its
gradient (``benchmark/lib/scope_time.py``): the grouped gate, up and
down matmuls of the routed layers with the SiLU product between them,
forward and backward together.  Nothing where the program's table
holds no such op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'moe_experts'


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
