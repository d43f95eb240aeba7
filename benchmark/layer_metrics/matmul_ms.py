"""Device time per step, chip 0, in the ``mul``, ``matmul`` and ``fc`` ops
and their gradients (``benchmark/lib/scope_time.py``): the encoder's
projections, the vocabulary head and, where the zoo builds attention
from ``matmul`` and ``softmax`` ops (sequences under ``flash_min_len``),
its two batched products."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['mul', 'matmul', 'fc'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
