"""Device time per step, chip 0, in the ``conv2d``, ``batch_norm`` and
``pool2d`` ops and their gradients (``benchmark/lib/scope_time.py``)."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['conv2d', 'batch_norm', 'pool2d'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
