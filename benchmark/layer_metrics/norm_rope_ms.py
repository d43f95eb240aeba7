"""Device time per step, chip 0, in the ``rms_norm`` and
``rotary_embedding`` ops and their gradients
(``benchmark/lib/scope_time.py``): the decoder block's norms (input,
post-attention, final, and the QK-norm over the whole q and k
projections) and the rotation of q and k."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['rms_norm', 'rotary_embedding'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
