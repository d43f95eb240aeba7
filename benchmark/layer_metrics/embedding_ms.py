"""Device time per step, chip 0, in the ``lookup_table*`` ops and their
gradients, whichever lowering ran: the row gather and scatter-add
kernels or the dense gather and scatter
(``benchmark/lib/scope_time.py``)."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

def belongs(op_type):
    return op_type.startswith('lookup_table')


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
