"""Device time per step, chip 0, in the ``fused_multihead_attention`` op
and its gradient, whichever side of ``dispatch()`` ran
(``benchmark/lib/scope_time.py``).  Declared only for the cell whose
program holds the op (``bert_base_s2048``): under ``flash_min_len``
the zoo builds attention from ``matmul``, ``softmax`` and ``dropout``
ops, whose time reads under those types, and this would be a constant
0 that no change can move."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

def belongs(op_type):
    return op_type == 'fused_multihead_attention'


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
