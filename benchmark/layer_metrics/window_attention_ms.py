"""Device time per step, chip 0, in the WINDOWED calls of the
``fused_multihead_attention`` op and their gradients: the op lowers a
call with a ``window`` inside a scope of its own, ``window<n>``, so the
program's table (``benchmark/lib/scope_time.py``) reads
``fused_multihead_attention/window512`` and
``fused_multihead_attention_grad/window512`` for the sliding layers and
the bare op type for the full ones.  Kernels and the transposes around
them, forward and backward together; ``causal_attention_ms`` is this
plus the full layers' calls.  Nothing where the program's table holds
no such scope."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

ATTENTION = ('fused_multihead_attention/window',
             'fused_multihead_attention_grad/window')


def read(trace, run):
    from benchmark.lib import scope_time
    got = scope_time.measured(trace, run)
    if got is None:
        return None
    ns = sum(ns for scope, ns in got['by_scope'].items()
             if scope is not None and scope.startswith(ATTENTION))
    return trace.per_step_ms(ns) if ns else None
