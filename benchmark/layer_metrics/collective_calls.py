"""Collective instructions chip 0 executes per step (a ``-start`` and its
``-done`` are one call): ``benchmark/lib/scope_cost.py`` over
``fluid.profiler.cost_tables()``."""

LAYER = 'parallel runner'
UNIT = 'count/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import scope_cost
    rows = scope_cost.collectives(trace, run)
    if not rows:
        return None
    return sum(r.calls for r in rows) / trace.steps
