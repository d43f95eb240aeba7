"""Bytes chip 0 hands to collectives per step: the operand bytes of each
collective instruction (``fluid.profiler.cost_tables()``, from the
optimised HLO: under GSPMD no ``c_allreduce`` op runs and
``fluid/comms.py`` files no record) times its executions in the traced
block.  The note gives each collective's bytes, time and rate beside
the links' published rate."""

LAYER = 'parallel runner'
UNIT = 'MB/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import scope_cost
    rows = scope_cost.collectives(trace, run)
    if not rows:
        return None
    run.setdefault('notes', {})['collective_mb'] = \
        scope_cost.collectives_note(trace, rows)
    return sum(r.calls * r.cost.bytes for r in rows) / 1e6 / trace.steps
