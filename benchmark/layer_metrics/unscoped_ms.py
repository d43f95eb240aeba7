"""Device time per step, chip 0, of the non-collective ops to which the
program's table gives no fluid op (``benchmark/lib/scope_time.py``):
how far the split by op type can be trusted.  Its note is the whole
table, every scope with its ms per step and share, and the
instructions that hold most of the unscoped time."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import scope_time
    got = scope_time.measured(trace, run)
    if got is None:
        return None
    run.setdefault('notes', {})['unscoped_ms'] = \
        scope_time.table_note(trace, got)
    return trace.per_step_ms(got['by_scope'].get(None, 0))
