"""Seconds JAX spent tracing Python to jaxprs and lowering jaxprs to
MLIR during set-up: the compile plane folds JAX's own duration events
into ``compile/trace_seconds`` and ``compile/lower_seconds``, read as
``benchmark/lib/setup_totals.py`` took them (before the scope table of
a traced run added to them)."""

LAYER = 'compile plane'
UNIT = 's'
MOVES = 'setup_s'


def read(trace, run):
    if 'setup_seconds' not in run:      # nothing was set up
        return None
    from benchmark.lib import setup_totals
    got = setup_totals.totals(run)
    seconds = [got['compile/%s_seconds' % k] for k in ('trace', 'lower')]
    if None in seconds:
        return None
    run.setdefault('notes', {})['setup_trace_s'] = \
        'tracing %.2f s in %d jaxprs, lowering %.2f s in %d modules' % (
            seconds[0], got['compile/trace_count'] or 0,
            seconds[1], got['compile/lower_count'] or 0)
    return sum(seconds)
