"""Seconds inside JAX's ``backend_compile_duration`` during set-up:
programs the compiler built plus programs the persistent cache served
(the time to load them); the note prints the split.  Read as
``benchmark/lib/setup_totals.py`` took them.  Where the compile plane
counted no such program yet (a parent of the PR that added the
counters) nothing is returned."""

LAYER = 'compile plane'
UNIT = 's'
MOVES = 'setup_s'


def read(trace, run):
    if 'setup_seconds' not in run:      # nothing was set up
        return None
    from benchmark.lib import setup_totals
    got = setup_totals.totals(run)
    counts = [got['compile/backend_%s_count' % k]
              for k in ('built', 'loaded')]
    if counts == [None, None]:
        return None
    built, loaded = (got['compile/backend_%s_seconds' % k] or 0.0
                     for k in ('built', 'loaded'))
    run.setdefault('notes', {})['setup_backend_s'] = \
        'built %.2f s in %d programs, loaded from the persistent cache ' \
        '%.2f s in %d' % (
            built, counts[0] or 0, loaded, counts[1] or 0)
    return built + loaded
