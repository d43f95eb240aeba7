"""Host ms per quiet run that no ``executor/*`` span covers: the
runner's self time (``benchmark/lib/host_phases.py``).  With
``exec_bind_ms``, ``exec_place_ms``, ``exec_dispatch_ms`` and
``exec_release_ms`` it makes up ``host_run_ms``; the note names any
other phase the runs held."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'

NAMED = ('bind', 'feed_h2d', 'place_state', 'place_data', 'dispatch',
         'state_release')


def read(trace, run):
    from benchmark.lib import host_phases
    other = host_phases.other_phases_note(trace, NAMED)
    if other:
        run.setdefault('notes', {})['exec_unspanned_ms'] = \
            'phases in the quiet runs that no exec_* metric names, ' \
            'ms: ' + other
    return host_phases.unspanned_ms(trace)
