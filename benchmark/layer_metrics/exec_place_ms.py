"""Host ms per quiet run spent placing arguments: ``feed_h2d`` (the
one-chip executor staging host feeds) and ``place_state`` /
``place_data`` (the parallel runners putting every state and data array
on the mesh); ``benchmark/lib/host_phases.py``, median over the traced
block."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'

PHASES = ('feed_h2d', 'place_state', 'place_data')


def read(trace, run):
    from benchmark.lib import host_phases
    return host_phases.phase_ms(trace, PHASES)
