"""Host ms per quiet run in ``dispatch``: the call of the compiled
segment, which returns once the step is enqueued
(``benchmark/lib/host_phases.py``; median over the traced block)."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'

PHASES = ('dispatch',)


def read(trace, run):
    from benchmark.lib import host_phases
    return host_phases.phase_ms(trace, PHASES)
