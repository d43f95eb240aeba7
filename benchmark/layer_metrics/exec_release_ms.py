"""Host ms per quiet run in ``state_release``: dropping the last
references to the step's donated state, which waits on the runtime
where the execution that defined them is still in flight
(``benchmark/lib/host_phases.py``; median over the traced block)."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'

PHASES = ('state_release',)


def read(trace, run):
    from benchmark.lib import host_phases
    return host_phases.phase_ms(trace, PHASES)
