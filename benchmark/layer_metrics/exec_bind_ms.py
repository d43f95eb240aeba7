"""Host ms per quiet run in the executor's ``bind`` phase: resolving the
segment's state and data arguments from the scope and the feed
(``benchmark/lib/host_phases.py``; median over the traced block)."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'

PHASES = ('bind',)


def read(trace, run):
    from benchmark.lib import host_phases
    return host_phases.phase_ms(trace, PHASES)
