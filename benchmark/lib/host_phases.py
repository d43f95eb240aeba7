"""The executor's host phases inside the quiet runs of the traced
block.  ``run.py`` puts every span of the step records on the
profiler's clock as ``executor/<name>`` beside its own annotations
(``bench/run#i`` around each no-fetch ``Executor.run``), so a phase's
time in a run is the length of the union of its spans clipped to that
run's annotation, and what no ``executor/*`` span covers is the
runner's self time (plan lookup, feed checks, counters: code with no
span of its own).  Medians over the quiet runs.
"""

import statistics

from benchmark.lib.trace_reduce import length, union

QUIET = 'bench/run#'
PHASE = 'executor/'


def _clipped(spans, a, b):
    return union((max(s.start, a), min(s.end, b)) for s in spans
                 if s.end > a and s.start < b)


def quiet_runs(trace):
    """[(annotation, [executor spans that touch it])] or None where the
    trace holds no quiet run or no executor span at all (the tracer
    was off, or the harness did not map the records)."""
    if trace is None:
        return None
    phases = [s for s in trace.spans if s.name.startswith(PHASE)]
    runs = [s for s in trace.spans if s.name.startswith(QUIET)]
    if not phases or not runs:
        return None
    return [(r, [s for s in phases if s.end > r.start and
                 s.start < r.end]) for r in runs]


def phase_ms(trace, names):
    """Median over the quiet runs of the ms spent in the phases of
    these names; 0.0 where the runs hold none of them."""
    runs = quiet_runs(trace)
    if runs is None:
        return None
    wanted = {PHASE + n for n in names}
    return statistics.median(
        length(_clipped([s for s in spans if s.name in wanted],
                        r.start, r.end)) / 1e6
        for r, spans in runs)


def unspanned_ms(trace):
    """Median over the quiet runs of the part of the annotation no
    executor span covers."""
    runs = quiet_runs(trace)
    if runs is None:
        return None
    return statistics.median(
        (r.end - r.start - length(_clipped(spans, r.start, r.end))) / 1e6
        for r, spans in runs)


def other_phases_note(trace, named):
    """Phases seen in the quiet runs that no exec_* metric names, with
    their median ms: '' where there is none."""
    runs = quiet_runs(trace) or []
    seen = sorted({s.name[len(PHASE):] for _, spans in runs
                   for s in spans} - set(named))
    return ', '.join('%s %.3f' % (n, phase_ms(trace, [n])) for n in seen)
