"""Share of the traced window in which no op ran on the first chip."""

LAYER = 'device'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib.trace_reduce import length
    if trace is None:
        return None
    chip = trace.first
    return 100.0 * (1.0 - length(chip.busy) / (chip.end - chip.start))
