"""Loop-body executions in one train step: the program's gauge
``loop/trips`` (``paddle_tpu/fluid/executor.py`` ``_while_scan``), a
sum over ONE traced program of the trips of every differentiable loop
it holds as lowered (a masked scan runs every trip of its bound).  4
for a stack applied four times whose forward runs once; 8 where the
gradient op lowered the scan a second time to differentiate it.
Nothing where the program has no such gauge or holds no such loop."""

LAYER = 'executor'
UNIT = 'count'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('loop/trips', None)
    return float(value) if value else None
