"""The enqueue cost of a step: the host's clock around each no-fetch
``Executor.run`` of the traced block, median.  While it stays under the
device's step time the executor hides behind the device."""

import statistics

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    samples = run.get('quiet_run_host_ms')
    return statistics.median(samples) if samples else None
