"""The forward bodies' share of ``loop_ms``, percent: the device time
of the instructions the loop's body holds as the forward pass runs it,
over that of the forward and the transposed bodies together
(``benchmark/lib/loop_time.py``).  A backward pass costs about two
forwards, so about 33 where the loop's forward runs once a trip and
about 50 where the gradient runs it again before it can differentiate
it (a ``while_grad`` that replays its scan: the replay's instructions
are forward ones by name).  Nothing where ``loop_ms`` has nothing."""

LAYER = 'executor'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import loop_time
    got = loop_time.measured(trace, run)
    if got is None:
        return None
    return 100.0 * got['forward'] / (got['forward'] + got['backward'])
