"""Chunk steps the chunked state-space scans' state walks take in one
train step: the program's gauge ``ssd/chunks``
(``paddle_tpu/ops/ssd_ops.py``), a sum over ONE traced program of the
trips of every walk over the chunks' states it holds as lowered: each
Mamba-2 layer's forward, that forward once more where a recompute group
runs it again, and the reverse walk of its gradient (3 x T / 128 a
grouped layer today).  A trip is elementwise work on one [heads,
head_dim, states] state; the chunks' products run beside the walk, all
at once.  Nothing where the program has no such gauge or holds no such
op."""

LAYER = 'op lowerings'
UNIT = 'count/step'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('ssd/chunks', None)
    return float(value) if value else None
