"""Chunk steps the delta rule's state scans take in one train step:
the program's gauge ``kda/chunks`` (``paddle_tpu/ops/kda_ops.py``), a
sum over ONE traced program of the trips of every scan over chunks it
holds as lowered: each layer's forward and the reverse walk of its
gradient (2 x T / 64 a layer today).  The scan is the
sequential part of the op: its trips, not its FLOPs, set the op's time
at one sequence a chip, so a larger chunk or a kernel that
carries the state itself shows here.
Nothing where the program has no such gauge or holds no such op."""

LAYER = 'op lowerings'
UNIT = 'count/step'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('kda/chunks', None)
    return float(value) if value else None
