"""Chunk steps the selective scans' state walks take in one train
step: the program's gauge ``ssm/chunks``
(``paddle_tpu/ops/ssm_ops.py``), a sum over ONE traced program of the
trips of every scan over chunks it holds as lowered: each Mamba layer's
forward, that forward once more where a recompute group runs it again,
and the reverse walk of its gradient (3 x T / 256 a layer today).
Every trip walks its chunk's tokens one after another, so the op's time
goes with tokens, not with this count; a kernel that carries the state
itself, or a recompute group that keeps the scan's output, shows here.
Nothing where the program has no such gauge or holds no such op."""

LAYER = 'op lowerings'
UNIT = 'count/step'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('ssm/chunks', None)
    return float(value) if value else None
