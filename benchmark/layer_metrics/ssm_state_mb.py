"""What the selective scans keep between their forward and their
backward pass beside their operands, in one train step: the program's
gauge ``ssm/boundary_state_mb`` (``paddle_tpu/ops/ssm_ops.py``), a sum
over ONE traced program of the float32 [channels, states] state at each
chunk's start, every Mamba layer (T / 256 x 5120 x 16 x 4 bytes =
10.5 MB a layer and sequence today, where every token's state would be
2.7 GB).  A larger chunk keeps less and computes a longer chunk again.
Nothing where the program has no such gauge or holds no such op."""

LAYER = 'op lowerings'
UNIT = 'MB'
MOVES = 'peak_hbm'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('ssm/boundary_state_mb', None)
    return float(value) if value else None
