"""What the chunked state-space scans keep between their forward and
their backward pass beside their operands, in one train step: the
program's gauge ``ssd/boundary_state_mb``
(``paddle_tpu/ops/ssd_ops.py``), a sum over ONE traced program of the
float32 [heads, head_dim, states] state at each chunk's start, every
Mamba-2 layer (T / 128 x 64 x 64 x 128 x 4 bytes = 134 MB a layer and
8192-token sequence, where every token's state would be 17 GB).  A
larger chunk keeps less and computes a longer chunk again.  Nothing
where the program has no such gauge or holds no such op."""

LAYER = 'op lowerings'
UNIT = 'MB'
MOVES = 'peak_hbm'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('ssd/boundary_state_mb', None)
    return float(value) if value else None
