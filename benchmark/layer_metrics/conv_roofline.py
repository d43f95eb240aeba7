"""The convolutions' share of their roofline, chip 0: as
``matmul_roofline`` for the executed instructions that hold a
``convolution`` under ``conv2d`` or ``conv2d_grad`` (batch-norm and the
residual adds XLA fused into them ride in their bytes).  A tap on
padding is no multiply-add by the table's rule, so its FLOPs are 3.45%
under ``benchmark/lib/flops.py``'s for ResNet-50, and it counts no
input gradient for the stem, which no one computes."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'

TYPES = frozenset(['conv2d'])


def read(trace, run):
    from benchmark.lib import scope_cost
    return scope_cost.roofline_share(trace, run, 'conv_roofline',
                                     ('convolution',), TYPES)
