"""The matmuls' share of their roofline, chip 0: over the executed HLO
instructions that hold a ``dot`` (the TPU compiler prints it as a
``convolution``) under a ``mul``, ``matmul`` or ``fc`` op or its
gradient, the least time the chip could take for their FLOPs and bytes
(``fluid.profiler.cost_tables()``: counted from the optimised HLO's own
shapes by the rule in ``fluid/profiler.py``, recomputation and the
optimizer chains XLA fused in included) over their innermost traced
time.  That time is ``matmul_ms``'s less what the same scopes spend in
instructions that hold no dot; the note prints both, the whole cost
table by fluid op and the ten longest instructions with what they hold
(``benchmark/lib/scope_cost.py``).  Against the bf16 peak whatever the
product's type."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'

TYPES = frozenset(['mul', 'matmul', 'fc'])      # matmul_ms's


def read(trace, run):
    from benchmark.lib import scope_cost
    return scope_cost.roofline_share(trace, run, 'matmul_roofline',
                                     scope_cost.MATMUL_KINDS, TYPES)
