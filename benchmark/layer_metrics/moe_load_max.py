"""The largest expert load over the mean load, worst routed layer, on
the last run that fetched the loss: the program's gauge
``moe/load_max_over_mean`` (``paddle_tpu/fluid/moe_stats.py``, read
from the router's own load tensor on runs that fetch; 1.0 is perfectly
even, the number of experts is everything on one).  The grouped
matmuls' time follows the largest group's tail, so this says how far
the routing of the cell's batch is from the even split its FLOP count
assumes.  Nothing where the program has no such gauge."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('moe/load_max_over_mean', None)
    return None if value is None else float(value)
