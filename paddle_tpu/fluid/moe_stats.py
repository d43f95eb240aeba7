"""What a dropless MoE layer reports through ``fluid.monitor``.

``layers.moe`` asks the program to watch two of the layer's variables
(``Program.watch``): the router's expert loads ([E] int32, the group
sizes the grouped matmuls are handed) and ``moe_dispatch``'s count of
sorted rows that sit outside their expert's group.  A run that already
fetches something and blocks for it reads them beside the user's
fetches and calls ``record``; a quiet run reads nothing.

- counter ``moe/tokens_routed``: (token, expert) pairs routed, summed
  over the layers, on the runs that were read;
- counter ``moe/dropped_tokens``: pairs whose row the grouped matmuls
  would hand to another expert or to none on those runs (0: the
  routing is dropless by construction, and a sort and group sizes that
  disagree show here);
- gauge ``moe/load_max_over_mean``: the largest expert load over the
  mean load, the worst layer of the last run read (1.0 is perfectly
  even; E is everything on one expert).

A layer that holds a range of the experts (``experts_held``) also
reports, on the same runs:

- counter ``moe/rows_held``: the pairs routed to an expert held here,
  i.e. the rows the grouped matmuls compute.  A pair routed to an
  absent expert is no drop: ``moe/dropped_tokens`` stays 0;
- gauge ``moe/held_share``: rows held over rows routed, all such
  layers of the last run read together (held / all experts where the
  routing is even).

Under ``with_data_parallel`` the values are the whole batch's; under
the collective (shard_map) runner they are the first device's share.
"""

import numpy as np

from . import monitor


def record(values):
    """``values``: load, dropped, load, dropped, ... one pair a layer,
    as fetched."""
    worst = 0.0
    for load, dropped in zip(values[0::2], values[1::2]):
        load = np.asarray(load, np.int64)
        monitor.add('moe/tokens_routed', float(load.sum()))
        monitor.add('moe/dropped_tokens',
                    float(np.asarray(dropped, np.int64).sum()))
        worst = max(worst, float(load.max()) / max(load.mean(), 1e-9))
    monitor.set_gauge('moe/load_max_over_mean', worst)


def record_held(values):
    """``values``: load [E], held load [count], ... one pair a layer
    that holds a range of its experts, as fetched."""
    routed = held = 0.0
    for load, mine in zip(values[0::2], values[1::2]):
        routed += float(np.asarray(load, np.int64).sum())
        held += float(np.asarray(mine, np.int64).sum())
    monitor.add('moe/rows_held', held)
    monitor.set_gauge('moe/held_share', held / max(routed, 1.0))
