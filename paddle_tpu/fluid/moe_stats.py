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
  routing is even);
- gauge ``moe/held_rows_max``: the most rows any such layer held on
  the last run read;
- gauge ``moe/walked_share``: the rows such layers' permutation
  walked on the last run read over the rows of their buffers: it
  walks a buffer in chunks (``parallel.moe.held_rows_chunk``) up to
  the one that holds the last held row, and the expert MLP's
  element-wise work between its grouped matmuls walks the same
  chunks, so this is what the permutation's time and that of
  ``moe_experts`` outside its matmuls follow.

A layer whose router carries a choice bias that its train program
moves (``score_bias`` with a ``bias_update_rate``) reports:

- gauge ``moe/score_bias_abs_max``: the largest ``|b|`` over the
  experts of all such layers, as the last run read left it;
- counter ``moe/bias_updates``: the biases the runs read have moved,
  one a layer and run read (a ``for_test`` clone moves none and
  watches nothing).

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


class HeldLayers(object):
    """The record of a program's layers that hold a range of their
    experts.  ``Program.watch`` hands a record the fetched values and
    nothing else, and a layer's buffer is as long as its ``top_k``
    says: each layer leaves it here as it asks to be watched."""

    def __init__(self):
        self.top_k = []

    @classmethod
    def of(cls, program):
        """The program's one, or a new one."""
        return next((r for r in program._watched if isinstance(r, cls)),
                    None) or cls()

    def __call__(self, values):
        record_held(values, self.top_k)


def record_held(values, top_k):
    """``values``: load [E], held load [count], ... one pair a layer
    that holds a range of its experts, as fetched; ``top_k``: one a
    layer."""
    from ..parallel.moe import held_rows_bound, held_rows_chunk
    routed = held = most = walked = buffers = 0
    for load, mine, k in zip(values[0::2], values[1::2], top_k):
        pairs = int(np.asarray(load, np.int64).sum())
        rows = int(np.asarray(mine, np.int64).sum())
        routed += pairs
        held += rows
        most = max(most, rows)
        n_rows = held_rows_bound(pairs // k, k, (0, len(mine)))
        chunk = held_rows_chunk(n_rows)
        walked += min(-(-rows // chunk) * chunk, n_rows)
        buffers += n_rows
    monitor.add('moe/rows_held', float(held))
    monitor.set_gauge('moe/held_share', held / max(routed, 1.0))
    monitor.set_gauge('moe/held_rows_max', float(most))
    monitor.set_gauge('moe/walked_share', walked / max(buffers, 1.0))


def record_bias(values):
    """``values``: one [E] choice bias a layer, as fetched from the
    train program (read after the run's update)."""
    monitor.add('moe/bias_updates', float(len(values)))
    monitor.set_gauge('moe/score_bias_abs_max', max(
        float(np.abs(np.asarray(b, np.float64)).max()) for b in values))
