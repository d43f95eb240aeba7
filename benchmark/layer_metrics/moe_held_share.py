"""Rows held over rows routed, the routed layers of the last run that
fetched the loss together: the program's gauge ``moe/held_share``
(``paddle_tpu/fluid/moe_stats.py``), set by layers that hold a range of
their experts (``experts_held``).  It is the share of the (token,
expert) pairs whose expert lives on this chip, i.e. of the sorted
buffer that the grouped matmuls compute: held / all experts where the
routing is even (8 / 256 = 0.031), and the grouped matmuls' time and
``mfu``'s expected FLOPs move with it.  Beside it the counters
``moe/rows_held``, ``moe/tokens_routed`` and ``moe/dropped_tokens`` (a
pair routed to an absent expert is no drop: 0).  Nothing where the
program has no such gauge."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('moe/held_share', None)
    if value is None:
        return None
    flat = monitor.flat()
    run.setdefault('notes', {})['moe_held_share'] = (
        'moe/rows_held %d of moe/tokens_routed %d on the runs read; '
        'moe/dropped_tokens %d'
        % (flat.get('moe/rows_held', 0), flat.get('moe/tokens_routed', 0),
           flat.get('moe/dropped_tokens', 0)))
    return float(value)
