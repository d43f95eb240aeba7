"""[block_q, block_k] score tiles the block-mask flash calls walk in
one train step, forward and backward: the program's gauge
``sdar/tiles_visited`` (``paddle_tpu/ops/pallas/flash_attention.py``
``_count_tiles``), a sum over ONE traced program of the tiles that
hold a visible pair, a kernel instance a head, as each call is
lowered: a forward call's at its blocks, a one-pass backward's once
and a two-pass backward's twice at theirs, a recompute group's second
forward with them.  A kernel that leaves out more of the mask's empty
tiles, other blocks, or both query copies stacked over one pass of the
keys show here before they show in time.  Beside it, as a note,
``sdar/visible_pairs`` (the pairs the mask lets through, a head) and
``sdar/masked_share``.  Nothing where the program has no such gauge or
holds no such call."""

LAYER = 'kernels'
UNIT = 'count/step'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('sdar/tiles_visited', None)
    if not value:
        return None
    run.setdefault('notes', {})['bd_tiles_visited'] = (
        'sdar/visible_pairs %d a head and step; sdar/masked_share %s on '
        'the last run that fetched'
        % (monitor.gauge_value('sdar/visible_pairs', 0),
           monitor.gauge_value('sdar/masked_share', None)))
    return float(value)
