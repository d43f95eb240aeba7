"""The remote stream's share of the (query, key) pairs EVA attention
scores, a head and step: the program's static gauges
``eva/remote_pairs / (eva/local_pairs + eva/remote_pairs)``, set as a
coarse-mask attention call is lowered (``ops/fused_ops.py``).  It is
what the sequence length does to the mechanism: 0 at one window,
0.0588 at two (this cell), 0.48 at the published 32768 positions; the
remote calls' time and ``mfu``'s FLOPs move with it.  A reading with
no better side: the manifest wants one, and ``higher`` (more of the
work is the new mechanism's) is declared as ``moe_held_share`` was.
Beside it, as a note, ``eva/chunks`` and the gauge
``eva/remote_weight_mean``: the summaries' mean share of the softmax
over the queries that have any, on the last run that fetched.  Nothing
where the program has no such gauge."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    remote = monitor.gauge_value('eva/remote_pairs', None)
    local = monitor.gauge_value('eva/local_pairs', None)
    if remote is None or local is None or not remote + local:
        return None
    run.setdefault('notes', {})['eva_remote_share'] = (
        'eva/local_pairs %d, eva/remote_pairs %d, eva/chunks %d a head '
        'and step; eva/remote_weight_mean %s on the last run that '
        'fetched'
        % (local, remote, monitor.gauge_value('eva/chunks', 0),
           monitor.gauge_value('eva/remote_weight_mean', None)))
    return float(remote) / float(remote + local)
