"""Of the train step's temporaries where their sum is largest
(``temp_peak`` of ``fluid.memviz``'s row, as ``hbm_residual_gb`` reads
it), the bytes a recompute group's second forward defined: what the
group did NOT keep and holds again while its backward runs.  Beside
``hbm_residual_gb`` (what the groups DID keep: their inputs) it is the
memory side of the trade ``recompute_ms`` is the time side of.  0.0
where the step holds a group and nothing recomputed is alive at the
peak; nothing where the program has no pass table or its step holds no
group.  ``better: lower`` only because a metric has to say."""

LAYER = 'executor'
UNIT = 'GB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split, pass_time
    got = memory_split.split(run)
    if got is None or 'temp_peak' not in got['step'] or \
            not pass_time.holds_group():
        return None
    return got['step']['temp_peak']['by_class'].get('recomputed', 0.0) / 1e9
