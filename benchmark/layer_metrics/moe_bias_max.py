"""The largest absolute value of the routers' choice bias over the
experts of all layers, as the last run that fetched the loss left it:
the program's gauge ``moe/score_bias_abs_max``
(``paddle_tpu/fluid/moe_stats.py``), set by layers whose router adds a
bias to its sigmoid scores for the choice of the experts and moves it
after every train step by gamma towards an even load.  It says how far
the balancing has pushed the choice from the plain top-k (the startup
values are drawn at a stated size; each step moves an expert's by
gamma), and with ``moe_load_max`` whether it is winning.  Beside it
the counter ``moe/bias_updates``.  Nothing where the program has no
such gauge.  Declared "lower is better" only because a metric must
say; it is a reading, not a goal."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('moe/score_bias_abs_max', None)
    if value is None:
        return None
    run.setdefault('notes', {})['moe_bias_max'] = (
        'moe/bias_updates %d on the runs read'
        % monitor.flat().get('moe/bias_updates', 0))
    return float(value)
