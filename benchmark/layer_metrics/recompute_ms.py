"""Device time per step, chip 0, of the instructions a recompute
group's SECOND forward runs (``fluid.backward.recompute_guard`` ->
``jax.checkpoint``: the backward pass computes the group's forward
again from its inputs before it differentiates it): what the step pays
for not keeping the group's activations
(``benchmark/lib/pass_time.py``, over the program's
``fluid.profiler.pass_tables()``).  An op whose own backward rule runs
a chunk's forward again (``ssd_scan``, ``selective_scan``,
``kda_attention``) does that under its ``_grad`` scope: backward, not
here.  Its note is the step by pass and fluid op type: first forward,
second forward, backward, and the time that has no pass.  Nothing where
the program has no such table or its steps hold no group."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import pass_time
    got = pass_time.measured(trace, run)
    if got is None:
        return None
    run.setdefault('notes', {})['recompute_ms'] = \
        pass_time.table_note(trace, got)
    return trace.per_step_ms(got['by_pass']['recomputed'])
