"""Device time inside the bodies of the program's differentiable loops,
forward and backward: the trace names HLO instructions, the program's
own table (``paddle_tpu.fluid.profiler.loop_tables``, from the same
parse of the executables it holds as the scope table) says which of
them lie in the body of a fluid ``while`` as the forward pass runs it
and which in the body of its transpose, and ``trace_reduce`` has
already given every instant of chip 0 to the innermost op running
then.  The join is ``scope_time``'s (an instruction is looked up in
the table of the module run it fell in); this file only sums.

A program without the table (a parent of the PR that added it), or
whose steps hold no such loop, gives ``None``: the readers then leave
their metric out.
"""

from benchmark.lib import scope_time

_KEY = 'loop_time'


def measured(trace, run):
    """{'forward': ns, 'backward': ns} of this traced run's block,
    computed once and kept in ``run``; None where there is no trace, no
    table, or no instruction of a loop's body ran."""
    if trace is None:
        return None
    if _KEY not in run:
        run[_KEY] = _measure(trace, run)
    return run[_KEY]


def _measure(trace, run):
    from paddle_tpu.fluid import profiler
    if not hasattr(profiler, 'loop_tables'):
        return None
    # the scope table first, as scope_cost does: it takes set-up's
    # 'compile/*' totals before anything is lowered again
    scope_time.measured(trace, run)
    by_side, _ = scope_time.reduce_by_scope(
        trace.first, scope_time._module_runs(run, profiler),
        profiler.loop_tables(), profiler)
    got = {side: by_side.get(side, 0) for side in ('forward', 'backward')}
    return got if sum(got.values()) else None
