"""Device time by the pass an instruction runs in: the trace names HLO
instructions, the program's own table (``paddle_tpu.fluid.profiler.
pass_tables``, from the same parse of the executables it holds as the
scope table) says which of them are the first forward pass, which the
second forward of a recompute group (``jax.checkpoint``'s
``rematted_computation``) and which the backward pass, and
``trace_reduce`` has already given every instant of chip 0 to the
innermost op running then.  The join is ``scope_time``'s (an
instruction is looked up in the table of the module run it fell in),
over a table that holds each instruction's (fluid scope, pass): this
file only sums, by pass and by fluid op type.

An optimizer's ops and what the scope table gives no fluid op belong
to no pass; collectives are left out, as in ``scope_time``: the four
columns sum to its non-collective total.

A program without the table (a parent of the PR that added it), or
whose steps hold no recompute group, gives ``None``: the readers then
leave their metric out.
"""

import collections

from benchmark.lib import scope_time

_KEY = 'pass_time'
PASSES = ('forward', 'recomputed', 'backward')


def measured(trace, run):
    """{'by_pass': {pass or None: ns}, 'by_type': {op type: {pass:
    ns}}} of this traced run's block, computed once and kept in
    ``run``; None where there is no trace, no table, or no instruction
    of a group's second forward is in the step's program."""
    if trace is None:
        return None
    if _KEY not in run:
        run[_KEY] = _measure(trace, run)
    return run[_KEY]


def holds_group():
    """Whether a program this process holds runs a recompute group's
    second forward; False too where the program has no pass table."""
    from paddle_tpu.fluid import profiler
    return hasattr(profiler, 'pass_tables') and any(
        'recomputed' in table.values()
        for tables in profiler.pass_tables().values() for table in tables)


def _measure(trace, run):
    from paddle_tpu.fluid import profiler
    # the scope table first, as scope_cost does: it takes set-up's
    # 'compile/*' totals before anything is lowered again
    if scope_time.measured(trace, run) is None or not holds_group():
        return None
    scopes, passes = profiler.scope_tables(), profiler.pass_tables()
    both = {module: [{name: (scope, by_pass.get(name))
                      for name, scope in by_scope.items()}
                     for by_scope, by_pass in zip(tables, passes[module])]
            for module, tables in scopes.items()}
    summed, _ = scope_time.reduce_by_scope(
        trace.first, scope_time._module_runs(run, profiler), both, profiler)
    by_pass = collections.Counter()
    by_type = collections.defaultdict(collections.Counter)
    for found, ns in summed.items():
        scope, phase = found or (None, None)
        by_pass[phase] += ns
        if phase is not None:
            by_type[scope_time.op_type(scope)][phase] += ns
    return {'by_pass': by_pass, 'by_type': by_type}


def table_note(trace, got, top=40):
    """The table a choice of what a group keeps starts from: a row a
    fluid op type, its forward and ``_grad`` scopes together, columns
    first forward / second forward / backward in ms a step, sorted by
    the second."""
    by_pass = got['by_pass']
    ms = trace.per_step_ms
    total = sum(by_pass.values())
    rows = sorted(got['by_type'].items(),
                  key=lambda kv: (-kv[1]['recomputed'], -sum(kv[1].values())))
    lines = ['device time by pass, chip 0, ms/step: forward %.3f + '
             'recomputed %.3f + backward %.3f + no pass (optimizer, '
             'unscoped) %.3f = %.3f non-collective; by fluid op type:'
             % (tuple(ms(by_pass[p]) for p in PASSES) +
                (ms(by_pass[None]), ms(total))),
             '  %-32s %10s %10s %10s' % (('',) + PASSES)]
    for kind, row in rows[:top]:
        lines.append('  %-32s %10.3f %10.3f %10.3f'
                     % ((kind,) + tuple(ms(row[p]) for p in PASSES)))
    if len(rows) > top:
        rest = [sum(row[p] for _, row in rows[top:]) for p in PASSES]
        lines.append('  %-32s %10.3f %10.3f %10.3f'
                     % (('(%d more)' % (len(rows) - top),) +
                        tuple(ms(ns) for ns in rest)))
    return '\n'.join(lines)
