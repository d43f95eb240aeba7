"""Device time by the fluid op it was lowered from: the trace names HLO
instructions, the program's own table (``paddle_tpu.fluid.profiler.
scope_tables``, built from the executables it holds) says which fluid
op each belongs to, and ``trace_reduce`` has already given every
instant of chip 0 to the innermost op running then.

Two programs run in the traced block, the quiet step and the one that
fetches the loss, and they share instruction names (both hold a
``fusion.933``).  So every piece is looked up under the module run of
the trace's ``XLA Modules`` line it falls in: the program does that
(``profiler.module_runs`` / ``program_at`` / ``instruction_scopes``,
the same code its own ``stop_trace`` table comes from), this file only
sums.  ``Reduced`` does not keep that line, so the block's trace is
read once more, from where ``run.py``'s ``trace_block`` left it.  On a
TPU a trace without the line is an error; off it (the CPU rehearsal,
whose trace has no such line) the pieces are one group and every table
of the process is a candidate.

Collectives are left out: they have a metric of their own.  What the
table gives no fluid op is kept under ``None`` and reported as
``unscoped_ms`` with the instructions that hold most of it.

A program without the table (a parent of the PR that added it) gives
``None`` everywhere: the readers then leave their metric out.
"""

import collections
import os
import time

from benchmark.lib import setup_totals, trace_reduce

_KEY = 'scope_time'


def reduce_by_scope(timeline, runs, tables, profiler):
    """-> ({scope or None: ns}, {instruction: ns} of the unscoped) from
    one chip's innermost segments and its module runs ([] for none)."""
    pieces = [(b - a, profiler.program_at(runs, a), op.name)
              for a, b, op in timeline.segments
              if op.kind != trace_reduce.COLLECTIVE]
    scopes = profiler.instruction_scopes(
        [(program, name) for _, program, name in pieces], tables)
    by_scope = collections.Counter()
    unscoped = collections.Counter()
    for (ns, _, name), scope in zip(pieces, scopes):
        by_scope[scope] += ns
        if scope is None:
            unscoped[name] += ns
    return by_scope, unscoped


def _module_runs(run, profiler):
    """The first chip's module runs in the traced block's profile."""
    import jax
    if jax.default_backend() != 'tpu':
        return []       # the rehearsal: no such line off the chip
    from benchmark import run as harness    # where trace_block wrote it
    planes = trace_reduce.device_planes(trace_reduce.load(
        trace_reduce.newest_xplane(os.path.join(
            harness.OUT_DIR, 'trace', run['cell'].name))))
    runs = profiler.module_runs(planes[min(planes)])
    if not runs:
        raise RuntimeError(
            "the trace's first chip has no 'XLA Modules' line: ops "
            'cannot be told apart by program')
    return runs


def measured(trace, run):
    """{'by_scope', 'unscoped', 'table_seconds'} of this traced run,
    computed once and kept in ``run``; None where there is no trace or
    the program has no scope table."""
    if trace is None:
        return None
    if _KEY not in run:
        run[_KEY] = _measure(trace, run)
    return run[_KEY]


def _measure(trace, run):
    from paddle_tpu.fluid import profiler
    if not hasattr(profiler, 'scope_tables'):
        return None
    # building the table lowers and compiles (from jit's and JAX's
    # caches) what the runners jitted lazily: set-up's 'compile/*'
    # totals are taken before that, whatever order the readers run in
    setup_totals.totals(run)
    t0 = time.perf_counter()
    tables = profiler.scope_tables()
    by_scope, unscoped = reduce_by_scope(
        trace.first, _module_runs(run, profiler), tables, profiler)
    return {'by_scope': by_scope, 'unscoped': unscoped,
            'table_seconds': time.perf_counter() - t0,
            'modules': {m: len(ts) for m, ts in tables.items()}}


def op_type(scope):
    """'fused_adam/pack' -> 'fused_adam'; 'mul_grad' -> 'mul': the type
    whose forward or backward the scope is."""
    kind = scope.split('/', 1)[0]
    return kind[:-5] if kind.endswith('_grad') else kind


def per_step_ms(trace, run, belongs):
    """Innermost device ms per step of the scopes whose op type
    ``belongs`` (a predicate) accepts; None where nothing was
    measured."""
    got = measured(trace, run)
    if got is None:
        return None
    return trace.per_step_ms(sum(
        ns for scope, ns in got['by_scope'].items()
        if scope is not None and belongs(op_type(scope))))


def table_note(trace, got, top=40):
    """The whole by-scope table as lines: scope, ms per step, share of
    the non-collective device time."""
    total = sum(got['by_scope'].values()) or 1
    rows = sorted(got['by_scope'].items(), key=lambda kv: -kv[1])
    lines = ['device time by fluid op, chip 0, ms/step (share of %.3f '
             'ms non-collective; table built in %.2f s from %s)'
             % (trace.per_step_ms(total), got['table_seconds'],
                ', '.join('%s x%d' % kv
                          for kv in sorted(got['modules'].items())))]
    for scope, ns in rows[:top]:
        lines.append('  %-44s %9.3f %6.2f%%' % (
            scope or '(unscoped)', trace.per_step_ms(ns),
            100.0 * ns / total))
    if len(rows) > top:
        lines.append('  %-44s %9.3f' % (
            '(%d more)' % (len(rows) - top),
            trace.per_step_ms(sum(ns for _, ns in rows[top:]))))
    held = got['unscoped'].most_common(8)
    if held:
        lines.append('  unscoped holds: ' + ', '.join(
            '%s %.3f' % (name, trace.per_step_ms(ns))
            for name, ns in held))
    return '\n'.join(lines)
