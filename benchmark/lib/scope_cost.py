"""Device time against the chip's peaks: the trace says how long each
HLO instruction ran, the program's own cost table
(``paddle_tpu.fluid.profiler.cost_tables``, from the same parse of the
executables it holds as the scope table) says how many FLOPs and bytes
one execution of it is, by one documented rule (``fluid/profiler.py``),
and ``peaks.py`` what the chip could do.  No hand count per family.

Chip 0's executed op events of the traced block are joined to
``(program, instruction)`` the way ``scope_time.reduce_by_scope`` joins
them to scopes (``profiler.program_at`` / ``instruction_scopes`` /
``instruction_costs``: the same walk picks the same program's table for
both), executions counted, innermost time summed.  The roofline seconds
of one execution are ``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)``
(``flops.roofline_seconds``); a share is the sum of those over the
executions over the sum of their innermost time.  Every share is
against the bf16 peak, whatever the product's type: the notes give the
type beside it.

A program without the cost table (a parent of the PR that added it)
gives ``None`` everywhere: the readers then leave their metric out.
"""

import collections
import time

from benchmark.lib import flops, peaks, scope_time

_KEY = 'scope_cost'
# the TPU compiler writes every dot as a convolution
MATMUL_KINDS = ('dot', 'convolution')
# chip-to-chip interconnect of one v5e chip, Google Cloud documentation,
# "TPU v5e": 1,600 Gbit/s
LINK_GBPS = 1600 / 8.0

Row = collections.namedtuple('Row', 'program name scope cost calls ns')


def join(timeline, runs, scopes, costs, profiler):
    """[Row] of one chip's executed instructions: the fluid scope and
    the Cost the program's tables give each, how often it ran and its
    innermost ns, from the chip's op events and its module runs ([] for
    none)."""
    calls, ns = collections.Counter(), collections.Counter()
    for op in timeline.ops:
        calls[(profiler.program_at(runs, op.start), op.name)] += 1
    for a, b, op in timeline.segments:
        ns[(profiler.program_at(runs, a), op.name)] += b - a
    keys = sorted(calls)
    return [Row(program, name, scope, cost, calls[(program, name)],
                ns[(program, name)])
            for (program, name), scope, cost in zip(
                keys, profiler.instruction_scopes(keys, scopes),
                profiler.instruction_costs(keys, costs))]


def measured(trace, run):
    """{'rows', 'table_seconds', 'scope_table_seconds'} of this traced
    run, computed once and kept in ``run``; None where there is no trace
    or the program has no cost table."""
    if trace is None:
        return None
    if _KEY not in run:
        run[_KEY] = _measure(trace, run)
    return run[_KEY]


def _measure(trace, run):
    from paddle_tpu.fluid import profiler
    if not hasattr(profiler, 'cost_tables'):
        return None
    # the scope table first: it takes set-up's 'compile/*' totals before
    # anything is lowered again, and its seconds are what a parent's
    # table costs; what this file adds to them is timed apart
    scoped = scope_time.measured(trace, run)
    t0 = time.perf_counter()
    rows = join(trace.first, scope_time._module_runs(run, profiler),
                profiler.scope_tables(), profiler.cost_tables(), profiler)
    return {'rows': rows, 'table_seconds': time.perf_counter() - t0,
            'scope_table_seconds': scoped['table_seconds']}


def least_seconds(row, peak):
    """(roofline seconds of all of a row's executions, the side that
    bounds one), or None where its FLOPs are not known."""
    if row.cost is None or row.cost.flops is None:
        return None
    least, side = flops.roofline_seconds(row.cost.flops, row.cost.bytes,
                                         *peak)
    return row.calls * least, side


def _is_collective(row):
    return row.cost is not None and row.cost.kind == 'collective'


def holding(rows, kinds, op_types):
    """The rows whose instruction holds one of ``kinds`` with known
    FLOPs under a scope of one of ``op_types``."""
    return [r for r in rows
            if r.scope and scope_time.op_type(r.scope) in op_types and
            r.cost is not None and r.cost.kind in kinds and
            r.cost.flops is not None]


def roofline_share(trace, run, name, kinds, op_types):
    """Percent: roofline seconds over innermost traced time of the
    executed instructions that hold one of ``kinds`` under a scope of
    ``op_types``; files the whole cost table as the note ``name``.
    None where nothing was measured or no such instruction ran."""
    got = measured(trace, run)
    if got is None:
        return None
    peak = peaks.chip_peak(run['device_kind'])
    chosen = holding(got['rows'], kinds, op_types)
    ns = sum(r.ns for r in chosen)
    if not ns:
        return None
    scoped_ns = sum(r.ns for r in got['rows'] if r.scope and
                    scope_time.op_type(r.scope) in op_types and
                    not _is_collective(r))      # as scope_time's sum
    run.setdefault('notes', {})[name] = '\n'.join(
        [table_note(trace, got, peak),
         '  %s reads %.3f ms/step in instructions that hold a %s; the '
         'scopes of %s hold %.3f ms/step more in instructions that '
         'hold none' % (name, trace.per_step_ms(ns), ' / '.join(kinds),
                        ', '.join(sorted(op_types)),
                        trace.per_step_ms(scoped_ns - ns)),
         longest_note(trace, got, peak)])
    return 100.0 * sum(least_seconds(r, peak)[0] for r in chosen) / \
        (ns / 1e9)


def _rates(trace, ns, nflops, nbytes, least, side):
    """The columns after a row's name: ms/step, GFLOP and MB a step,
    TFLOP/s, GB/s, share of roofline, bounding side.  '-': not known.
    A row that holds a custom call has its boundary's bytes and no
    rate: what the call reads of its operands no HLO text says (the
    chip's grouped matmul skips the rows past its last group)."""
    seconds = ns / 1e9 or float('nan')
    known = nflops is not None
    return '%9.3f %10s %10.1f %8s %8s %7s %s' % (
        trace.per_step_ms(ns),
        '%.2f' % (nflops / 1e9 / trace.steps) if known else '-',
        nbytes / 1e6 / trace.steps,
        '%.1f' % (nflops / 1e12 / seconds) if known else '-',
        '%.1f' % (nbytes / 1e9 / seconds) if known else '-',
        '%.1f%%' % (100.0 * least / seconds) if known else '-',
        side if known else '-')


def table_note(trace, got, peak, top=40):
    """The whole by-scope cost table as lines.  Collectives are left
    out, as in ``scope_time``'s table: they have metrics of their own."""
    by_scope = {}
    for r in got['rows']:
        if r.cost is None or _is_collective(r):
            continue
        # ns, FLOPs (None once a custom call is held), bytes, least s
        acc = by_scope.setdefault(r.scope, [0, 0, 0, 0.0])
        acc[0] += r.ns
        acc[2] += r.calls * r.cost.bytes
        if r.cost.flops is None or acc[1] is None:
            acc[1] = None
        else:
            acc[1] += r.calls * r.cost.flops
            acc[3] += least_seconds(r, peak)[0]
    lines = ['cost by fluid op, chip 0, per step, against %.0f TFLOP/s '
             'bf16 and %.0f GB/s (cost table joined in %.2f s after the '
             "scope table's %.2f s)"
             % (peak[0] / 1e12, peak[1] / 1e9, got['table_seconds'],
                got['scope_table_seconds']),
             '  %-44s %9s %10s %10s %8s %8s %7s %s'
             % ('scope', 'ms', 'GFLOP', 'MB', 'TFLOP/s', 'GB/s',
                'of roof', 'bound by')]
    ranked = sorted(by_scope.items(), key=lambda kv: -kv[1][0])
    for scope, (ns, nflops, nbytes, least) in ranked[:top]:
        lines.append('  %-44s %s' % (
            scope or '(unscoped)',
            _rates(trace, ns, nflops, nbytes, least,
                   flops.roofline_seconds(nflops or 0, nbytes, *peak)[1])))
    if len(ranked) > top:
        lines.append('  (%d more scopes, %.3f ms)' % (
            len(ranked) - top,
            trace.per_step_ms(sum(v[0] for _, v in ranked[top:]))))
    lines.append('  %-44s %9.3f  (control flow, the waits of asynchronous '
                 'copies, what no table knows)' % (
                     'instructions with no cost', trace.per_step_ms(sum(
                         r.ns for r in got['rows'] if r.cost is None))))
    return '\n'.join(lines)


def longest_note(trace, got, peak, top=10):
    """The longest instructions by innermost time, each with what it
    holds (a trace's ``fusion.933`` says nothing), per EXECUTION: the
    quiet step and the one that fetches are two programs with a row
    each."""
    lines = ['  the %d longest instructions, per execution (executions '
             'a step; ms; GFLOP; MB; TFLOP/s; GB/s; share of roofline; '
             'bound by):' % top]
    costed = [r for r in got['rows']
              if r.cost is not None and not _is_collective(r)]
    for r in sorted(costed, key=lambda r: -r.ns)[:top]:
        seconds = r.ns / 1e9 / r.calls or float('nan')
        known = r.cost.flops is not None
        least, side = flops.roofline_seconds(
            r.cost.flops, r.cost.bytes, *peak) if known else (0.0, '-')
        lines.append('  %-28s %5.2f %8.3f %9s %9.1f %7s %7s %7s %-7s  '
                     '%s, %s %s %s' % (
                         r.name, r.calls / trace.steps, seconds * 1e3,
                         '%.2f' % (r.cost.flops / 1e9) if known else '-',
                         r.cost.bytes / 1e6,
                         '%.1f' % (r.cost.flops / 1e12 / seconds)
                         if known else '-',
                         '%.1f' % (r.cost.bytes / 1e9 / seconds)
                         if known else '-',
                         '%.1f%%' % (100.0 * least / seconds)
                         if known else '-', side,
                         r.scope or '(unscoped)', r.cost.kind,
                         r.cost.dtype, r.cost.shapes[:160]))
    return '\n'.join(lines)


def collectives(trace, run):
    """The rows of chip 0's executed collectives (a ``-done`` costs
    nothing and is no call); None where nothing was measured."""
    got = measured(trace, run)
    if got is None:
        return None
    return [r for r in got['rows'] if _is_collective(r)]


def collectives_note(trace, rows):
    """Each collective's bytes, its time from start to end and the rate
    beside the link's."""
    spans = collections.defaultdict(list)
    for op in trace.first.async_collectives:    # from -start to -done
        spans[op.name].append(op.end - op.start)
    lines = ['collectives of chip 0, per execution: bytes it hands over, '
             'time from start to end, rate (a chip\'s links: %.0f GB/s, '
             'the published 1,600 Gbit/s)' % LINK_GBPS]
    for r in sorted(rows, key=lambda r: -r.cost.bytes):
        # beside the op line where it runs there, else its own event
        # (two programs can hold one name: the row's time is its own)
        times = spans.get(r.name)
        ns = sum(times) / len(times) if times else r.ns / r.calls
        lines.append('  %-24s %9.2f MB over %s chips, %.1f a step, '
                     '%8.3f ms, %6.1f GB/s; %s: %s' % (
                         r.name, r.cost.bytes / 1e6, r.cost.group or '?',
                         r.calls / trace.steps, ns / 1e6,
                         r.cost.bytes / (ns or float('nan')),
                         r.cost.dtype, r.cost.shapes[:120]))
    return '\n'.join(lines)
