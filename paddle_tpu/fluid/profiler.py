"""Profiler: per-op time summary + XLA trace capture.

Reference: python/paddle/fluid/profiler.py:129 (profiler context
manager) over platform/profiler.h:166-175 EnableProfiler/
DisableProfiler, which print a per-op time table sorted by
`sorted_key` in {'calls','total','max','min','ave'}.

TPU-native split, mirroring the reference's two profilers:

- tracer_option='Serial': while profiling is enabled the executor
  compiles each device op as its OWN one-op segment and host-times it
  to completion (block_until_ready).  That is the reference's
  host-side RecordEvent semantics — per-op serialization is the
  documented price of op-granular timing there too (the CUDA profiler
  also serializes streams per event).  NOTE the measured program is a
  different (unfused) compilation of the same ops.
- tracer_option='Default' (round 5): the PRODUCTION program runs
  untouched under a jax.profiler device-trace capture; on exit the
  trace's per-kernel events are attributed back to fluid op types
  through the named_scope metadata every lowering runs under
  (executor._lower_ops -> XLA op_metadata of the compiled HLO) and
  summed into the same sorted table.  This is the reference's
  DeviceTracer leg (platform/device_tracer.h: CUPTI kernels correlated
  back to op RecordEvents) — per-op attribution of the REAL fused run.
  The trace is the ``.xplane.pb`` jax.profiler writes; its op events
  name HLO instructions only, so the fluid op of each comes from the
  executables this process holds (``hlo_scopes`` / ``scope_tables``).

stop_profiler prints the sorted table; summary_records() /
summary_string() expose it programmatically.  start_trace()/
stop_trace() capture without printing: the same table, plus the
events for tools/timeline.py's merged Perfetto file.
"""

import bisect
import collections
import contextlib
import os
import re

import jax

_SORT_KEYS = ('calls', 'total', 'max', 'min', 'ave')

_enabled = False
_mode = 'Serial'         # 'Serial' | 'Default' (trace-derived)
_records = {}  # op type -> [calls, total, max, min]
_folded = False          # records already added to fluid.monitor
_trace_path = None
_prof_trace_dir = None   # capture dir while a 'Default' profile runs


def is_enabled():
    """True when the executor must split per-op ('Serial' mode only:
    the trace-derived mode measures the production program)."""
    return _enabled and _mode == 'Serial'


def record_op(op_type, seconds):
    """Executor hook: account one timed execution of `op_type`."""
    rec = _records.get(op_type)
    if rec is None:
        _records[op_type] = [1, seconds, seconds, seconds]
    else:
        rec[0] += 1
        rec[1] += seconds
        rec[2] = max(rec[2], seconds)
        rec[3] = min(rec[3], seconds)


def reset_profiler():
    """Drop all accumulated per-op records (reference
    platform::ResetProfiler)."""
    global _folded
    _records.clear()
    _folded = False


def summary_records():
    """{op_type: {'calls', 'total', 'max', 'min', 'ave'}} (seconds)."""
    return {t: {'calls': c, 'total': tot, 'max': mx, 'min': mn,
                'ave': tot / c}
            for t, (c, tot, mx, mn) in _records.items()}


def summary_string(sorted_key='total'):
    """The reference's profiler table (profiler.h:166 prints Event
    rows sorted by sorted_key)."""
    if sorted_key not in (None,) + _SORT_KEYS:
        raise ValueError('sorted_key must be one of %s, got %r'
                         % (_SORT_KEYS, sorted_key))
    key = sorted_key or 'total'
    rows = sorted(summary_records().items(),
                  key=lambda kv: kv[1][key], reverse=True)
    lines = ['%-28s %8s %12s %12s %12s %12s'
             % ('Event', 'Calls', 'Total(ms)', 'Min(ms)', 'Max(ms)',
                'Ave(ms)')]
    for t, r in rows:
        lines.append('%-28s %8d %12.4f %12.4f %12.4f %12.4f'
                     % (t, r['calls'], r['total'] * 1e3,
                        r['min'] * 1e3, r['max'] * 1e3,
                        r['ave'] * 1e3))
    return '\n'.join(lines)


def _registered_op_types():
    from ..ops import registry
    return set(registry._REGISTRY)


def _resolve_component(comp, op_types, per_instance):
    """One scope-path component -> attribution name or None.  Strips
    transform wrappers (transpose(jvp(relu))) and, in per-instance
    mode, resolves '<type>#<idx>' instance suffixes (the FLAGS_opprof
    scope names) to the full instance name."""
    base = comp
    while '(' in base and base.endswith(')'):
        base = base[base.index('(') + 1:-1]
    for cand in (comp, base):
        if _is_op_type(cand, op_types):
            return cand
        if per_instance and '#' in cand:
            typ = cand.rsplit('#', 1)[0]
            if typ in op_types:
                return cand
    return None


def attribute_trace_events(events, op_types=None, per_instance=False,
                           with_stats=False):
    """Map device-trace kernel events back to fluid op types.

    `events` are chrome-trace events (``load_trace_events``, or a
    trace.json's 'traceEvents').  Each kernel event carries
    args['tf_op'] — a fluid scope from ``scope_tables()``, or a raw
    XLA op_metadata op_name, i.e. the jax.named_scope path the
    executor wrapped the lowering in ('jit_segment_x/relu/max' or,
    under whole-program autodiff,
    'jit_.../transpose(jvp(...))/relu/...').  Attribution:
    the first path component that names a registered op type; kernels
    with no such component (copies, infeed, grad-only glue) land under
    'unattributed/<hlo name>'.  Returns {name: [calls, total_s, max_s,
    min_s]}.

    `per_instance=True` (the fluid.opprof mode) resolves the
    '<type>#<block-index>' instance scopes FLAGS_opprof emits, and
    splits FUSED kernel time across constituent ops: a fusion event
    whose tf_op carries multiple ';'/','-separated source paths has
    its duration divided equally among them, with the shares of
    unresolvable constituents filed under the honest
    'unattributed/<hlo name>' bucket rather than inflating the ops
    that did match.

    Tolerant by contract: real captures contain malformed rows (counter
    events without dur, instant events, non-string tf_op metadata,
    null fields) — those are skipped or zero-timed, never raised on,
    so one odd event cannot lose a whole profile.  `with_stats=True`
    returns (recs, {'events', 'attributed', 'dropped'}) so skipped
    rows are COUNTED, not silently eaten.

    Both positive and negative lookups are cached per tf_op string
    (a capture repeats each unattributed scope on every step; without
    the negative cache every repeat re-splits the path)."""
    op_types = op_types or _registered_op_types()
    recs = {}
    cache = {}   # tf_op -> tuple(resolved names) | () for negative
    n_events = n_attr = dropped = 0

    def _fold(name, sec, calls=1):
        rec = recs.get(name)
        if rec is None:
            recs[name] = [calls, sec, sec, sec]
        else:
            rec[0] += calls
            rec[1] += sec
            rec[2] = max(rec[2], sec)
            rec[3] = min(rec[3], sec)

    for e in events:
        if not isinstance(e, dict):
            dropped += 1
            continue
        if e.get('ph') != 'X':
            continue   # counter/instant/metadata rows are filtered by
        n_events += 1  # design, not malformed
        args = e.get('args') or {}
        tf_op = args.get('tf_op') if isinstance(args, dict) else None
        if not tf_op or not isinstance(tf_op, str):
            dropped += 1
            continue
        try:
            # an event other events nest in counts its own part only
            sec = float(e.get('self_dur', e.get('dur')) or 0) * 1e-6
        except (TypeError, ValueError):
            sec = 0.0
        hit = cache.get(tf_op)
        if hit is None:
            if per_instance:
                # fusion events carry multiple source paths; each path
                # resolves (or not) independently
                paths = [p for p in re.split('[;,]', tf_op) if p]
            else:
                paths = [tf_op]
            resolved = []
            for p in paths:
                name = None
                for comp in p.split('/'):
                    name = _resolve_component(comp, op_types,
                                              per_instance)
                    if name is not None:
                        break
                resolved.append(name)
            hit = tuple(resolved)
            cache[tf_op] = hit   # negative ((None,)*n) cached too
        matched = [n for n in hit if n is not None]
        if not matched:
            # per-HLO-name bucket: distinct kernels share a scope
            # path, so the bucket keys on the event name instead
            _fold('unattributed/' +
                  str(e.get('name', '?')).split('.')[0], sec)
            continue
        n_attr += 1
        share = sec / len(hit)
        leftover = share * (len(hit) - len(matched))
        for name in matched:
            _fold(name, share)
        if leftover > 0:
            _fold('unattributed/' +
                  str(e.get('name', '?')).split('.')[0], leftover)
    if with_stats:
        return recs, {'events': n_events, 'attributed': n_attr,
                      'dropped': dropped}
    return recs


# ------------------------------------------------ HLO instruction scopes
# A device trace names HLO instructions (``fusion.933``), and XLA renames
# them whenever a lowering changes.  The executor lowers every fluid op
# inside ``jax.named_scope(op.type)``, so each instruction of the
# OPTIMISED HLO still says where it came from, in the ``op_name`` of its
# metadata: ``jit(segment_x)/mul/dot_general``, or
# ``jit(segment_wpg_x)/transpose(jvp(mul))/dot_general`` for backward
# code jax derived inside the scope.  The rule, written once:
#
# - an instruction counts to the first component of its ``op_name``,
#   the primitive's own name at the end left out, that is a registered
#   fluid op type; jax's transform wrappers are looked through, and a
#   ``transpose`` among them makes it that type's backward
#   (``mul_grad``, the name the explicit grad op lowers under);
# - a plain named scope the lowering itself opened right under the op's
#   is kept as ``<type>/<scope>``;
# - a fusion counts to the ``dot`` / ``convolution`` / custom call it
#   holds, else to its root; a root that carries no scope (the tuple of
#   a multi-output fusion, a bitcast or copy XLA put there) stands for
#   the nearest of its operands inside the fusion that does, and only
#   if none does, the fusion's own ``op_name`` decides;
# - an instruction the TPU compiler expands and renames itself keeps no
#   ``op_name`` of the program's (a grouped matmul comes out as Mosaic
#   calls named ``ragged-dot-none``): it counts to the op whose
#   registration declares that name (``ops.registry.COMPILER_NAMED``);
#   forward and backward cannot be told apart there;
# - an instruction with no fluid scope counts to none.
_TRANSFORMS = re.compile(
    r'^(jvp|transpose|vmap|checkpoint|remat|custom_jvp|custom_vjp)'
    r'\((.*)\)$')
_HLO_MODULE = re.compile(r'^HloModule\s+([^\s,]+)')
_HLO_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$')
_HLO_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?(\S+)\s+=\s+')
_HLO_OPERAND = re.compile(r'%([^\s,()]+)')
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r'\bcalls=%?([^\s,}]+)')
_HELD_BY_FUSION = ('dot', 'convolution', 'custom-call')

_Instruction = collections.namedtuple(
    '_Instruction', 'name opcode operands op_name calls root')


def _is_op_type(name, op_types):
    """Registered, or the generic gradient of a registered type (grad
    ops have no registry entry of their own)."""
    return name in op_types or (name.endswith('_grad') and
                                name[:-5] in op_types)


def fluid_scope(op_name, op_types=None):
    """The fluid op an HLO instruction was lowered from, by the rule
    above, from the ``op_name`` of its metadata: ``'mul'``,
    ``'mul_grad'``, ``'<type>/<scope>'``, or None."""
    if not op_name:
        return None
    op_types = op_types or _registered_op_types()
    parts = op_name.split('/')
    for i, comp in enumerate(parts[:-1]):
        backward = False
        m = _TRANSFORMS.match(comp)
        while m:
            backward = backward or m.group(1) == 'transpose'
            comp = m.group(2)
            m = _TRANSFORMS.match(comp)
        comp = comp.split('#', 1)[0]    # FLAGS_opprof's instance suffix
        if not _is_op_type(comp, op_types):
            continue
        if backward and not comp.endswith('_grad'):
            comp += '_grad'
        inner = parts[i + 1] if i + 2 < len(parts) else ''
        return comp + '/' + inner if inner and '(' not in inner else comp
    from ..ops import registry
    for prefix, op_type in registry.COMPILER_NAMED.items():
        if op_name.startswith(prefix):
            return op_type
    return None


def _closing(text, start):
    """Index just past the parenthesis that closes the one at
    ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == '(') - (text[i] == ')')
        if depth == 0:
            return i + 1
    return len(text)


def _parse_hlo(text):
    """An HLO module's text -> (module name, {computation name:
    [_Instruction]}).  ``operands`` stays the text between the
    opcode's parentheses: only a fusion's root needs it split."""
    module, computations, body = '', {}, None
    for line in text.splitlines():
        if body is not None:
            m = _HLO_INSTRUCTION.match(line)
            if m:
                i = m.end()
                # the shape: one word, or a tuple that holds spaces
                i = _closing(line, i) if line[i] == '(' else \
                    line.find(' ', i)
                paren = line.find('(', i)
                if paren < 0:
                    continue
                end = _closing(line, paren)
                op_name = _HLO_OP_NAME.search(line, end)
                calls = _HLO_CALLS.search(line, end)
                body.append(_Instruction(
                    m.group(2), line[i:paren].strip(),
                    line[paren + 1:end - 1],
                    op_name.group(1) if op_name else '',
                    calls.group(1) if calls else None,
                    bool(m.group(1))))
            elif line.startswith('}'):
                body = None
            continue
        m = _HLO_COMPUTATION.match(line)
        if m:
            body = computations[m.group(1)] = []
        elif not module:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
    return module, computations


def _fusion_scope(fusion, body, op_types):
    scopes = {ins.name: fluid_scope(ins.op_name, op_types)
              for ins in body}
    for ins in body:
        if ins.opcode in _HELD_BY_FUSION and scopes[ins.name]:
            return scopes[ins.name]
    by_name = {ins.name: ins for ins in body}
    roots = [ins for ins in body if ins.root] or body[-1:]
    queue, seen = collections.deque(roots), set()
    while queue:                # breadth first: the nearest operand
        ins = queue.popleft()
        if scopes[ins.name]:
            return scopes[ins.name]
        for name in _HLO_OPERAND.findall(ins.operands):
            if name in by_name and name not in seen:
                seen.add(name)
                queue.append(by_name[name])
    return fluid_scope(fusion.op_name, op_types)


def hlo_scopes(hlo_text, op_types=None):
    """One compiled module's optimised HLO text (``Compiled.as_text()``)
    -> (module name, {instruction name: fluid scope or None}) for every
    instruction a trace can name: those of fused computations are left
    out, their fusion stands for them."""
    op_types = op_types or _registered_op_types()
    module, computations = _parse_hlo(hlo_text)
    fused = {ins.calls for body in computations.values() for ins in body
             if ins.opcode == 'fusion'}
    table = {}
    for name, body in computations.items():
        if name in fused:
            continue
        for ins in body:
            if ins.opcode == 'fusion' and ins.calls in computations:
                table[ins.name] = _fusion_scope(
                    ins, computations[ins.calls], op_types)
            else:
                table[ins.name] = fluid_scope(ins.op_name, op_types)
    return module, table


def scope_tables():
    """{HLO module name: [table, ...]} (tables as ``hlo_scopes`` gives
    them) of every executable this process holds
    (``CompilePlane.held_hlo``).  Built when asked for and at no other
    time: it prints and parses whole modules, seconds at BERT-base.  Two
    programs of one name (a segment planned for two fetch lists) keep a
    table each; ``pick_table`` tells them apart."""
    from . import compile_cache
    tables = {}
    for _key, text in compile_cache.plane().held_hlo():
        module, table = hlo_scopes(text)
        tables.setdefault(module, []).append(table)
    return tables


def pick_table(candidates, instruction_names):
    """Of the tables of same-named modules, the one that knows most of
    the instructions one run of the module executed and, among equals,
    holds the fewest others ({} for none)."""
    if not candidates:
        return {}
    if len(candidates) == 1:
        return candidates[0]
    names = set(instruction_names)
    return max(candidates,
               key=lambda t: (len(names.intersection(t)), -len(t)))


# a module run is named "<module name>(<program id>)"
_PROGRAM_ID = re.compile(r'\(\d+\)$')
_MODULE_LINE = 'XLA Modules'


def module_runs(plane):
    """Sorted [(start ns, end ns, name)] of the module runs of one
    device plane of a trace (its 'XLA Modules' line; the name is the
    module's with the program id, ``jit_segment_x(12)``); [] where the
    plane has no such line."""
    return sorted(
        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
        for line in plane.lines if line.name == _MODULE_LINE
        for ev in line.events)


def program_at(runs, t):
    """The name of the module run (``module_runs``) that holds the
    instant ``t``; '' for none."""
    i = bisect.bisect_right(runs, (t, float('inf'), '')) - 1
    return runs[i][2] if i >= 0 and t <= runs[i][1] else ''


def instruction_scopes(ops, tables):
    """[(program, instruction name)] of executed instructions -> their
    fluid scopes (None for none), in order.  ``program`` is the name of
    the module run the instruction ran in (``program_at``) or a
    module's bare name: two programs of one module name, the quiet step
    and the one that fetches, hold a ``fusion.933`` each, so the
    instructions of each program are looked up in the one table
    (``pick_table``) of that module name that knows most of them.
    Where the program is not known ('': a trace without module runs)
    every table of ``tables`` is a candidate."""
    by_program = {}
    for i, (program, _name) in enumerate(ops):
        by_program.setdefault(program, []).append(i)
    scopes = [None] * len(ops)
    for program, indices in by_program.items():
        candidates = tables.get(_PROGRAM_ID.sub('', program))
        if candidates is None and not program:
            candidates = [t for ts in tables.values() for t in ts]
        table = pick_table(candidates, {ops[i][1] for i in indices})
        for i in indices:
            scopes[i] = table.get(ops[i][1])
    return scopes


# ---------------------------------------------------- reading the trace
_DEVICE_PLANE = re.compile(r'^/device:[A-Za-z]+:\d+$')
_TRACED_INSTRUCTION = re.compile(r'^%?(\S+) = ')


def _newest_xplane(logdir):
    import glob
    paths = glob.glob(os.path.join(logdir, '**', '*.xplane.pb'),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _self_durations(events):
    """[(start, duration)] of one trace line -> the part of each
    duration no later-starting event of the line covers (a ``while``
    covers its body's ops; durations must not be summed)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [d for _s, d in events]
    stack = []                      # indices of the open nest
    for i in order:
        start, dur = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] \
                <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(0.0, d) for d in own]


def load_trace_events(logdir):
    """The newest ``.xplane.pb`` under ``logdir`` (what jax.profiler
    writes on this runtime) as chrome-trace 'X' events, times in us:
    one pid per plane, one tid per line.  An event that is an executed
    HLO instruction (a TPU plane's 'XLA Ops' line, or a CPU thunk with
    an ``hlo_op`` stat) carries ``args['tf_op']``: the fluid scope
    ``scope_tables()`` gives it, else the module's name, and
    ``self_dur`` where other events of its line nest inside it."""
    path = _newest_xplane(logdir)
    if path is None:
        return []
    from jax.profiler import ProfileData
    tables = scope_tables()
    out = []
    for pid, plane in enumerate(ProfileData.from_file(path).planes):
        out.append({'ph': 'M', 'pid': pid, 'name': 'process_name',
                    'args': {'name': plane.name}})
        device = bool(_DEVICE_PLANE.match(plane.name))
        lines = list(plane.lines)
        runs = module_runs(plane) if device else []
        for tid, line in enumerate(lines):
            op_line = device and line.name == 'XLA Ops'
            rows = []
            for ev in line.events:
                row = {'ph': 'X', 'pid': pid, 'tid': tid,
                       'name': ev.name, 'ts': ev.start_ns / 1e3,
                       'dur': ev.duration_ns / 1e3}
                if op_line:
                    m = _TRACED_INSTRUCTION.match(ev.name)
                    row['name'] = m.group(1) if m else ev.name
                    row['module'] = program_at(runs, ev.start_ns)
                elif not device:
                    stats = dict(ev.stats)
                    if 'hlo_op' in stats:
                        row['name'] = str(stats['hlo_op'])
                        row['module'] = str(stats.get('hlo_module', ''))
                rows.append(row)
            _attach_scopes([r for r in rows if 'module' in r], tables)
            out.extend(rows)
    return out


def _attach_scopes(rows, tables):
    """Give the instruction events of one line their ``tf_op`` and
    ``self_dur``."""
    if not rows:
        return
    programs = [row.pop('module') for row in rows]
    scopes = instruction_scopes(
        [(p, row['name']) for p, row in zip(programs, rows)], tables)
    for row, program, scope in zip(rows, programs, scopes):
        row['args'] = {'tf_op': scope or _PROGRAM_ID.sub('', program) or
                       'unknown_module'}
    for row, own in zip(rows, _self_durations(
            [(r['ts'], r['dur']) for r in rows])):
        if own != row['dur']:
            row['self_dur'] = own


def _attach_span_tracer():
    """Auto-attach the fluid.trace span tracer to a starting device
    capture, and emit the paired clock-sync annotation (the device
    trace records 'pt_clock_sync' on ITS clock while the tracer notes
    the host epoch-us — tools/timeline.py merges on that offset)."""
    from . import trace as trace_mod
    trace_mod.attach_capture()
    try:
        with jax.profiler.TraceAnnotation('pt_clock_sync'):
            trace_mod.mark_clock_sync()
    except Exception:
        pass


def start_profiler(state='All', tracer_option='Serial'):
    """Enable profiling (reference EnableProfiler).  `state` kept for
    API parity; on TPU there is no CPU/GPU split to select.
    tracer_option='Serial' re-segments per op and host-times each;
    'Default' captures a device trace of the PRODUCTION program and
    attributes kernels back to ops on stop (reference DeviceTracer)."""
    global _enabled, _mode, _prof_trace_dir
    if state not in ('CPU', 'GPU', 'All'):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    if tracer_option not in ('Serial', 'Default', 'OpDetail',
                             'AllOpDetail'):
        raise ValueError('unknown tracer_option %r' % (tracer_option,))
    reset_profiler()
    if _prof_trace_dir is not None:
        # a 'Default' capture is still active (start called twice /
        # mode switch without stop): close it or the device trace runs
        # forever and the next start_trace raises
        import shutil
        from . import trace as trace_mod
        try:
            jax.profiler.stop_trace()
        finally:
            # drop the rider, restore its state — even when the jax
            # stop raises, or the tracer stays force-enabled forever
            trace_mod.detach_capture()
        shutil.rmtree(_prof_trace_dir, ignore_errors=True)
        _prof_trace_dir = None
    _mode = 'Serial' if tracer_option == 'Serial' else 'Default'
    if _mode == 'Default':
        import tempfile
        _prof_trace_dir = tempfile.mkdtemp(prefix='pt_prof_')
        jax.profiler.start_trace(_prof_trace_dir)
        # one capture yields host AND device events: the span tracer
        # rides along so stop_profiler can write the merged timeline
        _attach_span_tracer()
    _enabled = True


def _fold_into_monitor():
    """Fold the per-op table into the always-on stats registry under
    'profiler/<op>/…' keys, so one monitor.snapshot()/dump_jsonl()
    carries BOTH the cheap counters and the last profile's per-op
    accounting (the reference keeps StatRegistry and the profiler
    side by side; here they meet at stop time)."""
    global _folded
    if _folded:
        # a second stop_profiler (defensive stop, re-reading the
        # returned table) must not re-add the same cumulative records
        return
    _folded = True
    from . import monitor
    for t, (c, tot, mx, mn) in _records.items():
        # 'unattributed/<hlo>' buckets carry '/' — keep them one level
        safe = t.replace('/', ':')
        monitor.add('profiler/%s/calls' % safe, float(c))
        monitor.add('profiler/%s/total_seconds' % safe, tot)


def stop_profiler(sorted_key='total', profile_path=None):
    """Disable profiling and print the sorted per-op table (reference
    DisableProfiler).  profile_path, when given, receives the table as
    a text file — and, after a 'Default' (device-trace) profile, the
    MERGED host+device chrome-trace timeline lands next to it as
    '<table path>.timeline.json' (a directory profile_path gets
    'profile_summary.txt' + 'profile_summary.txt.timeline.json'
    inside), so one profile yields both the table and the step
    timeline.  Returns the table string, folds the per-op records
    into fluid.monitor under 'profiler/…' keys, and resets the tracer
    mode to 'Serial' so a later bare start_profiler()/is_enabled()
    sequence never inherits a stale 'Default' trace mode."""
    global _enabled, _mode, _prof_trace_dir
    _enabled = False
    device_events = []
    host_cap = None
    if _mode == 'Default' and _prof_trace_dir is not None:
        import shutil
        from . import trace as trace_mod
        try:
            jax.profiler.stop_trace()
        finally:
            # detach even when the jax stop raises, or the attached
            # capture keeps recording (and buffering) forever
            host_cap = trace_mod.detach_capture()
        device_events = load_trace_events(_prof_trace_dir)
        recs, stats = attribute_trace_events(
            [e for e in device_events if 'args' in e], with_stats=True)
        _records.update(recs)
        if stats['dropped']:
            # malformed capture rows are counted, not silently eaten
            from . import monitor as _monitor
            _monitor.add('profiler/dropped_events',
                         float(stats['dropped']))
        shutil.rmtree(_prof_trace_dir, ignore_errors=True)
        _prof_trace_dir = None
    _mode = 'Serial'
    _fold_into_monitor()
    table = summary_string(sorted_key)
    print(table)
    if profile_path:
        if os.path.isdir(profile_path) or profile_path.endswith(os.sep):
            # pre-round-4 callers passed a trace DIRECTORY here; keep
            # them working by dropping the table inside it
            os.makedirs(profile_path, exist_ok=True)
            profile_path = os.path.join(profile_path,
                                        'profile_summary.txt')
        d = os.path.dirname(profile_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(profile_path, 'w') as f:
            f.write(table + '\n')
        if host_cap is not None:
            from . import trace as trace_mod
            merged = trace_mod.merge_device_trace(
                trace_mod.chrome_events(host_cap['events']),
                device_events, sync_host_us=host_cap['sync_us'],
                capture_t0_us=host_cap['t0_us'])
            trace_mod.write_chrome(profile_path + '.timeline.json',
                                   merged)
    return table


@contextlib.contextmanager
def profiler(state='All', sorted_key='total',
             profile_path='/tmp/profile.txt', tracer_option='Serial'):
    """Profiling scope.  tracer_option='Serial': ops run
    one-per-segment and host-timed (op-granular, but an unfused
    program).  'Default': the production program runs untouched under
    a device-trace capture, kernels attributed back to ops.  On exit
    the sorted table prints (and lands in profile_path)."""
    start_profiler(state, tracer_option=tracer_option or 'Serial')
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    yield


def start_trace(logdir='/tmp/profile'):
    """Device-trace capture (Perfetto/XPlane) — the DeviceTracer leg.
    The fluid.trace span tracer auto-attaches, so ONE capture yields
    host phase spans AND device kernels; stop_trace writes the host
    side as 'host_trace.json' next to the device dump and
    tools/timeline.py merges the two into one Perfetto file.

    Like start_profiler, double-starts fail with a clear error instead
    of jax's raw 'profiler already started' (only one device trace can
    run per process, and a 'Default' profile capture owns it too)."""
    global _trace_path
    if _trace_path is not None:
        raise RuntimeError(
            'a trace capture is already active (logdir %r): call '
            'stop_trace() before starting another' % (_trace_path,))
    if _prof_trace_dir is not None:
        raise RuntimeError(
            "a profiler capture (tracer_option='Default') owns the "
            'device tracer: call stop_profiler() before start_trace()')
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _trace_path = logdir
    _attach_span_tracer()


def stop_trace():
    """Stop the device capture; returns the logdir.  The capture's
    ``.xplane.pb`` is read back (``load_trace_events``): its per-op
    device time becomes the profiler's table (``summary_records()`` /
    ``summary_string()``), and its events persist as
    '<logdir>/device.trace.json' beside the attached span tracer's
    host events, '<logdir>/host_trace.json', for the timeline merger
    (tools/timeline.py)."""
    global _trace_path
    from . import trace as trace_mod
    try:
        jax.profiler.stop_trace()
    finally:
        # detach even when the jax stop raises (trace already stopped
        # by code driving jax.profiler directly), or the rider stays
        # force-enabled and its capture buffer grows unboundedly
        host_cap = trace_mod.detach_capture()
    path, _trace_path = _trace_path, None
    if path is None:
        return path
    device_events = load_trace_events(path)
    reset_profiler()
    _records.update(attribute_trace_events(
        [e for e in device_events if 'args' in e]))
    try:
        trace_mod.write_chrome(os.path.join(path, 'device.trace.json'),
                               device_events)
        if host_cap is not None:
            trace_mod.write_host_trace(
                os.path.join(path, 'host_trace.json'), host_cap)
    except OSError:
        pass  # read-only logdir: the .xplane.pb is still usable
    return path


record_event = jax.profiler.TraceAnnotation
