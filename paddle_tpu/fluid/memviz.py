"""fluid.memviz — device-memory observability plane.

The reference framework ships a real memory subsystem
(paddle/fluid/memory/ allocator stats behind STAT_ADD, the
FLAGS_fraction_of_gpu_memory_to_use arena, the eager-deletion pass);
paddle_tpu's story stopped at one coarse gauge —
``comms.record_memory`` folding every executable's
``memory_analysis()`` into a job-wide-max
``executor/segment_peak_bytes``.  Too blunt for the collective
planner's HBM headroom gate (one big program suppressed
quantization/fusion for every other program) and useless for
debugging an OOM.  This module is the memory plane, built the way
PR 4 built the time plane, in four coupled pieces:

**Peak attribution.**  ``record_segment(...)`` runs once per new AOT
executable entry (compile, memory hit or disk hit — never per step)
and decomposes its ``memory_analysis()`` peak into NAMED contributors:
per-argument bytes split param / state / feed from the boundary specs,
per-output bytes with the op desc that produces each, the temp
arena, and the alignment overhead XLA adds over the raw specs — so
the row SUMS back to the analysis totals, nothing is vibes.  Rows key
on (program, segment) in a bounded registry; ``/statusz``'s ``memory``
section renders the top-K table, and ``peak_bytes(program)`` is the
per-program HBM input ``comms_plan.hbm_headroom_bytes`` reads instead
of the global max.

**The temp arena has names.**  ``build_tables()``, on demand (a traced
run, ``/statusz`` after it, ``tools/step_hlo_hash.py --memory``; never
at compile time: a module's text costs seconds), gives every row
``temp_peak``: where, by ``fluid.profiler``'s walk of the scheduled HLO
text (``live_tables()``, the same ONE parse the scope and cost tables
come from), the sum of the executable's live temporaries is largest,
that sum, the bytes alive there by class (residual / gradient /
optimizer / working / unscoped) and by fluid op, and the ten largest
buffers.  A program a runner jitted lazily (every mesh program, and
the one-chip programs while the AOT plane is off) has no executable
in hand at its first run; jit's own caches answer for it by then
(``CompilePlane.held_executables``), so the same call files its REAL
``memory_analysis()`` row and the first-run estimate goes.

**Who set the high-water mark.**  ``device_marks()`` /
``first_run_end()`` bracket the FIRST run of every new executable
(the AOT plane's new entry, the runners' ``first_run`` branch; a first
run may wait for its outputs, no steady step reads anything) with the
allocator's ``peak_bytes_in_use`` / ``peak_bytes_reserved``:
``high_water()`` holds the list in run order and, per mark, the
executable whose first run last raised it; gauges
``memviz/hwm_in_use_bytes`` / ``memviz/hwm_reserved_bytes`` are the
marks as last read.

**Live-HBM accounting.**  ``live_census()`` walks ``jax.live_arrays()``
and classifies every resident device buffer: ``param`` (registered
parameter names), ``state`` (other scope-resident values — optimizer
slots, batch-norm stats), ``feed`` (runtime-staged H2D buffers, the
``core.mark_owned`` registry), ``exec`` (generated executable code
from the attribution rows) and ``other`` (in-flight temporaries,
caller-held fetches).  ``maybe_sample(step, scope)`` — the per-step
sampler behind ``FLAGS_memviz`` — emits
``memviz/live_bytes/<class>`` gauges, a high-watermark gauge, and a
Perfetto COUNTER TRACK (``trace.counter``) merged into the existing
timeline by tools/timeline.py, so memory and time read on one axis.
Off (the default) the executor pays one flag read per step.

**OOM forensics.**  The executor's segment dispatch (and both
parallel runners) route allocation failures (RESOURCE_EXHAUSTED /
out-of-memory) through ``oom_incident``: a rate-limited flight-
recorder dump embedding the full memory snapshot — live census,
per-segment peaks, largest buffers, active serving tenants — and an
actionable error note naming the top contributors, the memory analog
of PR 5's NaN provenance.

**Budget watermarks.**  ``FLAGS_memviz_budget_bytes`` (default:
detected device memory via ``device.memory_stats()``, where the
backend reports it) turns the census into a utilization gauge with a
watermark detector (``FLAGS_memviz_watermark``) and a growth-spike
detector (``FLAGS_memviz_spike_factor`` over the running EMA) that
auto-dump the snapshot BEFORE the OOM; ``/healthz`` carries the
degradation and the rank-0 aggregator's job view shows per-worker
utilization.

Hot-path discipline mirrors monitor/trace/comms: NO jax imports at
module level, attribution runs at compile/cache-resolution time only,
the sampler is flag-gated, and the census is O(live arrays) only when
sampling.
"""

import re as _re
import threading
import time

from . import monitor
from .flags import get_flag

__all__ = [
    'record_segment', 'record_segment_estimate', 'report',
    'build_tables', 'device_marks', 'first_run_end',
    'watch_first_run', 'high_water', 'temp_peak',
    'peak_bytes', 'top_contributors',
    'program_label', 'program_scope', 'current_program',
    'note_params', 'live_census', 'last_census', 'maybe_sample',
    'budget_bytes', 'memory_pressure', 'is_oom_error', 'oom_incident',
    'format_incident', 'register_scope_provider', 'reset',
]

_lock = threading.Lock()
_tls = threading.local()

# (program_label, segment_label) -> attribution row; insertion-ordered
# and bounded like comms._MEMORY (distinct executables are bounded by
# the compile caches, but a retrace loop must not leak)
_SEGMENTS = {}
_SEGMENTS_CAP = 512
# the compile plane's key of an AOT executable -> its row's key
_HELD_ROWS = {}
# first runs of new executables, in run order: the allocator's marks
# before and after each (bounded like the rows)
_FIRST_RUNS = []
TOP_BUFFERS = 10
# program-object labeling: monotonic sequence, stamped on the Program
_prog_seq = [0]
# registered parameter names (census param-vs-state classification)
_PARAM_NAMES = set()
_PARAM_NAMES_CAP = 65536
# callables returning [(label, scope)] beyond the active scope — the
# serving plane registers its tenant table here
_SCOPE_PROVIDERS = []
# detector / incident state
_state = {'ema': None, 'hwm': 0.0, 'last_census': None,
          'budget_detected': None}

TOP_K = 8


def reset():
    """Drop the registries and detector state (tests, bench entry
    isolation).  Registered scope providers survive — they mirror
    module lifetime, not run lifetime."""
    with _lock:
        _SEGMENTS.clear()
        _HELD_ROWS.clear()
        del _FIRST_RUNS[:]
        _PARAM_NAMES.clear()
        _state.update({'ema': None, 'hwm': 0.0, 'last_census': None,
                       'budget_detected': None})
    # the dump limiter moved into the shared trace-side helper; a
    # reset must still re-open the interval or back-to-back tests
    # (and bench entries) silently stop dumping
    from . import trace
    trace.reset_rate_limits('memviz/')


# ------------------------------------------------------- program labels
def program_label(program):
    """A stable human-readable label for a Program object, assigned on
    first sight ('prog3').  The label keys attribution rows and the
    ambient program_scope the planner's headroom gate reads."""
    label = getattr(program, '_memviz_label', None)
    if label is None:
        with _lock:
            label = getattr(program, '_memviz_label', None)
            if label is None:
                _prog_seq[0] += 1
                label = 'prog%d' % _prog_seq[0]
                try:
                    program._memviz_label = label
                except Exception:
                    pass
    return label


class _ProgramScope(object):
    __slots__ = ('_label', '_prev')

    def __init__(self, label):
        self._label = label

    def __enter__(self):
        self._prev = getattr(_tls, 'program', None)
        _tls.program = self._label
        return self

    def __exit__(self, *exc):
        _tls.program = self._prev
        return False


def program_scope(label_or_program):
    """Ambient 'this thread is planning/tracing/running THIS program'
    context: comms_plan.hbm_headroom_bytes() resolves the per-program
    peak through it.  Accepts a label string or a Program."""
    label = label_or_program if isinstance(label_or_program, str) \
        else program_label(label_or_program)
    return _ProgramScope(label)


def current_program():
    """The ambient program label, or None outside a program_scope."""
    return getattr(_tls, 'program', None)


def note_params(names):
    """Register parameter names for the census's param-vs-state split
    (the executor calls this once per program when sampling is on)."""
    with _lock:
        if len(_PARAM_NAMES) < _PARAM_NAMES_CAP:
            _PARAM_NAMES.update(str(n) for n in names)


# ------------------------------------------------------ peak attribution
def _nbytes_of_spec(spec):
    """Bytes of one boundary spec (ShapeDtypeStruct / array-like)."""
    try:
        n = getattr(spec, 'nbytes', None)
        if n is not None:
            return float(n)
        import numpy as _np
        size = 1
        for s in getattr(spec, 'shape', ()):
            size *= int(s)
        return float(size * _np.dtype(spec.dtype).itemsize)
    except Exception:
        return 0.0


def analysis_fields(compiled):
    """``compiled.memory_analysis()`` as a plain dict, or None.
    Tolerates backends where the call raises, returns None, or returns
    partial fields — counted in ``memviz/analysis_unavailable`` so a
    dark memory plane is a scrape away, never a silent skip."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        monitor.add('memviz/analysis_unavailable')
        return None
    if ma is None:
        monitor.add('memviz/analysis_unavailable')
        return None

    def _field(name):
        try:
            v = getattr(ma, name, None)
            return float(v) if v is not None else None
        except Exception:
            return None

    out = {'argument_bytes': _field('argument_size_in_bytes'),
           'output_bytes': _field('output_size_in_bytes'),
           'temp_bytes': _field('temp_size_in_bytes'),
           'peak_bytes': _field('peak_memory_in_bytes'),
           'generated_code_bytes': _field(
               'generated_code_size_in_bytes'),
           # of the outputs, what is a donated argument's memory
           'alias_bytes': _field('alias_size_in_bytes')}
    if all(v is None for v in out.values()):
        monitor.add('memviz/analysis_unavailable')
        return None
    for k in ('argument_bytes', 'output_bytes', 'temp_bytes',
              'generated_code_bytes', 'alias_bytes'):
        out[k] = out[k] or 0.0
    if out['peak_bytes'] is None:
        # a backend that reports no peak: arg+out+temp is the
        # live-set bound
        out['peak_bytes'] = (out['argument_bytes'] +
                             out['output_bytes'] + out['temp_bytes'])
    return out


def _op_for_output(seg, name):
    """The op desc producing segment output `name` (attribution's
    'originating op'), or None for pass-through values."""
    if seg is None:
        return None
    try:
        for op in reversed(seg.ops):
            for out_name in op.output_arg_names:
                if out_name == name:
                    return op.type
    except Exception:
        pass
    return None


def _resolve_program(program):
    prog = program if isinstance(program, str) or program is None \
        else program_label(program)
    return prog or current_program() or 'unlabeled'


def _classify_args(state_specs, data_specs, param_names=None):
    """(contributors, classes) over the named boundary arguments:
    per-name bytes split param / state / feed."""
    if param_names is None:
        params = _PARAM_NAMES
    else:
        params = set(str(n) for n in param_names)
    contributors = []
    classes = {'param': 0.0, 'state': 0.0, 'feed': 0.0}
    for names_bytes, cls_of in (
            (state_specs or {},
             lambda n: 'param' if n in params else 'state'),
            (data_specs or {}, lambda n: 'feed')):
        for n, spec in names_bytes.items():
            b = _nbytes_of_spec(spec)
            cls = cls_of(n)
            classes[cls] += b
            contributors.append({'name': str(n), 'class': cls,
                                 'bytes': b, 'op': None})
    return contributors, classes


def _largest(contributors):
    """A row's ``top_buffers``: the TOP_K named contributors by bytes."""
    return sorted((c for c in contributors if c['bytes']),
                  key=lambda c: -c['bytes'])[:TOP_K]


def _file_row(prog, row):
    key = (prog, row['segment'])
    evicted_prog = None
    with _lock:
        if key not in _SEGMENTS and len(_SEGMENTS) >= _SEGMENTS_CAP:
            (ep, _es) = next(iter(_SEGMENTS))
            _SEGMENTS.pop((ep, _es))
            for held in [h for h, k in _HELD_ROWS.items()
                         if k == (ep, _es)]:
                del _HELD_ROWS[held]
            # keep the per-program gauge label set bounded: when a
            # program's LAST row rotates out, its gauge goes too — a
            # frozen peak for a long-gone program misleads scrapes
            if not any(p == ep for (p, _s) in _SEGMENTS):
                evicted_prog = ep
        _SEGMENTS[key] = row
        prog_peak = max((r['peak_bytes'] for (p, _s), r
                         in _SEGMENTS.items() if p == prog),
                        default=0.0)
    if evicted_prog is not None and evicted_prog != prog:
        monitor.remove_gauge('memviz/program_peak_bytes/%s'
                             % evicted_prog)
    monitor.add('memviz/segments_attributed')
    monitor.set_gauge('memviz/program_peak_bytes/%s' % prog, prog_peak)
    return row


def record_segment(program, segment_label, compiled, state_specs,
                   data_specs, seg=None, param_names=None, held_key=None):
    """Decompose one AOT executable's peak into named contributors and
    file the row under (program, segment).  Runs once per new
    executable entry — compile, memory hit or disk hit — NEVER per
    step.  ``held_key``: the compile plane's key of the executable, by
    which ``build_tables()`` finds the row again.  Returns the row or
    None when the backend has no analysis (counted, not silent)."""
    fields = analysis_fields(compiled)
    if fields is None:
        return None
    prog = _resolve_program(program)
    contributors, classes = _classify_args(state_specs, data_specs,
                                           param_names)
    if seg is not None:
        for n in seg.output_names:
            # donated state aliases its input buffer; only NEW outputs
            # add to the output arena — attribute what we can name
            op = _op_for_output(seg, n)
            contributors.append({'name': str(n), 'class': 'output',
                                 'bytes': None, 'op': op})
    named_args = classes['param'] + classes['state'] + classes['feed']
    row = {
        'program': prog,
        'segment': str(segment_label),
        'peak_bytes': fields['peak_bytes'],
        'argument_bytes': fields['argument_bytes'],
        'output_bytes': fields['output_bytes'],
        'temp_bytes': fields['temp_bytes'],
        'generated_code_bytes': fields['generated_code_bytes'],
        'alias_bytes': fields['alias_bytes'],
        'classes': classes,
        # alignment/padding XLA adds over the raw boundary specs: the
        # residual that keeps sum(classes) + overhead == argument_bytes
        'arg_overhead_bytes': fields['argument_bytes'] - named_args,
        'top_buffers': _largest(contributors),
        'outputs': [c for c in contributors
                    if c['class'] == 'output'][:TOP_K],
        'ts': time.time(),
    }
    if held_key is not None:
        with _lock:
            _HELD_ROWS[held_key] = (prog, row['segment'])
    return _file_row(prog, row)


def record_segment_estimate(program, segment_label, state, data,
                            outputs=None, seg=None):
    """ESTIMATED attribution for segments compiled through the
    shape-polymorphic shared jits (the parallel/collective runners):
    at its first run such an executable is not in hand (jit holds it),
    so the row is built from the bound argument and output arrays
    themselves — peak = arguments + outputs, temps unknown (a LOWER
    bound, flagged ``estimated``).  It is the FIRST-RUN bound the
    per-program headroom gate reads on exactly the multi-program
    collective path it was built for; ``build_tables()`` replaces it
    with the executable's real ``memory_analysis()`` row (jit's caches
    answer ``lower().compile()`` by then) and its ``temp_peak``.  Runs
    at first_run only."""
    prog = _resolve_program(program)
    contributors, classes = _classify_args(state, data)
    out_total = 0.0
    state_names = set(state or {})
    for n, v in (outputs or {}).items():
        # donated state aliases its input buffer (donate_argnums):
        # an updated-state output is the SAME memory as its argument
        # and must not count twice — only genuinely new outputs add
        b = 0.0 if n in state_names else _nbytes_of_spec(v)
        out_total += b
        contributors.append({'name': str(n), 'class': 'output',
                             'bytes': b or None,
                             'op': _op_for_output(seg, n)})
    arg_total = classes['param'] + classes['state'] + classes['feed']
    row = {
        'program': prog,
        'segment': str(segment_label),
        'peak_bytes': arg_total + out_total,
        'argument_bytes': arg_total,
        'output_bytes': out_total,
        'temp_bytes': 0.0,
        'generated_code_bytes': 0.0,
        'alias_bytes': 0.0,
        'classes': classes,
        'arg_overhead_bytes': 0.0,
        'estimated': True,
        'top_buffers': _largest(contributors),
        'outputs': [c for c in contributors
                    if c['class'] == 'output'][:TOP_K],
        'ts': time.time(),
    }
    return _file_row(prog, row)


def temp_peak(live):
    """A row's ``temp_peak`` from fluid.profiler's live table
    (``hlo_live`` / ``live_tables``): the sum, the point, the bytes by
    class and by fluid op, the count of buffers alive there, the ten
    largest, and the two figures the walk keeps apart: the bytes alive
    there in another memory space and what the loops' bodies compute
    anew for their next trip."""
    return {'bytes': float(live['bytes']), 'point': live['point'],
            'op': live['op'], 'by_class': dict(live['by_class']),
            'by_op': {str(op): b for op, b in sorted(
                live['by_op'].items(), key=lambda kv: -kv[1])},
            'buffers': len(live['buffers']),
            'top_buffers': live['buffers'][:TOP_BUFFERS],
            'elsewhere_bytes': float(live['elsewhere_bytes']),
            'carried_anew_bytes': float(live['carried_anew_bytes'])}


def _one_device(specs):
    """{name: spec} with every spec that says how it is sharded cut to
    one device's shard: ``memory_analysis()`` counts one device."""
    import jax
    out = {}
    for n, spec in (specs or {}).items():
        sharding = getattr(spec, 'sharding', None)
        try:
            if sharding is not None:
                spec = jax.ShapeDtypeStruct(
                    sharding.shard_shape(spec.shape), spec.dtype)
        except Exception:
            pass
        out[n] = spec
    return out


def build_tables():
    """Give every executable this process holds its real
    ``memory_analysis()`` row and the row's ``temp_peak`` (the module
    docstring).  ON DEMAND ONLY: it prints and parses each held
    module once (``CompilePlane.held_tables``: seconds at BERT-base,
    shared with fluid.profiler's scope and cost tables) and asks jit's
    caches for the lazily jitted ones.  Returns the rows it touched."""
    from . import compile_cache, profiler
    live = profiler.live_tables()
    touched = []
    for key, executable, noted in \
            compile_cache.plane().held_executables():
        label = noted[1] if noted else None
        with _lock:     # an AOT row by its key, a lazy one by its label
            at = _HELD_ROWS.get(key) or (tuple(label) if label else None)
            row = _SEGMENTS.get(at)
        if row is None or row.get('estimated'):
            # a lazily jitted program: the executable is in hand now
            fields = analysis_fields(executable)
            if fields is None:
                continue
            prog, segment = label or ('unlabeled', 'held:%s' % (key,))
            args = noted[0] if noted else (None, {}, {})
            contributors, classes = _classify_args(
                _one_device(args[1]), _one_device(args[2]))
            named = sum(classes.values())
            fresh = dict(row or {}, program=prog, segment=str(segment),
                         classes=classes,
                         arg_overhead_bytes=fields['argument_bytes'] -
                         named, ts=time.time(), **fields)
            fresh.pop('estimated', None)
            fresh['top_buffers'] = _largest(contributors)
            fresh.setdefault('outputs', [])
            row = _file_row(prog, fresh)
        table = live.get(key)
        if table is not None and table[1] is not None:
            row['module'] = table[0]
            row['temp_peak'] = temp_peak(table[1])
        touched.append(row)
    return touched


# ---------------------------------------------------- high-water marks
_MARKS = ('peak_bytes_in_use', 'peak_bytes_reserved')


def device_marks():
    """The allocator's two high-water marks, the largest over this
    process's devices; None where the backend reports none (CPU).
    Read before a new executable's first dispatch and handed to
    ``first_run_end``."""
    try:
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
    except Exception:
        return None
    if not any(stats):
        return None
    return {m: float(max(s.get(m, 0) for s in stats)) for m in _MARKS}


def watch_first_run(executable, segment_label):
    """``executable`` for ONE call, its first, with the marks read
    around it (the AOT plane's new entry, whose dispatch is not a
    ``first_run`` of the runner's)."""
    def first(*args):
        before = device_marks()
        out = executable(*args)
        first_run_end(before, None, segment_label, out)
        return out
    return first


def first_run_end(before, program, segment_label, outputs=None):
    """File a new executable's first run: wait for its outputs (a
    first run may; no other step does), read the marks again, and keep
    both under (program, segment) at the end of the run-ordered
    list.  Never raises: a first run that could not be filed is a
    missing entry of ``high_water()``'s list, and what its outputs
    raised surfaces where they are next read."""
    try:
        if outputs is not None:
            import jax
            jax.block_until_ready(outputs)
        after = device_marks()
        entry = {'program': _resolve_program(program),
                 'segment': str(segment_label),
                 'before': before, 'after': after, 'ts': time.time()}
        with _lock:
            if len(_FIRST_RUNS) >= _SEGMENTS_CAP:
                del _FIRST_RUNS[0]
            entry['order'] = (_FIRST_RUNS[-1]['order'] + 1
                              if _FIRST_RUNS else 0)
            _FIRST_RUNS.append(entry)
        if after:
            monitor.set_gauge('memviz/hwm_in_use_bytes',
                              after['peak_bytes_in_use'])
            monitor.set_gauge('memviz/hwm_reserved_bytes',
                              after['peak_bytes_reserved'])
        return entry
    except Exception:
        return None


def high_water():
    """{'first_runs': every new executable's first run in run order
    (program, segment, the allocator's marks before and after),
    'raised_by': per mark, the (program, segment) whose first run
    last raised it and the bytes it left it at}: which executable set
    ``peak_bytes_in_use`` and which ``peak_bytes_reserved``.  A mark
    is raised while one of the process's own programs runs for the
    first time, or by what runs outside them (a caller's own jit, a
    batch put on the device): a ``before`` above the previous entry's
    ``after``, filed with ``program`` None.  What a steady step raises
    (nothing, where every program ran once) is not seen."""
    with _lock:
        runs = [dict(r) for r in _FIRST_RUNS]
    raised = {}
    last = None
    for r in runs:
        if not (r['before'] and r['after']):
            continue
        for mark in _MARKS:
            if last is not None and r['before'][mark] > last[mark]:
                # between two first runs: none of the program's own
                # executables is new there, so what the caller ran
                raised[mark] = {
                    'program': None, 'order': r['order'],
                    'segment': 'outside the program\'s executables, '
                    'before %s/%s' % (r['program'], r['segment']),
                    'bytes': r['before'][mark]}
            if r['after'][mark] > r['before'][mark]:
                raised[mark] = {'program': r['program'],
                                'segment': r['segment'],
                                'order': r['order'],
                                'bytes': r['after'][mark]}
        last = r['after']
    return {'first_runs': runs, 'raised_by': raised}


def report(limit=32):
    """Attribution rows for /statusz, largest peak first: the top-K
    table that replaces the four scalars."""
    with _lock:
        rows = [dict(r) for r in _SEGMENTS.values()]
        runs = {(r['program'], r['segment']): r for r in _FIRST_RUNS}
    for r in rows:
        # the allocator's marks around the executable's first run and
        # its place in the run order (``high_water()`` has the list)
        first = runs.get((r['program'], r['segment']))
        if first is not None:
            r['first_run'] = dict(first)
    rows.sort(key=lambda r: -r['peak_bytes'])
    return rows[:limit]


def peak_bytes(program=None):
    """Largest recorded segment peak for `program` (a label), or None
    when nothing is recorded for it.  `program=None` returns the
    job-wide max over every recorded row (None when empty) — callers
    needing the legacy global behavior fall back to the
    executor/segment_peak_bytes gauge."""
    with _lock:
        vals = [r['peak_bytes'] for (p, _s), r in _SEGMENTS.items()
                if program is None or p == program]
    return max(vals) if vals else None


def top_contributors(k=TOP_K):
    """The k largest named buffers across every recorded segment —
    the 'what is actually filling HBM' list OOM notes lead with."""
    with _lock:
        rows = list(_SEGMENTS.values())
    out = []
    seen = set()
    for r in rows:
        for c in r['top_buffers']:
            # dedup per PROGRAM: one buffer feeding several segments of
            # a program lists once, but identically-shaped same-named
            # buffers of DIFFERENT programs (model replicas, tenants)
            # are distinct device residency and must both show
            key = (r['program'], c['name'])
            if key in seen:
                continue
            seen.add(key)
            out.append(dict(c, program=r['program'],
                            segment=r['segment']))
    out.sort(key=lambda c: -c['bytes'])
    return out[:k]


# ---------------------------------------------------------- live census
def register_scope_provider(fn):
    """Register a callable returning [(label, core.Scope)] the census
    should walk beyond the active scope — the serving plane registers
    its tenant table so tenant residency is attributable."""
    with _lock:
        if fn not in _SCOPE_PROVIDERS:
            _SCOPE_PROVIDERS.append(fn)


def _walk_scope(scope, out, prefix=''):
    """id(array) -> name over one scope tree.  READ-ONLY: the census
    must never allocate — a SelectedRows is registered through its
    backing rows/value arrays, NOT core.as_array (whose to_dense()
    would materialize a fresh dense copy on device every sample)."""
    from . import core
    try:
        items = list(scope._vars.items())
        kids = list(scope.kids)
    except Exception:
        return
    for n, v in items:
        if v is None:
            continue
        name = prefix + str(n)
        if isinstance(v, core.LoDTensor):
            v = v.data
        if isinstance(v, core.SelectedRows):
            for part in (v.rows, v.value):
                if hasattr(part, 'nbytes'):
                    out[id(part)] = name
            continue
        if hasattr(v, 'nbytes'):
            out[id(v)] = name
    for kid in kids:
        _walk_scope(kid, out, prefix)


def live_census(scope=None):
    """One pass over ``jax.live_arrays()`` classified into
    param / state / feed / exec / other bytes, plus per-tenant
    residency for registered serving scopes.  Post-step only (the
    sampler or an incident) — this is O(live arrays).

    Caveat: the ``exec`` class sums generated-code bytes from the
    ATTRIBUTION registry, which is compile-time history — executables
    of a program that was since dropped still count until their rows
    rotate out of the bounded registry (array classes always reflect
    true liveness; cross-check a surprising ``exec`` share against
    the compile plane's entry count)."""
    import jax
    from . import core
    scope_names = {}
    _walk_scope(core.global_scope(), scope_names)
    if scope is not None and scope is not core.global_scope():
        _walk_scope(scope, scope_names)
    tenant_ids = {}      # id(array) -> tenant label
    with _lock:
        providers = list(_SCOPE_PROVIDERS)
        params = set(_PARAM_NAMES)
        exec_bytes = sum(r['generated_code_bytes']
                         for r in _SEGMENTS.values())
    for provider in providers:
        try:
            for label, sc in provider():
                t_names = {}
                _walk_scope(sc, t_names)
                scope_names.update(t_names)
                for i in t_names:
                    tenant_ids[i] = str(label)
        except Exception:
            pass
    classes = {'param': 0.0, 'state': 0.0, 'feed': 0.0,
               'exec': exec_bytes, 'other': 0.0}
    tenants = {}
    total = 0.0
    n_arrays = 0
    try:
        arrays = jax.live_arrays()
    except Exception:
        arrays = []
    for arr in arrays:
        try:
            b = float(arr.nbytes)
        except Exception:
            continue
        total += b
        n_arrays += 1
        i = id(arr)
        name = scope_names.get(i)
        if name is not None:
            classes['param' if name in params else 'state'] += b
            t = tenant_ids.get(i)
            if t is not None:
                tenants[t] = tenants.get(t, 0.0) + b
        elif core.is_owned(arr):
            # the mark_owned registry IS the staged-feed set: runtime-
            # created H2D buffers not (yet) visible through any scope
            classes['feed'] += b
        else:
            classes['other'] += b
    # exec (generated executable code) is resident device memory too:
    # fold it into the total so the classes SUM to total_bytes — the
    # stacked counter track, the incident rendering and the budget
    # utilization all read one consistent arithmetic
    total += exec_bytes
    census = {'classes': classes, 'total_bytes': total,
              'arrays': n_arrays, 'tenants': tenants,
              'ts': time.time()}
    with _lock:
        _state['last_census'] = census
    return census


def last_census():
    """The most recent census (sampler or incident), or None."""
    return _state['last_census']


# --------------------------------------------------------------- budget
def budget_bytes():
    """The HBM budget the watermarks measure against:
    FLAGS_memviz_budget_bytes when set, else the device's reported
    memory limit (``memory_stats()['bytes_limit']``, memoized; None on
    backends that report nothing — CPU)."""
    flag = float(get_flag('FLAGS_memviz_budget_bytes', 0) or 0)
    if flag > 0:
        return flag
    detected = _state['budget_detected']
    if detected is None:
        detected = 0.0
        try:
            import jax
            stats = jax.devices()[0].memory_stats()
            if stats:
                detected = float(stats.get('bytes_limit') or 0.0)
        except Exception:
            pass
        with _lock:
            _state['budget_detected'] = detected
    return detected or None


def memory_pressure():
    """/healthz degradation input: {'utilization', 'degraded',
    'budget_bytes', 'live_bytes'} from the last census, or None before
    any sample (or without a budget)."""
    census = _state['last_census']
    if census is None:
        # no census yet: don't touch the device just to answer
        # /healthz on a process that never sampled
        return None
    budget = budget_bytes()
    if not budget:
        return None
    util = census['total_bytes'] / budget
    watermark = float(get_flag('FLAGS_memviz_watermark', 0.9) or 0.9)
    return {'utilization': round(util, 4),
            'degraded': util >= watermark,
            'budget_bytes': budget,
            'live_bytes': census['total_bytes']}


# -------------------------------------------------------------- sampler
def maybe_sample(step, scope=None):
    """Per-step sampler entry (the executor calls this after each
    step): OFF (FLAGS_memviz unset, the default) it costs one flag
    read.  On, every FLAGS_memviz_sample_steps'th step takes a census,
    publishes the per-class gauges + high watermark, feeds the
    Perfetto counter track, and runs the watermark/spike detectors."""
    if not get_flag('FLAGS_memviz'):
        return None
    stride = int(get_flag('FLAGS_memviz_sample_steps', 1) or 1)
    if stride > 1 and step % stride:
        return None
    t0 = time.perf_counter()
    census = live_census(scope)
    classes = census['classes']
    for cls, b in classes.items():
        monitor.set_gauge('memviz/live_bytes/%s' % cls, b)
    monitor.set_gauge('memviz/live_bytes_total', census['total_bytes'])
    monitor.set_gauge('memviz/live_arrays', census['arrays'])
    with _lock:
        # read-modify-write under the lock: concurrent samplers
        # (serving dispatcher + trainer) must not lose a watermark
        hwm = max(_state['hwm'], census['total_bytes'])
        _state['hwm'] = hwm
    monitor.set_gauge('memviz/live_bytes_hwm', hwm)
    monitor.add('memviz/samples')
    from . import trace
    trace.counter('memviz/live_bytes',
                  {cls: classes[cls] for cls in sorted(classes)})
    _check_watermarks(step, census)
    monitor.observe('memviz/sample_seconds',
                    time.perf_counter() - t0)
    return census


def _check_watermarks(step, census):
    """Budget watermark + growth-spike detectors over one census; a
    trip auto-dumps the flight recorder with the snapshot embedded
    BEFORE the allocator fails.  Never raises."""
    try:
        total = census['total_bytes']
        budget = budget_bytes()
        tripped = None
        if budget:
            util = total / budget
            monitor.set_gauge('memviz/budget_utilization', util)
            watermark = float(get_flag('FLAGS_memviz_watermark', 0.9)
                              or 0.9)
            if util >= watermark:
                monitor.add('memviz/watermark_trips')
                tripped = {'detector': 'watermark', 'step': step,
                           'utilization': util,
                           'budget_bytes': budget}
        factor = float(get_flag('FLAGS_memviz_spike_factor', 2.0)
                       or 0.0)
        with _lock:
            ema = _state['ema']
            _state['ema'] = total if ema is None else \
                0.9 * ema + 0.1 * total
        if tripped is None and ema is not None and ema > 0 and \
                factor > 0 and total > factor * ema:
            monitor.add('memviz/spike_trips')
            tripped = {'detector': 'spike', 'step': step,
                       'live_bytes': total, 'ema_bytes': ema,
                       'factor': factor}
        if tripped is not None:
            _auto_dump('memviz_%s_step%s'
                       % (tripped['detector'], step),
                       dict(tripped, kind='memory_pressure',
                            snapshot=snapshot(census=census)))
    except Exception:
        monitor.add('memviz/detector_errors')


def _auto_dump(tag, extra):
    """Rate-limited flight-recorder dump (one per
    FLAGS_memviz_dump_interval_s) so a persistently-pressured job
    cannot spam /tmp."""
    from . import trace
    interval = float(get_flag('FLAGS_memviz_dump_interval_s', 60.0)
                     or 60.0)
    # the shared limiter claims atomically: two concurrent detector
    # trips must produce ONE dump, not race past the limiter together
    path = trace.rate_limited_dump('memviz/detector', interval,
                                   tag=tag, extra=extra)
    if path:
        monitor.add('memviz/detector_dumps')
    return path


# -------------------------------------------------------- OOM forensics
# anchored on the canonical allocator markers: bare substrings would
# let an identifier containing 'OOM' (a model named BLOOM) or a
# host-side 'failed to allocate' (thread pool) hijack the forensics
# path and burn the rate-limited dump on a non-memory failure
_OOM_RE = _re.compile(
    r'RESOURCE[_ ]EXHAUSTED'
    r'|[Oo]ut of (?:device )?memory'
    r'|\bOOM\b'
    r'|[Ff]ailed to allocate (?:memory|device|\d)'
    r'|Allocation failure')


def is_oom_error(e):
    """Does this exception look like a device allocation failure?"""
    return _OOM_RE.search(str(e)) is not None


def snapshot(scope=None, census=None):
    """The full memory snapshot an incident embeds: live census,
    per-segment peaks, largest buffers, serving tenants, budget.
    `segments`/`top_buffers` are the attribution REGISTRY's view —
    compile-time history of everything this process built, which may
    include programs no longer resident; the census classes are the
    ground truth of what is live right now."""
    census = census or live_census(scope)
    tenants = census.get('tenants') or {}
    return {
        'census': census,
        'segments': report(limit=TOP_K),
        'top_buffers': top_contributors(TOP_K),
        'serving_tenants': tenants,
        'budget': memory_pressure(),
    }


def oom_incident(e, step=None, scope=None):
    """Allocation-failure hook (executor + parallel runners): count
    it, dump the flight recorder with the memory snapshot embedded
    (rate-limited: one dump per FLAGS_memviz_oom_interval_s), and
    return the actionable note naming the top contributors.  Never
    raises — the original error must surface."""
    try:
        monitor.add('memviz/oom_incidents')
        program = current_program()
        snap = snapshot(scope)
        snap.update({'kind': 'oom', 'step': step, 'program': program,
                     'error': str(e)[:500]})
        from . import trace
        interval = float(get_flag('FLAGS_memviz_oom_interval_s', 30.0)
                         or 30.0)
        path = trace.rate_limited_dump('memviz/oom', interval,
                                       tag='oom_step%s' % step,
                                       extra=snap)
        if path:
            monitor.add('memviz/oom_dumps')
        return format_incident(snap, path)
    except Exception:
        return None


def _mib(b):
    b = float(b)
    if b >= (1 << 30):
        return '%.2fGiB' % (b / (1 << 30))
    if b >= (1 << 20):
        return '%.1fMiB' % (b / (1 << 20))
    if b >= 1024:
        return '%.1fKiB' % (b / 1024.0)
    return '%dB' % int(b)


def format_incident(snap, dump_path=None):
    """Render an OOM snapshot as the exception-note block: live HBM by
    class, the largest resident segments and named buffers, tenants,
    and where the full dump landed."""
    lines = ['device memory exhausted']
    census = snap.get('census') or {}
    classes = census.get('classes') or {}
    if classes:
        lines.append('  live HBM %s across %s arrays (%s)' % (
            _mib(census.get('total_bytes', 0.0)),
            census.get('arrays', 0),
            ', '.join('%s=%s' % (c, _mib(classes[c]))
                      for c in sorted(classes) if classes[c])))
    budget = snap.get('budget')
    if budget:
        lines.append('  budget %s at %.0f%% utilization%s' % (
            _mib(budget['budget_bytes']),
            100.0 * budget['utilization'],
            ' (DEGRADED)' if budget['degraded'] else ''))
    for r in (snap.get('segments') or [])[:3]:
        lines.append('  segment %s/%s peak %s (args %s, temps %s)'
                     % (r['program'], r['segment'],
                        _mib(r['peak_bytes']),
                        _mib(r['argument_bytes']),
                        _mib(r['temp_bytes'])))
    tops = snap.get('top_buffers') or []
    if tops:
        lines.append('  largest buffers: ' + ', '.join(
            '%s=%s (%s)' % (c['name'], _mib(c['bytes']), c['class'])
            for c in tops[:5]))
    tenants = snap.get('serving_tenants') or {}
    if tenants:
        lines.append('  serving tenants resident: ' + ', '.join(
            '%s=%s' % (t, _mib(b))
            for t, b in sorted(tenants.items(), key=lambda kv: -kv[1])))
    if dump_path:
        lines.append('  memory snapshot embedded in flight dump: %s'
                     % dump_path)
    return '\n'.join(lines)
