"""fluid.opprof — op-level cost attribution plane.

The runtime can say where a step's milliseconds go by *phase*
(``trace.step_report()``) and where its bytes go by *op*
(``memviz`` peak attribution) — but not where its TIME goes by op:
``profiler.attribute_trace_events`` rolls device kernels up to op
*type* only, only while the legacy profiler is armed, and two ``fc``
layers are indistinguishable because the executor's per-op
``jax.named_scope`` carries the type alone.  This module is the time
analog of the memviz plane, in four coupled pieces:

**Instance provenance.**  Under ``FLAGS_opprof`` the executor wraps
each op lowering in ``jax.named_scope('<type>#<block-index>')``
(``op_scope()`` here computes the suffix) so XLA op_metadata — and
therefore every device-capture kernel event's ``tf_op`` path —
resolves to a SPECIFIC op desc, not a type.  Fingerprint-neutral by
construction: scope names never enter ``compile_cache.fingerprint``
(it hashes op descs + arg specs + lowering flags), and the flag keys
neither the in-memory segment cache nor the plan cache, so flipping
it causes zero retraces — only a fresh trace materializes the new
names.

**Capture attribution.**  ``record_capture(events)`` folds any
chrome-trace capture (a live ``jax.profiler`` trace, or the merged
timeline via ``tools/timeline.py --ops``) through
``profiler.attribute_trace_events(per_instance=True)`` into a bounded
per-(program, segment, op-instance) registry, with fused-kernel time
split across constituent instances and the remainder filed under an
honest ``unattributed/`` bucket.  Rollups by op type and by layer
(the layer naming reuses ``parallel/plan.match_partition_rules``'s
regex rule set), ``opprof/*`` monitor points, the ``/statusz``
``op_costs`` top-K table and ``stat_summary.py --ops`` all read this
one registry.

**Eager replay profiler.**  On snapshot steps (``FLAGS_opprof`` on,
every ``FLAGS_opprof_snapshot_steps``-th step) the executor stashes a
survivable copy of each warmed segment's bound inputs plus that
step's measured synchronous device wall.  ``replay_all()`` (on
demand: HTTP ``/opprof`` or ``tools/op_costs.py``) replays each
stashed segment op-by-op through the eager op registry — the same
walk ``health.nan_provenance`` uses post-mortem — timing every op
and sizing its outputs.  Raw eager walls are then NORMALIZED so each
segment's instance costs sum to its measured compiled wall: the
replay supplies the per-op *distribution*, the live step supplies the
*total* — which is why the CPU container and any capture-less run
still get a cost table whose segment sums agree with
``trace.step_report()`` phase walls (both raw and normalized numbers
are kept; nothing is vibes).

**The worklist.**  ``kernel_worklist()`` ranks contiguous
same-segment op runs (maximal same-type runs) by attributable ms/step
and bytes moved, cross-references the ``ops/pallas/common.py`` dispatch
registry's declared ``op_types`` coverage to mark runs a fused kernel
already serves, and ``write_worklist()`` emits ``op_worklist.json`` —
the artifact ROADMAP item 5's next kernels are chosen from.

Hot-path discipline mirrors memviz: no jax import at module level,
``FLAGS_opprof`` off costs ONE flag read per step (the
``want_snapshot`` gate in ``Executor._step_scope``), instance naming is
trace-time only, and all registries are bounded and lock-disciplined
(tools/staticcheck.py LOCK_MODULES).
"""

import json
import re
import threading
import time

from . import monitor
from .flags import get_flag

__all__ = [
    'enabled', 'instancing', 'op_scope', 'want_snapshot',
    'note_segment', 'replay_all', 'record_capture', 'report',
    'rollup_by_type', 'rollup_by_layer', 'kernel_worklist',
    'write_worklist', 'http_report', 'reset',
]

_lock = threading.Lock()

# (program, segment) -> cost row; insertion-ordered, bounded (the
# distinct-executable population is bounded by the compile caches, but
# a retrace loop must not leak)
_COSTS = {}
_COSTS_CAP = 512
# (program, segment) -> replay snapshot {ops, state, data, step,
# prefer_test, measured_s}; state copies pin device buffers, so this
# registry is small and overwritten per snapshot step
_SNAPSHOTS = {}
_SNAPSHOTS_CAP = 64
# instance scope -> (op type, layer label): lets capture-sourced rows
# (which carry scope strings, not op descs) join the layer rollup
_INSTANCE_OPS = {}
_INSTANCE_OPS_CAP = 8192
# trace-time block-index memo: (id(block), len(ops)) -> {id(op): idx}
_BLOCK_IDX = {}
_BLOCK_IDX_CAP = 64

_INSTANCE_RE = re.compile(r'^(.*)#(\d+)$')
_GENERIC_LAYER = re.compile(r'([A-Za-z]\w*?_\d+)\.')
_LAYER_RULES = None

TOP_K = 16


def reset():
    """Drop every registry (tests, bench entry isolation)."""
    with _lock:
        _COSTS.clear()
        _SNAPSHOTS.clear()
        _INSTANCE_OPS.clear()
        _BLOCK_IDX.clear()


# ---------------------------------------------------- instance provenance
def enabled():
    return bool(get_flag('FLAGS_opprof'))


def instancing():
    """Whether the executor should emit instance-suffixed scope names.
    Read at TRACE time (lowerings run once per compiled segment), so
    this is never a per-step cost."""
    return bool(get_flag('FLAGS_opprof'))


def _block_index(op):
    """Index of `op` within its Program block — the stable instance
    suffix.  Identity-based (Operator defines no __eq__) and memoized
    per block, so a whole-block lowering stays O(block)."""
    try:
        ops = op.block.ops
    except Exception:
        return -1
    key = (id(op.block), len(ops))
    idx = _BLOCK_IDX.get(key)
    if idx is None:
        idx = {id(o): i for i, o in enumerate(ops)}
        with _lock:
            if len(_BLOCK_IDX) >= _BLOCK_IDX_CAP:
                _BLOCK_IDX.clear()
            _BLOCK_IDX[key] = idx
    return idx.get(id(op), -1)


def op_scope(op):
    """The instance scope name for an op desc: ``<type>#<block-index>``.
    Stable across retraces of the same Program (the block's op list is
    the identity), and what a device capture's ``tf_op`` path carries
    back when ``FLAGS_opprof`` was on at trace time."""
    return '%s#%d' % (op.type, _block_index(op))


def split_instance(name):
    """('fc#3') -> ('fc', 3); a bare type maps to index None."""
    m = _INSTANCE_RE.match(name)
    if m:
        try:
            return m.group(1), int(m.group(2))
        except ValueError:
            pass
    return name, None


# ----------------------------------------------------- layer attribution
def _layer_rules():
    """Compiled layer-naming regexes, shared with the auto-sharding
    planner: ``parallel/plan.default_rules``'s rule patterns name the
    layer families (fc/mul, embedding, moe experts); reusing them here
    keeps 'layer' meaning the same thing in both planes."""
    global _LAYER_RULES
    if _LAYER_RULES is None:
        pats = []
        try:
            from ..parallel import plan as _plan
            for pat, _rule in _plan.default_rules():
                if pat != r'.*':   # the catch-all is not a layer name
                    pats.append(re.compile(pat))
        except Exception:
            pass
        _LAYER_RULES = pats
    return _LAYER_RULES


def layer_of(op):
    """Layer label for an op desc, from its var names: first match of
    the plan rule regexes wins (``fc_2.w_0`` -> ``fc_2``), then the
    generic ``<layer>_N.`` LayerHelper prefix, else None."""
    names = list(op.input_arg_names) + list(op.output_arg_names)
    for rx in _layer_rules():
        for n in names:
            m = rx.search(n)
            if m:
                return m.group(0).split('.')[0]
    for n in names:
        m = _GENERIC_LAYER.match(n)
        if m:
            return m.group(1)
    return None


# ------------------------------------------------------- replay snapshots
def want_snapshot(step):
    """The per-step gate ``Executor._step_scope`` reads ONCE per step:
    False immediately when ``FLAGS_opprof`` is off (one flag read —
    the whole disabled-path cost), else the snapshot cadence."""
    if not get_flag('FLAGS_opprof'):
        return False
    k = int(get_flag('FLAGS_opprof_snapshot_steps', 16) or 1)
    return int(step) % max(k, 1) == 0


def note_segment(program, segment, ops, state, data, step,
                 prefer_test=False, measured_s=None):
    """Stash a warmed segment's inputs (survivable copies, made by the
    executor before donation eats the state) + its measured
    synchronous device wall for later eager replay.  Overwrites the
    previous snapshot for the same (program, segment) — the registry
    holds the LATEST warm step, not a history."""
    key = (str(program or '?'), str(segment))
    # resolve instance names BEFORE taking the lock: op_scope ->
    # _block_index acquires it on a memo miss (the mid-run flag-flip
    # path, where the segment compiled without instance naming)
    named = [(op_scope(op), op.type, layer_of(op)) for op in ops]
    with _lock:
        if key not in _SNAPSHOTS and \
                len(_SNAPSHOTS) >= _SNAPSHOTS_CAP:
            _SNAPSHOTS.pop(next(iter(_SNAPSHOTS)))
        _SNAPSHOTS[key] = {
            'ops': list(ops), 'state': dict(state), 'data': dict(data),
            'step': int(step), 'prefer_test': bool(prefer_test),
            'measured_s': (float(measured_s)
                           if measured_s is not None else None),
        }
        for inst, typ, layer in named:
            if len(_INSTANCE_OPS) >= _INSTANCE_OPS_CAP:
                _INSTANCE_OPS.clear()
            _INSTANCE_OPS[inst] = (typ, layer)
    monitor.add('opprof/snapshots')


def snapshots():
    with _lock:
        return {k: {'ops': len(v['ops']), 'step': v['step'],
                    'measured_s': v['measured_s']}
                for k, v in _SNAPSHOTS.items()}


def _replay_one(snap):
    """Replay one stashed segment op-by-op through the eager registry
    (the ``health.nan_provenance`` walk, timed): per-op wall + output
    bytes.  Returns (ordered {instance: cells}, raw_total_s).

    Two passes: an untimed warmup first — the first eager execution of
    each op pays its own trace+compile, which would otherwise dominate
    the distribution the normalization preserves — then the timed
    walk over warm per-op executables."""
    import jax
    from .executor import _lower_ops, _op_writes
    warm_env = {}
    warm_env.update(snap['data'])
    warm_env.update(snap['state'])
    for op in snap['ops']:
        _lower_ops([op], warm_env, snap['step'], snap['prefer_test'])
    try:
        jax.block_until_ready([v for v in warm_env.values()
                               if hasattr(v, 'block_until_ready')])
    except Exception:
        pass
    env = {}
    env.update(snap['data'])
    env.update(snap['state'])
    rows = {}
    raw_total = 0.0
    for op in snap['ops']:
        inst = op_scope(op)
        t0 = time.perf_counter()
        _lower_ops([op], env, snap['step'], snap['prefer_test'])
        outs = [env[n] for n in _op_writes(op) if n in env]
        try:
            jax.block_until_ready(outs)
        except Exception:
            pass
        wall = time.perf_counter() - t0
        raw_total += wall
        nbytes = 0
        for v in outs:
            try:
                nbytes += int(getattr(v, 'nbytes', 0) or 0)
            except Exception:
                pass
        cell = rows.get(inst)
        if cell is None:
            rows[inst] = {'type': op.type, 'layer': layer_of(op),
                          'calls': 1, 'raw_s': wall, 'max_s': wall,
                          'bytes': nbytes}
        else:
            cell['calls'] += 1
            cell['raw_s'] += wall
            cell['max_s'] = max(cell['max_s'], wall)
            cell['bytes'] += nbytes
    return rows, raw_total


def replay_all():
    """Replay every stashed snapshot and fold NORMALIZED per-instance
    costs into the registry: each segment's instance ms scale so they
    sum to its measured compiled wall (raw eager walls are kept in
    ``raw_ms`` — the normalization is visible, not hidden).  Returns
    {(program, segment) label: replayed op count}."""
    with _lock:
        pending = dict(_SNAPSHOTS)
    done = {}
    for (program, segment), snap in pending.items():
        try:
            rows, raw_total = _replay_one(snap)
        except Exception as e:
            done['%s/%s' % (program, segment)] = 'error: %s' % e
            continue
        measured = snap.get('measured_s')
        scale = ((measured / raw_total)
                 if measured and raw_total > 0 else 1.0)
        instances = {}
        for inst, c in rows.items():
            instances[inst] = {
                'type': c['type'], 'layer': c['layer'],
                'calls': c['calls'],
                'ms_per_step': round(c['raw_s'] * scale * 1e3, 6),
                'raw_ms': round(c['raw_s'] * 1e3, 6),
                'max_ms': round(c['max_s'] * 1e3, 6),
                'bytes_per_step': c['bytes'],
            }
        row = {
            'source': 'replay', 'step': snap['step'],
            'measured_ms': (round(measured * 1e3, 6)
                            if measured else None),
            'replay_raw_ms': round(raw_total * 1e3, 6),
            'normalized': bool(measured and raw_total > 0),
            'unattributed_ms': 0.0,
            'instances': instances,
        }
        _store_row(program, segment, row)
        done['%s/%s' % (program, segment)] = len(snap['ops'])
        monitor.add('opprof/replays')
    _publish_gauges()
    return done


def _store_row(program, segment, row):
    key = (str(program or '?'), str(segment))
    with _lock:
        if key not in _COSTS and len(_COSTS) >= _COSTS_CAP:
            _COSTS.pop(next(iter(_COSTS)))
        _COSTS[key] = row


def _publish_gauges():
    with _lock:
        rows = list(_COSTS.values())
    attributed = sum(c['ms_per_step'] for r in rows
                     for c in r['instances'].values())
    unattributed = sum(r.get('unattributed_ms') or 0.0 for r in rows)
    n_inst = sum(len(r['instances']) for r in rows)
    monitor.set_gauge('opprof/instances', float(n_inst))
    monitor.set_gauge('opprof/attributed_ms_total', round(attributed, 6))
    monitor.set_gauge('opprof/unattributed_ms_total',
                      round(unattributed, 6))


# ----------------------------------------------------- capture attribution
def record_capture(events, program='capture', steps=1):
    """Fold a chrome-trace capture (device profiler output or a merged
    ``tools/timeline.py`` timeline) into the registry: events group by
    their jit scope (the first ``tf_op`` path component — one group
    per compiled segment), each group runs through the per-instance
    attribution (fused-kernel splits + honest leftovers), totals
    divide by `steps` for per-step costs."""
    from . import profiler as _profiler
    groups = {}
    dropped_total = 0
    examined = 0
    for e in events:
        if not isinstance(e, dict):
            examined += 1        # attribution would count it as an
            dropped_total += 1   # examined-then-dropped event; keep
            continue             # the grouping filter just as honest
        if e.get('ph') != 'X':
            continue
        args = e.get('args') or {}
        tf_op = args.get('tf_op') if isinstance(args, dict) else None
        seg = 'device'
        if isinstance(tf_op, str) and tf_op:
            seg = tf_op.split(';', 1)[0].split(',', 1)[0] \
                       .split('/', 1)[0] or 'device'
        groups.setdefault(seg, []).append(e)
    steps = max(int(steps), 1)
    for seg, evs in sorted(groups.items()):
        recs, stats = _profiler.attribute_trace_events(
            evs, per_instance=True, with_stats=True)
        dropped_total += stats['dropped']
        instances = {}
        unattributed_s = 0.0
        for name, (calls, total_s, max_s, _min_s) in recs.items():
            if name.startswith('unattributed/'):
                unattributed_s += total_s
                continue
            typ, _idx = split_instance(name)
            known = _INSTANCE_OPS.get(name)
            instances[name] = {
                'type': typ, 'layer': known[1] if known else None,
                'calls': calls,
                'ms_per_step': round(total_s * 1e3 / steps, 6),
                'max_ms': round(max_s * 1e3, 6),
                'bytes_per_step': 0,
            }
        row = {
            'source': 'capture', 'steps': steps,
            'events': stats['events'], 'dropped': stats['dropped'],
            'unattributed_ms': round(unattributed_s * 1e3 / steps, 6),
            'instances': instances,
        }
        _store_row(program, seg, row)
        examined += stats['events']
    if examined:
        monitor.add('opprof/capture_events', float(examined))
    if dropped_total:
        monitor.add('opprof/dropped_events', float(dropped_total))
    _publish_gauges()
    return {'segments': len(groups), 'dropped': dropped_total}


# ------------------------------------------------------------- rollups
def _all_rows():
    with _lock:
        return {k: {kk: (dict(vv) if kk == 'instances' else vv)
                    for kk, vv in r.items()}
                for k, r in _COSTS.items()}


def rollup_by_type():
    """{op type: {'ms_per_step', 'calls', 'bytes_per_step'}} across
    every registry row."""
    out = {}
    for row in _all_rows().values():
        for cell in row['instances'].values():
            agg = out.setdefault(cell['type'],
                                 {'ms_per_step': 0.0, 'calls': 0,
                                  'bytes_per_step': 0})
            agg['ms_per_step'] = round(
                agg['ms_per_step'] + cell['ms_per_step'], 6)
            agg['calls'] += cell['calls']
            agg['bytes_per_step'] += cell.get('bytes_per_step', 0)
    return out


def rollup_by_layer():
    """{layer label: ms_per_step}; instances with no resolvable layer
    land under '(no layer)'."""
    out = {}
    for row in _all_rows().values():
        for inst, cell in row['instances'].items():
            layer = cell.get('layer')
            if layer is None:
                known = _INSTANCE_OPS.get(inst)
                layer = known[1] if known else None
            layer = layer or '(no layer)'
            out[layer] = round(out.get(layer, 0.0) +
                               cell['ms_per_step'], 6)
    return out


def report(limit=TOP_K):
    """The ``/statusz op_costs`` section: top-K instances by
    attributable ms/step, rollups, and per-segment source/agreement
    metadata.  JSON-able by construction."""
    rows = _all_rows()
    flat = []
    for (program, segment), row in rows.items():
        for inst, cell in row['instances'].items():
            flat.append(dict(cell, instance=inst, program=program,
                             segment=segment, source=row['source']))
    flat.sort(key=lambda c: (-c['ms_per_step'], c['instance']))
    total = sum(c['ms_per_step'] for c in flat)
    for c in flat:
        c['share_pct'] = round(100.0 * c['ms_per_step'] / total, 2) \
            if total > 0 else 0.0
    segments = []
    for (program, segment), row in rows.items():
        segments.append({
            'program': program, 'segment': segment,
            'source': row['source'],
            'instances': len(row['instances']),
            'attributed_ms': round(sum(
                c['ms_per_step']
                for c in row['instances'].values()), 6),
            'unattributed_ms': row.get('unattributed_ms', 0.0),
            'measured_ms': row.get('measured_ms'),
        })
    return {
        'enabled': enabled(),
        'top': flat[:max(int(limit), 1)],
        'segments': segments,
        'by_type': rollup_by_type(),
        'by_layer': rollup_by_layer(),
        'unattributed_ms': round(sum(
            r.get('unattributed_ms') or 0.0 for r in rows.values()), 6),
        'snapshots': len(_SNAPSHOTS),
    }


# ------------------------------------------------------------ worklist
def kernel_worklist(limit=TOP_K):
    """Rank contiguous same-segment op runs by attributable ms/step
    (tie: bytes moved, then name — deterministic).  A run is a maximal
    sequence of same-type instances adjacent in their segment's op
    order.  Each run cross-references the pallas dispatch registry's
    declared ``op_types`` coverage: ``covered_by`` names the kernel that
    already serves it (worklist readers skip those, or read them as
    validation that the ranking finds the kernels we already built)."""
    try:
        from ..ops.pallas import common as _pallas
    except Exception:
        _pallas = None
    runs = []
    for (program, segment), row in _all_rows().items():
        ordered = list(row['instances'].items())
        # order instances by block index where present (capture rows
        # iterate in attribution order; replay rows are already in
        # segment op order — indices make both deterministic)
        ordered.sort(key=lambda kv: (
            split_instance(kv[0])[1]
            if split_instance(kv[0])[1] is not None else 1 << 30))
        i = 0
        while i < len(ordered):
            j = i
            typ = ordered[i][1]['type']
            while j + 1 < len(ordered) and \
                    ordered[j + 1][1]['type'] == typ:
                nxt = split_instance(ordered[j + 1][0])[1]
                cur = split_instance(ordered[j][0])[1]
                if nxt is not None and cur is not None and \
                        nxt != cur + 1:
                    break   # same type but not contiguous in the block
                j += 1
            members = ordered[i:j + 1]
            ms = round(sum(c['ms_per_step'] for _, c in members), 6)
            nbytes = sum(c.get('bytes_per_step', 0)
                         for _, c in members)
            covered = None
            if _pallas is not None:
                try:
                    covered = _pallas.covering_kernel([typ])
                except Exception:
                    covered = None
            span = [split_instance(members[0][0])[1],
                    split_instance(members[-1][0])[1]]
            runs.append({
                'program': program, 'segment': segment,
                'op_type': typ,
                'ops': [m[0] for m in members],
                'span': span,
                'ms_per_step': ms,
                'bytes_per_step': nbytes,
                'source': row['source'],
                'covered_by': covered,
            })
            i = j + 1
    runs.sort(key=lambda r: (-r['ms_per_step'], -r['bytes_per_step'],
                             r['segment'], r['op_type'],
                             str(r['ops'])))
    runs = runs[:max(int(limit), 1)]
    for rank, r in enumerate(runs, 1):
        r['rank'] = rank
    monitor.set_gauge('opprof/worklist_candidates', float(len(runs)))
    return runs


def write_worklist(path='op_worklist.json', limit=TOP_K):
    """Emit the ranked worklist artifact ROADMAP item 5 consumes."""
    doc = {
        'version': 1,
        'generated_by': 'fluid.opprof',
        'candidates': kernel_worklist(limit),
        'by_type': rollup_by_type(),
        'by_layer': rollup_by_layer(),
        'segments': report(limit)['segments'],
    }
    with open(path, 'w') as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    return path


def http_report(replay=True, limit=TOP_K):
    """The ``/opprof`` endpoint body: replay whatever is stashed, then
    the full report + worklist."""
    out = {}
    if replay:
        try:
            out['replayed'] = replay_all()
        except Exception as e:   # a broken replay must not 500 the
            out['replay_error'] = str(e)     # whole report
    out['report'] = report(limit)
    out['worklist'] = kernel_worklist(limit)
    return out
