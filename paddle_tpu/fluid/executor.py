"""Executor: lowers Program segments into cached jitted XLA computations.

Reference contract: python/paddle/fluid/executor.py:680 (Executor.run) over
the C++ op-by-op interpreter (framework/executor.cc:449-455 hot loop).

TPU-native re-design: instead of interpreting ops one-by-one (which would
put a host round-trip between every op), the executor partitions each block
into maximal runs of device ops ("segments"), lowers every segment into ONE
jitted XLA computation by chaining the ops' JAX lowering rules through a
functional environment, and caches the result.  This is the whole-graph
analog of the reference's nGraph engine-op precedent
(operators/ngraph/ngraph_engine.h) promoted to be THE execution model:
  - op granularity exists only at trace time; XLA fuses across ops
  - buffer liveness / garbage collection (framework/garbage_collector.h)
    is subsumed by XLA buffer assignment: only segment outputs materialize
  - in-place optimizer updates become input->output donated buffers
Host ops (feed/fetch/save/load/print) cut segments and run on the host.
"""

import contextlib
import time as _time_mod
import weakref

import numpy as np
import jax

from . import comms
from . import compile_cache
from . import core
from . import faultinject as _finject
from . import framework
from . import memviz as _memviz
from . import monitor
from . import supervisor as _sup
from . import timeseries as _tseries
from . import trace as _trace
from .flags import get_flag
from ..ops import registry


def _stat_nbytes(v):
    """Host-side byte count of a feed/fetch value for the monitor
    counters.  Runs per feed var per step, so it must stay O(1):
    jax.Array and np.ndarray expose nbytes directly; anything else
    (lists, scalars) counts as 0 rather than paying an np.asarray
    materialization just for a stats counter — the executor converts
    those exactly once on its own path."""
    if isinstance(v, core.LoDTensor):
        v = v.data
    n = getattr(v, 'nbytes', None)
    return float(n) if n is not None else 0.0


class _Segment(object):
    __slots__ = ('ops', 'input_names', 'state_names', 'output_names',
                 'compiled', 'bucket_ops', 'prefer_test', 'binder',
                 'pbinder', 'health_params', 'comms_key')

    def __init__(self, ops):
        self.ops = ops
        self.input_names = []
        self.state_names = []
        self.output_names = []
        # ops whose max_trip_count is stamped per step by the
        # auto-bucket counting pass (static membership, computed once)
        self.bucket_ops = [op for op in ops
                           if op.attrs.get('__bucket_group__')
                           is not None]
        # executables: LRU keyed by the lowering-flag tuple (+ bucket
        # sizes, + per-shape AOT spec keys when the compile plane is
        # on) — bucketing/re-tracing would otherwise grow this without
        # bound in a long-running service
        self.compiled = compile_cache.LRUCache(
            lambda: get_flag('FLAGS_segment_cache_capacity', 32),
            'executor/segment_cache_evictions')
        self.prefer_test = False
        # steady-state argument binders (built lazily at first run):
        # `binder` serves the single-device executor (staged feeds),
        # `pbinder` the parallel/collective runners (raw feeds)
        self.binder = None
        self.pbinder = None
        # (param names this segment updates, param->grad map) for the
        # FLAGS_health_summaries reductions; resolved lazily
        self.health_params = None
        # fluid.comms registry key (the compile fingerprint the
        # parallel/collective runners trace under): dispatches look up
        # the segment's collective records through it
        self.comms_key = None


class _Plan(list):
    """An execution plan: _Segment | ('host', op) | ('bucket', op)
    items, plus plan-level precomputation.  `device_feed_names` is the
    union of every segment's state/input names (and bucket-count
    reads): only feeds in it are staged onto the device — a feed read
    exclusively by host ops must stay host-side, or it would cross to
    the device and straight back every step.  `donatable_feed_names`
    are the fed STATE names with exactly ONE consumer in the plan (and
    no host/bucket items keeping feeds visible in the scope): only
    those may be donated by pointer — any shared buffer must be copied
    before donation or a later consumer reads a deleted array."""

    __slots__ = ('device_feed_names', 'donatable_feed_names')


class _BindTable(object):
    """Bindings of one (segment, feed keyset): which argument names
    come from the feed dict, and — for scope-sourced names — WHICH
    scope dict owns each one.  Owner dicts are resolved once and
    revalidated against the scope's structural chain token, so the
    steady-state bind never walks the scope parent chain."""

    __slots__ = ('state_feed', 'data_feed', 'state_scope', 'data_scope',
                 'scope_ref', 'token', 'state_slots', 'data_slots')

    def __init__(self, seg, keyset):
        self.state_feed = tuple(n for n in seg.state_names
                                if n in keyset)
        self.data_feed = tuple(n for n in seg.input_names if n in keyset)
        self.state_scope = tuple(n for n in seg.state_names
                                 if n not in keyset)
        self.data_scope = tuple(n for n in seg.input_names
                                if n not in keyset)
        self.scope_ref = None
        self.token = -1
        self.state_slots = ()
        self.data_slots = ()


def _uninitialized(name):
    return RuntimeError(
        'Variable %s is not initialized: feed it or run the startup '
        'program first' % name)


# concrete device-array class for hot-loop type checks: `type(v) is
# _ArrayImpl` costs ~60ns where `isinstance(v, jax.Array)` pays the
# ABC __instancecheck__ (~1us) — per name per step, that dominates the
# bind at a few hundred parameters
from jax._src.array import ArrayImpl as _ArrayImpl

_process_default_device = None


def _is_default_device(device):
    """True iff entering jax.default_device(device) would be a no-op:
    `device` is already where jax places un-pinned computations.  The
    context costs ~0.1 ms per jit call on the dispatch path, so the
    steady-state run loop skips it whenever it cannot matter."""
    cfg = jax.config.jax_default_device
    if cfg is not None:
        return cfg == device
    global _process_default_device
    if _process_default_device is None:
        _process_default_device = jax.devices()[0]
    return device == _process_default_device


def _normalize_feed_value(v):
    """The `_lookup_input` feed conversion, as a standalone step for
    binders fed RAW (un-staged) feed dicts."""
    if isinstance(v, core.LoDTensor):
        v = v.data
    if isinstance(v, jax.Array):
        return v
    return np.asarray(v)


class _SegmentBinder(object):
    """Per-(plan, segment) argument binder — the steady-state fast
    path's core.  At first use per feed keyset it precompiles the
    name->source split (feed vs scope) and resolves scope names to
    their owning `_vars` dicts; each later step binds `state`/`data`
    with one dict read per name — no per-step dict comprehensions over
    `_lookup_input`, no isinstance chains for device-resident values,
    no scope parent-chain walks.  Donated-state safety is a
    once-per-buffer ownership check (core.mark_owned/is_owned) instead
    of an unconditional per-step device copy."""

    __slots__ = ('_seg', '_tables', '_raw_feed')

    _EMPTY = frozenset()

    def __init__(self, seg, raw_feed=False):
        self._seg = seg
        self._tables = {}
        self._raw_feed = raw_feed

    def _resolve(self, tab, scope):
        """Slow path: walk the scope chain once per name and cache the
        owning dicts; counted so tools/check_hot_path.py can assert the
        steady state never comes back here."""
        for names, slot_attr in ((tab.state_scope, 'state_slots'),
                                 (tab.data_scope, 'data_slots')):
            slots = []
            for n in names:
                owner = scope._owner_vars(n)
                if owner is None:
                    raise _uninitialized(n)
                slots.append((n, owner))
            setattr(tab, slot_attr, tuple(slots))
        tab.scope_ref = weakref.ref(scope)
        tab.token = scope._chain_token()
        monitor.add('executor/scope_lookups',
                    float(len(tab.state_scope) + len(tab.data_scope)))

    def bind(self, feed, scope, donate_feed_state=True):
        """One step's (state, data) argument dicts for the segment."""
        t0 = _time_mod.perf_counter()
        keyset = frozenset(feed) if feed else self._EMPTY
        # tables key on (feed keyset, scope identity): a multi-tenant
        # server alternating per-tenant scopes over ONE resident
        # program must keep each tenant's resolved owner slots — a
        # keyset-only table would re-walk the scope chain on every
        # tenant switch.  id() reuse after a scope dies is caught by
        # the weakref revalidation below; the table map itself is
        # bounded so a scope-churning caller cannot grow it forever.
        tkey = (keyset, id(scope))
        tab = self._tables.get(tkey)
        if tab is None:
            if len(self._tables) >= 256:
                self._tables.clear()
            tab = self._tables[tkey] = _BindTable(self._seg, keyset)
        ref = tab.scope_ref
        if ref is not None and ref() is scope and \
                tab.token == scope._chain_token():
            monitor.add('executor/fastpath_hits')
        else:
            self._resolve(tab, scope)
        state = {}
        data = {}
        for out, slots in ((state, tab.state_slots),
                           (data, tab.data_slots)):
            for n, owner in slots:
                v = owner[n]
                if type(v) is _ArrayImpl:
                    out[n] = v       # device-resident: pointer-passing
                elif v is None:
                    raise _uninitialized(n)
                elif isinstance(v, jax.Array):
                    out[n] = v       # exotic array subclass
                else:
                    out[n] = core.as_array(v)
        raw = self._raw_feed
        for n in tab.state_feed:
            v = feed[n]
            if raw:
                v = _normalize_feed_value(v)
            if donate_feed_state and isinstance(v, jax.Array) and \
                    not core.is_owned(v):
                # state buffers are donated to the jitted step; a
                # CALLER-owned fed array must survive it — copy.
                # Runtime-staged buffers (is_owned) pass by pointer.
                v = jax.numpy.array(v, copy=True)
            state[n] = v
        for n in tab.data_feed:
            v = feed[n]
            data[n] = _normalize_feed_value(v) if raw else v
        t1 = _time_mod.perf_counter()
        monitor.observe('executor/bind_seconds', t1 - t0)
        _trace.record('bind', t0, t1)
        return state, data


class FetchHandle(object):
    """A fetch resolving asynchronously (`return_numpy='async'`): the
    device->host copy is REQUESTED at construction without blocking
    dispatch of the next step; `as_numpy()` blocks on it.
    `np.asarray(handle)` also resolves it.  The handle holds the live
    device buffer, not a snapshot: resolve it BEFORE running a step
    that donates the fetched variable (e.g. fetching a parameter the
    next step updates in place), or resolution fails on the deleted
    buffer."""

    __slots__ = ('_val', '_np', '_resolver')

    def __init__(self, val, resolver=None):
        val = core.as_array(val)
        self._val = val
        self._np = None
        self._resolver = resolver
        if isinstance(val, jax.Array):
            try:
                val.copy_to_host_async()
            except Exception:
                pass  # non-prefetchable array kinds: as_numpy still works

    @property
    def value(self):
        """The raw device-side value, unresolved."""
        return self._val

    def as_numpy(self):
        if self._np is None:
            t0 = _time_mod.perf_counter()
            try:
                if self._resolver is not None:
                    self._np = self._resolver(self._val)
                else:
                    self._np = np.asarray(self._val)
            except RuntimeError as e:
                if 'deleted' in str(e).lower():
                    raise RuntimeError(
                        'async fetch resolved after its buffer was '
                        'donated: a later step updated this variable '
                        'in place.  Call as_numpy() before running a '
                        'step that donates the fetched var, or fetch '
                        'with return_numpy=True.') from e
                raise
            t1 = _time_mod.perf_counter()
            monitor.observe('executor/fetch_blocked_seconds', t1 - t0)
            _trace.record('fetch_d2h', t0, t1)
        return self._np

    def __array__(self, dtype=None):
        arr = self.as_numpy()
        return arr.astype(dtype) if dtype is not None else arr


def _release_donated_state(state):
    """Drop the LAST references to a step's donated state buffers,
    visibly.  Once the outputs are published to the scope, this dict is
    all that keeps the previous step's donated buffers alive — and
    dropping a donated buffer whose defining execution is still in
    flight blocks in the runtime's deleter until the step completes
    (measured ~the whole step on the CPU backend).  Left to frame
    teardown, that wait bills to no statement at all: it was THE
    unattributed gap between dispatch and fetch this tracer was built
    to expose.  Same work either way; now it has a name, a histogram
    and a span.  Shared by the single-device executor and the
    parallel/collective runners."""
    t0 = _time_mod.perf_counter()
    state.clear()
    t1 = _time_mod.perf_counter()
    monitor.observe('executor/state_release_seconds', t1 - t0)
    _trace.record('state_release', t0, t1)


def _survivable_copy(v):
    """A copy of a segment argument that survives the step: state
    buffers are DONATED to the executable (deleted once it runs), so
    NaN-provenance replay and update-ratio summaries must snapshot
    them beforehand.  Device values copy on device (async — the copy
    dispatches ahead of the step and never blocks it); everything else
    is already host-owned."""
    if isinstance(v, jax.Array):
        try:
            return jax.numpy.array(v, copy=True)
        except Exception:
            return np.asarray(v)
    return v


def _segment_label(seg, comms_key=None):
    """A segment's name in watchdog dumps, memory rows and the
    first-run list.  One chip: the same ops planned for another fetch
    list are another executable (the step that fetches the loss and
    the quiet one), so the count of outputs is part of the name."""
    if comms_key is not None:
        return '%dops@%s' % (len(seg.ops), str(comms_key)[:8])
    names = sorted(seg.output_names)
    return '%dops:%s+%d' % (len(seg.ops), ','.join(names[:3]),
                            len(names))


def _dispatch_span(comms_key, records):
    """The segment-dispatch trace span, annotated with the segment's
    collective profile (payload/wire bytes, per-kind call counts, mesh
    axes, participants) when it has one.  No span kwargs otherwise:
    disabled-mode cost must stay one truth test, one call and one
    global load, allocation free (the merged timeline names the segment
    anyway via the jit scope); the profile itself is the memoized
    summary of the frozen records."""
    if records and _trace.is_active():
        annot = comms.summary_for(comms_key)
        if annot:
            return _trace.span('dispatch', **annot)
    return _trace.span('dispatch')


def _segment_health_names(seg):
    """(params this segment updates, param->grad name map) for the
    tensor-health summaries — resolved once per segment from the
    owning program."""
    program = seg.ops[0].block.program
    pnames = set(p.name for p in program.all_parameters())
    gmap = getattr(program, '_grad_name_map', {})
    updated = sorted(pnames & set(seg.output_names))
    return (updated, {p: g for p, g in gmap.items() if p in pnames})


def _op_reads(op):
    return [n for ns in op.inputs.values() for n in ns]


def _op_writes(op):
    return [n for ns in op.outputs.values() for n in ns]


def _op_dep_reads(op):
    """Reads for the plan dataflow analysis: the declared input slots,
    plus gradient-carrying while loops' carries — _lower_while seeds
    loop state from the env even when the body only WRITES the var, so
    its initializer in an upstream segment must stay live."""
    names = list(_op_reads(op))
    names += op.attrs.get('__carry_names__', ())
    return names


def _recompute_runs(ops):
    """``ops`` cut into runs: a maximal run of neighbours that carry
    one ``__recompute__`` group (backward.recompute_guard), every
    other op alone."""
    runs = []
    for op in ops:
        group = op.attrs.get('__recompute__')
        if group is not None and runs and \
                runs[-1][0].attrs.get('__recompute__') == group:
            runs[-1].append(op)
        else:
            runs.append([op])
    return runs


def _lower_recomputed(run, env, step, prefer_test, stop_names=()):
    """One recompute group under ``jax.checkpoint``: what it reads of
    the env goes in, what it writes comes out, and a differentiation
    of the trace keeps the former and computes the rest again.
    ``stop_names``: what the group writes that the backward pass
    treats as constant (a router's indices and loads inside a decoder
    block), pinned as it is written, before a consumer reads it.
    Counter ``executor/recompute_groups``: one a lowering."""
    monitor.add('executor/recompute_groups', 1)
    written, reads = set(), []
    for op in run:
        for n in _op_reads(op):
            if n not in written and n in env and n not in reads:
                reads.append(n)
        written.update(_op_writes(op))

    def group(values):
        local = dict(values)
        for op in run:
            _lower_op(op, local, step, prefer_test)
            for n in _op_writes(op):
                if n in stop_names and n in local:
                    local[n] = jax.lax.stop_gradient(local[n])
        return {n: local[n] for n in written if n in local}

    env.update(jax.checkpoint(group)({n: env[n] for n in reads}))


def _lower_ops(ops, env, step, prefer_test):
    """Run a list of ops' lowering rules over a functional env."""
    # a loop whose gradient op is lowered in this same trace hands it
    # the vjp of its one forward scan (the per-op path; under the
    # whole-program vjp the grad ops are never lowered)
    differentiated_here = set(op.attrs['sub_block'] for op in ops
                              if op.type == 'while_grad')
    for run in _recompute_runs(ops):
        if '__recompute__' in run[0].attrs:
            _lower_recomputed(run, env, step, prefer_test)
        else:
            (op,) = run
            _lower_op(op, env, step, prefer_test,
                      op.attrs.get('sub_block') in differentiated_here)


def _lower_op(op, env, step, prefer_test, keep_vjp=False):
    """One op's lowering rule over a functional env (``keep_vjp``: a
    `while` whose gradient op follows in the same trace)."""
    CF_LOWERINGS = {'while': _lower_while,
                    'conditional_block': _lower_conditional_block,
                    'while_grad': _lower_while_grad,
                    'conditional_block_grad': _lower_conditional_block_grad}
    cf = CF_LOWERINGS.get(op.type)
    if cf is not None:
        with jax.named_scope(op.type):
            if op.type == 'while':
                cf(op, env, step, prefer_test, keep_vjp=keep_vjp)
            else:
                cf(op, env, step, prefer_test)
        return
    opdef = registry.get(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        if not names:
            continue
        try:
            ins[slot] = [env[n] for n in names]
        except KeyError as e:
            err = RuntimeError(
                'op %s reads undefined var %s' % (op.type, e))
            _add_note(err, _op_error_context(op, {}))
            raise err from e
    ctx = registry.LowerCtx(step, op.attrs.get('__op_seed__', 0),
                            prefer_test)
    try:
        # per-op trace attribution: the reference wraps every op run
        # in a profiler RecordEvent (framework/operator.cc:170); here
        # the scope name flows into XLA op metadata so Perfetto
        # traces and HLO dumps read as fluid op names
        with jax.named_scope(op.type):
            outs = opdef.run(ctx, ins, op.attrs)
    except Exception as e:
        # enforce-style error context (reference: PADDLE_ENFORCE +
        # op_callstack, platform/enforce.h, framework/op_call_stack.h)
        _add_note(e, _op_error_context(op, ins))
        raise
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for n, v in zip(names, vals):
            env[n] = v


def _subblock_carry(sub_ops, env):
    """Names the sub-block writes that exist in the parent env: the loop
    state (reference: while_op keeps them in step scopes,
    operators/controlflow/while_op.cc)."""
    writes = []
    seen = set()
    for op in sub_ops:
        for n in _op_writes(op):
            if n in env and n not in seen:
                seen.add(n)
                writes.append(n)
    return writes


def _loop_vjp_name(op):
    """Where a loop's forward op leaves the vjp of its scan for its
    gradient op, in the env of one trace (never a segment output)."""
    return 'while@VJP@%d' % op.attrs['sub_block']


def _lower_while(op, env, step, prefer_test, keep_vjp=False):
    """while op -> lax.while_loop.  Static shapes; parent vars the
    sub-block only reads are captured as closure constants.

    When the loop carries gradients (__needs_grad__, set by
    backward._control_flow_backward) it lowers instead to a bounded,
    masked lax.scan — semantically `for i in range(max_trip_count):
    carry = cond ? body(carry) : carry` — which reverse mode can
    differentiate.  The scan runs ONCE a step either way: under the
    whole-program vjp (_make_segment_fn) it is part of the one
    differentiated forward, and its residuals are the scan's own; on
    the per-op path (`keep_vjp`: the while_grad op is lowered in the
    same trace) the scan is taken under jax.vjp HERE and the vjp kept
    in the env for the grad op, which then replays nothing.  The carry
    ENTRY values are stashed for a grad op that does have to replay
    (one cut into another segment; the reference keeps them in step
    scopes: operators/controlflow/while_op.cc)."""
    import jax
    import jax.numpy as jnp
    program = op.block.program
    sub = program.blocks[op.attrs['sub_block']]
    cond_name = op.input('Condition')[0]
    if op.attrs.get('__needs_grad__'):
        carry_names = list(op.attrs['__carry_names__'])
        for n, en in zip(carry_names, op.attrs['__entry_names__']):
            if n not in env:
                raise RuntimeError(
                    'while loop state %s is not initialized before the '
                    'loop' % n)
            env[en] = env[n]
        init = {n: env[n] for n in carry_names}
        max_t = int(op.attrs['max_trip_count'])
        if not keep_vjp:
            env.update(_while_scan(sub.ops, carry_names, cond_name, init,
                                   env, max_t, step, prefer_test))
            return
        float_carries = list(op.attrs['__float_carries__'])
        closure = {n: jnp.asarray(env[n])
                   for n in op.attrs['__closure_names__']}
        outer = {n: v for n, v in env.items() if n not in closure}

        def fwd(entry_carry, closure):
            final = _while_scan(sub.ops, carry_names, cond_name,
                                entry_carry, dict(outer, **closure),
                                max_t, step, prefer_test)
            return ({n: final[n] for n in float_carries},
                    {n: v for n, v in final.items()
                     if n not in float_carries})

        floats, env[_loop_vjp_name(op)], rest = jax.vjp(
            fwd, {n: jnp.asarray(v) for n, v in init.items()}, closure,
            has_aux=True)
        env.update(floats)
        env.update(rest)
        return
    carry_names = _subblock_carry(sub.ops, env)
    if cond_name not in carry_names:
        carry_names.append(cond_name)

    def cond_fn(carry):
        return jnp.asarray(carry[cond_name]).reshape(())

    def body_fn(carry):
        local = dict(env)
        local.update(carry)
        _lower_ops(sub.ops, local, step, prefer_test)
        # carries must be dtype-stable across iterations: AMP-marked ops
        # inside the body may emit bf16 from an f32 entry carry (the
        # __amp__/__amp_gray__ lowerings), which lax.while_loop rejects
        # as a carry-aval mismatch — pin to the entry dtype, the same
        # rule _while_scan and conditional_block already apply
        return {n: jnp.asarray(local[n]).astype(
            jnp.asarray(carry[n]).dtype) for n in carry_names}

    init = {n: env[n] for n in carry_names}
    final = jax.lax.while_loop(cond_fn, body_fn, init)
    env.update(final)


def _while_scan(sub_ops, carry_names, cond_name, init, outer_env, max_t,
                step, prefer_test):
    """Bounded masked-scan rendering of a while loop: every iteration
    computes the body, but the carry only advances while the condition
    holds.  Unlike lax.while_loop this is reverse-mode differentiable
    (lax.scan saves per-iteration residuals for the vjp).

    Truncation guard: if the condition is STILL true after max_t
    iterations (max_trip_count underestimated the real trip count), the
    float carries are poisoned with NaN instead of silently returning
    the truncated recurrence — the failure is loud (NaN loss;
    FLAGS_check_nan_inf names the var) rather than numerically wrong.
    When the loop exits within the bound the guard adds exact 0.0."""
    import jax
    import jax.numpy as jnp

    init = {n: jnp.asarray(init[n]) for n in carry_names}

    def body(carry, _):
        pred = jnp.asarray(carry[cond_name]).reshape(()).astype(bool)
        local = dict(outer_env)
        local.update(carry)
        # the loop's body in the optimised HLO, forward and (under
        # transpose(...)) backward: fluid.profiler.loop_tables
        with jax.named_scope(registry.LOOP_BODY_SCOPE):
            _lower_ops(sub_ops, local, step, prefer_test)
        merged = {}
        for n in carry_names:
            new = jnp.asarray(local[n]).astype(carry[n].dtype)
            merged[n] = jnp.where(pred, new, carry[n])
        return merged, None

    # gauge `loop/trips`: the body executions of the traced program's
    # differentiable loops, a step (every trip of a masked scan runs)
    registry.trace_sum('loop/trips', max_t)
    final, _ = jax.lax.scan(body, init, None, length=max_t)
    truncated = jnp.asarray(final[cond_name]).reshape(()).astype(bool)
    poison = jnp.where(truncated, jnp.float32(jnp.nan), jnp.float32(0))
    out = {}
    for n in carry_names:
        v = final[n]
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = v + poison.astype(v.dtype)
        out[n] = v
    return out


def _control_flow_grad(op, env, make_fwd, kept_vjp=None):
    """Shared plumbing for while_grad / conditional_block_grad: collect
    the cotangents, pull them back and write the grads.  With
    ``kept_vjp`` (a loop whose forward op ran in this trace:
    _lower_while) nothing is run again; else entries + closure values
    come from the env and the forward (make_fwd builds it from the
    collected pieces) is re-run under jax.vjp.
    The op wiring comes from backward._control_flow_backward."""
    import jax
    import jax.numpy as jnp
    carry_names = list(op.attrs['__carry_names__'])
    float_carries = list(op.attrs['__float_carries__'])
    closure_names = list(op.attrs['__closure_names__'])

    if kept_vjp is None:
        entries = {n: jnp.asarray(env[en])
                   for n, en in zip(carry_names, op.input('Entry'))}
        base_env = {n: env[n] for n in op.input('X')
                    if n in env and n not in carry_names
                    and n not in closure_names}
        closure_vals = {n: jnp.asarray(env[n]) for n in closure_names}
        fwd = make_fwd(carry_names, float_carries, base_env)
        out, kept_vjp = jax.vjp(fwd, entries, closure_vals)
    else:
        out = {n: env[n] for n in float_carries}
    cots = {}
    for n, g in zip(float_carries, op.input('GRAD::Out')):
        cots[n] = jnp.asarray(env[g]).astype(out[n].dtype).reshape(
            out[n].shape)
    d_entry, d_closure = kept_vjp(cots)
    for n, gname in zip(float_carries, op.output('GRAD::Entry')):
        env[gname] = d_entry[n]
    for n, gname in zip(closure_names, op.output('GRAD::X')):
        env[gname] = d_closure[n]


def _lower_while_grad(op, env, step, prefer_test):
    """Gradient of a while op.  Gradients flow to the entry values of
    the loop state and to closure reads (e.g. weights used inside the
    body: one gradient, the sum over the trips).  Where the forward op
    was lowered in this trace it left the vjp of its scan
    (_lower_while `keep_vjp`) and the loop's forward runs once a step;
    a grad op alone in its segment re-runs the bounded masked scan
    from the saved carry entries under jax.vjp.  Reference analog:
    WhileGradOp replaying step scopes
    (operators/controlflow/while_op.cc)."""
    program = op.block.program
    sub = program.blocks[op.attrs['sub_block']]
    cond_name = op.input('Condition')[0]
    max_t = int(op.attrs['max_trip_count'])

    def make_fwd(carry_names, float_carries, base_env):
        def fwd(entry_carry, closure):
            outer = dict(base_env)
            outer.update(closure)
            final = _while_scan(sub.ops, carry_names, cond_name,
                                entry_carry, outer, max_t, step,
                                prefer_test)
            return {n: final[n] for n in float_carries}
        return fwd

    _control_flow_grad(op, env, make_fwd,
                       env.pop(_loop_vjp_name(op), None))


def _lower_conditional_block_grad(op, env, step, prefer_test):
    """Gradient of a conditional_block: jax.vjp over `lax.cond(pred,
    sub_block, identity, entries)` from the saved carry entries.
    Reference analog: ConditionalBlockGradOp
    (operators/controlflow/conditional_block_op.cc)."""
    import jax
    import jax.numpy as jnp
    program = op.block.program
    sub = program.blocks[op.attrs['sub_block']]
    pred = jnp.asarray(env[op.input('Cond')[0]]).reshape(())

    def make_fwd(carry_names, float_carries, base_env):
        def fwd(entry_carry, closure):
            outer = dict(base_env)
            outer.update(closure)

            def true_fn(carry):
                local = dict(outer)
                local.update(carry)
                _lower_ops(sub.ops, local, step, prefer_test)
                return {n: jnp.asarray(local[n]).astype(carry[n].dtype)
                        for n in carry_names}

            final = jax.lax.cond(pred, true_fn, lambda c: dict(c),
                                 {n: jnp.asarray(entry_carry[n])
                                  for n in carry_names})
            return {n: final[n] for n in float_carries}
        return fwd

    _control_flow_grad(op, env, make_fwd)


def _lower_conditional_block(op, env, step, prefer_test):
    """conditional_block -> lax.cond with an identity false branch
    (reference: operators/controlflow/conditional_block_op.cc).  With
    __needs_grad__ the carry ENTRY values are stashed for the grad op
    (_lower_conditional_block_grad)."""
    import jax
    import jax.numpy as jnp
    program = op.block.program
    sub = program.blocks[op.attrs['sub_block']]
    cond_name = op.input('Cond')[0]
    if op.attrs.get('__needs_grad__'):
        carry_names = list(op.attrs['__carry_names__'])
        for n, en in zip(carry_names, op.attrs['__entry_names__']):
            if n not in env:
                raise RuntimeError(
                    'conditional_block output %s is not initialized '
                    'before the branch' % n)
            env[en] = env[n]
    else:
        carry_names = _subblock_carry(sub.ops, env)

    def true_fn(carry):
        local = dict(env)
        local.update(carry)
        _lower_ops(sub.ops, local, step, prefer_test)
        return {n: jnp.asarray(local[n]).astype(
            jnp.asarray(carry[n]).dtype) for n in carry_names}

    init = {n: jnp.asarray(env[n]) for n in carry_names}
    pred = jnp.asarray(env[cond_name]).reshape(())
    final = jax.lax.cond(pred, true_fn, lambda c: dict(c), init)
    env.update(final)


def _add_note(e, note):
    """Attach context to an exception (PEP 678).  Interpreters without
    add_note (< 3.11) get the same `__notes__` list stamped directly —
    tooling (pytest, the error-context tests, incident reports) reads
    the attribute, even though the 3.10 traceback renderer won't print
    it.  Never raises: the real error must never be masked."""
    if hasattr(e, 'add_note'):
        e.add_note(note)
        return
    try:
        notes = getattr(e, '__notes__', None)
        if notes is None:
            notes = e.__notes__ = []
        notes.append(note)
    except Exception:
        pass


def _flight_dump_note(tag, extra=None):
    """Dump the flight recorder for an incident (a failed dispatch, a
    NaN trip): the line that names the dump, None while the tracer is
    off."""
    dump = _trace.dump_on_error(tag, extra=extra)
    if dump:
        return ('trace flight recorder (last %d steps) dumped to %s'
                % (len(_trace.steps()), dump))


def _op_error_context(op, ins):
    """One text block describing the failing op: type, input
    shapes/dtypes, and the user callstack recorded at op creation."""
    lines = ['error raised while lowering op [%s]' % op.type]
    for slot, names in op.inputs.items():
        vals = ins.get(slot, [])
        for n, v in zip(names, vals):
            lines.append('  input %s[%s]: shape=%s dtype=%s'
                         % (slot, n, getattr(v, 'shape', '?'),
                            getattr(v, 'dtype', '?')))
    stack = op.attrs.get('__op_callstack__') or []
    if stack:
        lines.append('op created at (most recent call first):')
        lines.extend('  ' + s for s in stack)
    return '\n'.join(lines)


def _feed_mismatch_note(program, feed):
    """Diagnostic for segment failures: list feeds whose shapes diverge
    from their declared layers.data specs.  Declared shapes are
    ADVISORY in fluid (the bucketing front-end legitimately feeds
    re-bucketed dims and the executor re-traces per shape), so
    divergence is not an error by itself — but when a segment fails
    with a raw XLA shape error, the diverging feed is almost always
    the cause, and naming it turns a dot_general dump into a usable
    message (reference: data_feeder/enforce discipline)."""
    block = program.global_block()
    lines = []
    for name, val in sorted(feed.items()):
        var = block._find_var_recursive(name)
        if var is None or getattr(var, 'lod_level', 0):
            continue
        spec = getattr(var, 'shape', None)
        if isinstance(val, core.LoDTensor):
            val = val.data
        try:
            arr_shape = np.shape(val)
        except Exception:
            arr_shape = None
        if not spec or arr_shape is None or arr_shape == ():
            continue
        spec = tuple(int(s) for s in spec)
        ok = len(arr_shape) == len(spec) and all(
            s < 0 or s == d for s, d in zip(spec, arr_shape))
        if not ok and len(arr_shape) == len(spec) - 1 and \
                spec[-1] == 1:
            # label convention: [N] feeding a [-1, 1] var
            ok = all(s < 0 or s == d
                     for s, d in zip(spec[:-1], arr_shape))
        if not ok:
            lines.append("  feed '%s': shape %s, declared %s"
                         % (name, tuple(arr_shape), spec))
    if lines:
        return ('feeds diverging from their declared shapes (-1 dims '
                'accept any size; a diverging feed is the usual cause '
                'of XLA shape errors):\n' + '\n'.join(lines))
    return None


def _wpg_partition(segment):
    """Whole-program-grad eligibility + partition for a train segment
    (FLAGS_whole_program_grad): instead of lowering each synthesized
    *_grad op — whose per-op jax.vjp replays give XLA hundreds of
    small vjp islands to fuse — lower ONLY the forward/optimizer ops
    and take one jax.vjp over the whole forward region.  Same math
    (the per-op grads ARE vjp of the same lowerings, and stochastic
    ops key their RNG on (op_seed, step) so replay and whole-trace
    see identical masks), but XLA schedules the backward as one graph
    — the hand-written-JAX shape.  Measured motivation: BERT-s2048 at
    byte/FLOP parity with its hand-JAX ceiling still ran ~10% slower
    on a diffuse small-fusion tail (pre-round reading).

    Eligible programs may contain control flow (while/conditional_block
    lower to differentiable masked scans / lax.cond when they carry
    gradients) and multiple losses (one seed fill each).  Returns None
    when the segment is ineligible: no backward region, a backward
    region holding ops the single vjp does NOT reproduce (e.g.
    RecomputeOptimizer's re-emitted forward spans, whose whole point —
    freeing activations — the vjp would silently defeat), or a needed
    gradient whose primal is not a segment boundary input."""
    ops = segment.ops
    roles = [op.attrs.get('__op_role__', 'forward') for op in ops]
    if 'backward' not in roles:
        return None
    first_bwd = roles.index('backward')
    pre = ops[:first_bwd]
    bwd = [op for op in ops[first_bwd:]
           if op.attrs.get('__op_role__') == 'backward']
    post = [op for op in ops[first_bwd:]
            if op.attrs.get('__op_role__') != 'backward']
    program = ops[0].block.program
    gmap = getattr(program, '_grad_name_map', {})
    rev = {g: p for p, g in gmap.items()}
    # The backward region must consist ONLY of ops the one jax.vjp
    # replaces: synthesized *_grad ops, the autodiff seed fills
    # (append_backward's fill_constant of loss@GRAD — one per loss),
    # zero-cotangent placeholders, and grad-accumulation sums.  Any
    # other backward-role op has semantics the vjp does not reproduce
    # — notably RecomputeOptimizer's re-emitted forward spans and
    # recompute_barrier ops (backward.py _RecomputePlan), which exist
    # to FREE activation memory: replacing them with a vjp that keeps
    # every activation as a residual would silently defeat recompute.
    seeds = []
    for op in bwd:
        t = op.type
        if t.endswith('_grad') or t == 'fill_zeros_like':
            continue
        ws = _op_writes(op)
        if t == 'sum' and ws and all(n in rev for n in ws):
            continue  # gradient aggregation: the vjp sums contributions
        if t in ('fill_constant', 'fill_any_like') and len(ws) == 1 \
                and ws[0] in rev:
            seeds.append((rev[ws[0]], ws[0],
                          float(op.attrs.get('value', 1.0))))
            continue
        return None
    if not seeds:
        return None
    if len(set(p for p, _, _ in seeds)) != len(seeds):
        return None  # two seeds of one root: ambiguous, keep per-op
    pre_writes = set()
    pre_reads = set()
    for op in pre:
        pre_writes.update(_op_writes(op))
        pre_reads.update(_op_dep_reads(op))
    if any(p not in pre_writes for p, _, _ in seeds):
        # a loss whose forward region is not in this segment (e.g. a
        # second loss built AFTER the first backward): this segment
        # cannot re-derive it, keep the per-op path
        return None
    # Each grad name belongs to ONE loss's backward walk (multi-loss
    # programs append one fill + walk per append_backward call, in
    # program order): record the seed region that (last) writes it, so
    # the vjp can deliver THAT loss's gradient — not the total over
    # all seeds, which is what a single cotangent bundle would give
    # and which per-op semantics only matches for single-loss programs.
    bwd_writes = set()
    region_of = {}
    region = -1
    seed_fill_names = set(g for _, g, _ in seeds)
    for op in bwd:
        ws = _op_writes(op)
        if op.type in ('fill_constant', 'fill_any_like') and ws and \
                ws[0] in seed_fill_names:
            region += 1
        bwd_writes.update(ws)
        for n in ws:
            region_of[n] = max(region, 0)
    later_reads = set()
    for op in post:
        later_reads.update(_op_dep_reads(op))
    needed = sorted(bwd_writes & (later_reads |
                                  set(segment.output_names)))
    boundary = set(segment.state_names) | set(segment.input_names)
    seed_gnames = {g: (p, v) for p, g, v in seeds}
    grad_to_primal = {}
    for g in needed:
        if g in seed_gnames:
            continue  # d(loss)=seed_val: filled directly, no vjp slot
        p = rev.get(g)
        if p is None or p not in boundary:
            # a consumed gradient of an intermediate value: the per-op
            # path must carry it (rare — e.g. feeding an activation
            # grad to a fetch); fall back
            return None
        if p not in pre_reads:
            # the primal never flows into THIS segment's forward (its
            # chain was cut into an earlier segment, e.g. by an
            # auto-bucket split): the vjp would return a zero gradient
            # where the per-op grad chain crosses the cut — fall back
            return None
        grad_to_primal[g] = (p, region_of.get(g, 0))
    # stop_gradient vars and the no_grad_set recorded by
    # append_backward: the pruning pass treated them as constants, so
    # the vjp must too — lax.stop_gradient is applied at WRITE time
    # inside the traced forward (see _make_segment_fn), before any
    # consumer reads them
    block = ops[0].block
    no_grad = set(getattr(program, '_backward_no_grad_names', ()))
    seed_primals = set(p for p, _, _ in seeds)
    stop_names = []
    for op in pre:
        for n in _op_writes(op):
            if n in no_grad:
                stop_names.append(n)
                continue
            v = block._find_var_recursive(n)
            if v is not None and v.stop_gradient and \
                    n not in seed_primals:
                stop_names.append(n)
    # post (optimizer-role) ops run after the whole forward+vjp, same
    # as their original program position after the backward block —
    # in-place param writes (sgd ParamOut = Param) are ordinary env
    # rebinds, exactly as in the per-op path.  A forward-role op
    # INTERLEAVED into the backward block would land in `post` and is
    # also safe: nothing in `pre` or the vjp reads its output (program
    # order), and its own reads resolve against the completed env.
    return {'pre': pre, 'post': post, 'seeds': seeds,
            'seed_gnames': seed_gnames,
            'grad_to_primal': grad_to_primal,
            'stop_names': set(stop_names)}


def _make_segment_fn(segment, prefer_test=False, whole_program_grad=False):
    ops = segment.ops
    output_names = list(segment.output_names)

    wpg = _wpg_partition(segment) if whole_program_grad else None

    if wpg is not None:
        import jax.numpy as jnp
        pre, post = wpg['pre'], wpg['post']
        g2p = wpg['grad_to_primal']
        wrt_names = sorted(set(p for p, _ in g2p.values()))
        seeds = wpg['seeds']
        seed_gnames = wpg['seed_gnames']
        stop_names = wpg['stop_names']
        CF_FWD = ('while', 'conditional_block')

        def fn(step, state, data):
            registry.begin_trace()
            env0 = {}
            env0.update(data)
            env0.update(state)
            wrt = {n: env0[n] for n in wrt_names}
            others = {n: v for n, v in env0.items()
                      if n not in wrt}

            def fwd(wrt_vals):
                env = dict(others)
                env.update(wrt_vals)
                for run in _recompute_runs(pre):
                    if '__recompute__' in run[0].attrs:
                        # a recompute group: one checkpointed lowering
                        _lower_recomputed(run, env, step, prefer_test,
                                          stop_names)
                    else:
                        for op in run:
                            lower_one(op, env)
                return {p: env[p] for p, _, _ in seeds}, env

            def lower_one(op, env):
                if op.type in CF_FWD and \
                        not op.attrs.get('__needs_grad__'):
                    # the backward pass gave this loop/branch no
                    # gradient (no cotangent reaches its outputs),
                    # but a raw lax.while_loop cannot sit on a
                    # differentiated path under jax.vjp — lower it
                    # against a shadow env whose reads are
                    # gradient-stopped, exactly the per-op
                    # semantics (no grads flow through it)
                    shadow = dict(env)
                    wrapped = {}
                    for n in set(_op_dep_reads(op)):
                        if n in shadow:
                            v = jax.lax.stop_gradient(shadow[n])
                            shadow[n] = wrapped[n] = v
                    _lower_ops([op], shadow, step, prefer_test)
                    for n, v in shadow.items():
                        if n in wrapped and v is wrapped[n]:
                            continue  # an unmodified pinned read
                        if n not in env or env[n] is not v:
                            env[n] = v
                    return
                _lower_ops([op], env, step, prefer_test)
                # stop_gradient / no_grad_set vars are constants
                # to the pruning pass — pin them for the vjp at
                # write time, before any consumer reads them
                for n in _op_writes(op):
                    if n in stop_names and n in env:
                        env[n] = jax.lax.stop_gradient(env[n])

            roots, vjp_fn, env = jax.vjp(fwd, wrt, has_aux=True)
            # one backward pass per loss (usually one): cotangent only
            # on that loss's root, zeros elsewhere — per-op grad names
            # carry PER-LOSS contributions, not the total over seeds
            regions_used = sorted(set(r for _, r in g2p.values())) \
                or [0]
            d_by_region = {}
            for r in regions_used:
                cts = {p: jnp.full_like(jnp.asarray(roots[p]),
                                        v if i == r else 0.0)
                       for i, (p, _, v) in enumerate(seeds)}
                d_by_region[r], = vjp_fn(cts)
            for g, (p, r) in g2p.items():
                env[g] = d_by_region[r][p]
            for g, (p, v) in seed_gnames.items():
                # d(loss) itself: the seed value, materialized only if
                # something downstream reads it
                env[g] = jnp.full_like(jnp.asarray(env[p]), v)
            _lower_ops(post, env, step, prefer_test)
            return {n: env[n] for n in output_names}

        fn.__name__ = 'segment_wpg_%s_x%d' % (
            ops[0].type if ops else 'empty', len(ops))
        return fn

    def fn(step, state, data):
        registry.begin_trace()
        env = {}
        env.update(data)
        env.update(state)
        _lower_ops(ops, env, step, prefer_test)
        return {n: env[n] for n in output_names}

    # segment identity in traces: ops span + count (reference names SSA
    # executors' spans per graph; here one jit program per segment)
    fn.__name__ = 'segment_%s_x%d' % (ops[0].type if ops else 'empty',
                                      len(ops))
    return fn


def _jit_segment(segment, auto_layout=False, whole_program_grad=False):
    """jit a segment for the executor's own run loop.  With
    FLAGS_segment_auto_layout, state/data boundary layouts are chosen
    by XLA (jax.experimental.layout AUTO): the persistent state —
    notably f32 AMP master weights — then lives in the layout the
    compute wants across steps, so the per-step relayout copies at the
    jit boundary disappear (the steady state feeds each step's outputs
    straight back in as inputs with matching layouts)."""
    fn = _make_segment_fn(segment, segment.prefer_test,
                          whole_program_grad=whole_program_grad)
    if auto_layout:
        from jax.experimental.layout import Format, Layout
        auto = Format(Layout.AUTO)
        return jax.jit(fn, in_shardings=(None, auto, auto),
                       out_shardings=auto, donate_argnums=(1,))
    return jax.jit(fn, donate_argnums=(1,))


def _pallas_flag_items():
    """Pallas kernel dispatch happens at trace time, so every knob that
    flips a fused/dense decision must key the executable — both the
    persistent fingerprint and the per-step in-memory cache key."""
    return (bool(get_flag('FLAGS_pallas_force', False)),
            bool(get_flag('FLAGS_pallas_quant_collective', True)))


def _lowering_flag_items(prefer_test, wpg, auto=False):
    """The flag values that change a segment's lowering — exactly the
    set the in-memory executable key already guards — as a fingerprint
    component."""
    return (bool(prefer_test), bool(wpg), bool(auto),
            str(get_flag('FLAGS_conv_precision', 'highest'))) + \
        _pallas_flag_items()


def _step_spec():
    import numpy as _np
    return jax.ShapeDtypeStruct((), _np.int32)


def _aot_build(seg, wpg, state_specs, data_specs, device=None):
    """Trace + XLA-compile one segment ahead of time for concrete
    boundary specs: ``jax.jit(fn).lower(specs).compile()``.  The
    returned executable is called exactly like the lazily-jitted one
    (python-int step and numpy args are accepted), but the compile has
    already happened — and the lowering can run on a background thread.
    `device` pins the executable to the executor's place (the lazily-
    jitted path compiles inside jax.default_device(device); the AOT
    build must match or a non-default-place executor would get a
    device-0 executable).  Returns (compiled, out_specs,
    from_jax_cache) for the plane's disk entry."""
    import contextlib
    import numpy as _np
    t0 = _time_mod.perf_counter()
    fn = _make_segment_fn(seg, seg.prefer_test, whole_program_grad=wpg)
    ctx = contextlib.nullcontext() if (
        device is None or _is_default_device(device)) \
        else jax.default_device(device)
    with ctx:
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(
            _step_spec(), state_specs, data_specs)
        out_info = lowered.out_info
        compiled, from_jax_cache = compile_cache.compile_lowered(lowered)
    t1 = _time_mod.perf_counter()
    monitor.add('executor/aot_compiles')
    monitor.add('executor/segments_lowered')
    monitor.observe('executor/segment_compile_seconds', t1 - t0)
    _trace.record('compile', t0, t1, {'ops': len(seg.ops)})
    # per-segment XLA memory accounting (argument/output/temp/peak
    # bytes): the HBM-budget input the placement planner and /statusz
    # read; never raises, cheap (compile-time only).  The spec digest
    # keeps bucketed/per-shape variants of one segment as DISTINCT
    # rows — they are distinct resident executables, and the gauges
    # sum residency
    import hashlib as _hashlib
    from . import comms as _comms
    spec_tag = _hashlib.sha1(
        repr((state_specs, data_specs)).encode()).hexdigest()[:8]
    _comms.record_memory(
        '%dops:%s@%s' % (len(seg.ops),
                         ','.join(sorted(seg.output_names)[:3]),
                         spec_tag),
        compiled)
    out_specs = {n: (tuple(int(s) for s in v.shape),
                     _np.dtype(v.dtype).str)
                 for n, v in out_info.items()}
    return compiled, out_specs, from_jax_cache


def _specs_from_args(state, data):
    """ShapeDtypeStruct pytrees mirroring bound (state, data) dicts."""
    import numpy as _np

    def spec(v):
        return jax.ShapeDtypeStruct(
            tuple(int(s) for s in getattr(v, 'shape', ())),
            compile_cache.canonical_dtype(
                getattr(v, 'dtype', _np.float32)))

    return ({n: spec(v) for n, v in state.items()},
            {n: spec(v) for n, v in data.items()})


def _lowering_args(step, state, data):
    """What ``jitted.lower()`` must be given to name the program a lazy
    jit compiled for this call (``CompilePlane.note_lazy``): the step
    as passed and, for every array, its shape, dtype, weak type and,
    where it is committed, its sharding."""
    def spec(v):
        if not isinstance(v, jax.Array):
            return v if isinstance(v, (int, float)) else \
                jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
        return jax.ShapeDtypeStruct(
            v.shape, v.dtype, weak_type=v.weak_type,
            sharding=v.sharding if v.committed else None)

    return (spec(step), {n: spec(v) for n, v in state.items()},
            {n: spec(v) for n, v in data.items()})


from jax.core import Tracer as _Tracer


def _any_tracer(step, state, data):
    if isinstance(step, _Tracer):
        return True
    for d in (state, data):
        for v in d.values():
            if isinstance(v, _Tracer):
                return True
    return False


class CompiledStep(object):
    """A program compiled to one jittable callable — the public
    'compile program -> function' surface (the reference's
    Executor::Prepare returning an ExecutorPrepareContext,
    framework/executor.h:81, re-imagined for whole-graph XLA).

    fn(step, state, data) -> {output_name: array}; `state` holds the
    in-place-updated names (parameters, optimizer slots), `data` the
    pure inputs.  The function is pure and jit/grad/shard-compatible.

    Concrete calls dispatch through a compile-plane-shared jit (no
    donation — caller-owned state must survive): repeated calls never
    re-trace, a SECOND CompiledStep of a content-identical program
    reuses the first one's jit object (fingerprint-keyed,
    compile_cache.py), and with FLAGS_compile_cache_dir the XLA
    compile itself persists across processes.  Called under an outer
    trace (jit/grad/vmap) it degrades to the raw traceable `fn`, so
    composability is unchanged."""

    __slots__ = ('fn', 'input_names', 'state_names', 'output_names',
                 '_jitted')

    def __init__(self, fn, input_names, state_names, output_names,
                 jitted=None):
        self.fn = fn
        self.input_names = list(input_names)
        self.state_names = list(state_names)
        self.output_names = list(output_names)
        self._jitted = jitted

    def __call__(self, step, state, data):
        if self._jitted is not None and \
                not _any_tracer(step, state, data):
            return self._jitted(step, state, data)
        return self.fn(step, state, data)


class _WarmupResult(object):
    """Handle over one Executor.warmup() submission: `submitted` /
    `skipped` segment counts and `wait()` to block until every
    background compile resolved (compile errors surface lazily at the
    first run of the failing segment, not here)."""

    __slots__ = ('futures', 'submitted', 'skipped')

    def __init__(self, futures, submitted, skipped):
        self.futures = list(futures)
        self.submitted = submitted
        self.skipped = skipped

    def done(self):
        return all(f.done() for f in self.futures)

    def wait(self, timeout=None):
        """Block until every submitted compile resolved, or `timeout`
        seconds total (ONE deadline, not per future).  Never raises:
        check done() to see whether the deadline cut the wait short; a
        failed background compile recompiles lazily at first run."""
        if self.futures:
            from concurrent.futures import wait as _futures_wait
            _futures_wait(self.futures, timeout=timeout)
        return self


class CompiledPipeline(object):
    """A multi-segment program compiled to its execution plan: device
    segments are cached jitted executables, host ops (save/load/print/
    PS pulls) run between them through the scope.  NOT a pure function
    — host ops may touch external state — so it cannot nest under
    jit/grad; for that, restructure the program into one device
    segment (CompiledStep).

    __call__(feed, scope=None) runs one step against `scope` (default:
    the global scope, where the startup program put the parameters)
    and returns the fetches in order."""

    __slots__ = ('_exe', '_program', '_plan', 'input_names',
                 'fetch_names', 'host_op_types')

    def __init__(self, executor, program, plan, feed_names,
                 fetch_names):
        self._exe = executor
        self._program = program
        self._plan = plan
        self.input_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.host_op_types = [it[1].type for it in plan
                              if not isinstance(it, _Segment)
                              and it[0] == 'host']

    def __call__(self, feed=None, scope=None, return_numpy=True):
        scope = scope or core.global_scope()
        exe = self._exe
        with exe._step_scope(self._program, scope):
            return exe._run_plan(self._program, self._plan, feed or {},
                                 self.fetch_names, scope, return_numpy)


class Executor(object):
    """Reference: python/paddle/fluid/executor.py:680."""

    def __init__(self, place=None):
        self.place = place or core.XLAPlace(0)
        self._step = 0
        self._posture = (False, False, 0.0)
        # FLAGS_status_port: the status/metrics HTTP plane starts with
        # the first executor (no-op when the flag is 0 or a server is
        # already up)
        from . import health as _health
        _health.ensure_serving()

    def close(self):
        pass

    def compile(self, program, feed_names=(), fetch_names=(),
                prefer_test=False, allow_host=False):
        """Compile `program`.

        Single-segment programs (no host ops) return a CompiledStep —
        ONE pure jittable function usable under jit/grad/shard_map.
        Programs that split around host ops (save/load/print/PS pulls)
        cannot be one pure function; with allow_host=True they compile
        to a CompiledPipeline — each device segment is a cached jitted
        executable, host ops run between them through a scope — the
        general 'compile a program' surface (the reference's
        Executor::Prepare caches exactly this per-program op plan,
        framework/executor.h:81)."""
        from . import framework as _fw

        def _norm(names):
            return [v.name if isinstance(v, _fw.Variable) else v
                    for v in names]

        feed_names = _norm(feed_names)
        fetch_names = _norm(fetch_names)
        monitor.add('executor/programs_compiled')
        plan = self._get_plan(program, tuple(sorted(feed_names)),
                              tuple(fetch_names), prefer_test)
        segs = [it for it in plan if isinstance(it, _Segment)]

        def _pipeline():
            known_out = set()
            known_in = set()
            for it in plan:
                if isinstance(it, _Segment):
                    known_out.update(it.output_names)
                    known_in.update(it.input_names)
                    known_in.update(it.state_names)
                else:
                    known_out.update(_op_writes(it[1]))
                    known_in.update(_op_reads(it[1]))
            missing = [n for n in fetch_names if n not in known_out]
            if missing:
                raise ValueError(
                    'fetch vars %r are not produced by the program'
                    % (missing,))
            bogus = [n for n in feed_names if n not in known_in]
            if bogus:
                raise ValueError(
                    'feed names %r are not read by the program'
                    % (bogus,))
            return CompiledPipeline(self, program, plan, feed_names,
                                    fetch_names)

        # programs carrying per-step host hooks (async-PS push/pull,
        # k-step LocalSGD sync) cannot be a pure step even when they
        # lower to one device segment — the hooks ARE the training
        # semantics (reference: Communicator send queues,
        # operators/distributed/communicator.h:175)
        hooked = bool(getattr(program, '_ps_async', None) or
                      getattr(program, '_local_sgd', None))
        if hooked and not prefer_test:
            if not allow_host:
                raise ValueError(
                    'this program has per-step host hooks (async-PS '
                    'communicator / LocalSGD) and cannot compile to a '
                    'pure step — pass allow_host=True for a '
                    'CompiledPipeline, or run it with Executor.run')
            return _pipeline()
        if len(segs) != 1 or len(plan) != 1:
            if allow_host:
                return _pipeline()
            cuts = [it for it in plan if not isinstance(it, _Segment)]
            why = []
            host = [it[1].type for it in cuts if it[0] == 'host']
            if host:
                why.append('host ops %r' % (host,))
            if any(it[0] == 'bucket' for it in cuts):
                why.append('auto-bucketed unbounded while loops (pass '
                           'max_trip_count to bound them)')
            raise ValueError(
                'Executor.compile needs a single-segment program for a '
                'pure jittable step; this one splits into %d segments '
                'around %s — pass allow_host=True for a '
                'CompiledPipeline, or run it with Executor.run'
                % (len(segs), ' and '.join(why) or 'program cuts'))
        seg = segs[0]
        missing = [n for n in fetch_names if n not in seg.output_names]
        if missing:
            raise ValueError(
                'fetch vars %r are not produced by the compiled step '
                '(a fetch must be written by the program; pure inputs '
                'are available to the caller already)' % (missing,))
        known = set(seg.input_names) | set(seg.state_names)
        bogus = [n for n in feed_names if n not in known]
        if bogus:
            raise ValueError(
                'feed names %r are not read by the program (inputs: '
                '%r)' % (bogus, sorted(known)))
        wpg = bool(get_flag('FLAGS_whole_program_grad'))
        fn = _make_segment_fn(seg, prefer_test, whole_program_grad=wpg)
        # the compile plane keys the jit on the segment's content
        # fingerprint (donate=False: CompiledStep state is caller-owned)
        # so compiling the same program twice — or a program `run`
        # already planned — never pays a second trace, and the XLA
        # compile dedupes across processes via the persistent cache.
        # output_names is part of the executable interface: the same
        # ops planned for a different fetch set returns different vars
        fp = compile_cache.fingerprint(
            seg.ops, (),
            _lowering_flag_items(prefer_test, wpg) +
            tuple(sorted(seg.output_names)),
            donate=False, purpose='jit')
        jitted = compile_cache.plane().shared_jit(
            fp, lambda: jax.jit(fn))
        return CompiledStep(fn, seg.input_names, seg.state_names,
                            seg.output_names, jitted=jitted)

    # ------------------------------------------------------------------
    def warmup(self, program=None, feed_shapes=None, fetch_list=None,
               scope=None, prefer_test=False, wait=False):
        """Compile a program's segments in the BACKGROUND, ahead of the
        first run() — the parallel half of the AOT compile plane.

        `feed_shapes` maps each feed name to its spec: a (shape, dtype)
        pair, an example array, or a jax.ShapeDtypeStruct.  Pass the
        same feed names and `fetch_list` the later run() calls will use
        (they key the plan).  Parameters/optimizer state resolve from
        `scope` (run the startup program first) or from static var
        declarations.  Segment output shapes propagate to downstream
        segments; segments cut off by host-op outputs or un-stamped
        auto-bucket trip counts are skipped and compile lazily.

        Every resolvable segment is fingerprinted and submitted to the
        compile pool (FLAGS_compile_threads): disk entries deserialize,
        everything else traces (foreground — cheap) and XLA-compiles
        (background — the expensive part, concurrent across segments).
        Executables are delivered via futures, so step 1 blocks only on
        the segment it is about to execute, not the whole plan.

        Returns a result object with `.wait()`; `wait=True` blocks
        until every submitted compile finished.  Calling warmup marks
        the process 'warmed': run() uses the AOT plane from then on
        even without a cache dir (memory-only)."""
        import threading as _threading
        import numpy as _np
        program = program or framework.default_main_program()
        scope = scope or core.global_scope()
        plane = compile_cache.plane()
        plane.mark_warmed()
        feed_shapes = feed_shapes or {}
        fetch_list = fetch_list or []
        fetch_names = [v.name if isinstance(v, framework.Variable)
                       else v for v in fetch_list]

        canon = compile_cache.canonical_dtype

        def as_spec(v):
            if isinstance(v, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(v.shape, canon(v.dtype))
            if isinstance(v, core.LoDTensor):
                v = v.data
            shp = getattr(v, 'shape', None)
            if shp is not None and hasattr(v, 'dtype'):
                return jax.ShapeDtypeStruct(
                    tuple(int(s) for s in shp), canon(v.dtype))
            shape, dtype = v
            return jax.ShapeDtypeStruct(
                tuple(int(s) for s in shape), canon(dtype))

        feed_specs = {k: as_spec(v) for k, v in feed_shapes.items()}
        # suppress the plan-build verify hook for this _get_plan: the
        # forced warmup verification below re-runs the pass with the
        # richer boundary feed_specs — verifying twice would double
        # every verify/* stat and burn the /statusz trail
        self._warmup_verifies = True
        try:
            plan = self._get_plan(program, tuple(sorted(feed_specs)),
                                  tuple(fetch_names), prefer_test)
        finally:
            self._warmup_verifies = False
        # FORCED static verification (flag or not): warmup is the
        # declared pre-compile step, so an illegal graph must fail
        # here with a named diagnostic, not as a tracer stack five
        # frames deep.  Flag off runs the O(ops) invariant + donation
        # pass; flag on adds the shape/dtype walk seeded with the
        # warmup boundary specs.
        from . import progcheck as _progcheck
        _progcheck.verify_program(
            program, feed_names=tuple(sorted(feed_specs)),
            fetch_names=tuple(fetch_names),
            feed_specs={k: (tuple(v.shape), v.dtype)
                        for k, v in feed_specs.items()},
            plan=plan, origin='warmup',
            level='full' if _progcheck.enabled() else 'fast')
        auto = bool(get_flag('FLAGS_segment_auto_layout'))
        wpg = bool(get_flag('FLAGS_whole_program_grad'))
        device = self.place.jax_device()
        t_start = _time_mod.perf_counter()
        env = {}        # scope-as-of-this-plan-position specs
        unknown = set()  # names only a real step can produce
        block = program.global_block()

        def spec_of(name):
            if name in feed_specs:
                return feed_specs[name]
            if name in unknown:
                return None
            if name in env:
                return env[name]
            v = scope.find_var(name)
            if v is not None:
                v = core.as_array(v)
                if hasattr(v, 'shape') and hasattr(v, 'dtype'):
                    return jax.ShapeDtypeStruct(
                        tuple(int(s) for s in v.shape),
                        canon(v.dtype))
            var = block._find_var_recursive(name)
            if var is not None and var.shape and \
                    all(int(s) >= 0 for s in var.shape):
                try:
                    return jax.ShapeDtypeStruct(
                        tuple(int(s) for s in var.shape),
                        canon(core.convert_dtype(var.dtype)))
                except Exception:
                    return None
            return None

        futures = []
        submitted = skipped = 0
        for item in plan:
            if not isinstance(item, _Segment):
                # host/bucket legs run with real data at step time;
                # whatever they write only a real step can shape
                for n in _op_writes(item[1]):
                    env.pop(n, None)
                    unknown.add(n)
                continue
            seg = item
            buckets = tuple(op.attrs.get('max_trip_count')
                            for op in seg.bucket_ops)
            resolvable = not auto and all(buckets)
            state_specs, data_specs = {}, {}
            if resolvable:
                for names, dst in ((seg.state_names, state_specs),
                                   (seg.input_names, data_specs)):
                    for n in names:
                        s = spec_of(n)
                        if s is None:
                            resolvable = False
                            break
                        dst[n] = s
                    if not resolvable:
                        break
            if not resolvable:
                skipped += 1
                monitor.add('executor/warmup_skipped')
                for n in seg.output_names:
                    env.pop(n, None)
                    unknown.add(n)
                continue
            specs = compile_cache.arg_specs(state_specs, data_specs)
            # output_names folded in: must match the run-path key below
            # exactly or warmup's pre-compiles never hit
            fp = compile_cache.fingerprint(
                seg.ops, specs,
                _lowering_flag_items(seg.prefer_test, wpg) +
                (int(getattr(device, 'id', 0)),) +
                tuple(sorted(seg.output_names)),
                donate=True)
            out_specs = plane.out_specs(fp)
            if plane.lookup(fp) is None and out_specs is None:
                loaded = plane.disk_load(fp, with_specs=True)
                if loaded is not None:
                    compiled, out_specs = loaded
                    monitor.add('executor/compile_cache_disk_hit')
                    plane.store(fp, compiled)
                    plane.note_out_specs(fp, out_specs)
            if out_specs is None:
                # trace in the foreground (cheap, and it yields the
                # output specs downstream segments need), compile in
                # the pool (the expensive part, concurrent); both
                # under the executor's device, matching _aot_build
                import contextlib

                def _dev_ctx():
                    return contextlib.nullcontext() \
                        if _is_default_device(device) \
                        else jax.default_device(device)

                monitor.add('executor/segments_lowered')
                fn = _make_segment_fn(seg, seg.prefer_test,
                                      whole_program_grad=wpg)
                with _dev_ctx(), _trace.span('warmup_lower',
                                             ops=len(seg.ops)):
                    lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                        _step_spec(), state_specs, data_specs)
                out_specs = {
                    n: (tuple(int(s) for s in v.shape),
                        _np.dtype(v.dtype).str)
                    for n, v in lowered.out_info.items()}
                plane.note_out_specs(fp, out_specs)

                def build(_lowered=lowered, _specs=out_specs,
                          _ctx=_dev_ctx):
                    t0 = _time_mod.perf_counter()
                    with _ctx():
                        compiled, from_jax_cache = \
                            compile_cache.compile_lowered(_lowered)
                    t1 = _time_mod.perf_counter()
                    monitor.add('executor/aot_compiles')
                    monitor.observe(
                        'executor/segment_compile_seconds', t1 - t0)
                    # background-pool span: thread-aware, shows the
                    # warmup futures overlapping the first steps
                    _trace.record('warmup_compile', t0, t1)
                    return compiled, _specs, from_jax_cache

                fut = plane.submit(fp, build)
                from concurrent.futures import Future
                if isinstance(fut, Future):
                    futures.append(fut)
                submitted += 1
                monitor.add('executor/warmup_segments')
            for n, (shp, dt) in (out_specs or {}).items():
                env[n] = jax.ShapeDtypeStruct(tuple(shp), _np.dtype(dt))
                unknown.discard(n)

        res = _WarmupResult(futures, submitted, skipped)
        if wait or not futures:
            res.wait()
            monitor.observe('executor/warmup_seconds',
                            _time_mod.perf_counter() - t_start)
        else:
            remaining = [len(futures)]
            lock = _threading.Lock()

            def _done(_f):
                with lock:
                    remaining[0] -= 1
                    last = remaining[0] == 0
                if last:
                    monitor.observe(
                        'executor/warmup_seconds',
                        _time_mod.perf_counter() - t_start)

            for f in futures:
                f.add_done_callback(_done)
        return res

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, feed_var_name='feed',
            fetch_var_name='fetch'):
        """Run one step.  `return_numpy` accepts True (block and
        convert each fetch), False (raw device values), or 'async'
        (FetchHandle per fetch: the D2H copy starts immediately but
        resolution blocks only at as_numpy()).  use_program_cache=False
        bypasses the program's plan cache: the plan (and its segment
        executables) is rebuilt for this call — the reference's
        uncached Executor.run semantics, paid in recompiles.

        What the program watches (``Program.watch``) rides along on a
        run that already fetches and blocks, whichever runner takes
        it; a quiet run reads nothing."""
        from .compiler import CompiledProgram
        base = program.program if isinstance(program, CompiledProgram) \
            else program or framework.default_main_program()
        watched = base._watched if fetch_list and return_numpy is True \
            else {}
        if not watched:
            return self._run(program, feed, fetch_list, scope,
                             return_numpy, use_program_cache)
        n_user = len(fetch_list)
        out = self._run(
            program, feed,
            list(fetch_list) + sum(watched.values(), []), scope,
            return_numpy, use_program_cache)
        rest = out[n_user:]
        for record, names in watched.items():
            record(rest[:len(names)])
            rest = rest[len(names):]
        return out[:n_user]

    def _run(self, program, feed, fetch_list, scope, return_numpy,
             use_program_cache):
        """Pick the runner.  All three (and CompiledPipeline) run their
        plan inside `_step_scope` and dispatch every segment through
        `_dispatch_segment`; what a runner owns is how arguments are
        placed, how the executable is built and what its fingerprint
        holds."""
        from .compiler import CompiledProgram
        from .parallel_executor import run_parallel, run_collective
        compiled = None
        if isinstance(program, CompiledProgram):
            if program._is_data_parallel:
                compiled = program
            program = program.program
        program = program or framework.default_main_program()
        scope = scope or core.global_scope()
        feed = feed or {}
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in fetch_list or []]
        if compiled is not None:
            return run_parallel(self, compiled, feed, fetch_names, scope,
                                return_numpy)
        if getattr(program, '_collective_dp', False):
            return run_collective(self, program, feed, fetch_names,
                                  scope, return_numpy)
        plan = self._get_plan(program, tuple(sorted(feed.keys())),
                              tuple(fetch_names),
                              use_cache=use_program_cache)
        with self._step_scope(program, scope):
            return self._run_plan(program, plan, feed, fetch_names,
                                  scope, return_numpy)

    @contextlib.contextmanager
    def _step_scope(self, program, scope):
        """What a step boundary is, for every runner (one chip,
        `with_data_parallel`, the collective runner, CompiledPipeline):
        the only place that counts the step and calls each plane's
        step hook.  The body is the runner's plan walk and fetch
        resolution, inside the step span and the ambient memviz
        program label (per-(program, segment) HBM attribution and the
        collective planner's per-program headroom resolve through
        it).  A body that raises closes the span and skips everything
        after it: a failed step is not a completed one.  Every hook
        costs one flag or module-global read while its plane is off."""
        if _sup.active():
            # self-healing controller: a pending recovery executes at
            # this step boundary (and raises supervisor.Recovered so
            # the train loop re-reads the rewound step counter)
            _sup.on_step_begin(self)
        self._step += 1
        if _finject.armed():
            # chaos hook: 'executor.step:die@N' is worker death mid-run
            _finject.check('executor.step', step=self._step)
        t0 = _time_mod.perf_counter()
        # the step's debugging posture, read once here and not per
        # segment: the NaN sweep, the tensor-health summaries, the
        # hung-step watchdog's deadline
        self._posture = (
            bool(get_flag('FLAGS_check_nan_inf')),
            bool(get_flag('FLAGS_health_summaries')),
            float(get_flag('FLAGS_step_timeout_s', 0.0) or 0.0))
        with _trace.step_span(self._step):
            with _memviz.program_scope(_memviz.program_label(program)):
                yield
            _memviz.maybe_sample(self._step, scope)
            self._post_step(program, scope)
        # dispatch-side wall time: jit dispatch is async, so this is the
        # host cost of one step (compiles land here on cold caches)
        monitor.add('executor/run_calls')
        monitor.observe('executor/run_seconds',
                        _time_mod.perf_counter() - t0)
        # /healthz readiness staleness: when did this process last
        # complete a step (one clock read + dict store)
        monitor.set_gauge('executor/last_step_unix_ts',
                          _time_mod.time())
        # windowed-history sample at the step boundary
        _tseries.maybe_sample(self._step)
        if _sup.active():
            # checkpoint cadence runs at the step boundary, on this
            # thread: a snapshot here can never mix two steps' params
            _sup.on_step_end(self)

    def _post_step(self, program, scope):
        """k-step LocalSGD sync and the async-PS grad push / param
        pull, at the end of every runner's step."""
        lsgd = getattr(program, '_local_sgd', None)
        if lsgd:
            lsgd['count'] = lsgd.get('count', 0) + 1
            if lsgd['count'] % lsgd['period'] == 0:
                self._local_sgd_sync(scope, lsgd['params'])
        if getattr(program, '_ps_async', None):
            from .incubate.fleet.parameter_server import ps_async_step
            ps_async_step(self, scope, program)

    def _local_sgd_sync(self, scope, param_names):
        """LocalSGD sync point: average trainable params across trainer
        processes (reference: transpiler/collective.py LocalSGD)."""
        from ..distributed.collective_utils import process_mean
        vals = [core.as_array(scope.find_var(n)) for n in param_names]
        for n, avg in zip(param_names, process_mean(vals)):
            scope.set_var(n, avg)

    # ------------------------------------------------------------------
    def _get_plan(self, program, feed_names, fetch_names,
                  prefer_test=False, use_cache=True):
        from . import profiler as _profiler
        # per-op profiling compiles every device op as its own one-op
        # segment (separately cached), so each can be host-timed —
        # the reference's per-op RecordEvent granularity
        per_op = _profiler.is_enabled()
        if not use_cache:
            # use_program_cache=False: rebuild the plan for THIS call
            # and leave program._exec_cache untouched (fresh segments,
            # fresh executables — the uncached reference semantics)
            monitor.add('executor/plan_cache_bypass')
            plan = self._build_plan(program, feed_names, fetch_names,
                                    per_op=per_op)
            if prefer_test:
                for it in plan:
                    if isinstance(it, _Segment):
                        it.prefer_test = True
            self._verify_plan_build(program, plan, feed_names,
                                    fetch_names)
            return plan
        # prefer_test keys the cache so test-mode lowering never shares
        # executables with the training-mode plan
        key = ('plan', feed_names, fetch_names, id(self), prefer_test,
               per_op)
        plan = program._exec_cache.get(key)
        monitor.add('executor/plan_cache_hit' if plan is not None
                    else 'executor/plan_cache_miss')
        if plan is None:
            plan = self._build_plan(program, feed_names, fetch_names,
                                    per_op=per_op)
            if prefer_test:
                for it in plan:
                    if isinstance(it, _Segment):
                        it.prefer_test = True
            self._verify_plan_build(program, plan, feed_names,
                                    fetch_names)
            program._exec_cache[key] = plan
        return plan

    def _verify_plan_build(self, program, plan, feed_names,
                           fetch_names):
        """Static-verification hook on the plan-BUILD path (cache
        misses only — the steady state never comes here): consult the
        'progcheck.mutate' chaos site, then run the fluid.progcheck
        pass when FLAGS_program_verify is on.  Error-class findings
        raise ProgramVerifyError before anything traces."""
        if _finject.armed():
            c = _finject.check('progcheck.mutate')
            if c is not None and c['action'] == 'mutate':
                from . import progcheck
                progcheck.mutate(program, c['arg'] or 1, plan=plan)
        if get_flag('FLAGS_program_verify') and \
                not getattr(self, '_warmup_verifies', False):
            from . import progcheck
            progcheck.verify_program(program, feed_names=feed_names,
                                     fetch_names=fetch_names,
                                     plan=plan, origin='run')

    # host ops with no program-state writes (print/save write stdout /
    # files, never scope vars): deferring one past later device ops is
    # observably identical when nothing later rewrites what it reads
    _DEFERRABLE_HOST_OPS = ('print', 'save', 'save_combine')

    def _defer_readonly_host_ops(self, ops):
        """Reorder a block's op list so deferrable host ops run after
        the device ops that follow them, when no later op rewrites
        their reads.  Without this, a print/save between forward and
        backward cuts the plan into two segments — the program can no
        longer compile to one pure step (Executor.compile) and the
        whole-program-grad partition cannot see the forward region.
        The reference interleaves host ops freely because its executor
        is op-by-op (framework/executor.cc:449); a segment compiler
        buys the fused program back by commuting read-only host ops
        with the pure ops they don't depend on."""
        deferred = []  # (op, read names) pending placement
        out = []
        for op in ops:
            writes = set(_op_writes(op))
            if writes and deferred:
                # flush every deferred op whose read is about to be
                # rewritten — and any deferred BEFORE it, so host side
                # effects keep their relative program order
                last = max((i for i, (_, reads) in enumerate(deferred)
                            if reads & writes), default=-1)
                if last >= 0:
                    out.extend(d for d, _ in deferred[:last + 1])
                    deferred = deferred[last + 1:]
            if op.type in self._DEFERRABLE_HOST_OPS:
                deferred.append((op, set(_op_reads(op))))
            else:
                out.append(op)
        out.extend(d for d, _ in deferred)
        return out

    def _build_plan(self, program, feed_names, fetch_names,
                    per_op=False):
        block = program.global_block()
        items = []  # list of _Segment | ('host', op)
        cur = []
        CONTROL_FLOW = ('while', 'conditional_block', 'while_grad',
                        'conditional_block_grad')
        for op in self._defer_readonly_host_ops(block.ops):
            if op.type in CONTROL_FLOW:
                if op.type == 'while' and \
                        op.attrs.get('__auto_bucket__'):
                    # unbounded differentiable while: cut here so the
                    # carries are concrete in the scope, count trips on
                    # the host, then compile downstream at the bucket
                    if cur:
                        items.append(_Segment(cur))
                        cur = []
                    items.append(('bucket', op))
                cur.append(op)
                continue
            if op.type in registry.HOST_OPS or not registry.is_registered(
                    op.type):
                if not registry.is_registered(op.type):
                    raise RuntimeError('op %s is not registered' % op.type)
                if cur:
                    items.append(_Segment(cur))
                    cur = []
                items.append(('host', op))
            else:
                cur.append(op)
        if cur:
            items.append(_Segment(cur))

        if per_op:
            # profiling granularity: one op per segment (dataflow
            # analysis below then scopes inputs/outputs per op)
            split = []
            for it in items:
                if isinstance(it, _Segment):
                    split.extend(_Segment([op]) for op in it.ops)
                else:
                    split.append(it)
            items = split

        # dataflow analysis: inputs / outputs per segment
        feed_set = set(feed_names)
        fetch_set = set(fetch_names)
        # extra outputs: vars consumed outside the program by host
        # protocols (e.g. async-PS grad push), exempt from DCE
        extra_outputs = set(getattr(program, '_extra_output_names', ()))
        if get_flag('FLAGS_health_summaries'):
            # tensor-health grad norms need the PARAM gradients
            # observable at the segment boundary (activation grads stay
            # DCE-able — materializing those would defeat fusion).
            # Plans are cached: set the flag before the first run of a
            # program for its grads to surface.
            gmap = getattr(program, '_grad_name_map', {})
            if gmap:
                pnames = set(p.name for p in program.all_parameters())
                extra_outputs |= set(g for p, g in gmap.items()
                                     if p in pnames)
        # reads of later items, computed backwards
        later_reads = [set()] * len(items)
        acc = set()
        for i in range(len(items) - 1, -1, -1):
            later_reads[i] = set(acc)
            item = items[i]
            ops = item.ops if isinstance(item, _Segment) else [item[1]]
            for op in ops:
                acc.update(_op_dep_reads(op))
        for i, item in enumerate(items):
            if not isinstance(item, _Segment):
                continue
            written = set()
            reads_before_write = set()
            for op in item.ops:
                for n in _op_dep_reads(op):
                    if n not in written:
                        reads_before_write.add(n)
                written.update(_op_writes(op))
            persistable = set()
            for n in written:
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    persistable.add(n)
            outputs = written & (persistable | later_reads[i] |
                                 fetch_set | extra_outputs)
            # state = inputs that are also written (in-place params etc.)
            state = sorted(reads_before_write & written)
            inputs = sorted(reads_before_write - set(state))
            item.input_names = inputs
            item.state_names = state
            item.output_names = sorted(outputs)
        # census param-vs-state classification: the parameters of
        # every planned program are registered once, at plan-build time
        try:
            _memviz.note_params(p.name for p in program.all_parameters())
        except Exception:
            pass
        plan = _Plan(items)
        dev_names = set()
        consume_count = {}
        state_anywhere = set()
        pure_segments = True
        for it in items:
            if isinstance(it, _Segment):
                for n in set(it.state_names) | set(it.input_names):
                    consume_count[n] = consume_count.get(n, 0) + 1
                state_anywhere.update(it.state_names)
                dev_names.update(it.state_names)
                dev_names.update(it.input_names)
            else:
                pure_segments = False
                if it[0] == 'bucket':
                    # the host-side trip counter binds these through
                    # _lookup_input; staged device values are fine there
                    dev_names.update(_op_dep_reads(it[1]))
        plan.device_feed_names = frozenset(dev_names)
        # pointer-donation eligibility: a fed state buffer may only be
        # donated un-copied when exactly ONE plan item consumes it and
        # no host/bucket item exists (host plans publish feeds into the
        # scope, which would keep a reference to the deleted buffer)
        if pure_segments:
            plan.donatable_feed_names = frozenset(
                n for n in state_anywhere if consume_count.get(n) == 1)
        else:
            plan.donatable_feed_names = frozenset()
        return plan

    # ------------------------------------------------------------------
    @staticmethod
    def _reject_multilevel_lod(program, name, levels):
        """A >=2-level LoDTensor fed to a sequence lowering: the
        padded+mask representation carries ONE ragged level (the
        '@MASK' convention), so nested-sequence semantics
        (reference framework/lod_tensor.h:219, e.g. paragraphs of
        sentences) would silently degrade to dense math.  Fail loudly
        with the workaround instead (VERDICT r4 missing #4).  Taint
        propagates through dataflow (embedding(x) -> sequence_pool is
        the common nested pattern) and into control-flow sub-blocks."""
        LEVEL1_CONSUMERS = ('gru', 'lstm', 'lstmp', 'im2sequence',
                            'linear_chain_crf', 'crf_decoding')
        tainted = {name}
        all_ops = []
        for block in program.blocks:
            all_ops.extend(block.ops)
        # forward closure to a fixed point: sub-block ops may precede
        # their parent in `blocks` order
        changed = True
        while changed:
            changed = False
            for op in all_ops:
                if tainted.isdisjoint(op.input_arg_names):
                    continue
                if op.type.startswith('sequence_') or \
                        op.type in LEVEL1_CONSUMERS:
                    hit = sorted(tainted &
                                 set(op.input_arg_names))[0]
                    raise RuntimeError(
                        "feed '%s' carries a %d-level LoD and flows "
                        'into op [%s] (via %r), which lowers on the '
                        'padded+mask representation holding ONE '
                        'ragged level — nested sequences would '
                        'silently compute as dense. Flatten the '
                        'outer level into the batch dim (one row per '
                        'inner sequence) and feed the level-1 LoD, '
                        'or use reader.BucketedGeneratorLoader which '
                        "emits the '@MASK' feeds the sequence ops "
                        'consume.' % (name, levels, op.type, hit))
                for out in op.output_arg_names:
                    if out not in tainted:
                        tainted.add(out)
                        changed = True

    def _stage_feeds(self, program, plan, feed, device):
        """Batch every host-side feed value through ONE async
        jax.device_put ahead of dispatch: device_put returns
        immediately, so the H2D DMA overlaps the PREVIOUS step's
        compute (and composes with the reader's staging window, whose
        batches arrive here already device-resident and skip straight
        through).  Feeds read only by host ops (outside the plan's
        device_feed_names) stay host-side.  Staged buffers are
        runtime-owned: binders may donate them without the defensive
        per-step copy."""
        if not feed:
            return feed
        device_names = getattr(plan, 'device_feed_names', None)
        donatable = getattr(plan, 'donatable_feed_names', frozenset())
        staged = {}
        host_part = None
        nbytes = 0.0
        for k, v in feed.items():
            if isinstance(v, core.LoDTensor):
                if len(v.lod) >= 2:
                    self._reject_multilevel_lod(program, k, len(v.lod))
                v = v.data
            monitor.add('executor/feed_bytes', _stat_nbytes(v))
            if isinstance(v, jax.Array) or (
                    device_names is not None and k not in device_names):
                if k not in donatable and isinstance(v, jax.Array) \
                        and core.is_owned(v):
                    # a runtime-staged buffer (reader double-buffer)
                    # reaching a plan where this name has several
                    # consumers: withdraw the donation claim so the
                    # binder copies before the first donate
                    core.disown(v)
                staged[k] = v
                continue
            a = np.asarray(v)
            if host_part is None:
                host_part = {}
            host_part[k] = a
            nbytes += float(a.nbytes)
        monitor.add('executor/feed_vars', float(len(feed)))
        if host_part:
            with _trace.span('feed_h2d', nbytes=nbytes,
                             vars=len(host_part)):
                # on the default device, stage UNCOMMITTED (like the
                # state startup leaves in the scope): a committed feed
                # makes step 1's outputs committed, step 2 then binds
                # committed state and jit compiles the segment again
                put = jax.device_put(
                    host_part,
                    None if _is_default_device(device) else device)
            monitor.add('executor/h2d_bytes_async', nbytes)
            for k, a in put.items():
                # pointer-donation claim only where the plan proves a
                # single consumer (see _Plan.donatable_feed_names)
                staged[k] = core.mark_owned(a) if k in donatable else a
        return staged

    def _run_plan(self, program, plan, feed, fetch_names, scope,
                  return_numpy):
        """The one-chip runner's step body (Executor.run and
        CompiledPipeline, inside `_step_scope`): stage the feeds, walk
        the plan, resolve the fetches."""
        device = self.place.jax_device()
        feed = self._stage_feeds(program, plan, feed, device)
        fetched = {}
        prefer_test = any(isinstance(it, _Segment) and it.prefer_test
                          for it in plan)
        self._walk_plan(
            plan, feed, scope, fetched,
            lambda seg: self._run_segment(seg, feed, scope, device,
                                          fetched),
            lambda op: self._run_bucket_count(op, feed, scope, device,
                                              prefer_test))
        results = []
        for name in fetch_names:
            if name in fetched:
                val = fetched[name]
            else:
                val = scope.find_var(name)
                if val is None:
                    raise RuntimeError('fetch var %s not produced' % name)
            # byte accounting on the DENSE value (SelectedRows expose
            # nbytes only after densification)
            val = core.as_array(val)
            monitor.add('executor/fetch_bytes', _stat_nbytes(val))
            if return_numpy == 'async':
                # start the D2H copy now, block never: the handle
                # resolves on as_numpy() while later steps dispatch
                results.append(FetchHandle(val))
                continue
            if return_numpy:
                t0 = _time_mod.perf_counter()
                val = np.asarray(val)
                t1 = _time_mod.perf_counter()
                monitor.observe('executor/fetch_blocked_seconds',
                                t1 - t0)
                _trace.record('fetch_d2h', t0, t1)
            results.append(val)
        if fetch_names:
            monitor.add('executor/fetch_vars', float(len(fetch_names)))
        return results

    def _walk_plan(self, plan, feed, scope, fetched, run_segment,
                   run_bucket=None):
        """The plan walk of every runner: a segment goes to the
        runner's `run_segment`, a host op runs here through the scope.
        `run_bucket` (the auto-bucketed while's trip count) is the
        one-chip runner's alone."""
        if any(not isinstance(it, _Segment) for it in plan):
            # host ops read vars through the scope; make feeds visible
            for k, v in feed.items():
                scope.set_var(k, v.data if isinstance(v, core.LoDTensor)
                              else v)
        from . import profiler as _profiler
        prof = _profiler.is_enabled()
        for item in plan:
            if prof:
                t0 = _time_mod.perf_counter()
            if isinstance(item, _Segment):
                run_segment(item)
            elif item[0] == 'bucket':
                if run_bucket is None:
                    raise NotImplementedError(
                        'a while loop with an unbounded gradient '
                        '(auto-bucketed trip count) runs on one chip '
                        'only, not under a mesh')
                with _trace.span('bucket_count', op=item[1].type):
                    run_bucket(item[1])
            else:
                op = item[1]
                monitor.add('executor/host_ops_run')
                with _trace.span('host_op', op=op.type):
                    registry.get(op.type).fn(self, scope, op)
            if prof:
                if isinstance(item, _Segment):
                    # host-time to COMPLETION, not dispatch
                    for n in item.output_names:
                        if n in fetched:
                            jax.block_until_ready(fetched[n])
                    name = item.ops[0].type if len(item.ops) == 1 \
                        else 'segment[%d ops]' % len(item.ops)
                else:
                    name = item[1].type
                _profiler.record_op(name, _time_mod.perf_counter() - t0)

    def _lookup_input(self, name, feed, scope):
        """One-off argument lookup for the cold paths (bucket
        counting); the run loop binds through _SegmentBinder."""
        if name in feed:
            return _normalize_feed_value(feed[name])
        val = scope.find_var(name)
        if val is None:
            raise _uninitialized(name)
        return core.as_array(val)

    def _run_bucket_count(self, op, feed, scope, device,
                          prefer_test=False):
        """Host leg of the unbounded-while gradient: run the loop ONCE
        as a cheap non-differentiable lax.while_loop over the concrete
        carries, count the trips, round up to the next power of two,
        and stamp `max_trip_count` on every op of the bucket group
        (forward while + its grad).  Downstream segments compile once
        per distinct bucket (_run_segment keys its executable on the
        group's buckets) — O(log trips) compiles total, the bucketed-
        loader recipe applied to control flow."""
        import jax.numpy as jnp
        program = op.block.program
        sub = program.blocks[op.attrs['sub_block']]
        cond_name = op.input('Condition')[0]
        carry_names = list(op.attrs['__carry_names__'])
        if cond_name not in carry_names:
            carry_names.append(cond_name)
        env = {}
        for n in dict.fromkeys(_op_dep_reads(op)):
            env[n] = self._lookup_input(n, feed, scope)

        cache = op.attrs.setdefault('__count_fn__', {})
        count_jit = cache.get(prefer_test)
        if count_jit is None:
            def count(env_in, step, _pt=prefer_test):
                # `step` is traced so step-seeded stochastic ops
                # (dropout keys fold it in) draw the SAME values here
                # as in the real forward segment, and _pt matches the
                # segment's train/test lowering mode — the measured
                # trip count must match the loop the bucket will run
                def cond_fn(st):
                    carry, _ = st
                    return jnp.asarray(carry[cond_name]).reshape(
                        ()).astype(bool)

                def body_fn(st):
                    carry, i = st
                    local = dict(env_in)
                    local.update(carry)
                    _lower_ops(sub.ops, local, step, _pt)
                    new = {n: jnp.asarray(local[n]).astype(
                        jnp.asarray(carry[n]).dtype)
                        for n in carry_names}
                    return new, i + 1

                init = ({n: jnp.asarray(env_in[n])
                         for n in carry_names}, jnp.int32(0))
                _, trips = jax.lax.while_loop(cond_fn, body_fn, init)
                return trips

            count_jit = cache[prefer_test] = jax.jit(count)
        with jax.default_device(device):
            trips = int(count_jit(env, jnp.uint32(self._step)))
        bucket = 1
        while bucket < max(trips, 1):
            bucket *= 2
        gid = op.attrs['__bucket_group__']
        for o in op.block.ops:
            if o.attrs.get('__bucket_group__') == gid:
                o.attrs['max_trip_count'] = bucket

    def _run_segment(self, seg, feed, scope, device, fetched):
        """The one-chip runner's part of a segment: bind the arguments
        and resolve the executable (AOT compile plane, or a lazy jit);
        `_dispatch_segment` runs it."""
        # segments holding auto-bucketed while ops compile one
        # executable PER BUCKET (the masked-scan length is baked into
        # the trace); the cache also keys on the auto-layout flag so
        # toggling it takes effect on already-compiled programs
        auto = bool(get_flag('FLAGS_segment_auto_layout'))
        # flags that change the LOWERING must key the executable cache,
        # or toggling them after first compile is silently ignored
        prec = str(get_flag('FLAGS_conv_precision', 'highest'))
        wpg = bool(get_flag('FLAGS_whole_program_grad'))
        key = (auto, prec, wpg) + _pallas_flag_items() + \
            tuple(op.attrs.get('max_trip_count')
                  for op in seg.bucket_ops)
        binder = seg.binder
        if binder is None:
            binder = seg.binder = _SegmentBinder(seg)
        state, data = binder.bind(feed, scope)
        plane = compile_cache.plane()
        first_run = False
        if plane.active and not auto:
            # AOT compile plane: executables are content-addressed and
            # resolved memory -> in-flight future -> disk -> compile,
            # so a restarted process (or a warmup()ed one) runs its
            # first step without paying the trace+compile serially.
            # (auto-layout executables are excluded: they are known to
            # break when reloaded from the persistent cache, flags.py.)
            # The per-step lookup key is the CHEAP spec form — raw
            # (name, shape, dtype) in the binder's deterministic dict
            # order, no sort, no dtype stringification — the hot loop
            # pays attribute reads only; the canonical sorted form is
            # computed once, on miss, for the fingerprint.
            skey = (key,
                    tuple((n, getattr(v, 'shape', ()),
                           getattr(v, 'dtype', None))
                          for n, v in state.items()),
                    tuple((n, getattr(v, 'shape', ()),
                           getattr(v, 'dtype', None))
                          for n, v in data.items()))
            compiled = seg.compiled.get(skey)
            if compiled is None:
                monitor.add('executor/segment_cache_miss')
                specs = compile_cache.arg_specs(state, data)
                # the executor's device is part of the executable
                # identity: a non-default place compiles (and caches)
                # its own executable, matching the lazy path's
                # jax.default_device(device) compile.  So is the
                # segment's OUTPUT selection: the same ops planned for
                # a different fetch set is a different executable (it
                # returns different vars) — without it, the first
                # fetch set's executable would be served content-
                # addressed to every later plan over the same ops
                fp = compile_cache.fingerprint(
                    seg.ops, specs,
                    _lowering_flag_items(seg.prefer_test, wpg) +
                    (int(getattr(device, 'id', 0)),) +
                    tuple(sorted(seg.output_names)),
                    donate=True)
                state_specs, data_specs = _specs_from_args(state, data)
                try:
                    compiled = plane.obtain(
                        fp, lambda: _aot_build(seg, wpg, state_specs,
                                               data_specs, device))
                except Exception as e:
                    # the plane lowers here, before any dispatch: name
                    # a diverging feed as _dispatch_segment does
                    note = _feed_mismatch_note(
                        seg.ops[0].block.program, feed)
                    if note:
                        _add_note(e, note)
                    raise
                seg.compiled[skey] = compiled
                # memory-plane attribution: once per NEW executable
                # entry — compile, memory hit or disk hit all land
                # here, so a zero-retrace restarted process keeps its
                # per-(program, segment) peak decomposition
                row_label = '%dops:%s@%s' % (
                    len(seg.ops), ','.join(sorted(seg.output_names)[:3]),
                    fp[:8])
                _memviz.record_segment(
                    None, row_label, compiled, state_specs, data_specs,
                    seg=seg, held_key=fp)
                # this call only (seg.compiled holds the executable):
                # the allocator's marks around the entry's first run
                compiled = _memviz.watch_first_run(compiled, row_label)
            else:
                monitor.add('executor/segment_cache_hit')
        else:
            compiled = seg.compiled.get(key)
            # executable-cache accounting (reference STAT_ADD
            # counters): a miss lowers + compiles this segment; each
            # auto-bucket size is its own executable and counts as its
            # own miss
            first_run = compiled is None
            if first_run:
                monitor.add('executor/segment_cache_miss')
                monitor.add('executor/segments_lowered')
                compiled = seg.compiled[key] = _jit_segment(
                    seg, auto, whole_program_grad=wpg)
            else:
                monitor.add('executor/segment_cache_hit')

        def call(c=compiled):
            if _is_default_device(device):
                # `device` IS where jax would place this anyway, so the
                # default_device context is a no-op — and it must be
                # skipped CONSISTENTLY (first call included): a config
                # context present on call 1 but absent on call 2 makes
                # every later call miss jit's C++ fast path on the
                # config mismatch and re-enter the python dispatch
                # (~ms), which is exactly the host cost this path kills
                return c(self._step, state, data)
            with jax.default_device(device):
                return c(self._step, state, data)

        def shape_polymorphic():
            # an AOT executable is shape/tree-exact; an argument kind
            # it cannot absorb (exotic array subclass, odd scalar)
            # falls back to the shape-polymorphic jit — correctness
            # over the cached-compile win
            monitor.add('executor/compile_cache_fallbacks')
            jitted = seg.compiled[skey] = _jit_segment(
                seg, auto, whole_program_grad=wpg)
            return lambda: call(jitted)

        # the donated state is gone after the call: take its specs now
        noted = _lowering_args(self._step, state, data) \
            if first_run and _is_default_device(device) else None
        self._dispatch_segment(
            seg, call, state, data, feed, scope, fetched, first_run,
            # one chip lowers every op the plain way, so a segment can
            # be replayed op by op outside its executable
            replayable=True,
            aot_fallback=shape_polymorphic
            if plane.active and not auto else None)
        if noted is not None:
            # only a program that ran: one whose first call raised (a
            # feed of the wrong shape) cannot be lowered again, and
            # would fail every later scope table of the process
            plane.note_lazy((id(seg), key), compiled, noted,
                            label=(_memviz.current_program() or
                                   'unlabeled', _segment_label(seg)))

    def _dispatch_segment(self, seg, call, state, data, feed, scope,
                          fetched, first_run, comms_key=None,
                          replayable=False, aot_fallback=None,
                          describe_args=False):
        """What dispatching one compiled segment is, for every runner:
        `call()` runs the executable the runner resolved on the
        arguments it placed; everything around the call lives here, the
        error path included.

        `first_run`: the call traces and compiles (the 'compile' span
        and the compile-seconds histogram; exempt from the watchdog: a
        legitimate cold compile can exceed any step deadline).
        `comms_key`: the fingerprint a mesh runner's shared jit is
        registered under; its lowerings file collective records there
        at trace time, and jit holds the executable, so its first
        memory row is estimated from the arguments.  None on
        one chip: no collective to account, and the compile plane
        recorded the exact row when it built the executable.
        `replayable`: the segment's ops can be run again one by one,
        eagerly, on copies of these arguments (NaN provenance).  Not
        under a mesh: c_* lowerings need shard_map's
        bound axis names, mesh-aware lowerings need the trace mesh.
        `aot_fallback`: builds the call to retry with when an AOT
        executable refuses an argument's kind (the compile plane's
        executables are shape- and tree-exact).
        `describe_args`: on failure, spell out every argument's shape,
        dtype and sharding: shard_map's own errors name the positions
        of spec leaves, not variables."""
        step = self._step
        check_nan, health_on, step_timeout = self._posture
        replay = prev_params = hp = None
        if check_nan and replayable and get_flag('FLAGS_nan_replay',
                                                 True):
            # the op-by-op provenance replay needs the segment inputs
            # AS FED; state buffers are donated (deleted by the step),
            # so snapshot them now — async device copies, debug-mode
            # only (data args are not donated: pointers suffice)
            with _trace.span('nan_snapshot'):
                replay = ({n: _survivable_copy(v)
                           for n, v in state.items()}, dict(data))
        if health_on:
            hp = seg.health_params
            if hp is None:
                hp = seg.health_params = _segment_health_names(seg)
            if hp[0]:
                # update ratios compare against the pre-step weights,
                # which the donated step deletes — same snapshot rule;
                # a live nan-replay snapshot already paid for these
                # copies, reuse it instead of copying params twice
                src = replay[0] if replay is not None else None
                prev_params = {
                    n: (src[n] if src is not None and n in src
                        else _survivable_copy(state[n]))
                    for n in hp[0] if n in state}
        mesh = comms_key is not None
        recs = comms.records_for(comms_key) if mesh else ()
        chaos = _finject.armed()

        def run():
            if chaos:
                # 'executor.dispatch:stall:<s>' is a hung device call,
                # 'collective.dispatch:stall:<s>' a straggling
                # collective, ':fail' a fabric fault
                _finject.check('collective.dispatch' if mesh
                               else 'executor.dispatch', step=step)
            return call()

        def watched():
            res = run()
            # the execution sync must park INSIDE the guarded region:
            # jit dispatch is async, so a wedged device call (or a
            # collective blocked on a dead peer) would otherwise hang
            # later, at fetch or at the donated-state release, outside
            # the watchdog.  Armed-mode cost: the step loses
            # dispatch/compute overlap (an opt-in resilience posture).
            jax.block_until_ready(res)
            return res

        try:
            if first_run or recs:
                t0 = _time_mod.perf_counter()
            try:
                if first_run:
                    # the first call of a jitted segment traces +
                    # compiles synchronously (only execution is
                    # async), so timing it is the per-segment
                    # compile-latency histogram — and the step's
                    # 'compile' phase span; steady-state calls are the
                    # async 'dispatch' phase
                    marks = _memviz.device_marks()
                    with comms.collecting(comms_key) if mesh \
                            else contextlib.nullcontext(), \
                            _trace.span('compile'):
                        out = run()
                    if mesh:
                        recs = comms.records_for(comms_key)
                elif step_timeout > 0:
                    # hung-step watchdog (FLAGS_step_timeout_s): a
                    # dispatch blocked past the deadline dumps the
                    # flight recorder with this segment named and
                    # raises StepTimeoutError instead of hanging the
                    # process
                    with _dispatch_span(comms_key, recs):
                        out = _sup.guard_dispatch(
                            watched, _segment_label(seg, comms_key),
                            step_timeout, step=step)
                else:
                    with _dispatch_span(comms_key, recs):
                        out = run()
            except TypeError:
                if first_run or aot_fallback is None:
                    raise
                call = aot_fallback()
                with _trace.span('compile', ops=len(seg.ops)):
                    out = call()
            if first_run:
                monitor.observe(
                    'parallel/segment_compile_seconds' if mesh
                    else 'executor/segment_compile_seconds',
                    _time_mod.perf_counter() - t0)
                # whose first run raised the allocator's marks; waits
                # for the outputs, as only a first run may
                _memviz.first_run_end(
                    marks, None, _segment_label(seg, comms_key), out)
                if mesh:
                    # keeps the per-program HBM headroom gate live for
                    # programs a mesh runner compiled, until
                    # memviz.build_tables() has the real row
                    _memviz.record_segment_estimate(
                        None, _segment_label(seg, comms_key), state,
                        data, outputs=out, seg=seg)
            if recs:
                # achieved bandwidth needs the EXECUTION wall, not the
                # async dispatch: block here — the donated-state
                # release below would block on the in-flight execution
                # anyway, so this only moves that sync earlier and
                # attributes it to comms
                jax.block_until_ready(out)
                comms.account_dispatch(recs,
                                       _time_mod.perf_counter() - t0,
                                       compile_run=first_run)
        except Exception as e:
            note = _feed_mismatch_note(seg.ops[0].block.program, feed)
            if note:
                _add_note(e, note)
            if describe_args:
                _add_note(e, 'segment inputs:\n  ' + '\n  '.join(
                    '%s[%s]: %s %s %s' % (
                        group, n, getattr(v, 'shape', '?'),
                        getattr(v, 'dtype', '?'),
                        getattr(v, 'sharding', type(v).__name__))
                    for group, d in (('state', state), ('data', data))
                    for n, v in d.items()))
            oom_note = None
            if _memviz.is_oom_error(e):
                # OOM forensics (the memory analog of the NaN
                # provenance path): embed the live census + per-segment
                # peaks + largest buffers in the flight dump and name
                # the top contributors in the error itself
                oom_note = _memviz.oom_incident(e, step=step,
                                                scope=scope)
                if oom_note:
                    _add_note(e, oom_note)
            # one dump per incident: the OOM dump already embeds the
            # full flight recorder + snapshot, so the generic segfail
            # dump runs only when the OOM path didn't write one
            if not (oom_note and 'flight dump' in oom_note):
                note = _flight_dump_note('segfail_step%d' % step)
                if note:
                    _add_note(e, note)
            raise
        if check_nan:
            self._check_nan_inf(out, seg=seg, replay=replay)
        if health_on and hp[0]:
            from . import health as _health
            _health.summarize_step(step, out, prev_params or {},
                                   hp[0], hp[1])
        for n, v in out.items():
            scope.set_var(n, v)
            fetched[n] = v
        _release_donated_state(state)

    def _check_nan_inf(self, out, seg=None, replay=None):
        """Reference: CheckVarHasNanOrInf per-op sweep
        (framework/details/nan_inf_utils.h:28) — here per segment
        output, which is where values become observable.  The isfinite
        reduction runs ON DEVICE; only the per-var scalar verdict
        crosses to the host (the old path np.asarray'd every full
        output tensor every step).  All reductions dispatch before the
        first verdict blocks, so the device sweeps them in one wave —
        and since every verdict is already in flight, the error
        reports EVERY non-finite var of the step, not just the first.
        A trip then replays the segment op-by-op against the recorded
        inputs (fluid.health.nan_provenance) to name the op desc that
        first went non-finite — the reference's per-op sweep
        granularity, paid only post-mortem."""
        import jax.numpy as jnp
        verdicts = []
        for n, v in out.items():
            if isinstance(v, jax.Array):
                if jnp.issubdtype(v.dtype, jnp.floating):
                    verdicts.append((n, jnp.isfinite(v).all()))
            else:
                arr = np.asarray(core.as_array(v))
                if np.issubdtype(arr.dtype, np.floating):
                    verdicts.append((n, np.isfinite(arr).all()))
        bad = [n for n, ok in verdicts if not bool(ok)]
        if not bad:
            return
        monitor.add('health/nan_trips')
        from . import health as _health
        parts = ['nan/inf detected in %d var(s) [%s] (step %d)'
                 % (len(bad), ', '.join(bad), self._step)]
        report = None
        if seg is not None and replay is not None:
            with _trace.span('nan_replay', ops=len(seg.ops)):
                report = _health.nan_provenance(
                    seg.ops, replay[0], replay[1], self._step,
                    seg.prefer_test)
            parts.append(_health.format_provenance(report))
        # incident capture: the flight recorder holds the last N
        # steps' spans — exactly the window that produced the NaN —
        # dump it (with the provenance report embedded) before the
        # step loop unwinds
        extra = {'kind': 'nan_check', 'step': self._step,
                 'bad_vars': bad}
        if report is not None:
            extra['provenance'] = report
        note = _flight_dump_note('nan_step%d' % self._step, extra)
        if note:
            parts.append(note)
        # the provenance/dump notes go INTO the message (this
        # interpreter may predate PEP 678 add_note) and as notes for
        # 3.11+ tooling that renders them separately
        err = FloatingPointError('\n'.join(parts))
        for p in parts[1:]:
            _add_note(err, p)
        raise err


def _as_numpy(v):
    return np.asarray(core.as_array(v))


def _train_or_infer_from_dataset(executor, program, dataset, scope,
                                 thread, debug, fetch_list, fetch_info,
                                 print_period):
    """Shared body of train/infer_from_dataset.

    Reference: executor.py:1115 train_from_dataset -> TrainerFactory ->
    MultiTrainer threads (framework/trainer.h:64, hogwild_worker.cc:163).
    TPU-native: the native feeder (runtime/datafeed.cc) overlaps parsing
    with device steps; the jitted segment is the 'device worker'.
    thread=N (N>1) adds the Hogwild-worker overlap that remains
    meaningful on one XLA device: an N-deep background prefetch queue
    staging batches onto the device while the current step runs (the
    N-workers-one-queue shape; true hogwild param racing has no analog
    under jit, and the reference's N>1 result is nondeterministic
    anyway)."""
    program = program or framework.default_main_program()
    scope = scope or core.global_scope()
    fetch_list = fetch_list or []
    fetch_names = [v.name if isinstance(v, framework.Variable) else v
                   for v in fetch_list]
    # trainer/worker config plane (reference TrainerFactory in
    # executor.py:962): fleet opt_info picks the trainer class and can
    # set thread_num when the call leaves thread=0
    from .trainer_desc import TrainerFactory
    opt_info = getattr(program, '_fleet_opt', None)
    trainer = TrainerFactory()._create_trainer(opt_info)
    trainer._set_program(program)
    trainer._set_debug(debug)
    if thread:
        trainer._set_thread(thread)
    elif not opt_info or 'thread_num' not in opt_info:
        trainer._set_thread(0)  # serial default without explicit config
    trainer._gen_trainer_desc()
    thread = trainer.proto_desc['thread_num']
    step = 0
    if thread and int(thread) > 1:
        from .reader import _AsyncBatchIterator
        batches = _AsyncBatchIterator(dataset.batches, int(thread),
                                      executor.place.jax_device())
    else:
        batches = dataset.batches()
    for feed in batches:
        fetches = fetch_names if (fetch_names and print_period and
                                  step % print_period == 0) else []
        out = executor.run(program, feed=feed, fetch_list=fetches,
                           scope=scope)
        if fetches:
            info = fetch_info or fetch_names
            msg = ' '.join('%s=%s' % (k, np.asarray(v).ravel()[:4])
                           for k, v in zip(info, out))
            print('[dataset step %d] %s' % (step, msg))
        step += 1
    return step


def _train_from_dataset(self, program=None, dataset=None, scope=None,
                        thread=0, debug=False, fetch_list=None,
                        fetch_info=None, print_period=100):
    """Reference: executor.py:1115."""
    return _train_or_infer_from_dataset(
        self, program, dataset, scope, thread, debug, fetch_list,
        fetch_info, print_period)


def _infer_from_dataset(self, program=None, dataset=None, scope=None,
                        thread=0, debug=False, fetch_list=None,
                        fetch_info=None, print_period=100):
    """Inference-only dataset sweep: like train_from_dataset but the
    program MUST NOT update parameters (the reference keeps separate
    entry points, python/paddle/fluid/executor.py:1115 region).  Handed
    a training program, the optimizer/backward ops are pruned to a
    cached inference clone rather than silently applied."""
    program = program or framework.default_main_program()
    has_update = any(
        op.attrs.get('__op_role__') in ('optimize', 'backward')
        for op in program.global_block().ops)
    if has_update:
        # cache keyed on the program version: a mutation after the
        # first call (more layers, re-minimize) must re-clone, not
        # silently run the stale pre-mutation graph
        ver = getattr(program, '_version', 0)
        cached = getattr(program, '_infer_clone', None)
        if cached is None or cached[0] != ver:
            cached = (ver, program.clone(for_test=True))
            program._infer_clone = cached
        program = cached[1]
    return _train_or_infer_from_dataset(
        self, program, dataset, scope, thread, debug, fetch_list,
        fetch_info, print_period)


Executor.train_from_dataset = _train_from_dataset
Executor.infer_from_dataset = _infer_from_dataset
