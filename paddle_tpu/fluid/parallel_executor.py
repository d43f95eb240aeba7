"""Data-parallel execution over a device mesh (ParallelExecutor analog).

Reference: framework/parallel_executor.cc + details/ SSA graph executors:
per-device graph clones, NCCL allreduce op-handles, param broadcast
(BCastParamsToDevices, parallel_executor.cc:638).

TPU-native re-design (see compiler.py docstring): one jitted computation
under a jax.sharding.Mesh; GSPMD partitions the batch axis and inserts ICI
all-reduces for the replicated parameter updates.  Parameter "broadcast"
is jit auto-replication of the scope's single-device arrays.
"""

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import compile_cache
from . import core
from . import memviz as _memviz
from . import monitor
from . import trace as _trace
from .executor import (_SegmentBinder, FetchHandle, _make_segment_fn,
                       _lowering_args, _lowering_flag_items,
                       _segment_label)
from .flags import get_flag


def _mesh_fingerprint_key(mesh):
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape))


def _bind_segment_args(seg, feed, scope):
    """Steady-state (state, data) bind for the parallel runners: the
    same precompiled binder tables the single-device executor uses
    (raw feeds — the runners do their own sharding-aware device
    placement downstream, so no donation copy here either)."""
    binder = seg.pbinder
    if binder is None:
        binder = seg.pbinder = _SegmentBinder(seg, raw_feed=True)
    return binder.bind(feed, scope, donate_feed_state=False)


def _resolve_fetch(val, return_numpy):
    if return_numpy == 'async':
        return FetchHandle(val, resolver=_fetch_to_host)
    return _fetch_to_host(val) if return_numpy else val


def _fetch_values(fetch_names, fetched, scope, return_numpy):
    results = []
    for name in fetch_names:
        val = fetched.get(name)
        if val is None:
            val = core.as_array(scope.find_var(name))
        results.append(_resolve_fetch(val, return_numpy))
    return results


def _resolve_fetches(fetch_names, fetched, scope, return_numpy):
    """The step's fetch list resolved, inside the step span: a blocking
    D2H here is step time, recorded as the executor's 'fetch_d2h'
    phase (an async handle records its own when it resolves)."""
    if not fetch_names:
        return []
    if return_numpy and return_numpy != 'async':
        with _trace.span('fetch_d2h'):
            return _fetch_values(fetch_names, fetched, scope,
                                 return_numpy)
    return _fetch_values(fetch_names, fetched, scope, return_numpy)


def _default_mesh(places=None):
    devs = jax.devices()
    if places:
        devs = [p.jax_device() for p in places]
    return Mesh(np.array(devs), ('dp',))


def _to_global(val, sharding, per_process=False):
    """Place a host value onto the mesh with `sharding`.

    Single-process: plain device_put.  Multi-process (jax.distributed —
    the reference's NCCL2 multi-trainer mode, SURVEY.md §2.4), two host
    value semantics exist, mirroring the reference trainer contract:

    - per_process=True: the value is this trainer's LOCAL batch shard;
      shards concatenate into the global array (each trainer feeds its
      own data, like each reference trainer reads its own file split).
    - per_process=False: the value is the FULL global value, identical
      on every process (params/accumulators — parameter init determinism
      plays the role of BCastParamsToDevices); each process contributes
      the slices of it that its devices own, so non-replicated
      shardings (ZeRO accumulator sharding, TP param shardings) work.
    """
    if jax.process_count() == 1:
        return jax.device_put(val, sharding)
    if isinstance(val, jax.Array) and not val.is_fully_addressable \
            and len(val.sharding.device_set) > 1:
        # already a global array (a prior step's output); reshard if the
        # target differs (e.g. XLA propagated a dp-sharded layout onto a
        # value pinned replicated) — device_put compiles a collective
        # reshard, the multi-host analog of the single-process path
        if val.sharding.is_equivalent_to(sharding, val.ndim):
            return val
        return jax.device_put(val, sharding)
    arr = np.asarray(val)
    if per_process:
        return jax.make_array_from_process_local_data(sharding, arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def _batch_feed_names(program, feed):
    """Feed vars with a batch (-1 leading) dim in the program — the only
    feeds that are sharded over dp; fixed-shape feeds are replicated.
    Vars the program cannot resolve are included in the set, falling
    back to the divisibility heuristic in the shard decision."""
    names = set()
    blk = program.global_block()
    for n in feed:
        try:
            shp = tuple(getattr(blk.var(n), 'shape', ()) or ())
        except Exception:
            shp = ()
        if not shp or shp[0] == -1:
            names.add(n)
    return names


def _fetch_to_host(val):
    """Fetched value -> numpy, gathering non-addressable shards on
    multi-process meshes."""
    if isinstance(val, jax.Array) and not val.is_fully_addressable:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(
            val, tiled=True))
    return np.asarray(val)


import weakref

# per-mesh memo (weak keys: entries die with the mesh, and a recycled
# object address can never alias a stale entry)
_MESH_CACHE = weakref.WeakKeyDictionary()


def _mesh_memo(mesh):
    memo = _MESH_CACHE.get(mesh)
    if memo is None:
        memo = _MESH_CACHE[mesh] = {}
    return memo


def _local_dp_slice(mesh, dp_size):
    """Number of dp-axis shards this process feeds: dp size scaled by
    the fraction of mesh devices this process owns (exact for 1-axis dp
    meshes, which is what the DP runners build).  Cached per mesh — this
    runs per feed per step."""
    memo = _mesh_memo(mesh)
    key = ('ldp', dp_size)
    if key not in memo:
        total = mesh.devices.size
        local = sum(d.process_index == jax.process_index()
                    for d in mesh.devices.flat)
        memo[key] = max(1, dp_size * local // total)
    return memo[key]


def _guard_local_batch(name, val, mesh, dp_size):
    """Friendly error for a process-local feed batch that cannot be
    evenly sharded over this process's slice of the dp axis; returns
    True when the feed is shardable."""
    local_dp = _local_dp_slice(mesh, dp_size) if jax.process_count() > 1 \
        else dp_size
    if getattr(val, 'ndim', 0) >= 1 and local_dp and \
            val.shape[0] % local_dp == 0:
        return True
    if jax.process_count() > 1 and getattr(val, 'ndim', 0) >= 1:
        # feeds differ per process: claiming replication would silently
        # train each trainer on its own data
        raise ValueError(
            'feed %r local batch %d not divisible by the local dp '
            'slice (%d shards/process); pad the batch or resize the '
            'mesh' % (name, val.shape[0], local_dp))
    return False


def _check_mesh_spans_processes(mesh):
    """On a multi-process runtime the dp mesh must cover every process;
    a process-local mesh would drop cross-trainer gradient sync.
    Cached per mesh — this runs every step."""
    nproc = jax.process_count()
    if nproc > 1:
        memo = _mesh_memo(mesh)
        if 'span' not in memo:
            owners = set(d.process_index for d in mesh.devices.flat)
            if len(owners) != nproc:
                raise ValueError(
                    'mesh spans %d of %d processes; multi-process data '
                    'parallelism needs a global mesh (use the default '
                    'mesh or pass devices from jax.devices(), not local '
                    'places)' % (len(owners), nproc))
            memo['span'] = True
    return mesh


def _hint_to_spec(hint, mesh, shape):
    """Layer-stamped sharding hint (tuple over dims; each entry None, an
    axis name, or a tuple of axis names) -> PartitionSpec valid on
    `mesh`: axes absent from the mesh (or with indivisible dims) degrade
    to replication, so one program runs on any mesh.  The degrade
    itself is the auto-sharding planner's validate_spec (one
    implementation of the contract); a hint that degrades to full
    replication still returns an explicit replicated spec — a stamped
    hint is FINAL, it never falls through to a user/planner rule."""
    if len(hint) != len(shape):
        return None
    from ..parallel.plan import validate_spec
    spec = validate_spec(P(*hint), shape,
                         {a: int(mesh.shape[a])
                          for a in mesh.axis_names})
    return spec if spec is not None else P(*([None] * len(shape)))


def get_mesh(compiled, program=None, feed=None):
    if getattr(compiled, '_mesh', None) is None:
        mesh = None
        if program is not None and \
                getattr(compiled, '_param_sharding_rule', None) is None:
            # auto-sharding planner (FLAGS_auto_shard): an unannotated
            # program gets its dp x fsdp x tp mesh synthesized from
            # the chosen layout (over the user's places when given);
            # choose_mesh returns None when the planner is off and the
            # default 1-axis dp mesh stands.  Mesh and plan share the
            # CompiledProgram's lifetime: a budget/model/flag change
            # applies to programs built after it (the lowering-flag
            # convention), never to a live one mid-run.
            from ..parallel import plan as _ashard
            devices = [p.jax_device() for p in compiled._places] \
                if compiled._places else None
            mesh = _ashard.choose_mesh(compiled, program, feed,
                                       devices=devices)
        compiled._mesh = mesh if mesh is not None \
            else _default_mesh(compiled._places)
    return _check_mesh_spans_processes(compiled._mesh)


def _runner_plan(executor, cache, key, program, feed, fetch_names,
                 origin):
    """The mesh runners' plan lookup; the plan-BUILD verification hook
    is the single-device executor's discipline: cache misses only, one
    flag read."""
    plan = cache.get(key)
    monitor.add('parallel/plan_cache_hit' if plan is not None
                else 'parallel/plan_cache_miss')
    if plan is None:
        plan = executor._build_plan(program, tuple(sorted(feed.keys())),
                                    tuple(fetch_names))
        if get_flag('FLAGS_program_verify'):
            from . import progcheck
            progcheck.verify_program(
                program, feed_names=tuple(sorted(feed.keys())),
                fetch_names=tuple(fetch_names), plan=plan,
                origin=origin)
        cache[key] = plan
    return plan


def run_parallel(executor, compiled, feed, fetch_names, scope,
                 return_numpy):
    """`with_data_parallel` / `with_mesh`: one jit over the mesh per
    segment, GSPMD partitions it.  Owns the mesh, the sharding rule
    and the placement; the step is `Executor._step_scope`'s, the
    dispatch `Executor._dispatch_segment`'s."""
    program = compiled.program
    mesh = get_mesh(compiled, program, feed)
    ndev = mesh.devices.size
    monitor.set_gauge('parallel/device_count', ndev)
    monitor.set_gauge('parallel/process_count', jax.process_count())
    plan = _runner_plan(
        executor, compiled._exec_cache,
        ('pplan', tuple(sorted(feed.keys())), tuple(fetch_names)),
        program, feed, fetch_names, 'parallel')
    fetched = {}
    param_rule = getattr(compiled, '_param_sharding_rule', None)
    batch_axes = (mesh.axis_names[0],)
    auto_plan = None
    if param_rule is None:
        from ..parallel import plan as _ashard
        if _ashard.enabled():
            # auto-sharding planner: rule-matched PartitionSpecs for
            # the sharded params (None for replicated ones, so the
            # ZeRO accumulator wrapper below still fires), the batch
            # sharded over every data axis of the chosen layout, and
            # the weight-update phase sharded through the EXISTING
            # with_sharded_optimizer_states path (arXiv:2004.13336
            # unified with ReduceStrategy.Reduce, not a parallel
            # implementation)
            auto_plan = _ashard.plan_for(compiled, program,
                                         ndev=ndev, feed=feed)
            # the execution mesh may not be the plan's own (the user
            # hand-placed a mesh via with_mesh): re-validate every
            # spec against the ACTUAL mesh axes, like batch_axes and
            # update_axis below — axes the mesh lacks degrade to
            # replication instead of crashing NamedSharding
            mesh_sizes = {a: int(mesh.shape[a])
                          for a in mesh.axis_names}

            def param_rule(name, shape, _p=auto_plan, _ms=mesh_sizes):
                return _ashard.validate_spec(_p.param_rule(name, shape),
                                             shape, _ms)
            # honor the plan's batch axes EXACTLY — () means the plan
            # priced (and the HBM gate admitted) a replicated batch
            # (tp-only layouts), so falling back to the mesh's first
            # axis would execute a placement the candidate table never
            # described
            batch_axes = tuple(a for a in auto_plan.batch_axes
                               if a in mesh.axis_names)
            # the planner only sets the update axis when the user
            # hasn't: a USER-set axis is never overridden, and a
            # planner-set one re-validates against the actual mesh
            # (a hand-placed with_mesh may lack the plan's axis)
            user_set = getattr(compiled, '_shard_opt_states_axis',
                               None) is not None and \
                not getattr(compiled, '_auto_opt_axis', False)
            if not user_set:
                if auto_plan.update_axis in mesh.axis_names:
                    compiled._shard_opt_states_axis = \
                        auto_plan.update_axis
                    compiled._auto_opt_axis = True
                elif getattr(compiled, '_auto_opt_axis', False):
                    compiled._shard_opt_states_axis = None
                    compiled._auto_opt_axis = False
    hints = getattr(program, '_sharding_hints', None)
    if hints:
        # layer-stamped hints (moe expert weights on 'ep', attention
        # activations on 'sp') take precedence; the user rule fills in
        # the rest.  Under the auto-planner a hint whose axes ALL
        # degraded on this mesh (e.g. 'ep' on a planner-built
        # dp x fsdp x mp layout) falls through to the plan's rule
        # instead of pinning replication — the plan priced and
        # HBM-gated that rule spec, so executing anything else would
        # falsify the gate; a USER rule keeps the hint-is-final
        # contract
        user_rule = param_rule

        def param_rule(name, shape, _u=user_rule, _h=hints,
                       _ap=auto_plan):
            if name in _h:
                spec = _hint_to_spec(_h[name], mesh, shape)
                if spec is not None and (
                        _ap is None or
                        any(e is not None for e in spec)):
                    return spec
            return _u(name, shape) if _u is not None else None
    zero_axis = getattr(compiled, '_shard_opt_states_axis', None)
    if zero_axis is not None and zero_axis not in mesh.axis_names:
        # a pre-set axis (ReduceStrategy.Reduce defaults to 'dp') the
        # actual mesh lacks — e.g. a planner-built dp=1 layout drops
        # the size-1 dp axis: re-home onto the plan's update axis when
        # one exists, else skip the accumulator sharding rather than
        # KeyError on mesh.shape
        zero_axis = auto_plan.update_axis if (
            auto_plan is not None and
            auto_plan.update_axis in mesh.axis_names) else None
    if zero_axis is not None:
        param_names = set(p.name for p in program.all_parameters())
        base_rule = param_rule

        def param_rule(name, shape, _base=base_rule):  # noqa: F811
            if _base is not None:
                spec = _base(name, shape)
                if spec is not None:
                    return spec
            # accumulators (not model params): shard dim 0 over dp
            if name not in param_names and len(shape) >= 1 and \
                    shape[0] % mesh.shape[zero_axis] == 0 and \
                    shape[0] > 1:
                return P(zero_axis)
            return None
    if get_flag('FLAGS_program_verify') and param_rule is not None and \
            not getattr(compiled, '_progcheck_shard_ok', False):
        # static sharding legality of the RESOLVED rule (user
        # with_param_shardings specs are otherwise unvalidated until
        # NamedSharding throws mid-trace): unknown axes, indivisible
        # dims, axis reuse — checked once per CompiledProgram, before
        # the first segment traces
        from . import progcheck
        shapes = {p.name: tuple(p.shape)
                  for p in program.all_parameters()}
        progcheck.check_sharding(
            shapes, {n: param_rule(n, s) for n, s in shapes.items()},
            {a: int(mesh.shape[a]) for a in mesh.axis_names},
            label=_memviz.program_label(program),
            origin='with_param_shardings')
        compiled._progcheck_shard_ok = True
    batch_feeds = _batch_feed_names(program, feed)
    with executor._step_scope(program, scope):
        executor._walk_plan(
            plan, feed, scope, fetched,
            lambda seg: _run_segment_parallel(
                executor, seg, feed, scope, fetched, mesh, param_rule,
                batch_feeds, hints, batch_axes, auto_plan))
        return _resolve_fetches(fetch_names, fetched, scope,
                                return_numpy)


def _run_segment_parallel(executor, seg, feed, scope, fetched, mesh,
                          param_rule, batch_feeds, hints, batch_axes,
                          auto_plan):
    """One segment under GSPMD: place state and data by the resolved
    rule, build (or share) the jit with those `in_shardings`, hand it
    to the executor's dispatch."""
    repl = NamedSharding(mesh, P())
    dp_size = 1
    for a in batch_axes:
        dp_size *= mesh.shape[a]
    batch_spec = P(batch_axes if len(batch_axes) > 1
                   else batch_axes[0]) if batch_axes else P()

    def data_shard(name, val):
        if hints and name in hints and jax.process_count() == 1:
            spec = _hint_to_spec(hints[name], mesh,
                                 getattr(val, 'shape', ()))
            # under the auto-planner a fully-degraded hint falls
            # through to the plan's batch sharding (which the plan
            # priced); a hand-placed mesh keeps hint-is-final
            if spec is not None and (
                    auto_plan is None or
                    any(e is not None for e in spec)):
                return NamedSharding(mesh, spec)
        if name in feed and name in batch_feeds:
            # batch_axes == () (a tp-only auto plan): the batch stays
            # replicated, exactly as the plan priced it — but on a
            # multi-process run feeds are process-LOCAL, so claiming
            # replication would silently train each trainer on its
            # own data (the _guard_local_batch hazard): raise instead
            if batch_axes and _guard_local_batch(name, val, mesh,
                                                 dp_size):
                return NamedSharding(mesh, batch_spec)
            if not batch_axes and jax.process_count() > 1 and \
                    getattr(val, 'ndim', 0) >= 1:
                raise ValueError(
                    'feed %r: the auto-shard plan replicates the '
                    'batch (no data axis on mesh %r), but feeds are '
                    'process-local on a %d-process run — a replicated '
                    'claim would silently train each trainer on its '
                    'own data; choose a layout with a data axis or '
                    'feed identical global batches'
                    % (name, tuple(mesh.axis_names),
                       jax.process_count()))
        return repl

    def state_shard(name, val):
        if param_rule is not None:
            spec = param_rule(name, getattr(val, 'shape', ()))
            if spec is not None:
                return NamedSharding(mesh, spec)
        return repl

    state, data = _bind_segment_args(seg, feed, scope)
    # pin state shardings by resharding the inputs (device_put is a
    # no-op when the array already matches); outputs inherit XLA's
    # propagated shardings and flow back here next step
    with _trace.span('place_state'):
        state = {n: _to_global(v, state_shard(n, v))
                 for n, v in state.items()}

    def _convert_data(n, v):
        sh = data_shard(n, v)
        return _to_global(v, sh, per_process=sh.spec != P())
    with _trace.span('place_data'):
        data = {n: _convert_data(n, v) for n, v in data.items()}
    compiled = seg.compiled.get('parallel')
    first_run = compiled is None
    monitor.add('parallel/segment_cache_miss' if first_run
                else 'parallel/segment_cache_hit')
    if compiled is None:
        fn0 = _make_segment_fn(seg)

        # publish the mesh and the batch axes for the duration of
        # TRACING so mesh-aware op lowerings (ring_attention / moe_ffn,
        # ops/parallel_ops.py; the flash kernels' wrap) can open
        # shard_maps over its named axes; the context manager runs
        # inside the traced python body, i.e. exactly at trace time
        def fn(step, state, data, _fn0=fn0, _mesh=mesh):
            from ..parallel import mesh as pmesh
            with pmesh.use_trace_mesh(_mesh, batch_axes):
                return _fn0(step, state, data)
        fn.__name__ = fn0.__name__
        in_shardings = (None,
                        {n: state_shard(n, state[n])
                         for n in seg.state_names},
                        {n: data_shard(n, data[n]) for n in
                         seg.input_names})
        # the jit object is shared through the compile plane: a
        # re-built CompiledProgram (plan-cache churn, program version
        # bumps) with a content-identical segment + mesh + shardings
        # reuses the existing traced jit instead of re-tracing, and
        # with FLAGS_compile_cache_dir the underlying XLA compile
        # dedupes across processes via jax's persistent cache
        # the planner digest makes collective-planning decisions part
        # of the segment fingerprint: a flag/model change retraces
        # exactly once, an unchanged plan never retraces
        # the auto-shard digest keys the executable by the plan that
        # produced it (plan specs already ride repr(in_shardings);
        # the digest covers the flag/rules/model/budget inputs), so a
        # plan change retraces exactly once and an unchanged plan
        # never retraces
        from . import comms_plan
        from ..parallel import plan as _ashard
        fp = compile_cache.fingerprint(
            seg.ops,
            (_mesh_fingerprint_key(mesh), repr(in_shardings),
             tuple(sorted(seg.output_names)),
             comms_plan.digest(), _ashard.digest(),
             auto_plan.digest() if auto_plan is not None else None),
            _lowering_flag_items(False, False),
            donate=True, purpose='parallel')
        compiled = compile_cache.plane().shared_jit(
            fp, lambda: jax.jit(fn, in_shardings=in_shardings,
                                donate_argnums=(1,)))
        seg.compiled['parallel'] = compiled
        seg.comms_key = fp
    _dispatch_noting(executor, seg, compiled, executor._step, state,
                     data, feed, scope, fetched, first_run)


def _dispatch_noting(executor, seg, compiled, step, state, data, feed,
                     scope, fetched, first_run, describe_args=False):
    """Hand a mesh runner's shared jit to the executor's dispatch, and
    after a first call that ran, tell the compile plane how to find
    the compiled program again (a program whose first call raised
    cannot be lowered again, and is not noted)."""
    # the donated state is gone after the call: take its specs now
    noted = _lowering_args(step, state, data) if first_run else None
    executor._dispatch_segment(
        seg, lambda: compiled(step, state, data), state, data, feed,
        scope, fetched, first_run, comms_key=seg.comms_key,
        describe_args=describe_args)
    if first_run:
        compile_cache.plane().note_lazy(
            seg.comms_key, compiled, noted,
            label=(_memviz.current_program() or 'unlabeled',
                   _segment_label(seg, seg.comms_key)))


def run_collective(executor, program, feed, fetch_names, scope,
                   return_numpy):
    """Shard-map execution of a collective-rewritten program (fleet
    GradAllReduce mode): the program's c_allreduce_* ops lower to
    jax.lax collectives over the 'dp' mesh axis; each mesh device runs
    the trainer-local program on its batch shard."""
    if getattr(program, '_mesh', None) is None:
        program._mesh = _default_mesh()
    mesh = _check_mesh_spans_processes(program._mesh)
    monitor.set_gauge('parallel/device_count', mesh.devices.size)
    plan = _runner_plan(
        executor, program._exec_cache,
        ('cplan', tuple(sorted(feed.keys())), tuple(fetch_names),
         id(executor)),
        program, feed, fetch_names, 'collective')
    fetched = {}
    batch_feeds = _batch_feed_names(program, feed)
    with executor._step_scope(program, scope):
        executor._walk_plan(
            plan, feed, scope, fetched,
            lambda seg: _run_segment_collective(
                executor, seg, feed, scope, fetched, mesh, batch_feeds))
        # fetch resolution inside the step span, same as run_parallel:
        # a blocking D2H here is step time the report must attribute
        return _resolve_fetches(fetch_names, fetched, scope,
                                return_numpy)


def _run_segment_collective(executor, seg, feed, scope, fetched, mesh,
                            batch_feeds):
    """One segment under shard_map: batch feeds split over 'dp',
    everything else replicated; build (or share) the jitted shard_map,
    hand it to the executor's dispatch."""
    import jax.numpy as jnp
    ndev = mesh.devices.size
    state, data = _bind_segment_args(seg, feed, scope)
    data_specs = {n: (P('dp') if (n in feed and n in batch_feeds and
                                  getattr(data[n], 'ndim', 0) >= 1 and
                                  (jax.process_count() == 1 or
                                   _guard_local_batch(n, data[n], mesh,
                                                      ndev)))
                      else P())
                  for n in seg.input_names}
    if jax.process_count() > 1:
        # multi-trainer mode: feeds are process-local shards, params
        # replicated global arrays (reference NCCL2 multi-process DP)
        with _trace.span('place_state'):
            state = {n: _to_global(v, NamedSharding(mesh, P()))
                     for n, v in state.items()}
        with _trace.span('place_data'):
            data = {n: _to_global(
                        v, NamedSharding(mesh, data_specs[n]),
                        per_process=data_specs[n] != P())
                    for n, v in data.items()}
    compiled = seg.compiled.get('collective')
    first_run = compiled is None
    monitor.add('parallel/segment_cache_miss' if first_run
                else 'parallel/segment_cache_hit')
    if compiled is None:
        fn = _make_segment_fn(seg)
        in_specs = (P(),
                    {n: P() for n in seg.state_names},
                    data_specs)
        out_specs = {n: P() for n in seg.output_names}
        # shared through the compile plane, same contract as the
        # data-parallel runner above
        # planner decisions resolve at trace time against this
        # mesh; folding the digest in keys the executable (and its
        # comms records) by the plan that produced it
        from . import comms_plan
        from ..parallel import plan as _ashard
        fp = compile_cache.fingerprint(
            seg.ops,
            (_mesh_fingerprint_key(mesh), repr(in_specs),
             repr(out_specs), comms_plan.digest(),
             _ashard.digest()),
            _lowering_flag_items(False, False),
            donate=True, purpose='collective')

        def _build(_fn=fn, _in=in_specs, _out=out_specs):
            from ..compat import shard_map
            sm = shard_map(_fn, mesh=mesh, in_specs=_in,
                           out_specs=_out)
            return jax.jit(sm, donate_argnums=(1,))

        compiled = compile_cache.plane().shared_jit(fp, _build)
        seg.compiled['collective'] = compiled
        seg.comms_key = fp
    if jax.process_count() > 1:
        # a process-local scalar would carry an inconsistent
        # single-device sharding across processes; replicate it
        step = _to_global(np.int64(executor._step),
                          NamedSharding(mesh, P()))
    else:
        step = jnp.asarray(executor._step)
    _dispatch_noting(executor, seg, compiled, step, state, data, feed,
                     scope, fetched, first_run,
                     # shard_map's own errors name the positions of
                     # spec leaves, not variables: spell them out
                     describe_args=True)


class ParallelExecutor(object):
    """API-compat wrapper. Reference: python/paddle/fluid/parallel_executor.py."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None):
        from . import framework
        from .compiler import CompiledProgram
        from .executor import Executor
        program = main_program or framework.default_main_program()
        self._compiled = CompiledProgram(program).with_data_parallel(
            loss_name=loss_name, build_strategy=build_strategy,
            exec_strategy=exec_strategy)
        self._exe = Executor(core.XLAPlace(0))
        self._scope = scope

    def run(self, fetch_list, feed=None, feed_dict=None,
            return_numpy=True):
        feed = feed if feed is not None else feed_dict
        return self._exe.run(self._compiled, feed=feed,
                             fetch_list=fetch_list, scope=self._scope,
                             return_numpy=return_numpy)
