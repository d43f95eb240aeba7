"""Global flags registry.

Reference: ~60 gflags DEFINEs (platform/flags.cc + per-module), read from
FLAGS_* env vars at import (python/paddle/fluid/__init__.py:163-228) with
runtime get/set via pybind/global_value_getter_setter.cc.

Here: one registry, initialized from FLAGS_* env vars, with the
paddle 2.x-style get_flags/set_flags surface.
"""

import os

_DEFAULTS = {
    'FLAGS_check_nan_inf': False,
    'FLAGS_benchmark': False,
    'FLAGS_eager_delete_tensor_gb': 0.0,   # subsumed by XLA liveness
    'FLAGS_fraction_of_gpu_memory_to_use': 0.92,  # accepted, unused
    'FLAGS_cudnn_deterministic': False,
    'FLAGS_cpu_deterministic': False,
    'FLAGS_paddle_num_threads': 1,
    'FLAGS_use_pinned_memory': True,
    'FLAGS_print_op_timing': False,
    'FLAGS_sync_nccl_allreduce': False,    # XLA dataflow orders comms
    'FLAGS_communicator_fake_rpc': False,
    'FLAGS_rpc_deadline': 180000,
    'FLAGS_rpc_retry_times': 3,
    # let XLA choose boundary layouts for executor segments (AUTO
    # layouts), so persistent state lives in the layout the compute
    # wants.  Off by default: measured ~0 gain on the ResNet headline
    # (the boundary casts are layout-forced for any f32-master-weight
    # program; pre-round reading, not measured on current code).  The
    # installed jax takes Layout.AUTO only for arguments that carry no
    # layout of their own: host feeds and uncommitted arrays work (the
    # executor stages feeds uncommitted on the default device), a
    # committed device array fed by the caller is refused by jit.
    'FLAGS_segment_auto_layout': False,
    # Lower eligible train segments as forward ops + ONE jax.vjp over
    # the whole forward region instead of per-op synthesized grad
    # replay (executor._wpg_partition).  Identical math — the per-op
    # grads are vjp of the same lowerings and stochastic lowerings key
    # RNG on (op_seed, step) — but XLA schedules the backward as one
    # graph, the hand-written-JAX shape (BERT-long 144.7 -> 119.8
    # ms/step, pre-round reading).  DEFAULT ON since round 5;
    # ineligible segments (recompute programs, consumed intermediate
    # grads, split forwards) automatically keep the per-op path.
    'FLAGS_whole_program_grad': True,
    # AOT compile plane (compile_cache.py): a directory here turns on
    # the persistent on-disk segment-executable store AND the run
    # path's AOT compilation (jit(fn).lower(specs).compile()), so a
    # restarted process reloads executables instead of recompiling.
    # PADDLE_TPU_COMPILE_CACHE_DIR is the friendlier spelling of the
    # same knob; FLAGS_compile_cache_dir env/set_flags wins when both
    # are set.  Empty (the default) leaves the plane off — the PR-2
    # steady-state fast path is then byte-identical.
    'FLAGS_compile_cache_dir':
        os.environ.get('PADDLE_TPU_COMPILE_CACHE_DIR', ''),
    # background compile pool width for Executor.warmup / background
    # segment compilation; 0 = min(4, cpu_count)
    'FLAGS_compile_threads': 0,
    # LRU capacities for the long-running-service caches (0 = unbounded,
    # the pre-PR-3 behavior): per-program plan cache, per-segment
    # executable cache (per-shape AOT entries + bucket executables),
    # and the plane's process-wide fingerprint->executable map
    'FLAGS_plan_cache_capacity': 64,
    'FLAGS_segment_cache_capacity': 32,
    'FLAGS_compile_cache_memory_capacity': 256,
    # span tracer / flight recorder (fluid/trace.py): FLAGS_trace=1
    # enables span recording at import (the always-on production
    # posture); off, every trace.span() site costs one function call +
    # one global load.  FLAGS_trace_buffer_steps bounds the flight
    # recorder: the last N executor steps' span records are retained
    # for dump()/step_report() (dumped automatically on NaN-check or
    # dispatch failure), older steps evict ('trace/steps_dropped').
    'FLAGS_trace': False,
    'FLAGS_trace_buffer_steps': 16,
    # fluid.health status plane (fluid/health.py): a nonzero port
    # starts the background HTTP status server at the first Executor
    # construction, exposing /metrics (Prometheus), /healthz
    # (liveness+readiness), /statusz (JSON runtime report) and
    # /trace/dump (on-demand flight-recorder dump).  0 (the default)
    # leaves the plane off; health.serve(port) starts it explicitly
    # (port=0 there picks an ephemeral port).
    'FLAGS_status_port': 0,
    # readiness staleness bound: with steps recorded, /healthz reports
    # not-ready when the last step is older than this many seconds
    # (0 disables the age check — batch jobs legitimately pause)
    'FLAGS_status_ready_max_step_age': 0.0,
    # aggregator probe cadence AND per-worker scrape timeout for the
    # rank-0 merged status plane (distributed/launch.py wires the
    # worker endpoints): a dead worker flips aggregated readiness
    # within one interval
    'FLAGS_health_heartbeat_seconds': 2.0,
    # opt-in per-step tensor-health summaries (fluid/health.py): fused
    # on-device reductions — global grad norm, per-param weight/grad/
    # update norms, update ratios — dispatched in one wave with
    # scalar-only host transfer, recorded into monitor histograms and
    # trace spans.  Off (the default) adds ZERO per-step host cost
    # (tools/check_health.py gates this via check_hot_path).
    'FLAGS_health_summaries': False,
    # spike detector: a global grad norm this many times above its
    # running EMA auto-dumps the flight recorder (health/grad_spikes)
    'FLAGS_health_spike_factor': 10.0,
    # zero-update detector: this many consecutive steps with a zero
    # max update ratio auto-dump the flight recorder
    # (health/zero_update_trips); 0 disables
    'FLAGS_health_zero_update_steps': 3,
    # straggler detector (rank-0 aggregator): when the slowest rank's
    # p50 step wall exceeds the cross-rank median by this factor, count
    # comms/straggler_trips and (rate-limited, tracer live) auto-dump
    # the flight recorder with the skew report embedded; 0 disables
    'FLAGS_straggler_factor': 2.0,
    # NaN provenance (executor._check_nan_inf): with
    # FLAGS_check_nan_inf on, keep per-step device copies of segment
    # state so a tripped verdict can replay the segment op-by-op and
    # name the op that first produced a non-finite value.  On by
    # default (it only costs while nan-checking, itself a debug mode);
    # turn off to nan-check huge models without the state copies.
    'FLAGS_nan_replay': True,
    # collective planner (fluid/comms_plan.py): with the flag on, the
    # GradAllReduce transpiler consults the planner per gradient —
    # same-dtype small grads coalesce into fused buckets
    # (c_allreduce_fused), each bucket's reduction arm (dense flat vs
    # reduce-scatter+allgather vs block-scaled int8 quantized) is
    # chosen from the calibrated comms cost model (comms_model.json,
    # falling back to a built-in heuristic), and every dispatch
    # reports its arm + predicted-vs-measured wall through fluid.comms
    # (comms/plan_arm/*).  Off restores the v1.6 one-flat-allreduce-
    # per-grad rewrite bit for bit.
    'FLAGS_comms_plan': True,
    # quantized-allreduce arm (EQuARX-style, arXiv:2506.17615):
    # quantize -> int8 reduce-scatter with per-block fp32 scales ->
    # dequantize/reduce -> requantize -> int8 allgather.  OFF by
    # default (it changes numerics ~1e-2 relative on the reduced
    # grads); per-tensor gated by FLAGS_comms_quantize_min_bytes so
    # latency-bound small tensors keep the dense path even when on.
    'FLAGS_comms_quantize': False,
    # per-tensor (or per fused bucket) payload floor for the quantized
    # arm: below this the dense path runs — bit-exact fallback
    'FLAGS_comms_quantize_min_bytes': 65536,
    # block length for the per-block fp32 scales of the quantized arm
    # (scale overhead = 4/block/itemsize of the payload)
    'FLAGS_comms_quant_block': 256,
    # grad-bucket fusion byte target: consecutive same-dtype grads
    # coalesce into fused buckets up to this many bytes so the
    # per-collective latency term is paid once per bucket; 0 disables
    # fusion (every grad reduces alone)
    'FLAGS_comms_bucket_bytes': 4 << 20,
    # per-grad fusion eligibility floor when NO cost model is loaded:
    # grads at/above this many bytes are bandwidth-bound and reduce
    # alone (fusing them buys no latency but pays concat/split
    # copies).  With comms_model.json loaded the cutoff is the
    # model's own latency/bandwidth crossover alpha/beta instead.
    'FLAGS_comms_fuse_grad_max_bytes': 64 << 10,
    # calibrated cost model path (tools/comms_calibrate.py artifact);
    # empty = ./comms_model.json when present, else the built-in
    # heuristic (flat below FLAGS_comms_rs_ag_min_bytes, rs+ag above)
    'FLAGS_comms_model_path': '',
    # heuristic dense-strategy cut when no cost model is loaded:
    # payloads at/above this use reduce-scatter+allgather
    'FLAGS_comms_rs_ag_min_bytes': 8 << 20,
    # per-segment HBM budget the planner must respect (bytes; 0 = no
    # budget): bucket fusion caps its fused-buffer size to the
    # headroom left over executor/segment_peak_bytes, and the
    # quantized arm (which needs ~2.25x the payload in temporaries)
    # falls back dense when the headroom is tighter than that
    'FLAGS_comms_hbm_budget_bytes': 0,
    # device-memory observability plane (fluid/memviz.py): FLAGS_memviz
    # turns on the per-step live-HBM sampler — a census over
    # jax.live_arrays() classified param/state/feed/exec/other into
    # memviz/live_bytes/* gauges and a Perfetto counter track merged
    # into the step timeline.  Off (the default) the executor pays one
    # flag read per step (tools/check_memviz.py holds it to that);
    # peak ATTRIBUTION (per-(program, segment) decomposition of each
    # AOT executable's memory_analysis()) and OOM forensics are always
    # on — they run at compile/incident time, never per step.
    'FLAGS_memviz': False,
    # census cadence: sample every N'th step (1 = every step; the
    # census is O(live arrays), so big-residency jobs may thin it)
    'FLAGS_memviz_sample_steps': 1,
    # HBM budget for the watermark detector, bytes; 0 = auto-detect
    # from device.memory_stats()['bytes_limit'] where the backend
    # reports it (CPU reports nothing -> watermarks off)
    'FLAGS_memviz_budget_bytes': 0,
    # utilization fraction of the budget that trips the watermark
    # detector (memviz/watermark_trips + rate-limited snapshot dump)
    'FLAGS_memviz_watermark': 0.9,
    # growth-spike detector: live bytes this many times over the
    # running EMA auto-dump the snapshot BEFORE the OOM; 0 disables
    'FLAGS_memviz_spike_factor': 2.0,
    # rate limits for the detector and OOM-incident flight dumps
    'FLAGS_memviz_dump_interval_s': 60.0,
    'FLAGS_memviz_oom_interval_s': 30.0,
    # auto-sharding planner (parallel/plan.py): with the flag on, an
    # UNANNOTATED CompiledProgram (no with_mesh / with_param_shardings)
    # is planned automatically — regex rule -> PartitionSpec matching
    # over its parameters emits a dp x fsdp x tp layout, candidate
    # layouts are priced with the comms cost model and HBM-gated by
    # the memviz budget BEFORE compiling, and the weight-update /
    # optimizer phase shards through the existing ZeRO path
    # (with_sharded_optimizer_states).  The plan digest folds into
    # segment fingerprints, so plans never go stale against cached
    # executables and unchanged plans never retrace.  Off (the
    # default) is bit-for-bit the hand-placed behavior.
    'FLAGS_auto_shard': False,
    # elastic resilience plane (fluid/elastic.py): with the flag on,
    # fluid.io.save_persistables writes the manifest-led elastic
    # checkpoint format — per-shard files + sharding metadata +
    # content digests, atomic tmp+rename publish, last-good
    # generations kept — instead of the one-.npz native format.
    # load_persistables auto-DETECTS an elastic store regardless of
    # the flag (a manifest directory loads back, with cross-topology
    # resharding, wherever it came from).  Off (the default) keeps
    # the v1.6-shaped single-file save byte-identical.
    'FLAGS_elastic_checkpoint': False,
    # how many intact generations an elastic store retains after a
    # successful publish (the newest is never pruned; >= 1)
    'FLAGS_elastic_keep_generations': 2,
    # host-side staging cap (bytes) for the reshard-on-load assembly:
    # target shards are assembled and device_put in waves no larger
    # than this (further bounded by the memviz budget headroom when
    # the device reports one), so an N->M reshard never gathers a
    # full model onto the host
    'FLAGS_elastic_stage_bytes': 256 << 20,
    # static Program verifier (fluid/progcheck.py): with the flag on,
    # every plan build runs the FULL static pass — graph invariants
    # (dangling reads, undeclared writes, torn sub-blocks), the
    # shape/dtype inference walk over the op descs, donation-hazard
    # analysis of the built plan, and fingerprint-stability lint —
    # BEFORE anything traces; error-class findings raise
    # ProgramVerifyError naming the op, the class and the fix.  Off
    # (the default) costs one flag read per plan BUILD (zero per
    # step: plan-cache hits never reach the gate); invariant+donation
    # verification still runs FORCED (level='fast') in
    # Executor.warmup and on every transpiler/planner output.
    'FLAGS_program_verify': False,
    # fault-injection harness (fluid/faultinject.py): semicolon-
    # separated '<site>:<action>[:<arg>][@n[+]]' clauses armed at
    # import — e.g. 'elastic.shard_write:die@2' kills the process on
    # the 2nd checkpoint shard write.  Empty (the default) disarms:
    # every instrumented site costs one module-global read.
    'FLAGS_faultinject': '',
    # self-healing supervisor (fluid/supervisor.py): the freeze/revert
    # switch for an ATTACHED controller — 0 keeps the controller
    # watching and LOGGING intents (supervisor/frozen_intents,
    # acted=False in the decision log) but executes nothing: no saves,
    # no recoveries.  The primitives stay hand-drivable either way;
    # supervision only exists at all once supervisor.attach() ran.
    'FLAGS_supervisor': True,
    # periodic-checkpoint cadence, in executor steps (0 = no periodic
    # checkpoints): every N steps the attached supervisor snapshots
    # the program's persistables at the step boundary and writes an
    # elastic generation on a background thread — never two saves in
    # flight (backpressure defers), and the cadence DOUBLES when the
    # write wall approaches the distance between cadence points
    # (supervisor/cadence_stretched)
    'FLAGS_supervisor_checkpoint_steps': 0,
    # rejoin-wait budget (seconds) for a confirmed worker death: when
    # the priced reshard schedule costs MORE than this, the supervisor
    # waits up to the budget for the dead worker to rejoin before
    # degrading to the survivors; cheaper reshards degrade immediately
    'FLAGS_supervisor_rejoin_wait_s': 10.0,
    # hung-step watchdog (fluid/supervisor.py guard_dispatch): a
    # nonzero deadline (seconds) runs every steady-state segment
    # dispatch — executor and both parallel runners — under a guard
    # thread; a dispatch blocked past the deadline (collective waiting
    # on a dead peer) dumps the flight recorder with the segment
    # named, counts executor/step_timeouts and raises StepTimeoutError
    # instead of hanging the process forever.  0 (the default) costs
    # one flag read per segment.
    'FLAGS_step_timeout_s': 0.0,
    # worker-liveness miss tolerance (distributed/heartbeat.py + the
    # rank-0 health aggregator): this many CONSECUTIVE missed
    # scrapes/expired checks before a worker flips to down/lost — one
    # dropped packet is not a death.  Recoveries short of the
    # threshold count elastic/heartbeat_flaps.
    'FLAGS_heartbeat_misses': 3,
    # PS/RPC retry backoff (distributed/rpc_ps.py): bounded
    # exponential backoff with full jitter between reconnect attempts
    # — sleep in [0.5, 1.0] x min(base x 2^(attempt-1), max).  base
    # 0 disables (the pre-elastic immediate-retry behavior).
    'FLAGS_rpc_backoff_ms': 50,
    'FLAGS_rpc_backoff_max_ms': 2000,
    # f32 conv MXU precision: 'highest' (6-pass bf16 emulation,
    # reference-accurate fp32 — the default), 'high' (3-pass), or
    # 'default' (single-pass bf16 inputs): accuracy against speed.
    # The multi-pass convolutions are kept out of XLA's fusions
    # (ops/nn_ops.py conv2d): fused with a neighbour, LeNet b512 at
    # 'highest' does not compile on the v5e; chip_smoke.py prints the
    # line.
    'FLAGS_conv_precision': 'highest',
    # windowed history plane (fluid/timeseries.py): on, the executor's
    # step boundary and the rank-0 aggregator's heartbeat each append
    # one point per monitor registry entry into a bounded ring
    # (FLAGS_timeseries_window points per series, sampling every
    # FLAGS_timeseries_sample_steps steps); rates/deltas/windowed
    # percentiles are derived at read time at /timeseries.  Off (the
    # default) the step boundary pays one flag read —
    # tools/check_timeseries.py holds that against check_hot_path's
    # budgets.
    'FLAGS_timeseries': False,
    'FLAGS_timeseries_window': 512,
    'FLAGS_timeseries_sample_steps': 1,
    # supervisor state-transition flight dumps go through
    # trace.rate_limited_dump under this interval; 0 (the default)
    # keeps the one-dump-per-transition behavior, a positive value
    # bounds a transition storm to one dump per interval
    'FLAGS_supervisor_dump_interval_s': 0.0,
    # Pallas kernel library (ops/pallas/): every fused kernel sits
    # behind the auto-dispatch + dense-fallback contract (see
    # ops/pallas/common.py) — off-TPU or when a gate fails, the dense
    # XLA reference runs instead, and the decision + reason land in
    # pallas/<kernel>/dispatch_* counters surfaced at /statusz.
    # FLAGS_pallas_force promotes the fused path even off-TPU
    # (interpret mode) — the knob parity tests use to exercise the
    # kernels on the CPU mesh; never set it in production.
    'FLAGS_pallas_force': False,
    # fused block-scaled quantize->reduce-scatter for the quantized
    # collective arm: the int8 copy + fp32 dequant temporaries of the
    # dense arm never materialize in HBM, and comms_plan prices the
    # quant arm with the reduced quant_hbm_temp term when this is
    # available (see _QUANT_MEM_FACTOR_FUSED)
    'FLAGS_pallas_quant_collective': True,
}

# v1.6 scripts set these; the TPU runtime ACCEPTS them for script
# compatibility but nothing reads them — XLA subsumes the behavior
# (buffer liveness, stream sync, allocator fractions, host threading).
# tools/staticcheck.py exempts exactly this tuple from its
# dead-flag lint; adding a flag here is a statement that it is
# compat-only surface.
V16_COMPAT_ONLY = (
    'FLAGS_benchmark',
    'FLAGS_communicator_fake_rpc',
    'FLAGS_cpu_deterministic',
    'FLAGS_cudnn_deterministic',
    'FLAGS_eager_delete_tensor_gb',
    'FLAGS_fraction_of_gpu_memory_to_use',
    'FLAGS_paddle_num_threads',
    'FLAGS_print_op_timing',
    'FLAGS_sync_nccl_allreduce',
    'FLAGS_use_pinned_memory',
)

_flags = {}


def _coerce(default, raw):
    if isinstance(default, bool):
        return raw.lower() in ('1', 'true', 'yes', 'on')
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _init():
    for k, v in _DEFAULTS.items():
        raw = os.environ.get(k)
        _flags[k] = _coerce(v, raw) if raw is not None else v


_init()


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _flags.get(k) for k in keys}


def set_flags(d):
    for k, v in d.items():
        _flags[k] = v


def get_flag(key, default=None):
    return _flags.get(key, default)
