"""Stat-coverage audit: the monitor instrument points the observability
contract depends on must stay in the source (the CI-gate analog of
check_op_coverage.py, for fluid.monitor instead of the op registry).

Each entry below is (file, literal stat key) — a refactor that drops
one silently blinds production scraping, so this exits nonzero and
names the missing point.  Run from `make check`.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (repo-relative file, substring that must appear in it)
REQUIRED = [
    # executor core: segment executable cache, compile latency, volume
    ('paddle_tpu/fluid/executor.py', 'executor/segment_cache_hit'),
    ('paddle_tpu/fluid/executor.py', 'executor/segment_cache_miss'),
    ('paddle_tpu/fluid/executor.py', 'executor/segments_lowered'),
    ('paddle_tpu/fluid/executor.py', 'executor/segment_compile_seconds'),
    ('paddle_tpu/fluid/executor.py', 'executor/plan_cache_hit'),
    ('paddle_tpu/fluid/executor.py', 'executor/feed_bytes'),
    ('paddle_tpu/fluid/executor.py', 'executor/fetch_bytes'),
    ('paddle_tpu/fluid/executor.py', 'executor/run_seconds'),
    ('paddle_tpu/fluid/executor.py', 'executor/host_ops_run'),
    # steady-state fast path (PR 2): binder cache behavior, batched
    # async H2D, blocked fetch time — tools/check_hot_path.py budgets
    # these per step
    ('paddle_tpu/fluid/executor.py', 'executor/fastpath_hits'),
    ('paddle_tpu/fluid/executor.py', 'executor/scope_lookups'),
    ('paddle_tpu/fluid/executor.py', 'executor/bind_seconds'),
    ('paddle_tpu/fluid/executor.py', 'executor/h2d_bytes_async'),
    ('paddle_tpu/fluid/executor.py', 'executor/fetch_blocked_seconds'),
    ('paddle_tpu/fluid/executor.py', 'executor/plan_cache_bypass'),
    # AOT compile plane (PR 3): content-addressed executable reuse
    # across processes, background warmup, bounded in-memory caches —
    # tools/check_compile_cache.py exercises the cross-process path
    ('paddle_tpu/fluid/compile_cache.py',
     'executor/compile_cache_disk_hit'),
    ('paddle_tpu/fluid/compile_cache.py',
     'executor/compile_cache_disk_miss'),
    ('paddle_tpu/fluid/compile_cache.py',
     'executor/compile_cache_memory_hit'),
    ('paddle_tpu/fluid/compile_cache.py',
     'executor/compile_cache_corrupt'),
    ('paddle_tpu/fluid/executor.py', 'executor/aot_compiles'),
    ('paddle_tpu/fluid/executor.py', 'executor/warmup_seconds'),
    ('paddle_tpu/fluid/executor.py', 'executor/warmup_segments'),
    ('paddle_tpu/fluid/executor.py',
     'executor/segment_cache_evictions'),
    ('paddle_tpu/fluid/framework.py',
     'executor/plan_cache_evictions'),
    ('paddle_tpu/fluid/executor.py',
     'executor/compile_cache_fallbacks'),
    # data-parallel / collective runners
    ('paddle_tpu/fluid/parallel_executor.py', 'parallel/device_count'),
    ('paddle_tpu/fluid/parallel_executor.py',
     'parallel/segment_cache_miss'),
    ('paddle_tpu/fluid/executor.py',
     'parallel/segment_compile_seconds'),
    ('paddle_tpu/fluid/compiler.py',
     'compiler/data_parallel_programs_built'),
    # async input pipeline
    ('paddle_tpu/fluid/reader.py', 'reader/queue_depth'),
    ('paddle_tpu/fluid/reader.py', 'reader/batches_produced'),
    ('paddle_tpu/fluid/reader.py', 'reader/batches_consumed'),
    ('paddle_tpu/fluid/reader.py', 'reader/consume_blocked_seconds'),
    ('paddle_tpu/fluid/reader.py', 'reader/bytes_staged'),
    # PS / RPC planes
    ('paddle_tpu/fluid/incubate/fleet/parameter_server/__init__.py',
     'ps/push_bytes'),
    ('paddle_tpu/fluid/incubate/fleet/parameter_server/__init__.py',
     'ps/step_seconds'),
    ('paddle_tpu/distributed/rpc_ps.py', 'rpc/calls'),
    ('paddle_tpu/distributed/rpc_ps.py', 'rpc/call_seconds'),
    ('paddle_tpu/distributed/rpc_ps.py', 'rpc/retries'),
    ('paddle_tpu/distributed/communicator.py', 'communicator/sends'),
    ('paddle_tpu/distributed/communicator.py',
     'communicator/grads_merged'),
    # collective rewrites + trace-time lowering accounting
    ('paddle_tpu/fluid/transpiler/collective.py',
     'collective/%s_ops_inserted'),
    ('paddle_tpu/ops/collective_ops.py', 'collective/traced_bytes'),
    # profiler fold-in
    ('paddle_tpu/fluid/profiler.py', "profiler/%s/calls"),
    # span tracer / flight recorder (fluid/trace.py): its own counters
    # keep the trace plane observable through the monitor plane, and
    # the phase-span instrument sites across the hot path feed the
    # step_report() contract tools/check_trace.py gates end to end
    ('paddle_tpu/fluid/trace.py', 'trace/spans_recorded'),
    ('paddle_tpu/fluid/trace.py', 'trace/steps_recorded'),
    ('paddle_tpu/fluid/trace.py', 'trace/steps_dropped'),
    ('paddle_tpu/fluid/trace.py', 'trace/dumps_written'),
    ('paddle_tpu/fluid/executor.py', "_trace.span('feed_h2d'"),
    ('paddle_tpu/fluid/executor.py', "_trace.record('bind'"),
    ('paddle_tpu/fluid/executor.py', "_trace.span('dispatch'"),
    ('paddle_tpu/fluid/executor.py', "_trace.record('fetch_d2h'"),
    ('paddle_tpu/fluid/executor.py', 'executor/state_release_seconds'),
    ('paddle_tpu/fluid/reader.py', "_trace.record('reader_wait'"),
    ('paddle_tpu/fluid/parallel_executor.py', "_step_scope("),
    ('paddle_tpu/fluid/compile_cache.py', "'cache_deserialize'"),
    # health plane (fluid/health.py): the HTTP status surface, the
    # aggregator's worker probes, the tensor-health summaries and the
    # NaN/divergence detectors — tools/check_health.py exercises the
    # endpoints end to end, this audit keeps the instrument points
    ('paddle_tpu/fluid/health.py', 'health/http_requests'),
    ('paddle_tpu/fluid/health.py', 'health/scrapes'),
    ('paddle_tpu/fluid/health.py', 'health/worker_up'),
    ('paddle_tpu/fluid/health.py', 'health/summary_steps'),
    ('paddle_tpu/fluid/health.py', 'health/global_grad_norm'),
    ('paddle_tpu/fluid/health.py', 'health/update_ratio'),
    ('paddle_tpu/fluid/health.py', 'health/grad_spikes'),
    ('paddle_tpu/fluid/health.py', 'health/zero_update_trips'),
    ('paddle_tpu/fluid/health.py', 'health/detector_dumps'),
    ('paddle_tpu/fluid/executor.py', 'health/nan_trips'),
    ('paddle_tpu/fluid/executor.py', 'executor/last_step_unix_ts'),
    ('paddle_tpu/fluid/monitor.py', '# HELP'),
    ('paddle_tpu/distributed/launch.py', 'PADDLE_TPU_STATUS_WORKERS'),
    # serving plane (fluid/serving.py): continuous-batching SLO
    # surface — per-tenant queue depth, batch occupancy,
    # admission-to-completion latency, pad waste, and the
    # zero-retrace-after-warmup accounting; tools/check_serving.py
    # exercises them against a live two-thread soak
    ('paddle_tpu/fluid/serving.py', 'serving/queue_depth'),
    ('paddle_tpu/fluid/serving.py', 'serving/batch_occupancy'),
    ('paddle_tpu/fluid/serving.py', 'serving/admit_to_done_seconds'),
    ('paddle_tpu/fluid/serving.py', 'serving/bucket_pad_waste_bytes'),
    ('paddle_tpu/fluid/serving.py', 'serving/requests'),
    ('paddle_tpu/fluid/serving.py', 'serving/batches'),
    ('paddle_tpu/fluid/serving.py', 'serving/retraces'),
    ('paddle_tpu/fluid/serving.py', 'serving/warmup_buckets'),
    ('paddle_tpu/fluid/serving.py', "_trace.step_tags"),
    ('paddle_tpu/fluid/trace.py', 'step_tags'),
    # job-wide observability (fluid/comms.py + trace.collect_job +
    # the aggregator's skew detector): collective telemetry with
    # bytes-on-wire and per-(collective, size-bucket) bandwidth,
    # cross-worker trace collection tolerance counters, per-segment
    # XLA memory gauges, and the straggler detector —
    # tools/check_comms.py exercises the whole plane against a real
    # two-process job
    ('paddle_tpu/fluid/comms.py', 'comms/bytes_on_wire'),
    ('paddle_tpu/fluid/comms.py', 'comms/payload_bytes'),
    ('paddle_tpu/fluid/comms.py', 'comms/collective_calls'),
    ('paddle_tpu/fluid/comms.py', 'comms/bw_gbps'),
    ('paddle_tpu/fluid/comms.py', 'executor/segment_peak_bytes'),
    ('paddle_tpu/fluid/comms.py', 'executor/segment_temp_bytes'),
    ('paddle_tpu/ops/collective_ops.py', 'comms.record_trace'),
    ('paddle_tpu/ops/parallel_ops.py', 'comms.record_trace'),
    ('paddle_tpu/fluid/executor.py', 'comms.account_dispatch'),
    ('paddle_tpu/fluid/executor.py', 'comms.collecting'),
    # collective planner (fluid/comms_plan.py + the planned lowerings
    # in ops/collective_ops.py + the GradAllReduce bucket rewrite):
    # which arm ran, actual vs dense-equivalent wire bytes, the cost
    # model's predicted-vs-measured honesty, and the planner digest
    # folded into both runner fingerprints — tools/check_comms.py
    # asserts the counters move on a real quantized two-process job
    ('paddle_tpu/fluid/comms.py', 'comms/plan_arm/'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_wire_bytes'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_dense_equiv_bytes'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_predicted_seconds'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_measured_seconds'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_pred_over_measured'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_unpriced'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_fused_grads'),
    ('paddle_tpu/fluid/transpiler/collective.py',
     'collective/plan_buckets'),
    ('paddle_tpu/fluid/transpiler/collective.py',
     'collective/plan_fused_grads'),
    ('paddle_tpu/ops/collective_ops.py', '_planned_allreduce'),
    ('paddle_tpu/fluid/parallel_executor.py', 'comms_plan.digest'),
    ('paddle_tpu/fluid/health.py', 'comms_plan.program_plans'),
    ('paddle_tpu/fluid/executor.py', '_comms.record_memory'),
    # a restarted (disk-hit) process must keep memory accounting
    ('paddle_tpu/fluid/compile_cache.py', 'comms.record_memory'),
    ('paddle_tpu/fluid/trace.py', 'trace/collect_skipped'),
    ('paddle_tpu/fluid/trace.py', 'trace/collect_unanchored'),
    ('paddle_tpu/fluid/trace.py', 'ptClock'),
    ('paddle_tpu/fluid/health.py', 'comms/skew_ratio'),
    ('paddle_tpu/fluid/health.py', 'comms/straggler_trips'),
    ('paddle_tpu/fluid/health.py', 'step_rollup'),
    ('paddle_tpu/distributed/launch.py', 'PADDLE_TPU_STATUS_WORKERS'),
    ('tools/comms_calibrate.py', 'inv_bw_s_per_byte'),
    ('tools/timeline.py', 'collect_job'),
    # device-memory observability plane (fluid/memviz.py): per-
    # (program, segment) peak attribution, the live-HBM census sampler
    # + Perfetto counter track, OOM forensics and budget watermarks —
    # tools/check_memviz.py exercises the plane against a warmed LeNet
    ('paddle_tpu/fluid/memviz.py', 'memviz/segments_attributed'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/program_peak_bytes'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/live_bytes/'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/live_bytes_total'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/live_bytes_hwm'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/budget_utilization'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/watermark_trips'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/spike_trips'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/oom_incidents'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/oom_dumps'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/analysis_unavailable'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/samples'),
    # the allocator's high-water marks as last read around a new
    # executable's first run (memviz.high_water() names who raised
    # each), shown by `stat_summary.py --memory`
    ('paddle_tpu/fluid/memviz.py', 'memviz/hwm_in_use_bytes'),
    ('paddle_tpu/fluid/memviz.py', 'memviz/hwm_reserved_bytes'),
    ('paddle_tpu/fluid/executor.py', '_memviz.first_run_end'),
    ('paddle_tpu/fluid/executor.py', '_memviz.record_segment'),
    ('paddle_tpu/fluid/executor.py', '_memviz.maybe_sample'),
    ('paddle_tpu/fluid/executor.py', '_memviz.oom_incident'),
    ('paddle_tpu/fluid/trace.py', 'trace/counter_samples'),
    ('paddle_tpu/fluid/comms_plan.py', 'memviz.peak_bytes'),
    ('paddle_tpu/fluid/health.py', 'memviz.memory_pressure'),
    ('paddle_tpu/fluid/serving.py', 'register_scope_provider'),
    ('tools/stat_summary.py', 'memviz/live_bytes_total'),
    ('tools/stat_summary.py', 'memviz/hwm_in_use_bytes'),
    ('tools/stat_summary.py', 'memviz/hwm_reserved_bytes'),
    # auto-sharding planner (parallel/plan.py): plan build volume, the
    # priced-candidate table, the memviz HBM-gate rejections, the
    # unpriced-term honesty counter, the chosen-layout gauges, and the
    # digest folded into BOTH runner fingerprints —
    # tools/check_autoshard.py asserts the counters move on a real
    # two-process job with FLAGS_auto_shard=1
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_builds'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_candidates'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_hbm_rejected'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_unpriced'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_reused'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_params_sharded'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_layout_dp'),
    ('paddle_tpu/parallel/plan.py', 'parallel/plan_seconds'),
    ('paddle_tpu/fluid/parallel_executor.py', '_ashard.digest'),
    ('paddle_tpu/fluid/transpiler/collective.py',
     'auto_shard_plan.transpile_plan'),
    ('paddle_tpu/fluid/health.py', 'auto_shard_plan.report'),
    ('tools/stat_summary.py', 'parallel/plan_hbm_rejected'),
    # elastic resilience plane (fluid/elastic.py + fluid/faultinject.py
    # + the rpc/heartbeat retry satellites): crash-consistent store
    # volume, refusal accounting, the reshard schedule's predicted-vs-
    # measured honesty, staged-assembly waves, trainer re-admission,
    # heartbeat flap tolerance, rpc backoff, and the fault-injection
    # tallies — tools/check_elastic.py exercises the plane across real
    # process boundaries including a kill -9 mid-save
    ('paddle_tpu/fluid/elastic.py', 'elastic/checkpoints_saved'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/checkpoints_loaded'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/save_bytes'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/save_seconds'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/load_seconds'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/shards_written'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/last_generation'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/generations_pruned'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/refused_generations'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/refusal_dumps'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/reshard_params'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/reshard_wire_bytes'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/reshard_unpriced'),
    ('paddle_tpu/fluid/elastic.py',
     'elastic/reshard_predicted_seconds'),
    ('paddle_tpu/fluid/elastic.py',
     'elastic/reshard_measured_seconds'),
    ('paddle_tpu/fluid/elastic.py',
     'elastic/reshard_pred_over_measured'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/staging_waves'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/readmissions'),
    ('paddle_tpu/distributed/heartbeat.py', 'elastic/readmissions'),
    ('paddle_tpu/distributed/heartbeat.py',
     'elastic/heartbeat_flaps'),
    ('paddle_tpu/fluid/health.py', 'elastic/heartbeat_flaps'),
    ('paddle_tpu/fluid/faultinject.py', 'faultinject/armed'),
    ('paddle_tpu/fluid/faultinject.py', 'faultinject/hits'),
    ('paddle_tpu/fluid/faultinject.py', 'faultinject/fired'),
    ('paddle_tpu/distributed/rpc_ps.py', 'rpc/backoff_seconds'),
    ('paddle_tpu/distributed/rpc_ps.py', 'rpc_exhausted'),
    ('paddle_tpu/fluid/executor.py', '_finject.check'),
    ('paddle_tpu/fluid/executor.py', "'collective.dispatch'"),
    ('paddle_tpu/fluid/health.py', 'elastic.report'),
    # self-healing supervisor (fluid/supervisor.py + the hung-step
    # watchdog + serving shedding satellites): decision volume, the
    # checkpoint plane's backpressure/stretch/torn-resave accounting,
    # confirmed deaths -> recoveries with lost-work totals, step
    # timeouts, and the serving deadline/degraded shed counters —
    # tools/check_supervisor.py and tools/check_chaos.py exercise the
    # whole loop across real process boundaries
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/decisions'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/checkpoints_taken'),
    ('paddle_tpu/fluid/supervisor.py',
     'supervisor/checkpoint_deferred'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/checkpoint_torn'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/cadence_stretched'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/save_seconds'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/snapshot_seconds'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/deaths_confirmed'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/recoveries'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/recovery_seconds'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/lost_steps'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/hung_steps'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/rejoins_admitted'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/frozen_intents'),
    ('paddle_tpu/fluid/supervisor.py', 'supervisor/state_transitions'),
    ('paddle_tpu/fluid/supervisor.py', 'executor/step_timeouts'),
    ('paddle_tpu/fluid/executor.py', '_sup.guard_dispatch'),
    ('paddle_tpu/fluid/executor.py', '_sup.on_step_begin'),
    ('paddle_tpu/fluid/serving.py', 'serving/shed_expired'),
    ('paddle_tpu/fluid/serving.py', 'serving/shed_degraded'),
    ('paddle_tpu/fluid/serving.py', 'serving/degraded'),
    ('paddle_tpu/fluid/elastic.py', 'elastic/rejoin_retries'),
    ('paddle_tpu/fluid/health.py', 'supervisor.report'),
    ('paddle_tpu/fluid/health.py', 'peer_health'),
    # static Program verifier (fluid/progcheck.py): programs checked,
    # per-class diagnostic counters, seeded mutations, wall time —
    # tools/check_progcheck.py proves every class fires by name and
    # the /statusz verify section renders the report trail
    ('paddle_tpu/fluid/progcheck.py', 'verify/programs'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/clean'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/errors'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/warnings'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/diagnostics/'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/seconds'),
    ('paddle_tpu/fluid/progcheck.py', 'verify/mutations'),
    ('paddle_tpu/fluid/executor.py', '_verify_plan_build'),
    ('paddle_tpu/fluid/executor.py', 'progcheck.mutate'),
    ('paddle_tpu/fluid/parallel_executor.py', 'FLAGS_program_verify'),
    ('paddle_tpu/fluid/transpiler/collective.py',
     'progcheck.verify_program'),
    ('paddle_tpu/fluid/transpiler/__init__.py',
     'progcheck.verify_program'),
    ('paddle_tpu/fluid/comms_plan.py', 'verify_buckets'),
    ('paddle_tpu/parallel/plan.py', 'progcheck.check_sharding'),
    ('paddle_tpu/fluid/health.py', 'progcheck.report'),
    # time-series telemetry plane (fluid/timeseries.py): the
    # windowed-history sampler's own accounting, the job-history
    # retention at the aggregator, and the step-boundary/heartbeat
    # wiring that feeds them —
    # tools/check_timeseries.py exercises the plane against a live
    # two-process job
    ('paddle_tpu/fluid/timeseries.py', 'timeseries/samples'),
    ('paddle_tpu/fluid/timeseries.py', 'timeseries/sample_errors'),
    ('paddle_tpu/fluid/timeseries.py', 'timeseries/job_samples'),
    ('paddle_tpu/fluid/timeseries.py', 'timeseries/gap_points'),
    ('paddle_tpu/fluid/timeseries.py', 'timeseries/series'),
    ('paddle_tpu/fluid/executor.py', '_tseries.maybe_sample'),
    ('paddle_tpu/fluid/health.py', 'timeseries.job_sample'),
    ('paddle_tpu/fluid/health.py', 'timeseries.job_gap'),
    ('paddle_tpu/fluid/health.py', 'timeseries.http_query'),
    ('paddle_tpu/fluid/trace.py', 'trace/dumps_suppressed'),
    ('tools/stat_summary.py', 'ts.counter_deltas'),
    ('paddle_tpu/fluid/comms.py', 'comms/plan_pred_over_measured'),
    ('paddle_tpu/fluid/serving.py', 'serving/pad_waste_ratio'),
    ('paddle_tpu/fluid/serving.py', 'serving/warmup_buckets'),
    # rows a device capture could not attribute are counted
    ('paddle_tpu/fluid/profiler.py', 'profiler/dropped_events'),
    # manifold-constrained hyper-connections and the multi-token-
    # prediction module (ops/hyper_connection_ops.py, models/xing4.py):
    # lowerings, static gauges, and what the runs that fetch read
    # (benchmark/layer_metrics/mhc_stochastic_err.py, mtp_loss_share.py)
    ('paddle_tpu/ops/hyper_connection_ops.py', 'mhc/calls'),
    ('paddle_tpu/ops/hyper_connection_ops.py', 'mhc/streams'),
    ('paddle_tpu/ops/hyper_connection_ops.py', 'mhc/sinkhorn_iters'),
    ('paddle_tpu/models/xing4.py', 'mhc/stochastic_err'),
    ('paddle_tpu/models/xing4.py', 'mtp/loss'),
    ('paddle_tpu/models/xing4.py', 'mtp/loss_share'),
    ('paddle_tpu/ops/pallas/common.py', 'mhc/stochastic_err'),
    # recompute groups lowered (benchmark/layer_metrics/mhc_ms.py's note)
    ('paddle_tpu/fluid/executor.py', 'executor/recompute_groups'),
    # the selective state-space scan (ops/ssm_ops.py): lowerings, the
    # trips of its scans over chunks and the boundary states it keeps,
    # sums over one traced program
    # (benchmark/layer_metrics/ssm_chunks.py, ssm_state_mb.py)
    ('paddle_tpu/ops/ssm_ops.py', 'ssm/calls'),
    ('paddle_tpu/ops/ssm_ops.py', 'ssm/chunks'),
    ('paddle_tpu/ops/ssm_ops.py', 'ssm/boundary_state_mb'),
    # Mamba-2's chunked scan (ops/ssd_ops.py): the same three
    # (benchmark/layer_metrics/ssd_chunks.py, ssd_state_mb.py)
    ('paddle_tpu/ops/ssd_ops.py', 'ssd/calls'),
    ('paddle_tpu/ops/ssd_ops.py', 'ssd/chunks'),
    ('paddle_tpu/ops/ssd_ops.py', 'ssd/boundary_state_mb'),
]


def main():
    missing = []
    for rel, needle in REQUIRED:
        path = os.path.join(ROOT, rel)
        try:
            with open(path) as f:
                src = f.read()
        except OSError:
            missing.append('%s: FILE MISSING (needed %r)'
                           % (rel, needle))
            continue
        if needle not in src:
            missing.append('%s: instrument point %r disappeared'
                           % (rel, needle))
    print('stat instrument points: %d required, %d present'
          % (len(REQUIRED), len(REQUIRED) - len(missing)))
    if missing:
        for m in missing:
            print('MISSING  ' + m)
        return 1
    print('coverage: complete')
    return 0


if __name__ == '__main__':
    sys.exit(main())
