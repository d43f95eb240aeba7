"""Benchmark: ResNet-50 ImageNet training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N}

vs_baseline compares against 365 images/sec/GPU — the per-chip throughput
of the reference's V100 ParallelExecutor ResNet-50 path in the fluid-v1.6
era (the reference repo itself publishes no numbers; see BASELINE.md).
"""

import json
import os
import sys
import time

import numpy as np

# process birth, as close as a module can observe it: --cold children
# measure start->first-step from here (python+import cost included —
# that IS part of a service replica's restart latency)
_PROC_T0 = time.time()


def _enable_compile_cache():
    """JAX's persistent compile cache, placed by the one rule
    (compile_cache.place_jax_cache).  Only config updates: a parent
    that goes on to spawn --one children must not bring a backend up."""
    import jax
    from paddle_tpu.fluid import compile_cache
    compile_cache.place_jax_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)


def bench_resnet50(batch=128, steps=30, warmup=5, amp=True,
                   data_format='NHWC'):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, logits, loss, acc = models.resnet.build(
            data_format=data_format)
        opt = fluid.optimizer.Momentum(0.1, momentum=0.9)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(
                opt, use_dynamic_loss_scaling=True)
        opt.minimize(loss)

    rng = np.random.RandomState(0)
    import jax
    shape = (batch, 224, 224, 3) if data_format == 'NHWC' else \
        (batch, 3, 224, 224)
    # synthetic batch resident on device: measure compute, not the
    # host->device pipe (the input pipeline is benched separately)
    x = jax.device_put(rng.rand(*shape).astype('float32'))
    y = jax.device_put(rng.randint(0, 1000, (batch, 1)).astype('int32'))

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        # warm up BOTH call signatures used below (fetch vs no-fetch
        # compile to different XLA programs) so no compile lands in the
        # timed region
        for _ in range(warmup):
            exe.run(main, feed={'image': x, 'label': y}, fetch_list=[])
        l, = exe.run(main, feed={'image': x, 'label': y},
                     fetch_list=[loss])
        np.asarray(l)  # force completion of warmup before timing
        t0 = time.time()
        # steady-state steps: no per-step fetch, dispatch stays async
        for _ in range(steps - 1):
            exe.run(main, feed={'image': x, 'label': y}, fetch_list=[])
        last, = exe.run(main, feed={'image': x, 'label': y},
                        fetch_list=[loss])
        np.asarray(last)  # block on the last step
        dt = time.time() - t0
    return batch * steps / dt


# tools/profile_step.py sets this so the device trace covers ONLY the
# steady-state timed loop: wrapping warmup/compile floods the trace
# buffer with host events (1M cap) and the device plane gets dropped
TRACE_LOGDIR = None


# published peaks per chip, keyed by jax's device_kind:
# (bf16 TFLOP/s, HBM GB/s).  v5e: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s); v4 / v5p / v6e: the same pages for
# those parts.  A kind without a row is an error, never a default.
CHIP_PEAKS = {'TPU v5 lite': (197.0, 819.0),
              'TPU v5e': (197.0, 819.0),
              'TPU v4': (275.0, 1228.0),
              'TPU v5p': (459.0, 2765.0),
              'TPU v6 lite': (918.0, 1640.0),
              'TPU v6e': (918.0, 1640.0)}


def _chip_peak():
    """(peak bf16 TFLOP/s, peak HBM GB/s) of the attached chip."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in CHIP_PEAKS:
        raise KeyError(
            'no published peaks for device_kind %r: utilization against '
            'an assumed peak would be a made-up number; add a row to '
            'bench.CHIP_PEAKS with its source' % kind)
    return CHIP_PEAKS[kind]


# achieved TFLOP/s and MFU of the last timed program, merged into the
# benches' JSON lines.  Empty since the count it was made from went
# (Executor.program_cost, XLA's own cost analysis after a second
# compile): benchmark/run.py's `mfu` and the profiler's cost table
# (fluid.profiler.cost_tables) are the numbers now
LAST_PERF = {}

# set by _timed_steps from fluid.trace's flight recorder over the timed
# window: the per-step phase breakdown (bind / feed_h2d / dispatch /
# state_release / fetch_d2h ms) + wall percentiles, so a BENCH file
# EXPLAINS a regression (which phase grew) instead of just reporting it
LAST_PHASES = {}


def _step_phase_fields():
    return {'step_phases': LAST_PHASES} if LAST_PHASES else {}


def _monitor_fields():
    """Always-on runtime-stats subset recorded alongside throughput, so
    BENCH_*.json carries the counters (segment-cache behavior, compile
    seconds, bytes fed) next to every images/sec number.  Each --all
    entry runs in its own child process, so the registry is per-entry:
    these are the counts for THIS bench's runs (warmup included)."""
    try:
        from paddle_tpu.fluid import monitor
        hist = monitor.histogram_value(
            'executor/segment_compile_seconds') or {}
        run = monitor.histogram_value('executor/run_seconds') or {}
        bind = monitor.histogram_value('executor/bind_seconds') or {}
        return {'monitor': {
            'segment_cache_hit':
                monitor.counter_value('executor/segment_cache_hit'),
            'segment_cache_miss':
                monitor.counter_value('executor/segment_cache_miss'),
            'compile_seconds': round(hist.get('sum', 0.0), 3),
            'feed_bytes': monitor.counter_value('executor/feed_bytes'),
            # dispatch-side host accounting (steady-state fast path)
            'run_seconds': round(run.get('sum', 0.0), 4),
            'run_calls': run.get('count', 0),
            'fastpath_hits':
                monitor.counter_value('executor/fastpath_hits'),
            'scope_lookups':
                monitor.counter_value('executor/scope_lookups'),
            'bind_seconds': round(bind.get('sum', 0.0), 5),
            'h2d_bytes_async':
                monitor.counter_value('executor/h2d_bytes_async'),
        }}
    except Exception:
        return {}


def _flatten_metrics(rec, prefix='', out=None):
    """Numeric leaves of one bench record as dotted-path series names
    ('value', 'monitor.run_seconds', 'step_phases.dispatch_ms') — the
    per-series form BENCH_history.jsonl keeps and
    tools/check_regress.py gates on.  Bools and strings are not
    metrics; lists are positional noise and skipped."""
    out = {} if out is None else out
    if isinstance(rec, dict):
        for k, v in rec.items():
            _flatten_metrics(v, prefix + '%s.' % k, out)
    elif isinstance(rec, bool):
        pass
    elif isinstance(rec, (int, float)):
        out[prefix[:-1]] = float(rec)
    return out


def append_history(entry, rec, path=None):
    """Run-to-run regression substrate: every bench entry appends ONE
    JSON line (wall time, entry name, flattened numeric metrics) to
    BENCH_history.jsonl — the recorded trajectory
    tools/check_regress.py compares a fresh run against, so a
    regression between runs is a named CI failure instead of a human
    diffing BENCH_*.json by hand.  PADDLE_TPU_BENCH_HISTORY overrides
    the path (the regression gate's self-test isolates there);
    PADDLE_TPU_BENCH_RUN_ID groups lines from one sweep.  Never
    raises — history must not cost a bench its result."""
    try:
        if path is None:
            path = os.environ.get('PADDLE_TPU_BENCH_HISTORY') or \
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             'BENCH_history.jsonl')
        line = {'ts': round(time.time(), 3), 'entry': str(entry),
                'run_id': os.environ.get('PADDLE_TPU_BENCH_RUN_ID'),
                'metrics': _flatten_metrics(rec)}
        with open(path, 'a') as f:
            f.write(json.dumps(line, sort_keys=True) + '\n')
        return path
    except Exception:
        return None


def _perf_fields(step_s, cost):
    """Achieved TFLOP/s and HBM GB/s against the chip's published
    peaks.  Empty off a TPU: a CPU run has no device utilization to
    report.  On a TPU kind with no row in CHIP_PEAKS this raises."""
    import jax
    if not cost or not cost.get('flops') or \
            jax.devices()[0].platform != 'tpu':
        return {}
    peak_tf, peak_bw = _chip_peak()
    tflops = cost['flops'] / step_s / 1e12
    gbps = cost.get('bytes', 0.0) / step_s / 1e9
    return {'tflops': round(tflops, 2),
            'mfu_pct': round(100.0 * tflops / peak_tf, 2),
            'hbm_gbps': round(gbps, 1),
            'hbm_pct': round(100.0 * gbps / peak_bw, 1)}


class _wpg(object):
    """Scoped FLAGS_whole_program_grad=True for the transformer bench
    entries (one jax.vjp over the forward region instead of per-op
    grad replay — measured 10% on the s2048 flash path and never
    worse; pre-round reading, not measured on current code).  Restores the flag on exit so a
    same-process caller's programs keep the default per-op path."""

    def __enter__(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.flags import get_flag
        self._prev = bool(get_flag('FLAGS_whole_program_grad'))
        fluid.set_flags({'FLAGS_whole_program_grad': True})

    def __exit__(self, *exc):
        import paddle_tpu.fluid as fluid
        fluid.set_flags({'FLAGS_whole_program_grad': self._prev})


def _timed_steps(exe, main_prog, feed, loss, steps=20, warmup=3):
    # device-resident feeds: measure compute, not the host->device
    # transfer
    import jax
    from paddle_tpu.fluid import trace as pt_trace
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    for _ in range(warmup):
        exe.run(main_prog, feed=feed, fetch_list=[])
    l, = exe.run(main_prog, feed=feed, fetch_list=[loss])
    np.asarray(l)
    if TRACE_LOGDIR:
        jax.profiler.start_trace(TRACE_LOGDIR)
    # flight recorder over the timed window only (a few us/step): the
    # entry's JSON then carries the step-phase breakdown.  An ALREADY
    # enabled tracer (FLAGS_trace=1 posture) keeps its own ring size —
    # resizing it would silently discard the user's retained steps
    trace_was_on = pt_trace.is_active()
    if not trace_was_on:
        pt_trace.enable(buffer_steps=steps)
    try:
        t0 = time.time()
        for _ in range(steps - 1):
            exe.run(main_prog, feed=feed, fetch_list=[])
        last, = exe.run(main_prog, feed=feed, fetch_list=[loss])
        np.asarray(last)
        dt = time.time() - t0
    finally:
        if TRACE_LOGDIR:
            jax.profiler.stop_trace()
        global LAST_PHASES
        try:
            roll = pt_trace.step_report(last=steps)['rollup']
            LAST_PHASES = {
                'wall_p50_ms': round(roll['wall_p50_ms'], 3),
                'wall_p99_ms': round(roll['wall_p99_ms'], 3),
                'phases_ms_per_step': {
                    n: round(v / max(roll['count'], 1), 3)
                    for n, v in roll['phases_ms'].items()},
            }
        except Exception:
            LAST_PHASES = {}
        if not trace_was_on:
            pt_trace.disable()
            pt_trace.reset()
    return dt / steps


def bench_bert(batch=32, seq_len=128, steps=20, cfg=None):
    """BASELINE.json config 2: BERT-base pretrain step time.

    At seq 128 the bf16 batched attention chain is the fast path (the
    Pallas flash kernels engage at seq >= cfg.flash_min_len where the
    [T,T] probs start to matter)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    cfg = cfg or models.bert.BertConfig()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, enc, loss = models.bert.build_pretrain(cfg, seq_len)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(1e-4),
            use_dynamic_loss_scaling=True)
        opt.minimize(loss)
    rng = np.random.RandomState(0)
    batch_data = models.bert.synthetic_batch(cfg, batch, seq_len, rng)
    with _wpg(), fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        dt = _timed_steps(exe, main, batch_data, loss, steps)
    return dict({'metric': 'bert_base_pretrain_step_ms_b%d_s%d'
                 % (batch, seq_len),
                 'value': round(dt * 1000, 2), 'unit': 'ms/step',
                 'seq_per_sec': round(batch / dt, 1)},
                **LAST_PERF, **_step_phase_fields(),
                **_monitor_fields())


def bench_bert_long(batch=4, seq_len=2048, steps=10):
    """Long-context BERT step on the Pallas flash path (fused one-pass
    backward since round 5) — the configuration where the [T,T] probs
    would otherwise dominate HBM.  attn_dropout=0 keeps the metric
    comparable across rounds; bench_bert_long_dropout runs the
    reference-default config."""
    from paddle_tpu import models
    cfg = models.bert.BertConfig(max_pos=seq_len, attn_dropout=0.0)
    return dict(bench_bert(batch=batch, seq_len=seq_len, steps=steps,
                           cfg=cfg),
                metric='bert_base_long_ctx_step_ms_b%d_s%d'
                       % (batch, seq_len))


def bench_bert_long_dropout(batch=4, seq_len=2048, steps=10):
    """Long-context BERT with the REFERENCE-DEFAULT attention-prob
    dropout (0.1): since round 5 the dropout mask is drawn inside the
    flash kernels (counter hash keyed on op seed + step), so the
    [T, T] probs still never materialize — the last semantic asterisk
    on the long-context story (VERDICT r4 missing #1)."""
    from paddle_tpu import models
    cfg = models.bert.BertConfig(max_pos=seq_len, attn_dropout=0.1)
    return dict(bench_bert(batch=batch, seq_len=seq_len, steps=steps,
                           cfg=cfg),
                metric='bert_base_long_ctx_dropout_step_ms_b%d_s%d'
                       % (batch, seq_len))


def bench_resnet_infer(batch=32, steps=30, warmup=5):
    """Inference throughput through the deployment path: ResNet-50
    saved with save_inference_model, reloaded by AnalysisPredictor
    (the reference's inference stack ran this through TensorRT;
    here the predictor's program compiles to one XLA executable)."""
    import tempfile

    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    from paddle_tpu.inference import AnalysisConfig, \
        create_paddle_predictor

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        img = fluid.layers.data('image', shape=[224, 224, 3],
                                dtype='float32')
        logits = models.resnet.resnet(img, 1000, depth=50,
                                      is_test=True,
                                      data_format='NHWC')
    import shutil
    model_dir = tempfile.mkdtemp(prefix='bench_infer_')
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            fluid.io.save_inference_model(model_dir, ['image'],
                                          [logits], exe,
                                          main_program=main)
        predictor = create_paddle_predictor(AnalysisConfig(model_dir))
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    rng = np.random.RandomState(0)
    x = jax.device_put(
        rng.rand(batch, 224, 224, 3).astype('float32'))
    # pipelined serving throughput: dispatch stays async
    # (return_numpy=False), one blocking fetch closes the window
    for _ in range(warmup):
        out = predictor.run_dict({'image': x}, return_numpy=False)
    np.asarray(out[0])
    t0 = time.time()
    for _ in range(steps):
        out = predictor.run_dict({'image': x}, return_numpy=False)
    np.asarray(out[0])
    dt = (time.time() - t0) / steps
    return dict({'metric': 'resnet50_infer_images_per_sec_b%d' % batch,
                 'value': round(batch / dt, 1), 'unit': 'images/sec'},
                **_monitor_fields())


def bench_wide_deep(batch=2048, steps=30, is_sparse=False):
    """BASELINE.json config 3: Wide&Deep CTR throughput.

    is_sparse=True measures the SPARSE path (SelectedRows-style
    row-scatter embedding grads + per-row adagrad) the CTR workload
    actually exercises at scale."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, preds, loss = models.wide_deep.build(
            models.wide_deep.BASE, is_sparse=is_sparse)
        fluid.optimizer.Adagrad(0.01).minimize(loss)
    cfg = models.wide_deep.BASE
    rng = np.random.RandomState(0)
    feed = models.wide_deep.synthetic_batch(cfg, batch, rng)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        dt = _timed_steps(exe, main, feed, loss, steps)
    return dict({'metric': 'wide_deep_ctr_examples_per_sec_b%d%s'
                 % (batch, '_sparse' if is_sparse else ''),
                 'value': round(batch / dt, 1),
                 'unit': 'examples/sec'},
                **LAST_PERF, **_step_phase_fields(),
                **_monitor_fields())


def bench_wide_deep_sparse(batch=2048, steps=30):
    return bench_wide_deep(batch, steps, is_sparse=True)


def bench_host_sparse_push(batch=4096, vocab=10_000_000, dim=16,
                           slots=20, steps=50):
    """The host-table sparse pull/push path itself (FleetWrapper
    PullSparse/PushSparse analog): a 10M-row table that could never
    live in HBM, O(touched rows) per step."""
    import time as _t
    from paddle_tpu.parallel.sparse_embedding import HostShardedEmbedding
    emb = HostShardedEmbedding('bench_big_emb', vocab, dim,
                               optimizer='adagrad', learning_rate=0.05,
                               initializer_scale=0, seed=1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, slots)).astype('int64')
    grad = rng.randn(batch, slots, dim).astype('float32')
    emb._pull(ids)
    emb._push(ids, grad)
    t0 = _t.time()
    for _ in range(steps):
        emb._pull(ids)
        emb._push(ids, grad)
    dt = (_t.time() - t0) / steps
    del HostShardedEmbedding._REGISTRY['bench_big_emb']
    return {'metric': 'host_sparse_pull_push_examples_per_sec_b%d_v%dM'
            % (batch, vocab // 1_000_000),
            'value': round(batch / dt, 1), 'unit': 'examples/sec',
            'ms_per_step': round(dt * 1000, 3)}


def bench_rpc_sparse_push(batch=4096, vocab=10_000_000, dim=16,
                          slots=20, steps=50, n_servers=2):
    """The REMOTE sparse pull/push path: same workload as
    bench_host_sparse_push but the table lives in native pserver
    processes behind the framed-TCP protocol (runtime/ps_service.cc) —
    the listen_and_serv / parameter_prefetch leg the reference built
    gRPC zero-copy serde for (operators/distributed/grpc/
    grpc_serde.cc).  Measures the RPC overhead over the in-process
    number."""
    import time as _t
    from paddle_tpu.distributed import PsServer
    from paddle_tpu.parallel.sparse_embedding import (
        HostShardedEmbedding, RpcShardedEmbedding)
    servers = [PsServer() for _ in range(n_servers)]
    try:
        emb = RpcShardedEmbedding('bench_rpc_emb', vocab, dim,
                                  [s.endpoint for s in servers],
                                  optimizer='adagrad',
                                  learning_rate=0.05,
                                  initializer_scale=0)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, vocab, (batch, slots)).astype('int64')
        grad = rng.randn(batch, slots, dim).astype('float32')
        emb._pull(ids)
        emb._push(ids, grad)
        t0 = _t.time()
        for _ in range(steps):
            emb._pull(ids)
            emb._push(ids, grad)
        dt = (_t.time() - t0) / steps
        return {'metric':
                'rpc_sparse_pull_push_examples_per_sec_b%d_v%dM_s%d'
                % (batch, vocab // 1_000_000, n_servers),
                'value': round(batch / dt, 1), 'unit': 'examples/sec',
                'ms_per_step': round(dt * 1000, 3)}
    finally:
        HostShardedEmbedding._REGISTRY.pop('bench_rpc_emb', None)
        for s in servers:
            s.stop()


def bench_transformer(batch=32, src_len=64, tgt_len=64, steps=20):
    """BASELINE.json config 4: Transformer NMT step time."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, logits, loss = models.transformer.build(
            models.transformer.BASE, src_len, tgt_len)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(1e-4),
            use_dynamic_loss_scaling=True)
        opt.minimize(loss)
    cfg = models.transformer.BASE
    rng = np.random.RandomState(0)
    feed = models.transformer.synthetic_batch(cfg, batch, src_len,
                                              tgt_len, rng)
    with _wpg(), fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        dt = _timed_steps(exe, main, feed, loss, steps)
    return dict({'metric': 'transformer_nmt_tokens_per_sec_b%d' % batch,
                 'value': round(batch * tgt_len / dt, 1),
                 'unit': 'tokens/sec',
                 'step_ms': round(dt * 1000, 2)},
                **LAST_PERF, **_step_phase_fields(),
                **_monitor_fields())


def bench_resnet50_hostfed(batch=128, steps=20, warmup=3,
                           data_format='NHWC'):
    """ResNet-50 training fed from HOST memory through the async
    double-buffered DataLoader (capacity queue + 2-deep device_put
    window) — proves the input pipeline overlaps H2D with compute: the
    number should sit within a few % of the device-resident
    resnet50 entry (round-4 VERDICT item 4)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, logits, loss, acc = models.resnet.build(
            data_format=data_format)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Momentum(0.1, momentum=0.9),
            use_dynamic_loss_scaling=True)
        opt.minimize(loss)

    rng = np.random.RandomState(0)
    shape = (batch, 224, 224, 3) if data_format == 'NHWC' else \
        (batch, 3, 224, 224)
    # a couple of distinct host batches, cycled: the loader must
    # device_put fresh data each step (no accidental caching)
    host_batches = [
        {'image': rng.rand(*shape).astype('float32'),
         'label': rng.randint(0, 1000, (batch, 1)).astype('int32')}
        for _ in range(2)]

    n_total = warmup + steps

    def gen():
        for i in range(n_total):
            yield host_batches[i % 2]

    loader = fluid.io.DataLoader.from_generator(
        feed_list=[feeds['image'], feeds['label']], capacity=4,
        use_double_buffer=True)
    loader.set_batch_generator(gen)

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        it = iter(loader)
        for _ in range(warmup):
            exe.run(main, feed=next(it), fetch_list=[])
        l, = exe.run(main, feed=host_batches[0], fetch_list=[loss])
        np.asarray(l)
        t0 = time.time()
        n = 0
        for batch_data in it:
            exe.run(main, feed=batch_data, fetch_list=[])
            n += 1
        l, = exe.run(main, feed=host_batches[0], fetch_list=[loss])
        np.asarray(l)
        dt = time.time() - t0
        # baseline: the SAME host batches fed synchronously (numpy
        # straight into run, no background thread, no device window) —
        # the loader's overlap must beat this; the comparison, not the
        # absolute number, is the signal
        t0 = time.time()
        for i in range(max(4, steps // 4)):
            exe.run(main, feed=host_batches[i % 2], fetch_list=[])
        l, = exe.run(main, feed=host_batches[0], fetch_list=[loss])
        np.asarray(l)
        sync_dt = (time.time() - t0) / (max(4, steps // 4) + 1)
    return dict({'metric': 'resnet50_train_hostfed_images_per_sec_b%d'
                 % batch,
                 'value': round(batch * (n + 1) / dt, 1),
                 'unit': 'images/sec',
                 'sync_feed_images_per_sec': round(batch / sync_dt, 1)},
                **_monitor_fields())


def bench_lenet(batch=512, steps=30):
    """BASELINE.json config 0: MNIST LeNet throughput."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, pred, loss, acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(batch, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        dt = _timed_steps(exe, main, feed, loss, steps)
    return dict({'metric': 'lenet_mnist_images_per_sec_b%d' % batch,
                 'value': round(batch / dt, 1),
                 'unit': 'images/sec'},
                **LAST_PERF, **_step_phase_fields(),
                **_monitor_fields())


def bench_dispatch(depth=6, width=8, batch=4, steps=300, warmup=8):
    """Steady-state dispatch-side host cost per step, isolated: a tiny
    deep-ish MLP whose compute is ~free, fed device-resident data with
    no per-step fetch.  The device queue is drained OUTSIDE run() after
    every step, so `executor/run_seconds` sees pure host dispatch
    (binders, staging checks, jit call), never device backpressure —
    the metric the steady-state fast path moves; compute-bound entries
    bury it."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[width], dtype='float32')
        h = x
        for _ in range(depth):
            h = fluid.layers.fc(h, size=width, act='relu')
        loss = fluid.layers.reduce_mean(h)
        fluid.optimizer.SGD(0.01).minimize(loss)
    feed = {'x': jax.device_put(
        np.ones((batch, width), 'float32'))}
    pname = main.all_parameters()[0].name
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(warmup):
            exe.run(main, feed=feed, fetch_list=[])
        jax.block_until_ready(scope.find_var(pname))
        f0 = {k: v for k, v in monitor.flat().items()}
        for _ in range(steps):
            exe.run(main, feed=feed, fetch_list=[])
            jax.block_until_ready(scope.find_var(pname))
        f1 = monitor.flat()

    def d(key):
        return f1.get(key, 0.0) - f0.get(key, 0.0)

    per_step = d('executor/run_seconds/sum') / steps
    bind_n = d('executor/bind_seconds/count')
    return dict({'metric': 'dispatch_host_us_per_step_d%d' % depth,
                 'value': round(per_step * 1e6, 1),
                 'unit': 'us/step',
                 'fastpath_hit_rate': round(
                     d('executor/fastpath_hits') / steps, 3),
                 'bind_us_per_step': round(
                     1e6 * d('executor/bind_seconds/sum') /
                     max(bind_n, 1), 2)},
                **_monitor_fields())


def bench_cold_lenet(batch=64, steps=5, use_warmup=False):
    """--cold child: process-start -> first-train-step-complete wall
    time for LeNet (the metric a restarting/autoscaling service
    replica pays).  With FLAGS_compile_cache_dir set (the parent sets
    it), the first process populates the persistent segment store and
    the second starts from it; `use_warmup` additionally issues
    Executor.warmup right after the startup program so segment
    compilation (or disk loading) overlaps host-side setup."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, pred, loss, acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(batch, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        if use_warmup:
            exe.warmup(main, feed_shapes=feed, fetch_list=[loss])
        l, = exe.run(main, feed=feed, fetch_list=[loss])
        first_loss = float(np.asarray(l).ravel()[0])  # block: step is
        t_first = time.time() - _PROC_T0              # COMPLETE
        t0 = time.time()
        for _ in range(steps - 1):
            exe.run(main, feed=feed, fetch_list=[])
        l, = exe.run(main, feed=feed, fetch_list=[loss])
        np.asarray(l)
        steady = (time.time() - t0) / steps
    flat = monitor.flat()
    return {'metric': 'lenet_cold_start_to_first_step_s_b%d' % batch,
            'value': round(t_first, 3), 'unit': 'seconds',
            'steady_step_ms': round(steady * 1000, 2),
            'first_loss': first_loss,
            'compile_cache': {
                short: flat.get('executor/' + key, 0.0)
                for short, key in (
                    ('disk_hit', 'compile_cache_disk_hit'),
                    ('disk_miss', 'compile_cache_disk_miss'),
                    ('disk_writes', 'compile_cache_disk_writes'),
                    ('aot_compiles', 'aot_compiles'),
                    ('segments_lowered', 'segments_lowered'),
                    ('warmup_segments', 'warmup_segments'))}}


def _run_cold(cache_dir=None, out_path=None):
    """--cold driver: run bench_cold_lenet in three child processes
    against one FRESH private temp dir — cold (populates), warm
    (loads), and warm+warmup (loads in the background) — and print one
    JSON line per child plus a summary.  The bench NEVER touches
    PADDLE_TPU_COMPILE_CACHE_DIR / FLAGS_compile_cache_dir: 'cold'
    must mean cold, and wiping a user's shared production cache to get
    there is not this tool's call."""
    import shutil
    import subprocess
    import tempfile
    cleanup = cache_dir is None
    d = cache_dir or tempfile.mkdtemp(prefix='paddle_tpu_cold_')
    os.makedirs(d, exist_ok=True)
    results = {}
    for tag, kwargs in (('cold', {}), ('warm', {}),
                        ('warm_warmup', {'use_warmup': True})):
        # the one place a cache is put somewhere fresh on purpose: this
        # MEASURES a cold start, so JAX's own cache must be as empty as
        # the segment store.  It is placed from outside, through the
        # variable place_jax_cache() honours — no code moves it.
        env = dict(os.environ, FLAGS_compile_cache_dir=d,
                   JAX_COMPILATION_CACHE_DIR=os.path.join(d, 'xla'))
        p = subprocess.run(
            [sys.executable, '-u', os.path.abspath(__file__), '--one',
             'cold_lenet', json.dumps(kwargs)],
            capture_output=True, text=True, timeout=900, env=env)
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith('{')]
        if p.returncode != 0 or not line:
            sys.stderr.write('cold child %s failed (rc=%d): %s\n'
                             % (tag, p.returncode, p.stderr[-300:]))
            continue
        rec = json.loads(line[-1])
        rec['phase'] = tag
        results[tag] = rec
        print(json.dumps(rec))
    if 'cold' in results and 'warm' in results:
        summary = {
            'metric': 'lenet_cold_vs_warm_start_s',
            'cold_s': results['cold']['value'],
            'warm_s': results['warm']['value'],
            'warm_warmup_s': results.get('warm_warmup',
                                         {}).get('value'),
            'speedup': round(results['cold']['value'] /
                             max(results['warm']['value'], 1e-9), 2),
            'warm_disk_hits':
                results['warm']['compile_cache']['disk_hit'],
            'warm_retraces':
                results['warm']['compile_cache']['segments_lowered'],
        }
        print(json.dumps(summary))
        if out_path:
            with open(out_path, 'w') as f:
                json.dump({'entries': list(results.values()),
                           'summary': summary}, f, indent=1,
                          sort_keys=True)
        if cleanup:
            shutil.rmtree(d, ignore_errors=True)
        return summary
    if cleanup:
        shutil.rmtree(d, ignore_errors=True)
    return None


def bench_elastic_save(batch=64, steps=4, store=None):
    """--elastic child: train LeNet under an fsdp2 layout (2 host
    devices, fc weights + Adam moments genuinely scattered via the
    auto-shard planner) and write one elastic checkpoint generation —
    the save-side bandwidth number (manifest + per-shard files +
    digests, atomic publish), and a SHARDED source so the resume
    child's reshard schedule prices real collectives."""
    import tempfile
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import elastic, monitor
    from paddle_tpu.parallel import plan as _ashard
    from paddle_tpu import models
    store = store or tempfile.mkdtemp(prefix='pt_elastic_bench_')
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, pred, loss, acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(batch, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    fluid.set_flags({'FLAGS_auto_shard': True})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        comp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name,
            places=[fluid.XLAPlace(i) for i in range(2)])
        comp._auto_plan = _ashard.build_plan(main, ndev=2,
                                             layouts=[(1, 2, 1)])
        for _ in range(steps):
            l, = exe.run(comp, feed=feed, fetch_list=[loss])
        first_loss = float(np.asarray(l).ravel()[0])
        t0 = time.time()
        gen = elastic.save_checkpoint(store, main, executor=exe)
        save_s = time.time() - t0
    flat = monitor.flat()
    save_bytes = flat.get('elastic/save_bytes', 0.0)
    return {'metric': 'elastic_checkpoint_save_bw_mbps_b%d' % batch,
            'value': round(save_bytes / max(save_s, 1e-9) / 1e6, 2),
            'unit': 'MB/s',
            'save_seconds': round(save_s, 4),
            'save_bytes': save_bytes,
            'shards': flat.get('elastic/shards_written', 0.0),
            'generation': gen, 'store': store,
            'loss_at_save': first_loss}


def bench_elastic_resume(batch=64, steps=3, store=None):
    """--elastic child: process-start -> resumed-first-step-complete
    wall time on a DIFFERENT topology (single device) — the N->M
    reconfiguration latency an autoscaling trainer pays, measured
    cold (empty compile cache) and warm (persistent store hit) by the
    driver.  Carries the reshard schedule's predicted-vs-measured
    honesty ratio and the load-side bandwidth."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import elastic, monitor
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        feeds, pred, loss, acc = models.lenet.build()
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.rand(batch, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        t0 = time.time()
        info = elastic.resume(exe, store, main, feed_shapes=feed,
                              fetch_list=[loss])
        lowered_after_warmup = monitor.counter_value(
            'executor/segments_lowered')
        l, = exe.run(main, feed=feed, fetch_list=[loss])
        first_loss = float(np.asarray(l).ravel()[0])
        reconfig_s = time.time() - _PROC_T0
        resume_s = time.time() - t0
        for _ in range(steps - 1):
            exe.run(main, feed=feed, fetch_list=[loss])
        lowered_total = monitor.counter_value(
            'executor/segments_lowered')
    flat = monitor.flat()
    rs = info['reshard']
    return {'metric': 'elastic_reconfig_start_to_first_step_s_b%d'
                      % batch,
            'value': round(reconfig_s, 3), 'unit': 'seconds',
            'resume_s': round(resume_s, 3),
            'first_loss': first_loss,
            'loaded_generation': info['generation'],
            'load_seconds': info['seconds'],
            'load_bw_mbps': round(
                info['bytes'] / max(info['seconds'], 1e-9) / 1e6, 2),
            'reshard_predicted_s': rs['predicted_s'],
            'reshard_measured_s': rs['measured_s'],
            'reshard_pred_over_measured': rs['pred_over_measured'],
            'reshard_by_kind': rs['by_kind'],
            'staging_waves': rs['staging_waves'],
            'retraces_after_warmup': lowered_total -
                lowered_after_warmup,
            'compile_cache': {
                short: flat.get('executor/' + key, 0.0)
                for short, key in (
                    ('disk_hit', 'compile_cache_disk_hit'),
                    ('disk_writes', 'compile_cache_disk_writes'),
                    ('aot_compiles', 'aot_compiles'),
                    ('segments_lowered', 'segments_lowered'),
                    ('warmup_segments', 'warmup_segments'))}}


def _chaos_fields(stats):
    """--chaos summary: the soak's self-healing economics — recoveries
    vs injected fault kinds, lost work against the checkpoint
    cadence, checkpoint volume (incl. torn->resaved), and the bitwise
    post-recovery verification depth."""
    if not stats:
        return None
    return dict({
        'metric': 'chaos_soak_recoveries',
        'value': stats.get('recoveries'),
        'unit': 'recoveries',
    }, **stats)


def bench_chaos():
    """Drive the tools/check_chaos.py soak (the real multi-process
    chaos harness) and record its CHAOS_STATS line — one harness, one
    truth: the bench records exactly what the gate asserts."""
    import subprocess
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tools', 'check_chaos.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    try:
        p = subprocess.run([sys.executable, tool], env=env,
                           capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired as e:
        # a wedged soak is exactly what a chaos harness may produce:
        # record the outcome instead of dying without a BENCH entry
        return {'metric': 'chaos_soak_recoveries', 'value': None,
                'gate_rc': 'timeout',
                'gate_tail': (e.stdout or b'')[-1500:].decode(
                    'utf-8', 'replace') if isinstance(
                    e.stdout, bytes) else str(e.stdout)[-1500:]}
    stats = None
    for line in p.stdout.splitlines():
        if line.startswith('CHAOS_STATS '):
            stats = json.loads(line[len('CHAOS_STATS '):])
    rec = _chaos_fields(stats) or {'metric': 'chaos_soak_recoveries',
                                   'value': None}
    rec['gate_rc'] = p.returncode
    if p.returncode != 0:
        rec['gate_tail'] = p.stdout[-1500:]
    return rec


def _elastic_fields(results):
    """--elastic summary: cold vs warm N->M reconfiguration seconds
    through the persistent compile cache, the reshard schedule's
    predicted-vs-measured ratio, and checkpoint save/load
    bandwidth."""
    save, cold, warm = (results.get(k) for k in ('save', 'cold',
                                                 'warm'))
    if not (save and cold and warm):
        return None
    return {
        'metric': 'elastic_reconfig_cold_vs_warm_s',
        'cold_s': cold['value'],
        'warm_s': warm['value'],
        'speedup': round(cold['value'] / max(warm['value'], 1e-9), 2),
        'warm_disk_hits': warm['compile_cache']['disk_hit'],
        'warm_retraces_after_warmup': warm['retraces_after_warmup'],
        'save_bw_mbps': save['value'],
        'load_bw_mbps': warm['load_bw_mbps'],
        'reshard_pred_over_measured':
            warm['reshard_pred_over_measured'],
        'reshard_by_kind': warm['reshard_by_kind'],
    }


def _run_elastic(out_path=None):
    """--elastic driver: one dp2 child saves a generation, then two
    single-device children resume it against one FRESH compile-cache
    dir — cold (populates) and warm (disk hits, zero post-warmup
    retraces).  The topology change (2 devices -> 1) is the N->M
    reconfiguration being priced."""
    import shutil
    import subprocess
    import tempfile
    work = tempfile.mkdtemp(prefix='paddle_tpu_elastic_')
    store = os.path.join(work, 'store')
    cache = os.path.join(work, 'cache')
    results = {}
    jobs = (
        ('save', 'elastic_save', {'store': store},
         {'XLA_FLAGS': '--xla_force_host_platform_device_count=2'}),
        ('cold', 'elastic_resume', {'store': store}, {}),
        ('warm', 'elastic_resume', {'store': store}, {}),
    )
    try:
        for tag, name, kwargs, extra_env in jobs:
            # cold must mean cold: JAX's own cache goes under the same
            # fresh directory as the segment store (see _run_cold)
            env = dict(os.environ, FLAGS_compile_cache_dir=cache,
                       JAX_COMPILATION_CACHE_DIR=os.path.join(
                           cache, 'xla'))
            env.update(extra_env)
            p = subprocess.run(
                [sys.executable, '-u', os.path.abspath(__file__),
                 '--one', name, json.dumps(kwargs)],
                capture_output=True, text=True, timeout=900, env=env)
            line = [ln for ln in p.stdout.splitlines()
                    if ln.startswith('{')]
            if p.returncode != 0 or not line:
                sys.stderr.write('elastic child %s failed (rc=%d): '
                                 '%s\n' % (tag, p.returncode,
                                           p.stderr[-400:]))
                continue
            rec = json.loads(line[-1])
            rec['phase'] = tag
            results[tag] = rec
            print(json.dumps(rec))
        summary = _elastic_fields(results)
        if summary:
            print(json.dumps(summary))
            if out_path:
                with open(out_path, 'w') as f:
                    json.dump({'cmd': 'python bench.py --elastic',
                               'JAX_PLATFORMS':
                               os.environ.get('JAX_PLATFORMS', ''),
                               'entries': list(results.values()),
                               'summary': summary}, f, indent=1,
                              sort_keys=True)
        return summary
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_serving(feeders=4, requests_per_feeder=100, max_batch=32,
                  burst=16):
    """Multi-client serving soak: N concurrent feeders, two resident
    programs (different input widths — mixed shapes), mixed row
    counts, through fluid.serving's continuous batcher — against a
    SEQUENTIAL baseline (one request at a time through Executor.run,
    the pre-serving posture).  Reports requests/sec for both arms,
    the speedup, per-request p50/p99 admission-to-completion latency,
    mean batch occupancy, and the post-warmup retrace count (must be
    0: every bucket comes from the warmed AOT ladder).  Step wall
    percentiles come straight out of trace.step_report() over the
    tenant-tagged serving steps."""
    import threading
    import jax  # noqa: F401 — device init before the timed regions
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor, serving
    from paddle_tpu.fluid import trace as pt_trace

    def build(in_w, hid_w, seed):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[in_w], dtype='float32')
            h = fluid.layers.fc(x, hid_w, act='relu')
            y = fluid.layers.fc(h, 10, act='softmax')
        return main, startup, y

    exe = fluid.Executor(fluid.XLAPlace(0))
    tenants = {}
    for name, (in_w, hid_w, seed) in (('small', (16, 64, 21)),
                                      ('wide', (32, 96, 22))):
        mp, sp, y = build(in_w, hid_w, seed)
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(sp)
        tenants[name] = (mp, sc, y, in_w)
    rows_cycle = (1, 1, 2, 1, 4, 1)   # mostly single requests
    total_requests = feeders * requests_per_feeder

    def request_stream(seed):
        rng = np.random.RandomState(seed)
        for i in range(requests_per_feeder):
            name = ('small', 'wide')[(seed + i) % 2]
            rows = rows_cycle[i % len(rows_cycle)]
            in_w = tenants[name][3]
            yield name, rng.randn(rows, in_w).astype('float32')

    # -- sequential baseline: one blocking request at a time ---------
    for name, (mp, sc, y, in_w) in tenants.items():
        with fluid.scope_guard(sc):   # warm every shape out of band
            for rows in sorted(set(rows_cycle)):
                exe.run(mp, feed={'x': np.zeros((rows, in_w),
                                                'float32')},
                        fetch_list=[y])
    t0 = time.time()
    n_seq = 0
    for fid in range(feeders):
        for name, xv in request_stream(fid):
            mp, sc, y, _ = tenants[name]
            with fluid.scope_guard(sc):
                out, = exe.run(mp, feed={'x': xv}, fetch_list=[y])
            np.asarray(out)
            n_seq += 1
    seq_dt = time.time() - t0
    seq_rps = n_seq / seq_dt

    # -- continuous-batching soak ------------------------------------
    srv = serving.ServingExecutor(max_batch=max_batch, executor=exe)
    for name, (mp, sc, y, _w) in tenants.items():
        srv.add_program(name, mp, ['x'], [y], scope=sc)
    srv.warmup(wait=True)
    lowered0 = monitor.counter_value('executor/segments_lowered')
    trace_was_on = pt_trace.is_active()
    if not trace_was_on:
        pt_trace.enable(buffer_steps=2 * total_requests)
    latencies = []
    lat_lock = threading.Lock()

    def feeder(fid):
        pending = []
        for name, xv in request_stream(fid):
            t_sub = time.perf_counter()
            fut = srv.submit(name, {'x': xv})
            fut.add_done_callback(
                lambda _f, _t=t_sub: _record(_t))
            pending.append(fut)
            if len(pending) >= burst:
                for f in pending:   # pipelined: burst stays in flight
                    f.result(300)
                pending = []
        for f in pending:
            f.result(300)

    def _record(t_sub):
        done = time.perf_counter()
        with lat_lock:
            latencies.append(done - t_sub)

    t0 = time.time()
    threads = [threading.Thread(target=feeder, args=(fid,))
               for fid in range(feeders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    soak_dt = time.time() - t0
    retraces = monitor.counter_value(
        'executor/segments_lowered') - lowered0
    try:
        rep = pt_trace.step_report()
        srv_steps = [s for s in rep['steps'] if s.get('tags')]
        walls = sorted(s['wall_ms'] for s in srv_steps)
        step_walls = {
            'count': len(walls),
            'wall_p50_ms': round(walls[len(walls) // 2], 3)
            if walls else 0.0,
            'wall_p99_ms': round(
                walls[min(len(walls) - 1,
                          int(0.99 * len(walls)))], 3)
            if walls else 0.0,
        }
    except Exception:
        step_walls = {}
    if not trace_was_on:
        pt_trace.disable()
        pt_trace.reset()
    srv_rps = len(latencies) / soak_dt
    occ = monitor.histogram_value('serving/batch_occupancy') or {}
    lat_sorted = sorted(latencies)
    srv.close()
    return dict({
        'metric': 'serving_requests_per_sec',
        'value': round(srv_rps, 1),
        'unit': 'req/s',
        'feeders': feeders,
        'programs': len(tenants),
        'requests': len(latencies),
        'sequential_rps': round(seq_rps, 1),
        'vs_sequential': round(srv_rps / max(seq_rps, 1e-9), 2),
        'latency_p50_ms': round(
            1e3 * _pct_of(lat_sorted, 0.50), 2),
        'latency_p99_ms': round(
            1e3 * _pct_of(lat_sorted, 0.99), 2),
        'mean_batch_occupancy': round(
            occ.get('sum', 0.0) / max(occ.get('count', 1), 1), 3),
        'batches': monitor.counter_value('serving/batches'),
        'pad_waste_bytes': monitor.counter_value(
            'serving/bucket_pad_waste_bytes'),
        'retraces_post_warmup': retraces,
        'serving_step_walls': step_walls,
    }, **_monitor_fields())


def _pct_of(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(round(q * (len(sorted_vals) - 1))))]


def bench_serving_fleet(feeders=3, requests_per_feeder=80,
                        max_batch=8):
    """Skewed-tenant churn soak, fleet vs single replica: the SAME
    workload (three tenants, ~70% of traffic on one hot tenant, mixed
    row counts) and the SAME churn events (the hot tenant is
    relocated twice mid-soak) through two arms —

    - single replica: churn is evict -> re-register -> re-warm ON the
      serving path; requests to the hot tenant stall (retried at
      admission) until the re-warm finishes, so tail latency eats the
      whole warmup wall;
    - two-replica fleet: churn is ``fleet.migrate`` — the target is
      pre-warmed through the persistent compile cache while the
      SOURCE keeps serving, then the route flips; no request ever
      waits on a warmup.

    Reports per-request p50/p99 for both arms (the acceptance claim:
    fleet p99 held under churn while the single replica degrades),
    zero post-warmup retraces, and every migration matched to a
    priced decision in the fleet log."""
    import threading
    import jax  # noqa: F401 — device init before the timed regions
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import fleet, memviz, monitor, serving

    def build(hid_w, seed):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[16], dtype='float32')
            h = fluid.layers.fc(x, hid_w, act='relu')
            y = fluid.layers.fc(h, 10, act='softmax')
        return main, startup, y

    exe = fluid.Executor(fluid.XLAPlace(0))
    tenants = {}
    for name, (hid_w, seed) in (('hot', (64, 31)), ('warm', (96, 32)),
                                ('cold', (48, 33))):
        mp, sp, y = build(hid_w, seed)
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(sp)
        tenants[name] = (mp, sc, y)
    # ~70% of traffic on the hot tenant — the skew churn then hits
    skew = ('hot', 'hot', 'hot', 'warm', 'hot',
            'cold', 'hot', 'hot', 'warm', 'hot')
    rows_cycle = (1, 1, 2, 1, 4, 1)
    total = feeders * requests_per_feeder

    def run_arm(submit_fn, churn_fn):
        """One soak: N feeders over the skewed stream, churn fired at
        1/3 and 2/3 progress.  A submit that lands mid-churn (tenant
        momentarily unregistered on the single arm) retries at
        admission — the wait counts against its latency, which is the
        point."""
        latencies = []
        lock = threading.Lock()
        served = [0]
        errors = []
        churn_walls = []

        def feeder(fid):
            rng = np.random.RandomState(200 + fid)
            for i in range(requests_per_feeder):
                name = skew[(fid + i) % len(skew)]
                rows = rows_cycle[i % len(rows_cycle)]
                xv = rng.randn(rows, 16).astype('float32')
                t0 = time.perf_counter()
                try:
                    while True:
                        try:
                            fut = submit_fn(name, {'x': xv})
                            break
                        except KeyError:
                            time.sleep(0.002)   # tenant mid-churn
                    fut.result(300)
                except Exception as e:  # noqa: BLE001
                    errors.append('%s req %d: %s' % (name, i, e))
                    continue
                lat = time.perf_counter() - t0
                with lock:
                    latencies.append(lat)
                    served[0] += 1

        def churner():
            for frac in (1 / 3, 2 / 3):
                while served[0] < frac * total:
                    time.sleep(0.005)
                t0 = time.perf_counter()
                churn_fn()
                churn_walls.append(time.perf_counter() - t0)

        threads = [threading.Thread(target=feeder, args=(fid,))
                   for fid in range(feeders)]
        ct = threading.Thread(target=churner)
        t0 = time.time()
        for t in threads:
            t.start()
        ct.start()
        for t in threads:
            t.join(600)
        ct.join(60)
        dt = time.time() - t0
        lat = sorted(latencies)
        return {'requests': len(latencies), 'wall_s': dt,
                'rps': len(latencies) / dt,
                'p50_ms': 1e3 * _pct_of(lat, 0.50),
                'p99_ms': 1e3 * _pct_of(lat, 0.99),
                'churn_walls_s': [round(w, 3) for w in churn_walls],
                'errors': errors[:3]}

    # -- arm 1: single replica, churn on the serving path ------------
    srv = serving.ServingExecutor(max_batch=max_batch, executor=exe)
    for name, (mp, sc, y) in tenants.items():
        srv.add_program(name, mp, ['x'], [y], scope=sc)
    srv.warmup(wait=True)
    lowered0 = monitor.counter_value('executor/segments_lowered')

    def churn_single():
        # relocation without a second replica: the tenant leaves the
        # ladder and re-warms IN the serving path — its traffic waits
        mp, sc, y = tenants['hot']
        srv.remove_program('hot', drain=True)
        srv.add_program('hot', mp, ['x'], [y], scope=sc)
        srv.warmup_tenant('hot', wait=True)

    def submit_single(name, feed):
        # the readiness contract (serving.readiness): an unwarmed
        # tenant makes the replica unready — a load balancer holds
        # traffic until the re-warm finishes, so the wait lands on
        # the requests' latency
        t = srv._tenants.get(name)
        if t is None or not t.warmed:
            raise KeyError(name)
        return srv.submit(name, feed)

    single = run_arm(submit_single, churn_single)
    single_retraces = monitor.counter_value(
        'executor/segments_lowered') - lowered0
    srv.close()

    # -- arm 2: two-replica fleet, churn is a priced migration -------
    fl = fleet.Fleet()
    for i in range(2):
        fl.add_replica('r%d' % i,
                       serving.ServingExecutor(max_batch=max_batch,
                                               executor=exe))
    for name, (mp, sc, y) in tenants.items():
        fl.register_tenant(name, mp, ['x'], [y], scope=sc)
    fl.warmup(wait=True)
    memviz.live_census()       # the migration pricing input
    lowered0 = monitor.counter_value('executor/segments_lowered')

    fleet_arm = run_arm(fl.submit,
                        lambda: fl.migrate('hot', why='churn'))
    fleet_retraces = monitor.counter_value(
        'executor/segments_lowered') - lowered0
    moves = [d for d in fleet.decisions()
             if d['kind'] in ('migrate', 'evict') and d['acted']]
    unpriced = [d for d in moves if 'priced' not in d.get('info', {})]
    for s in fl.replicas().values():
        s.close()
    fl.close()

    return dict({
        'metric': 'serving_fleet_p99_ms',
        'value': round(fleet_arm['p99_ms'], 2),
        'unit': 'ms',
        'feeders': feeders,
        'replicas': 2,
        'programs': len(tenants),
        'requests': fleet_arm['requests'],
        'fleet_p50_ms': round(fleet_arm['p50_ms'], 2),
        'fleet_rps': round(fleet_arm['rps'], 1),
        'fleet_churn_walls_s': fleet_arm['churn_walls_s'],
        'fleet_errors': fleet_arm['errors'],
        # the degrading arm: same workload, same churn, one replica.
        # Deliberately NOT regression-gated (vs_baseline): its p99 IS
        # the churn warmup wall, an environmental quantity
        'single_replica_churn_p99_ms_vs_baseline':
            round(single['p99_ms'], 2),
        'single_replica_churn_p50_ms_vs_baseline':
            round(single['p50_ms'], 2),
        'single_replica_rps_vs_baseline': round(single['rps'], 1),
        'single_churn_walls_s_vs_baseline':
            single['churn_walls_s'],
        'single_errors_vs_baseline': single['errors'],
        'p99_held_under_churn':
            bool(fleet_arm['p99_ms'] <= single['p99_ms']),
        'retraces_post_warmup': fleet_retraces,
        'single_retraces_post_warmup_vs_baseline': single_retraces,
        'migrations': monitor.counter_value('fleet/migrations'),
        'priced_moves': len(moves),
        'unpriced_moves': len(unpriced),
        'routed_requests': monitor.counter_value(
            'fleet/routed_requests'),
        'fleet_decisions': len(fleet.decisions()),
    }, **_monitor_fields())


def bench_health_overhead(depth=4, width=64, batch=32, steps=60,
                          warmup=8):
    """FLAGS_health_summaries on/off A/B on one small MLP: the BENCH
    JSON records the per-step cost of the opt-in tensor-health
    reductions AND enforces the 'costs nothing when off' claim — the
    off posture must match the plain dispatch profile (summaries
    record zero health counters), and the on posture's overhead is
    published so a regression (e.g. a reduction that starts blocking
    per param) is visible in the trajectory, not just in a gate."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import health, monitor

    def build(seed):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[width], dtype='float32')
            h = x
            for _ in range(depth):
                h = fluid.layers.fc(h, size=width, act='relu')
            loss = fluid.layers.reduce_mean(fluid.layers.square(h))
            fluid.optimizer.SGD(0.01).minimize(loss)
        return main, startup, loss

    feed = {'x': jax.device_put(np.ones((batch, width), 'float32'))}

    def timed(flag_on, seed):
        # the flag keys the PLAN (param grads surface as segment
        # outputs), so each posture builds its own program
        fluid.flags.set_flags({'FLAGS_health_summaries': flag_on})
        health.reset_state()
        try:
            main, startup, loss = build(seed)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(main, feed=feed, fetch_list=[])
                pname = main.all_parameters()[0].name
                jax.block_until_ready(scope.find_var(pname))
                t0 = time.time()
                for _ in range(steps):
                    exe.run(main, feed=feed, fetch_list=[])
                    jax.block_until_ready(scope.find_var(pname))
                return (time.time() - t0) / steps
        finally:
            fluid.flags.set_flags({'FLAGS_health_summaries': False})

    off_s = timed(False, 42)
    recorded_off = monitor.counter_value('health/summary_steps')
    on_s = timed(True, 42)
    recorded_on = monitor.counter_value('health/summary_steps') - \
        recorded_off
    return dict({'metric': 'health_overhead_us_per_step_d%d' % depth,
                 'value': round((on_s - off_s) * 1e6, 1),
                 'unit': 'us/step',
                 'health_overhead': {
                     'off_us_per_step': round(off_s * 1e6, 1),
                     'on_us_per_step': round(on_s * 1e6, 1),
                     'overhead_pct': round(
                         100.0 * (on_s - off_s) / max(off_s, 1e-12),
                         1),
                     'summaries_recorded_off': recorded_off,
                     'summaries_recorded_on': recorded_on}},
                **_monitor_fields())


def bench_memviz_overhead(depth=4, width=64, batch=32, steps=60,
                          warmup=8):
    """FLAGS_memviz on/off A/B on one small MLP: the BENCH JSON
    records the per-step cost of the live-HBM sampler (census over
    jax.live_arrays() + gauges + counter track) AND enforces the
    'costs one flag read when off' claim — the off posture must record
    zero census samples (tools/check_memviz.py gates the counter
    budgets; this publishes the wall-clock trajectory so a sampler
    that starts blocking per step is visible in the numbers)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import memviz, monitor

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[width], dtype='float32')
        h = x
        for _ in range(depth):
            h = fluid.layers.fc(h, size=width, act='relu')
        loss = fluid.layers.reduce_mean(fluid.layers.square(h))
        fluid.optimizer.SGD(0.01).minimize(loss)
    feed = {'x': jax.device_put(np.ones((batch, width), 'float32'))}

    def timed(flag_on):
        # the flag gates only the post-step sampler (never the plan or
        # the lowering), so both postures share one program + executor
        fluid.flags.set_flags({'FLAGS_memviz': flag_on})
        try:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(main, feed=feed, fetch_list=[])
                pname = main.all_parameters()[0].name
                jax.block_until_ready(scope.find_var(pname))
                t0 = time.time()
                for _ in range(steps):
                    exe.run(main, feed=feed, fetch_list=[])
                    jax.block_until_ready(scope.find_var(pname))
                return (time.time() - t0) / steps
        finally:
            fluid.flags.set_flags({'FLAGS_memviz': False})

    memviz.reset()
    off_s = timed(False)
    samples_off = monitor.counter_value('memviz/samples')
    on_s = timed(True)
    samples_on = monitor.counter_value('memviz/samples') - samples_off
    return dict({'metric': 'memviz_overhead_us_per_step_d%d' % depth,
                 'value': round((on_s - off_s) * 1e6, 1),
                 'unit': 'us/step',
                 'memviz_overhead': {
                     'off_us_per_step': round(off_s * 1e6, 1),
                     'on_us_per_step': round(on_s * 1e6, 1),
                     'overhead_pct': round(
                         100.0 * (on_s - off_s) / max(off_s, 1e-12),
                         1),
                     'samples_recorded_off': samples_off,
                     'samples_recorded_on': samples_on,
                     'live_bytes_total': monitor.gauge_value(
                         'memviz/live_bytes_total')}},
                **_monitor_fields())


def bench_opprof_overhead(depth=4, width=64, batch=32, steps=60,
                          warmup=8):
    """FLAGS_opprof on/off A/B on one small MLP: the BENCH JSON
    records the per-step cost of the op-cost attribution plane
    (snapshot-step survivable copies + the synchronous dispatch that
    measures the segment wall) AND enforces the 'costs one flag read
    when off' claim — the off posture must record zero segment
    snapshots (tools/check_opprof.py gates the counter budgets; this
    publishes the wall-clock trajectory so a snapshot path that
    starts leaking into non-snapshot steps is visible).  The flag is
    fingerprint-neutral, so both postures share one program +
    executor — flipping it mid-run causes zero retraces."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor, opprof

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[width], dtype='float32')
        h = x
        for _ in range(depth):
            h = fluid.layers.fc(h, size=width, act='relu')
        loss = fluid.layers.reduce_mean(fluid.layers.square(h))
        fluid.optimizer.SGD(0.01).minimize(loss)
    feed = {'x': jax.device_put(np.ones((batch, width), 'float32'))}

    def timed(flag_on):
        # the flag gates only the snapshot/instance-naming plane
        # (never the plan or the fingerprint), so both postures share
        # one program + executor
        fluid.flags.set_flags({'FLAGS_opprof': flag_on})
        try:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(main, feed=feed, fetch_list=[])
                pname = main.all_parameters()[0].name
                jax.block_until_ready(scope.find_var(pname))
                t0 = time.time()
                for _ in range(steps):
                    exe.run(main, feed=feed, fetch_list=[])
                    jax.block_until_ready(scope.find_var(pname))
                return (time.time() - t0) / steps
        finally:
            fluid.flags.set_flags({'FLAGS_opprof': False})

    opprof.reset()
    off_s = timed(False)
    snaps_off = monitor.counter_value('opprof/snapshots')
    on_s = timed(True)
    snaps_on = monitor.counter_value('opprof/snapshots') - snaps_off
    return dict({'metric': 'opprof_overhead_us_per_step_d%d' % depth,
                 'value': round((on_s - off_s) * 1e6, 1),
                 'unit': 'us/step',
                 'opprof_overhead': {
                     'off_us_per_step': round(off_s * 1e6, 1),
                     'on_us_per_step': round(on_s * 1e6, 1),
                     'overhead_pct': round(
                         100.0 * (on_s - off_s) / max(off_s, 1e-12),
                         1),
                     'snapshots_recorded_off': snaps_off,
                     'snapshots_recorded_on': snaps_on}},
                **_monitor_fields())


def bench_parallel(batch=256, width=256, steps=30, warmup=5,
                   skew_seconds=20.0):
    """Collective-job bench (BENCH_comms.json): a GradAllReduce MLP
    over the host's device mesh measures bytes_on_wire per step and
    per-(collective, size-bucket) achieved bandwidth through the
    fluid.comms telemetry; a real two-subprocess job (rank 1 fed a 4x
    batch — a genuine straggler) then reports cross-rank skew from the
    rank-0 aggregator and the merged job timeline from
    trace.collect_job — so future collective PRs (ROADMAP item 3) can
    name what they moved."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import comms, layers, monitor
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce

    ndev = len(jax.devices())
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[width], dtype='float32')
        h = layers.fc(x, width, act='relu')
        h = layers.fc(h, width, act='relu')
        loss = layers.reduce_mean(layers.fc(h, 1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    exe = fluid.Executor(fluid.XLAPlace(0))
    rng = np.random.RandomState(0)
    feed = {'x': rng.rand(batch, width).astype('float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(warmup):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        wire0 = monitor.counter_value('comms/bytes_on_wire')
        t0 = time.perf_counter()
        for _ in range(steps):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        wall = time.perf_counter() - t0
        wire = monitor.counter_value('comms/bytes_on_wire') - wire0
    bw = {}
    for (kind, bucket), samples in sorted(comms.bw_samples().items()):
        s = sorted(samples)
        bw['%s/%s' % (kind, bucket)] = {
            'p50_gbps': round(s[len(s) // 2], 6),
            'max_gbps': round(s[-1], 6),
            'samples': len(s)}
    rec = {
        'metric': 'parallel_comms',
        'value': round(steps / wall, 2),
        'unit': 'steps/sec',
        'devices': ndev,
        'batch': batch,
        'bytes_on_wire_per_step': round(wire / max(1, steps), 1),
        'payload_bytes_total':
            monitor.counter_value('comms/payload_bytes'),
        'bandwidth': bw,
    }
    rec['plan_ab'] = _plan_ab_fields(batch=batch, width=width)
    rec.update(_skew_job_fields(skew_seconds))
    rec.update(_monitor_fields())
    return rec


def _plan_ab_fields(batch=256, width=256, rounds=6, per_round=4,
                    warmup=3):
    """Per-arm collective-planner A/B (interleaved): the same
    GradAllReduce MLP transpiled three ways — v1.6 dense flat
    (planner off), planned fused dense, planned quantized — each with
    its own program + scope + executable (the planner digest keys the
    fingerprints apart), timed in interleaved bursts so OS noise hits
    every arm equally.  Reports steps/sec, bytes-on-wire per step and
    the quantized arm's wire reduction vs dense, plus final losses so
    the parity claim rides in the artifact."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, monitor
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce

    # every arm pins the model path to a guaranteed-empty file so an
    # ambient ./comms_model.json (README's calibrate-then-bench order)
    # cannot flip the dense arms onto rs_ag and mislabel the A/B
    no_model = {'FLAGS_comms_model_path': os.devnull}
    arms = (
        ('dense_flat', dict(no_model, **{'FLAGS_comms_plan': False,
                                         'FLAGS_comms_quantize':
                                             False})),
        ('fused_dense', dict(no_model, **{'FLAGS_comms_plan': True,
                                          'FLAGS_comms_quantize':
                                              False})),
        ('quant', dict(no_model, **{'FLAGS_comms_plan': True,
                                    'FLAGS_comms_quantize': True,
                                    'FLAGS_comms_quantize_min_bytes':
                                        4096})),
    )
    keys = sorted({k for _, fl in arms for k in fl} |
                  {'FLAGS_comms_quantize_min_bytes'})
    prev = fluid.get_flags(keys)
    rng = np.random.RandomState(0)
    feed = {'x': rng.rand(batch, width).astype('float32'),
            'y': rng.rand(batch, 1).astype('float32')}
    setups = {}
    out = {}
    try:
        for name, fl in arms:
            fluid.set_flags(fl)
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = 7
            with fluid.program_guard(main_p, startup):
                x = layers.data('x', shape=[width], dtype='float32')
                y = layers.data('y', shape=[1], dtype='float32')
                h = layers.fc(x, width, act='relu')
                h = layers.fc(h, width, act='relu')
                # bounded regression objective: losses stay finite so
                # the per-arm parity rides in the artifact
                loss = layers.reduce_mean(layers.square_error_cost(
                    layers.fc(h, 1), y))
                fluid.optimizer.SGD(0.01).minimize(loss)
            GradAllReduce().transpile(startup, main_p, 0,
                                      ['127.0.0.1:0'], '127.0.0.1:0')
            scope = fluid.Scope()
            # one Executor PER ARM: parameter init folds the
            # executor's step counter into its RNG, so a shared
            # executor would hand each arm a different init and break
            # the cross-arm loss comparison
            exe = fluid.Executor(fluid.XLAPlace(0))
            with fluid.scope_guard(scope):
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(main_p, feed=feed, fetch_list=[loss])
            setups[name] = {'flags': fl, 'program': main_p,
                            'loss': loss, 'scope': scope, 'exe': exe,
                            'walls': [], 'wire': 0.0, 'steps': 0,
                            'final_loss': None}
        for _ in range(rounds):
            for name, _fl in arms:
                s = setups[name]
                fluid.set_flags(s['flags'])
                with fluid.scope_guard(s['scope']):
                    w0 = monitor.counter_value('comms/bytes_on_wire')
                    t0 = time.perf_counter()
                    for _ in range(per_round):
                        lv, = s['exe'].run(s['program'], feed=feed,
                                           fetch_list=[s['loss']])
                    s['walls'].append(time.perf_counter() - t0)
                    s['wire'] += monitor.counter_value(
                        'comms/bytes_on_wire') - w0
                    s['steps'] += per_round
                    s['final_loss'] = float(np.asarray(lv))
        for name, s in setups.items():
            best = min(s['walls']) / per_round
            out[name] = {
                'steps_per_sec': round(per_round / min(s['walls']), 2),
                'best_step_ms': round(best * 1e3, 3),
                'bytes_on_wire_per_step':
                    round(s['wire'] / max(1, s['steps']), 1),
                'final_loss': s['final_loss'],
            }
        dense = out.get('fused_dense', {})
        quant = out.get('quant', {})
        flat = out.get('dense_flat', {})
        if dense.get('bytes_on_wire_per_step') and \
                quant.get('bytes_on_wire_per_step'):
            out['quant_wire_reduction_x'] = round(
                dense['bytes_on_wire_per_step'] /
                quant['bytes_on_wire_per_step'], 2)
        if flat.get('best_step_ms') and dense.get('best_step_ms'):
            out['fused_vs_flat_step_delta_pct'] = round(
                100.0 * (dense['best_step_ms'] - flat['best_step_ms'])
                / flat['best_step_ms'], 1)
    finally:
        fluid.set_flags(prev)
    return out


def bench_kernels(rounds=6, per_round=4):
    """Pallas kernel-library interleaved A/B (BENCH_kernels.json): the
    quantized collective's fused element phases against their dense
    chain, same input, timed in interleaved bursts so OS noise hits
    both arms equally.  The artifact records the post-warmup retrace
    count (must be zero), bitwise parity, and which path the fused arm
    ran (off a TPU: the Pallas interpreter, labeled so)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import common as pallas_common
    from paddle_tpu.ops.pallas import quant_collective as qc

    rng = np.random.RandomState(0)
    out = {}
    # the wire collectives are identical in both arms, so the A/B times
    # the quantize + dequant/reduce/requant chain itself (jitted)
    n_ranks, cb, block = 8, 16, 256
    xq = jnp.asarray(
        rng.randn(n_ranks * cb, block).astype('float32'))
    traces = {'dense': 0, 'fused': 0}

    def _q(t):
        s = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return (jnp.clip(jnp.rint(t / s), -127, 127).astype(jnp.int8),
                s.astype(jnp.float32))

    def dense_fn(v):
        traces['dense'] += 1
        qv, s = _q(v.reshape(n_ranks, cb, block))
        red = jnp.sum(qv.astype(jnp.float32) * s, axis=0)
        return _q(red)

    interp = not pallas_common.on_tpu()

    def fused_fn(v):
        traces['fused'] += 1
        qv, s = qc.quantize_blocks(v, interp)
        return qc.dequant_reduce_requant(
            qv.reshape(n_ranks, cb, block),
            s.reshape(n_ranks, cb, 1), interp)

    jd, jf = jax.jit(dense_fn), jax.jit(fused_fn)
    rd, rf = jd(xq), jf(xq)
    parity = bool(
        np.array_equal(np.asarray(rd[0]), np.asarray(rf[0])) and
        np.array_equal(np.asarray(rd[1]), np.asarray(rf[1])))
    walls = {'dense': [], 'fused': []}
    for _ in range(rounds):
        for name, fn in (('dense', jd), ('fused', jf)):
            t0 = time.perf_counter()
            for _ in range(per_round):
                r = fn(xq)
            np.asarray(r[0])
            walls[name].append(time.perf_counter() - t0)
    out['quant_collective'] = {
        'dense': {'best_call_ms': round(
            min(walls['dense']) / per_round * 1e3, 3)},
        'fused': {'best_call_ms': round(
            min(walls['fused']) / per_round * 1e3, 3),
            'path': 'tpu' if not interp else 'interpret'},
        'post_warmup_retraces':
            traces['dense'] + traces['fused'] - 2,
        'parity_bitwise': parity,
    }
    return {'metric': 'pallas_kernels_ab', 'value': float(
        sum(v.get('post_warmup_retraces', 0) for v in out.values())),
        'unit': 'post_warmup_retraces', 'kernels': out}


def bench_autopilot(adapt_steps=60, rounds=4, per_round=3, warmup=2):
    """Closed-loop autopilot A/B (BENCH_autopilot.json): the SAME
    GradAllReduce MLP under the SAME faultinjected fabric drift
    (`collective.dispatch:delay` landing inside the measured dispatch
    wall), three ways — a STALE static comms model calibrated
    pre-drift, the autopilot arm starting from that same stale model
    but allowed to refit online, and a hand-tuned reference
    calibrated WITH the drift armed (the oracle the autopilot should
    converge toward).  The adaptation phase runs first on the
    autopilot arm alone (refits counted; the pending refit must move
    no digest); the reported numbers come from interleaved bursts so
    OS noise hits every arm equally, with the in-memory refit
    installed ONLY during the autopilot arm's bursts — account-time
    repricing is process-global, so leaving it installed would
    silently heal the static arms' honesty too.  Honesty per arm is
    delta(plan_predicted)/delta(plan_measured) over its own bursts."""
    import tempfile
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import (autopilot, comms, comms_plan,
                                  faultinject, layers, monitor)
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce

    tmp = tempfile.mkdtemp(prefix='bench_autopilot_')
    stale_path = os.path.join(tmp, 'stale_model.json')
    tuned_path = os.path.join(tmp, 'tuned_model.json')
    drift_spec = 'collective.dispatch:delay:0.05@1+'
    keys = ['FLAGS_comms_plan', 'FLAGS_comms_model_path',
            'FLAGS_comms_bucket_bytes', 'FLAGS_timeseries',
            'FLAGS_autopilot', 'FLAGS_autopilot_interval_s']
    prev = fluid.get_flags(keys)
    rng = np.random.RandomState(0)
    feed = {'x': rng.rand(64, 64).astype('float32')}

    def build():
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = 7
        with fluid.program_guard(main_p, startup):
            x = layers.data('x', shape=[64], dtype='float32')
            # weight grads land in distinct wire size buckets so the
            # two-parameter refit stays identifiable from live points
            h = layers.fc(x, 1024, act='relu')
            h = layers.fc(h, 32, act='relu')
            loss = layers.reduce_mean(h)
            fluid.optimizer.SGD(0.01).minimize(loss)
        GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                                  '127.0.0.1:0')
        return main_p, startup, loss

    def _pm():
        return (monitor.counter_value('comms/plan_predicted_seconds')
                or 0.0,
                monitor.counter_value('comms/plan_measured_seconds')
                or 0.0)

    def _honesty(p0m0, p1m1):
        dp, dm = p1m1[0] - p0m0[0], p1m1[1] - p0m0[1]
        return round(dp / dm, 4) if dm > 0 else None

    def _lowered():
        return ((monitor.counter_value('executor/segments_lowered')
                 or 0.0)
                + (monitor.counter_value('parallel/segment_cache_miss')
                   or 0.0))

    def calibrate(path, drift):
        # fit a comms model from REAL dispatch points: clean fabric ->
        # the stale pre-drift model; drift armed -> the tuned oracle
        comms.clear_dispatch_points()
        fluid.set_flags({'FLAGS_comms_model_path': os.devnull})
        if drift:
            faultinject.configure(drift_spec)
        try:
            main_p, startup, loss = build()
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                for _ in range(6):
                    exe.run(main_p, feed=feed, fetch_list=[loss])
        finally:
            faultinject.reset()
        alpha, beta = comms.fit_linear(
            comms.dispatch_points('allreduce'))
        with open(path, 'w') as f:
            json.dump({'collectives': {'allreduce': {
                'latency_s': alpha, 'inv_bw_s_per_byte': beta}}}, f)
        comms.clear_dispatch_points()
        return {'latency_us': round(alpha * 1e6, 1),
                'inv_bw_s_per_byte': beta}

    arms = (('static_stale', stale_path, False),
            ('autopilot', stale_path, True),
            ('static_tuned', tuned_path, False))
    out = {'arms': {}}
    try:
        fluid.set_flags({'FLAGS_comms_plan': True,
                         'FLAGS_comms_bucket_bytes': 32 << 10,
                         'FLAGS_timeseries': True,
                         'FLAGS_autopilot': True,
                         'FLAGS_autopilot_interval_s': 0.05})
        out['stale_model'] = calibrate(stale_path, drift=False)
        out['tuned_model'] = calibrate(tuned_path, drift=True)

        setups = {}
        for name, mpath, _is_ap in arms:
            fluid.set_flags({'FLAGS_comms_model_path': mpath})
            main_p, startup, loss = build()
            scope = fluid.Scope()
            # one Executor PER ARM: parameter init folds the step
            # counter into its RNG (cross-arm loss parity)
            exe = fluid.Executor(fluid.XLAPlace(0))
            with fluid.scope_guard(scope):
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(main_p, feed=feed, fetch_list=[loss])
            setups[name] = {'mpath': mpath, 'program': main_p,
                            'loss': loss, 'scope': scope, 'exe': exe,
                            'walls': [], 'pred': 0.0, 'meas': 0.0,
                            'steps': 0, 'final_loss': None}

        # ---- adaptation: drift on, autopilot arm alone, refit online
        faultinject.configure(drift_spec)
        fluid.set_flags({'FLAGS_comms_model_path': stale_path})
        autopilot.reset()
        autopilot.engage()
        refits0 = monitor.counter_value('autopilot/refits') or 0.0
        lowered0 = _lowered()
        s = setups['autopilot']
        pm0 = _pm()
        steps_to_refit = None
        pm_refit = None
        with fluid.scope_guard(s['scope']):
            for i in range(adapt_steps):
                s['exe'].run(s['program'], feed=feed,
                             fetch_list=[s['loss']])
                if steps_to_refit is None and \
                        (monitor.counter_value('autopilot/refits')
                         or 0.0) > refits0:
                    steps_to_refit = i + 1
                    pm_refit = _pm()
                elif steps_to_refit is not None and \
                        i + 1 >= steps_to_refit + 6:
                    break   # enough repriced post-refit samples
        out['adaptation'] = {
            'refits': int((monitor.counter_value('autopilot/refits')
                           or 0.0) - refits0),
            'steps_to_refit': steps_to_refit,
            'honesty_before_refit':
                _honesty(pm0, pm_refit) if pm_refit else None,
            'honesty_after_refit':
                _honesty(pm_refit, _pm()) if pm_refit else None,
            'retraces': int(_lowered() - lowered0),
        }
        autopilot.disengage()
        # stash the refit so it prices ONLY the autopilot arm's bursts
        with comms_plan._lock:
            ap_model = comms_plan._refit['pending'] or \
                comms_plan._refit['adopted']
        comms_plan.clear_refit()

        # ---- measurement: interleaved bursts under the same drift
        lowered_meas = _lowered()
        for _ in range(rounds):
            for name, mpath, is_ap in arms:
                s = setups[name]
                fluid.set_flags({'FLAGS_comms_model_path': mpath})
                if is_ap and ap_model:
                    comms_plan.install_refit(ap_model)
                pm_a = _pm()
                with fluid.scope_guard(s['scope']):
                    t0 = time.perf_counter()
                    for _ in range(per_round):
                        lv, = s['exe'].run(s['program'], feed=feed,
                                           fetch_list=[s['loss']])
                    s['walls'].append(time.perf_counter() - t0)
                pm_b = _pm()
                s['pred'] += pm_b[0] - pm_a[0]
                s['meas'] += pm_b[1] - pm_a[1]
                s['steps'] += per_round
                s['final_loss'] = float(np.asarray(lv))
                if is_ap:
                    comms_plan.clear_refit()
        out['post_warmup_retraces'] = int(_lowered() - lowered_meas)

        for name, _mpath, _is_ap in arms:
            s = setups[name]
            out['arms'][name] = {
                'steps_per_sec':
                    round(per_round / min(s['walls']), 2),
                'best_step_ms':
                    round(min(s['walls']) / per_round * 1e3, 3),
                'honesty':
                    _honesty((0.0, 0.0), (s['pred'], s['meas'])),
                'final_loss': s['final_loss'],
            }
        ap_h = out['arms']['autopilot']['honesty']
        tn_h = out['arms']['static_tuned']['honesty']
        tn_ms = out['arms']['static_tuned']['best_step_ms']
        ap_ms = out['arms']['autopilot']['best_step_ms']
        if ap_h is not None and tn_h is not None:
            out['autopilot_vs_tuned'] = {
                'honesty_gap': round(abs(ap_h - tn_h), 4),
                'step_delta_pct':
                    round(100.0 * (ap_ms - tn_ms) / tn_ms, 1),
            }
    finally:
        faultinject.reset()
        autopilot.disengage()
        comms_plan.clear_refit()
        fluid.set_flags(prev)
    return dict({'metric': 'autopilot_ab',
                 'value': out['arms'].get('autopilot', {}).get(
                     'honesty') or 0.0,
                 'unit': 'pred_over_measured'}, **out)


def bench_autoshard(batch=8, rounds=5, per_round=4, warmup=3):
    """Auto-sharding A/B (BENCH_autoshard.json): the SAME transformer
    block (qkv fc -> context-parallel attention -> proj -> MoE FFN,
    the test_sp_ep_fluid shape) trained three ways, interleaved so OS
    noise hits every arm equally —

      hand_spep:      the hand-placed dp2 x sp2 x ep2 mesh config
                      (FLAGS_auto_shard=0, the pre-planner posture),
      auto:           FLAGS_auto_shard=1 on the UNANNOTATED program
                      (no mesh, no rules, no axis names),
      auto_hbm_tight: same, under an injected HBM budget below the
                      fully-replicated residency, so the memviz gate
                      must REJECT at least one candidate layout before
                      anything compiles and the planner lands on a
                      scattered one.

    Per arm: best step wall, bytes-on-wire per step, attributed peak
    HBM, final loss (the parity claim rides in the artifact); the auto
    arms also embed their plan summary (chosen layout, candidate
    count, HBM rejections)."""
    return {'metric': 'autoshard_ab', 'unit': 'ms/step',
            'autoshard_ab': _autoshard_fields(batch, rounds,
                                              per_round, warmup)}


def _autoshard_fields(batch=8, rounds=5, per_round=4, warmup=3):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, memviz, monitor
    from paddle_tpu.parallel import mesh as pmesh
    from paddle_tpu.parallel import plan as auto_plan

    T, H, D, E, FF = 16, 4, 8, 4, 32
    DIM = H * D

    def build(seed=5):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[T, DIM], dtype='float32')
            y = layers.data('y', shape=[T, DIM], dtype='float32')
            qkv = layers.fc(x, size=3 * DIM, num_flatten_dims=2,
                            bias_attr=False)
            q, k, v = layers.split(qkv, 3, dim=-1)
            q = layers.reshape(q, [-1, T, H, D])
            k = layers.reshape(k, [-1, T, H, D])
            v = layers.reshape(v, [-1, T, H, D])
            att = layers.context_parallel_attention(q, k, v,
                                                    causal=True)
            att = layers.reshape(att, [-1, T, DIM])
            proj = layers.fc(att, size=DIM, num_flatten_dims=2,
                             bias_attr=False)
            h1 = layers.elementwise_add(x, proj)
            mo, aux = layers.moe(h1, num_experts=E, hidden_size=FF,
                                 aux_weight=0.01)
            out_v = layers.elementwise_add(h1, mo)
            mse = layers.reduce_mean(
                layers.square(layers.elementwise_sub(out_v, y)))
            loss = layers.elementwise_add(mse, aux)
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(batch, T, DIM).astype('float32'),
            'y': rng.randn(batch, T, DIM).astype('float32')}
    # the injected budget for the tight arm: below the fully-
    # replicated (dp-only) per-device residency, above the best
    # scattered candidate — the memviz gate must fire
    probe_main, _ps, _pl = build()
    free = auto_plan.build_plan(
        probe_main, ndev=8,
        feed_shapes={k: v.shape for k, v in feed.items()})
    repl_hbm = next(c['hbm_bytes'] for c in free.candidates
                    if tuple(c['layout']) == (8, 1, 1))
    auto_plan.reset()

    arms = (
        ('hand_spep', {'FLAGS_auto_shard': False,
                       'FLAGS_memviz_budget_bytes': 0}, True),
        ('auto', {'FLAGS_auto_shard': True,
                  'FLAGS_memviz_budget_bytes': 0}, False),
        ('auto_hbm_tight', {'FLAGS_auto_shard': True,
                            'FLAGS_memviz_budget_bytes':
                                repl_hbm * 0.8}, False),
    )
    prev = fluid.get_flags(['FLAGS_auto_shard',
                            'FLAGS_memviz_budget_bytes'])
    setups = {}
    out = {}
    try:
        for name, fl, hand_mesh in arms:
            fluid.set_flags(fl)
            main_p, startup, loss = build()
            comp = fluid.CompiledProgram(main_p).with_data_parallel(
                loss_name=loss.name)
            if hand_mesh:
                comp = comp.with_mesh(pmesh.create_mesh(dp=2, sp=2,
                                                        ep=2))
            scope = fluid.Scope()
            # one Executor per arm: parameter init folds the executor
            # step counter into its RNG (same rationale as
            # _plan_ab_fields)
            exe = fluid.Executor(fluid.XLAPlace(0))
            with fluid.scope_guard(scope):
                exe.run(startup)
                for _ in range(warmup):
                    exe.run(comp, feed=feed, fetch_list=[loss])
            setups[name] = {'flags': fl, 'comp': comp, 'loss': loss,
                            'scope': scope, 'exe': exe,
                            'program': main_p, 'walls': [],
                            'wire': 0.0, 'steps': 0,
                            'final_loss': None}
        for _ in range(rounds):
            for name, _fl, _hm in arms:
                s = setups[name]
                fluid.set_flags(s['flags'])
                with fluid.scope_guard(s['scope']):
                    w0 = monitor.counter_value('comms/bytes_on_wire')
                    t0 = time.perf_counter()
                    for _ in range(per_round):
                        lv, = s['exe'].run(s['comp'], feed=feed,
                                           fetch_list=[s['loss']])
                    s['walls'].append(time.perf_counter() - t0)
                    s['wire'] += monitor.counter_value(
                        'comms/bytes_on_wire') - w0
                    s['steps'] += per_round
                    s['final_loss'] = float(np.asarray(lv).ravel()[0])
        for name, s in setups.items():
            peak = memviz.peak_bytes(memviz.program_label(
                s['program']))
            row = {
                'best_step_ms': round(
                    min(s['walls']) / per_round * 1e3, 3),
                'steps_per_sec': round(per_round / min(s['walls']), 2),
                'bytes_on_wire_per_step':
                    round(s['wire'] / max(1, s['steps']), 1),
                'peak_hbm_bytes': peak,
                'final_loss': s['final_loss'],
            }
            ap = getattr(s['comp'], '_auto_plan', None)
            if ap is not None:
                row['plan'] = {
                    'layout': {'dp': ap.layout[0],
                               'fsdp': ap.layout[1],
                               'tp': ap.layout[2]},
                    'update_axis': ap.update_axis,
                    'candidates': len(ap.candidates),
                    'hbm_rejected': ap.rejected,
                    # the planner's own per-device residency estimate
                    # for the chosen layout (the quantity the memviz
                    # gate compared against the budget)
                    'est_hbm_bytes': round(ap.chosen['hbm_bytes'], 1),
                    'digest': ap.digest(),
                }
            out[name] = row
        tight = out.get('auto_hbm_tight', {}).get('plan', {})
        out['hbm_gate_fired'] = bool(tight.get('hbm_rejected'))
        hand = out.get('hand_spep', {})
        auto = out.get('auto', {})
        if hand.get('best_step_ms') and auto.get('best_step_ms'):
            out['auto_vs_hand_step_delta_pct'] = round(
                100.0 * (auto['best_step_ms'] - hand['best_step_ms'])
                / hand['best_step_ms'], 1)
    finally:
        fluid.set_flags(prev)
    return out


def _skew_job_fields(run_for):
    """The cross-rank half of bench_parallel: a real two-subprocess
    job (tests/comms_worker.py, rank 1 with a 4x batch), scraped for
    the aggregator's skew report and merged through collect_job.
    Degrades to {'skew': None} if the job cannot come up — the
    in-process comms numbers must survive a constrained container."""
    import socket
    import subprocess
    import urllib.request

    def free_port():
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def get(url, timeout=5):
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()

    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, 'tests', 'comms_worker.py')
    p0, p1 = free_port(), free_port()
    spec = '0=127.0.0.1:%d,1=127.0.0.1:%d' % (p0, p1)
    base = dict(os.environ,
                PADDLE_TPU_STATUS_WORKERS=spec,
                FLAGS_health_heartbeat_seconds='0.5',
                FLAGS_trace='1')
    procs = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(p1), str(run_for + 60), '4'],
            env=dict(base, PADDLE_TRAINER_ID='1',
                     PADDLE_TPU_STATUS_AGGREGATE='0'),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(p0), str(run_for + 60)],
            env=dict(base, PADDLE_TRAINER_ID='0',
                     PADDLE_TPU_STATUS_AGGREGATE='1'),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        agg = 'http://127.0.0.1:%d' % p0
        deadline = time.time() + run_for + 90
        skew = None
        while time.time() < deadline:
            try:
                code, body = get(agg + '/statusz')
                doc = json.loads(body)
                job = doc.get('job') or {}
                skew = job.get('skew')
                workers = job.get('workers') or {}
                if skew and len(workers) >= 2 and \
                        all(w.get('up') for w in workers.values()):
                    break
            except Exception:
                pass
            time.sleep(1.0)
        merged = None
        try:
            code, body = get(agg + '/trace/collect', timeout=30)
            doc = json.loads(body)
            merged = {
                'ranks': len(doc['ptJob']['workers']),
                'events': sum(1 for e in doc['traceEvents']
                              if e.get('ph') == 'X'),
                'skipped': len(doc['ptJob']['skipped']),
            }
        except Exception:
            pass
        out = {'skew': None, 'job_timeline': merged}
        if skew:
            wall = skew['wall']
            worst_phase = None
            if skew.get('phases'):
                name, ph = max(skew['phases'].items(),
                               key=lambda kv: kv[1]['ratio'])
                worst_phase = {'phase': name,
                               'slowest_rank': ph['slowest_rank'],
                               'ratio': round(ph['ratio'], 3)}
            out['skew'] = {
                'slowest_rank': wall['slowest_rank'],
                'skew_ratio': round(wall['skew_ratio'], 3),
                'max_p50_ms': round(wall['max_p50_ms'], 3),
                'median_p50_ms': round(wall['median_p50_ms'], 3),
                'worst_phase': worst_phase,
            }
        return out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass


SMOKE_BENCHES = (('dispatch', {}),
                 ('health_overhead', {}),
                 ('memviz_overhead', {}),
                 ('opprof_overhead', {}),
                 ('lenet', {'batch': 64, 'steps': 30}))


# --all entries: (name, kwargs), one configuration each
ALL_BENCHES = (
    ('lenet', {}),
    ('bert', {}),
    ('bert_long', {}),
    ('bert_long_dropout', {}),
    ('wide_deep', {}),
    ('wide_deep_sparse', {}),
    ('host_sparse_push', {}),
    ('rpc_sparse_push', {}),
    ('transformer', {}),
    ('resnet_infer', {}),
    ('resnet50_hostfed', {}),
    ('serving', {}),
)


def _run_entry(name, kwargs, timeout=900):
    """Run one bench entry in a child process under a deadline and
    print its JSON line (each entry gets a fresh monitor registry and,
    on the chip, the chip to itself — this parent never brings a
    backend up).  A child that exits non-zero has no result, whatever
    it printed.  Returns True on success."""
    import subprocess
    try:
        p = subprocess.run(
            [sys.executable, '-u', os.path.abspath(__file__),
             '--one', name, json.dumps(kwargs)],
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        sys.stderr.write('%s %s timed out after %ds\n'
                         % (name, kwargs or '', timeout))
        return False
    line = [ln for ln in p.stdout.splitlines() if ln.startswith('{')]
    if p.returncode != 0 or not line:
        sys.stderr.write('%s %s failed (rc=%d): %s\n'
                         % (name, kwargs or '', p.returncode,
                            p.stderr[-300:]))
        return False
    print(line[-1])
    return True


def _publish(name, flag, rec, out, device=None):
    """Print one entry, append it to the history and write its
    artifact — with the device it actually ran on beside the command:
    this process's, as JAX reports it, unless the entry ran elsewhere
    and says where."""
    if device is None:
        import jax
        dev = jax.devices()[0]
        device = {'platform': dev.platform, 'kind': dev.device_kind,
                  'count': len(jax.devices())}
    print(json.dumps(rec))
    append_history(name, rec)
    with open(out, 'w') as f:
        json.dump({'cmd': 'python bench.py ' + flag, 'device': device,
                   'entries': [rec]}, f, indent=1, sort_keys=True)


def main():
    if len(sys.argv) > 1 and sys.argv[1] in ('--parallel',
                                             '--auto-shard',
                                             '--autopilot'):
        # multi-device posture BEFORE the first jax import: the comms
        # and placement numbers need a real mesh (8 virtual CPU
        # devices when the host has no accelerator platform
        # configured)
        flags = os.environ.get('XLA_FLAGS', '')
        if 'xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=8'
            ).strip()
    _enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == '--one' and \
            len(sys.argv) < 3:
        sys.stderr.write('usage: bench.py --one NAME [kwargs-json]\n')
        sys.exit(2)
    if len(sys.argv) > 2 and sys.argv[1] == '--one':
        kwargs = json.loads(sys.argv[3]) if len(sys.argv) > 3 else {}
        if sys.argv[2] == 'resnet50':
            ips = bench_resnet50(**kwargs)
            rec = dict({
                'metric': 'resnet50_train_images_per_sec_chip',
                'value': round(ips, 2), 'unit': 'images/sec',
                'vs_baseline': round(ips / 365.0, 3)},
                **LAST_PERF, **_step_phase_fields(),
                **_monitor_fields())
        else:
            rec = globals()['bench_' + sys.argv[2]](**kwargs)
        print(json.dumps(rec))
        # every entry (--one is also how --all/--cold/--elastic spawn
        # children) lands one line in the run-to-run history
        if isinstance(rec, dict):
            append_history(sys.argv[2], rec)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--cold':
        # process-restart latency: cold (populate the persistent
        # compile cache) vs warm (start from it) vs warm+warmup.
        # Baseline recorded in BENCH_compile_cache.json.
        out = sys.argv[2] if len(sys.argv) > 2 else None
        _run_cold(out_path=out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--elastic':
        # elastic reconfiguration: save under dp2, resume on a
        # different topology cold vs warm through the persistent
        # compile cache, reshard predicted-vs-measured, checkpoint
        # save/load bandwidth.  Baseline recorded in
        # BENCH_elastic.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_elastic.json')
        _run_elastic(out_path=out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--chaos':
        # self-healing chaos soak: real multi-process job, >= 4
        # injected fault kinds, zero-intervention completion with
        # bounded lost work and bitwise post-recovery verification.
        # Baseline recorded in BENCH_chaos.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_chaos.json')
        rec = bench_chaos()
        # the soak's workers are forced onto the CPU (bench_chaos)
        _publish('chaos', '--chaos', rec, out,
                 device={'platform': 'cpu', 'kind': 'cpu',
                         'count': None})
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--serving':
        # multi-client serving soak (continuous batching vs
        # sequential single requests).  Baseline recorded in
        # BENCH_serving.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_serving.json')
        rec = bench_serving()
        _publish('serving_soak', '--serving', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--serving-fleet':
        # skewed-tenant churn soak: two-replica fleet (priced
        # migrations, p99 held) vs one replica eating the re-warm
        # wall on the serving path.  Baseline recorded in
        # BENCH_fleet.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_fleet.json')
        rec = bench_serving_fleet()
        _publish('serving_fleet', '--serving-fleet', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--kernels':
        # pallas kernel library A/B: shipped auto-dispatch vs the
        # dense reference per kernel, interleaved, dispatch counters
        # + zero-post-warmup-retrace proof in the artifact.  Baseline
        # recorded in BENCH_kernels.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_kernels.json')
        rec = bench_kernels()
        _publish('kernels', '--kernels', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--auto-shard':
        # auto-sharding planner A/B: FLAGS_auto_shard=1 on an
        # unannotated program vs the hand-placed sp/ep mesh config,
        # interleaved, with an HBM-gate rejection arm.  Baseline
        # recorded in BENCH_autoshard.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_autoshard.json')
        rec = bench_autoshard()
        _publish('autoshard', '--auto-shard', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--autopilot':
        # closed-loop autopilot A/B: stale static comms model vs
        # online-refitting autopilot vs drift-calibrated hand-tuned
        # reference, all under the same injected fabric drift.
        # Baseline recorded in BENCH_autopilot.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_autopilot.json')
        rec = bench_autopilot()
        _publish('autopilot', '--autopilot', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--parallel':
        # collective-job comms telemetry: bytes on wire, achieved
        # bandwidth per (collective, size bucket), cross-rank skew.
        # Baseline recorded in BENCH_comms.json.
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'BENCH_comms.json')
        rec = bench_parallel()
        _publish('parallel', '--parallel', rec, out)
        return
    if len(sys.argv) > 1 and sys.argv[1] == '--smoke':
        # CPU-friendly minutes-scale sweep: the dispatch micro-bench
        # (steady-state host time per step — the fast-path metric) and
        # a small LeNet entry, each in its own child process so the
        # monitor registry is per-entry.  Baseline recorded in
        # BENCH_fastpath_smoke.json.
        ok = [_run_entry(name, kwargs, timeout=600)
              for name, kwargs in SMOKE_BENCHES]
        sys.exit(0 if any(ok) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == '--all':
        # secondary configs (BASELINE.json 0,2,3,4); the default stays
        # the single-line ResNet metric
        ok = [_run_entry(name, kwargs) for name, kwargs in ALL_BENCHES]
        sys.exit(0 if any(ok) else 1)
    # NHWC is the TPU-native conv layout (channels on the 128-lane
    # minor dim)
    layout = os.environ.get('PADDLE_TPU_BENCH_LAYOUT', 'NHWC')
    if not _run_entry('resnet50', {'batch': 128, 'data_format': layout}):
        sys.exit(1)     # no result is a failure, never a 0.0 line


if __name__ == '__main__':
    main()
