"""Collective planner (fluid.comms_plan): cost-model-driven arm
selection (dense flat / reduce-scatter+allgather / block-scaled int8
quantized), grad-bucket fusion in the GradAllReduce transpiler, and
the observability contract (plan_arm counters, dense-equivalent wire
bytes, predicted-vs-measured, /statusz plan section).

Loss-parity posture mirrors test_dgc: the quantized arm must converge
within tolerance of the dense run on a small model, and fall back
BIT-EXACT when FLAGS_comms_quantize is off or every tensor sits below
the size floor."""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import comms, comms_plan, layers, monitor
from paddle_tpu.fluid.transpiler.collective import GradAllReduce

PLAN_FLAGS = ('FLAGS_comms_plan', 'FLAGS_comms_quantize',
              'FLAGS_comms_quantize_min_bytes',
              'FLAGS_comms_quant_block', 'FLAGS_comms_bucket_bytes',
              'FLAGS_comms_model_path', 'FLAGS_comms_rs_ag_min_bytes',
              'FLAGS_comms_hbm_budget_bytes')


@pytest.fixture(autouse=True)
def _clean():
    prev = fluid.get_flags(list(PLAN_FLAGS))
    monitor.reset()
    comms.reset()
    comms_plan.reset()
    yield
    fluid.set_flags(prev)
    monitor.reset()
    comms.reset()
    comms_plan.reset()


def _write_model(tmp_path, collectives):
    path = tmp_path / 'comms_model.json'
    path.write_text(json.dumps({'version': 1, 'devices': 8,
                                'collectives': collectives}))
    return str(path)


# ---------------------------------------------------------- unit: planner
def test_quant_wire_bytes_is_quarter_of_dense():
    payload = 4 << 20      # 4 MiB fp32
    dense = comms.wire_bytes('allreduce', payload, 8)
    quant = comms_plan.quant_wire_bytes(payload, 4, 8, block=256)
    # int8 payload + 4/256 scale overhead: ~dense/4 * 1.0156
    assert quant == pytest.approx(dense / 4 * (1 + 4 / 256), rel=1e-6)
    assert comms_plan.quant_wire_bytes(payload, 4, 1) == 0.0


def test_decide_dense_default_and_quant_gate():
    fluid.set_flags({'FLAGS_comms_quantize': False})
    d = comms_plan.decide(1 << 20, 4, 8)
    assert d['arm'] == 'dense' and d['strategy'] == 'flat'
    assert d['wire_bytes'] == d['dense_wire_bytes'] > 0
    # flag on: eligible above the floor, dense below it
    fluid.set_flags({'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 65536})
    assert comms_plan.decide(1 << 20, 4, 8)['arm'] == 'quant'
    assert comms_plan.decide(1 << 10, 4, 8)['arm'] == 'dense'
    # int8 payloads have nothing to quantize
    assert comms_plan.decide(1 << 20, 1, 8)['arm'] == 'dense'
    # single participant: nothing moves
    assert comms_plan.decide(1 << 20, 4, 1)['wire_bytes'] == 0.0
    # forced arm (calibrator) bypasses the gate
    fluid.set_flags({'FLAGS_comms_quantize': False})
    d = comms_plan.decide(1 << 20, 4, 8, forced_arm='quant')
    assert d['arm'] == 'quant'
    assert d['wire_bytes'] < d['dense_wire_bytes'] / 3


def test_decide_strategy_from_model(tmp_path):
    # model A: rs+ag much cheaper than flat -> rs_ag
    path = _write_model(tmp_path, {
        'allreduce': {'latency_s': 1e-3, 'inv_bw_s_per_byte': 1e-8},
        'reducescatter': {'latency_s': 1e-5,
                          'inv_bw_s_per_byte': 1e-10},
        'allgather': {'latency_s': 1e-5, 'inv_bw_s_per_byte': 1e-10}})
    fluid.set_flags({'FLAGS_comms_model_path': path})
    d = comms_plan.decide(1 << 20, 4, 8)
    assert d['strategy'] == 'rs_ag'
    # forced dense baseline skips strategy synthesis entirely
    forced = comms_plan.decide(1 << 20, 4, 8, forced_arm='dense')
    assert forced['arm'] == 'dense' and forced['strategy'] == 'flat'
    assert d['predicted_s'] == pytest.approx(
        2e-5 + 1e-10 * (comms.wire_bytes('reducescatter', 1 << 20, 8) +
                        comms.wire_bytes('allgather', (1 << 20) / 8,
                                         8)))
    # model B: flat cheaper -> flat
    path_b = tmp_path / 'b.json'
    path_b.write_text(json.dumps({'collectives': {
        'allreduce': {'latency_s': 1e-6, 'inv_bw_s_per_byte': 1e-12},
        'reducescatter': {'latency_s': 1e-3,
                          'inv_bw_s_per_byte': 1e-8},
        'allgather': {'latency_s': 1e-3, 'inv_bw_s_per_byte': 1e-8}}}))
    fluid.set_flags({'FLAGS_comms_model_path': str(path_b)})
    assert comms_plan.decide(1 << 20, 4, 8)['strategy'] == 'flat'


def test_decide_heuristic_without_model():
    fluid.set_flags({'FLAGS_comms_model_path': '/nonexistent.json',
                     'FLAGS_comms_rs_ag_min_bytes': 1 << 20})
    assert comms_plan.decide(1 << 19, 4, 8)['strategy'] == 'flat'
    assert comms_plan.decide(1 << 21, 4, 8)['strategy'] == 'rs_ag'
    assert comms_plan.decide(1 << 21, 4, 8)['predicted_s'] is None


def test_decide_partial_model_never_mislabels_prediction(tmp_path):
    # allreduce-only model + heuristic rs_ag pick: predicted_s must be
    # None (the rs_ag arm cannot be priced), NOT the flat prediction —
    # else the predicted-vs-measured honesty metrics are poisoned
    path = _write_model(tmp_path, {
        'allreduce': {'latency_s': 1e-5, 'inv_bw_s_per_byte': 1e-9}})
    fluid.set_flags({'FLAGS_comms_model_path': path,
                     'FLAGS_comms_rs_ag_min_bytes': 1 << 20})
    d = comms_plan.decide(1 << 21, 4, 8)
    assert d['strategy'] == 'rs_ag' and d['predicted_s'] is None
    # below the cut the flat pick keeps its (valid) flat prediction
    d = comms_plan.decide(1 << 19, 4, 8)
    assert d['strategy'] == 'flat' and d['predicted_s'] is not None


def test_quant_respects_hbm_headroom():
    fluid.set_flags({'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 1024,
                     'FLAGS_comms_hbm_budget_bytes': 1 << 20})
    monitor.set_gauge('executor/segment_peak_bytes', (1 << 20) - 4096)
    # headroom ~4KiB < 2.25 * 512KiB payload: quant degrades to dense
    assert comms_plan.decide(512 << 10, 4, 8)['arm'] == 'dense'
    monitor.set_gauge('executor/segment_peak_bytes', 0.0)
    assert comms_plan.decide(100 << 10, 4, 8)['arm'] == 'quant'


def test_bucket_grads_grouping_and_caps():
    grads = [('a', 1000, 'float32'), ('b', 1000, 'float32'),
             ('c', 500, 'float16'), ('d', 1000, 'float32'),
             ('e', 10 ** 9, 'float32'), ('f', 0, 'float32')]
    buckets = comms_plan.bucket_grads(grads, cap_bytes=2500)
    names = [b['names'] for b in buckets]
    # same-dtype grads group to the cap; dtype change opens a bucket;
    # oversized and unknown-size grads stand alone
    assert ['a', 'b'] in names            # 2000 <= cap, 'd' would pass
    assert ['c'] in names                 # dtype break
    assert ['e'] in names and ['f'] in names
    assert any('d' in n for n in names)
    # every grad appears exactly once
    flat = [n for b in buckets for n in b['names']]
    assert sorted(flat) == sorted(g[0] for g in grads)
    # cap 0 disables fusion entirely
    assert all(len(b['names']) == 1 for b in
               comms_plan.bucket_grads(grads, cap_bytes=0))


def test_fuse_cutoff_from_model_crossover(tmp_path):
    # bandwidth-bound grads skip fusion: without a model the flag is
    # the floor; with one, the model's own alpha/beta crossover
    fluid.set_flags({'FLAGS_comms_fuse_grad_max_bytes': 64 << 10})
    assert comms_plan.fuse_cutoff_bytes(cap=4 << 20) == 64 << 10
    path = _write_model(tmp_path, {
        'allreduce': {'latency_s': 1e-4, 'inv_bw_s_per_byte': 1e-9}})
    fluid.set_flags({'FLAGS_comms_model_path': path})
    # the alpha/beta crossover is in wire bytes; payload cutoff is
    # half (ring wire ~ 2x payload): 100KB wire -> 50KB payload
    assert comms_plan.fuse_cutoff_bytes(cap=4 << 20) == \
        pytest.approx(1e-4 / 1e-9 / 2)
    # large grads stand alone even when the cap would admit them
    buckets = comms_plan.bucket_grads(
        [('w', 200 << 10, 'float32'), ('b', 256, 'float32'),
         ('b2', 256, 'float32')], cap_bytes=4 << 20)
    assert [b['names'] for b in buckets] == [['w'], ['b', 'b2']]


def test_bucket_cap_respects_hbm_budget():
    fluid.set_flags({'FLAGS_comms_bucket_bytes': 4 << 20,
                     'FLAGS_comms_hbm_budget_bytes': 0})
    assert comms_plan.bucket_cap_bytes() == 4 << 20
    fluid.set_flags({'FLAGS_comms_hbm_budget_bytes': 2 << 20})
    monitor.set_gauge('executor/segment_peak_bytes', 1 << 20)
    # quarter of the 1MiB headroom, floored at 64KiB
    assert comms_plan.bucket_cap_bytes() == pytest.approx((1 << 20) / 4)
    monitor.set_gauge('executor/segment_peak_bytes', 2 << 20)
    assert comms_plan.bucket_cap_bytes() == 64 << 10


def test_order_axes_largest_first():
    assert comms_plan.order_axes([('sp', 2), ('dp', 8), ('mp', 4)]) \
        == ['dp', 'mp', 'sp']
    # stable tie-break by name
    assert comms_plan.order_axes([('b', 4), ('a', 4)]) == ['a', 'b']


def test_multi_axis_planned_allreduce_ring_ids():
    # a planned c_allreduce_sum with a ring_ids attr reduces over both
    # mesh axes (planner-ordered phases), matching a two-axis psum
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.compat import shard_map
    from paddle_tpu.ops import collective_ops, registry
    if len(jax.devices()) < 4:
        pytest.skip('needs a multi-axis mesh')
    from paddle_tpu.parallel import mesh as pmesh
    mesh = pmesh.create_mesh(dp=len(jax.devices()) // 2, mp=2)
    prev_rings = dict(collective_ops.RING_AXES)
    try:
        collective_ops.RING_AXES = {0: 'dp', 1: 'mp'}
        x = np.arange(len(jax.devices()) * 6,
                      dtype='float32').reshape(-1, 6)

        def f(v):
            out = registry.get('c_allreduce_sum').fn(
                registry.LowerCtx(0), {'X': [v]},
                {'ring_ids': [0, 1], 'plan': True})['Out'][0]
            return out, jax.lax.psum(v, ('dp', 'mp'))

        got, want = jax.jit(shard_map(
            f, mesh=mesh, in_specs=P('dp'),
            out_specs=(P('dp'), P('dp'))))(x)
        assert np.allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-6)
    finally:
        collective_ops.RING_AXES = prev_rings


def test_digest_tracks_flags_and_model(tmp_path):
    d0 = comms_plan.digest()
    assert d0 == comms_plan.digest()      # deterministic
    fluid.set_flags({'FLAGS_comms_quantize': True})
    d1 = comms_plan.digest()
    assert d1 != d0
    path = _write_model(tmp_path, {
        'allreduce': {'latency_s': 0, 'inv_bw_s_per_byte': 1e-10}})
    fluid.set_flags({'FLAGS_comms_model_path': path})
    d2 = comms_plan.digest()
    assert d2 != d1
    # the HBM-headroom gate reads a runtime gauge: a materially (power
    # of two) changed headroom must change the digest, so cached
    # executables can never be silently stale against the gate
    fluid.set_flags({'FLAGS_comms_hbm_budget_bytes': 1 << 20})
    monitor.set_gauge('executor/segment_peak_bytes', 0.0)
    d3 = comms_plan.digest()
    assert d3 != d2
    monitor.set_gauge('executor/segment_peak_bytes', (1 << 20) - 1024)
    assert comms_plan.digest() != d3


# ----------------------------------------------- transpiler bucket rewrite
def _build_mlp(width=64, seed=3):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[width], dtype='float32')
        h = layers.fc(x, width, act='relu')
        loss = layers.reduce_mean(layers.fc(h, 1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main_p, startup, loss


def test_transpiler_fuses_buckets():
    main_p, startup, _ = _build_mlp()
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    ops = [op.type for op in main_p.global_block().ops]
    # 4 small grads coalesce into one fused planned collective + the
    # reference's per-grad 1/nranks scale
    assert ops.count('c_allreduce_fused') == 1
    assert ops.count('c_allreduce_sum') == 0
    assert ops.count('scale') >= 4
    fused = [op for op in main_p.global_block().ops
             if op.type == 'c_allreduce_fused'][0]
    assert len(fused.input('X')) == 4
    assert fused.attrs['plan'] is True
    snap = monitor.snapshot()['collective']
    assert snap['plan_buckets'] == 1.0
    assert snap['plan_fused_grads'] == 4.0
    # ops_inserted reports collectives actually in the block (1 fused
    # bucket), bytes_per_step still the payload of all 4 synced grads
    assert snap['allreduce_ops_inserted'] == 1.0
    assert snap['allreduce_bytes_per_step'] > 0
    # the plan is on the /statusz registry
    plans = comms_plan.program_plans()
    assert plans['programs']
    (label, summary), = plans['programs'].items()
    assert summary['grads'] == 4 and len(summary['buckets']) == 1
    assert summary['buckets'][0]['arm_preview'] == 'dense'


def test_transpiler_off_restores_v16_shape():
    fluid.set_flags({'FLAGS_comms_plan': False})
    main_p, startup, _ = _build_mlp()
    n_before = len(main_p.global_block().ops)
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    ops = [op.type for op in main_p.global_block().ops]
    assert ops.count('c_allreduce_sum') == 4
    assert ops.count('c_allreduce_fused') == 0
    assert len(ops) == n_before + 8


def test_transpiler_bucket_cap_splits():
    # a tiny bucket target forces one planned collective per grad
    fluid.set_flags({'FLAGS_comms_bucket_bytes': 8})
    main_p, startup, _ = _build_mlp()
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    ops = [op.type for op in main_p.global_block().ops]
    assert ops.count('c_allreduce_sum') == 4
    assert ops.count('c_allreduce_fused') == 0


# -------------------------------------------------------- execution parity
def _train(n_steps=40, width=64, seed=0):
    comms.reset()
    main_p, startup, loss = _build_mlp(width=width)
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    exe = fluid.Executor(fluid.XLAPlace(0))
    rng = np.random.RandomState(seed)
    W = rng.randn(width, 1).astype('float32')
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(n_steps):
            xs = rng.randn(16, width).astype('float32')
            lv, = exe.run(main_p, feed={'x': xs}, fetch_list=[loss])
            losses.append(np.asarray(lv))
    return np.concatenate([l.reshape(-1) for l in losses])


def test_planned_dense_bit_exact_vs_v16():
    fluid.set_flags({'FLAGS_comms_plan': False})
    base = _train()
    fluid.set_flags({'FLAGS_comms_plan': True})
    planned = _train()
    # fused dense buckets compute the same elementwise sum
    assert np.array_equal(base, planned)


def test_quant_loss_parity_and_bit_exact_fallback():
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_quantize': False})
    dense = _train()
    fluid.set_flags({'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 256})
    quant = _train()
    # quantized training converges alongside dense: same trajectory
    # within a few percent, same final loss neighborhood (DGC-style
    # parity posture)
    assert quant.shape == dense.shape
    assert not np.array_equal(dense, quant)   # the arm really ran
    assert float(abs(quant[-1] - dense[-1])) <= \
        max(0.05 * abs(float(dense[-1])), 5e-3)
    assert np.max(np.abs(quant - dense)) <= \
        0.1 * max(1.0, float(np.max(np.abs(dense))))
    # below the floor every tensor is ineligible: BIT-EXACT fallback
    fluid.set_flags({'FLAGS_comms_quantize_min_bytes': 1 << 30})
    below_floor = _train()
    assert np.array_equal(dense, below_floor)
    # flag off: bit-exact again
    fluid.set_flags({'FLAGS_comms_quantize': False,
                     'FLAGS_comms_quantize_min_bytes': 256})
    off = _train()
    assert np.array_equal(dense, off)


def test_rs_ag_strategy_matches_flat():
    # force rs_ag for everything via the no-model heuristic cut
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_rs_ag_min_bytes': 1})
    rs = _train(n_steps=10)
    fluid.set_flags({'FLAGS_comms_rs_ag_min_bytes': 1 << 30})
    flat = _train(n_steps=10)
    assert np.allclose(rs, flat, rtol=1e-6, atol=1e-6)
    arm = monitor.counter_value('comms/plan_arm/dense')
    assert arm > 0


def test_dispatch_reports_arm_and_savings_counters():
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 256})
    _train(n_steps=6)
    flat = monitor.flat()
    assert flat.get('comms/plan_arm/quant', 0) > 0
    wire = flat.get('comms/plan_wire_bytes', 0)
    dense_equiv = flat.get('comms/plan_dense_equiv_bytes', 0)
    # ~4x payload reduction for fp32 -> int8+scales
    assert 0 < wire < 0.3 * dense_equiv
    assert flat.get('comms/plan_fused_grads', 0) > 0
    assert flat.get('comms/bytes_on_wire', 0) > 0


def test_predicted_vs_measured_with_model(tmp_path):
    path = _write_model(tmp_path, {
        'allreduce': {'latency_s': 1e-5, 'inv_bw_s_per_byte': 1e-9},
        'reducescatter': {'latency_s': 1e-5,
                          'inv_bw_s_per_byte': 1e-9},
        'allgather': {'latency_s': 1e-5, 'inv_bw_s_per_byte': 1e-9}})
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_model_path': path})
    _train(n_steps=6)
    flat = monitor.flat()
    assert flat.get('comms/plan_predicted_seconds', 0) > 0
    assert flat.get('comms/plan_measured_seconds', 0) > 0


def test_statusz_carries_comms_plan_section():
    from paddle_tpu.fluid import health
    fluid.set_flags({'FLAGS_comms_plan': True})
    _train(n_steps=3)
    doc = health.statusz()
    sec = doc.get('comms_plan')
    assert sec and sec['programs']
    assert sec['digest'].startswith('comms_plan(')
    assert sec['arm_counters']['dense'] > 0


def test_zero_retrace_post_warmup():
    # planner decisions are part of the segment fingerprint: repeated
    # steps after the first must never re-trace (segment cache hits
    # only), with the planner + quant arm active
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 256})
    comms.reset()
    main_p, startup, loss = _build_mlp()
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')
    exe = fluid.Executor(fluid.XLAPlace(0))
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(16, 64).astype('float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=feed, fetch_list=[loss])
        misses0 = monitor.counter_value('parallel/segment_cache_miss')
        for _ in range(5):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        assert monitor.counter_value('parallel/segment_cache_miss') \
            == misses0
        assert monitor.counter_value('parallel/segment_cache_hit') >= 5


def test_digest_steady_across_warmups():
    # the digest is flags + the model file's identity and nothing a
    # warm-up moves: the fingerprints that fold it stay put
    fluid.set_flags({'FLAGS_comms_plan': True})
    main_p, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.XLAPlace(0))
    shapes = {'x': ((16, 64), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        d0 = comms_plan.digest()
        assert 'refit' not in d0
        for _ in range(2):
            exe.warmup(main_p, feed_shapes=shapes,
                       fetch_list=[loss]).wait()
            assert comms_plan.digest() == d0


def test_stat_summary_plan_rollup(tmp_path, capsys):
    import importlib
    import os
    import sys
    fluid.set_flags({'FLAGS_comms_plan': True,
                     'FLAGS_comms_quantize': True,
                     'FLAGS_comms_quantize_min_bytes': 256})
    _train(n_steps=4)
    p = str(tmp_path / 'run.jsonl')
    monitor.dump_jsonl(p)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import stat_summary
    importlib.reload(stat_summary)
    rc = stat_summary.main(['--plan', p])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'arm quant' in out and 'reduction' in out
    # a record with no planner activity reports so
    monitor.reset()
    monitor.dump_jsonl(p)
    assert stat_summary.main(['--plan', p]) == 1


def test_fused_op_identity_without_mesh():
    # outside shard_map (single-device executor) the fused op is the
    # nranks==1 identity, like c_allreduce_sum
    from paddle_tpu.ops import registry
    xs = [np.ones((2, 2), 'float32'), np.arange(3, dtype='float32')]
    out = registry.get('c_allreduce_fused').fn(
        registry.LowerCtx(0), {'X': xs}, {'ring_id': 0, 'plan': True})
    assert len(out['Out']) == 2
    assert np.array_equal(np.asarray(out['Out'][0]), xs[0])
    assert np.array_equal(np.asarray(out['Out'][1]), xs[1])
