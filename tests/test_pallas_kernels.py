"""Pallas kernel library: parity vs dense references (interpret mode
on CPU), dispatch observability, the comms_plan fused-quant pricing,
and the trace-level rewrites that route existing Programs through the
fused ops with no user change."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor, progcheck
from paddle_tpu.fluid.flags import _DEFAULTS, set_flags
from paddle_tpu.ops import registry
from paddle_tpu.ops.pallas import common, flash_attention, quant_collective


_PALLAS_FLAGS = [k for k in _DEFAULTS if k.startswith('FLAGS_pallas_')]


@pytest.fixture(autouse=True)
def _reset_flags():
    # outside a program scope comms_plan.hbm_headroom_bytes() falls
    # back to the job-wide executor/segment_peak_bytes gauge, which
    # earlier files on the same xdist worker leave set: a 1.5 MiB
    # budget then has no headroom left before a test has run anything
    from paddle_tpu.fluid import memviz, monitor
    monitor.remove_gauge('executor/segment_peak_bytes')
    memviz.reset()
    yield
    set_flags({k: _DEFAULTS[k] for k in _PALLAS_FLAGS})
    set_flags({'FLAGS_comms_quantize': _DEFAULTS['FLAGS_comms_quantize'],
               'FLAGS_comms_hbm_budget_bytes':
               _DEFAULTS['FLAGS_comms_hbm_budget_bytes']})


def _force(on=True):
    set_flags({'FLAGS_pallas_force': on})


# ------------------------------------------------ dispatch contract

def _qkv(t=8):
    rng = np.random.RandomState(0)
    return [jnp.asarray(rng.randn(1, t, 1, 8).astype('float32'))
            for _ in range(3)]


def test_auto_partitioned_is_the_callers_word_and_beats_force():
    """dispatch() probes nothing: a caller hands it
    ctx.auto_partitioned, which LowerCtx takes from the mesh the GSPMD
    runner publishes while it traces (one device: nothing to
    partition).  A bare flash_attention() call that says so wraps
    nothing: dense then, counted, even under force (the op lowerings
    go through mesh_flash_attention, which opens a shard_map:
    test_flash_attention.py)."""
    from jax.sharding import Mesh
    from paddle_tpu.parallel import mesh as pmesh
    assert not registry.LowerCtx(0).auto_partitioned
    with pmesh.use_trace_mesh(Mesh(np.array(jax.devices()[:1]), ('dp',))):
        assert not registry.LowerCtx(0).auto_partitioned
    _force(True)
    with pmesh.use_trace_mesh(Mesh(np.array(jax.devices()[:2]), ('dp',))):
        ctx = registry.LowerCtx(0)
    assert ctx.auto_partitioned
    before = monitor.counter_value(
        'pallas/flash_attention/fallback/auto_partitioned')
    flash_attention.flash_attention(
        *_qkv(), min_seq=0, auto_partitioned=ctx.auto_partitioned)
    assert common._LAST['flash_attention'] == {
        'path': 'dense', 'reason': 'auto_partitioned', 'interpret': False}
    assert monitor.counter_value(
        'pallas/flash_attention/fallback/auto_partitioned') == before + 1


def test_pallas_flag_flip_rekeys_live_executor():
    """Flipping a FLAGS_pallas_* knob on an ALREADY-COMPILED executor
    must re-dispatch (the per-step executable cache keys on the pallas
    flag tuple); flipping back must be a cache hit, not a retrace."""
    from paddle_tpu.fluid.layer_helper import LayerHelper
    seq = flash_attention.FLASH_MIN_SEQ
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q, k, v = (layers.data(n, shape=[seq, 1, 8], dtype='float32')
                   for n in 'qkv')
        helper = LayerHelper('fused_multihead_attention')
        out = helper.create_variable_for_type_inference('float32')
        helper.append_op('fused_multihead_attention',
                         inputs={'Q': q, 'K': k, 'V': v},
                         outputs={'Out': out}, attrs={})
        loss = layers.reduce_mean(out)
    feed = dict(zip('qkv', (np.asarray(x) for x in _qkv(seq))))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(main, feed=feed, fetch_list=[loss])
        assert common._LAST['flash_attention']['path'] == 'dense'
        set_flags({'FLAGS_pallas_force': True})
        exe.run(main, feed=feed, fetch_list=[loss])
        assert common._LAST['flash_attention'] == {
            'path': 'fused', 'reason': 'forced_interpret',
            'interpret': True}
        set_flags({'FLAGS_pallas_force': False})
        lowered = monitor.counter_value('executor/segments_lowered')
        exe.run(main, feed=feed, fetch_list=[loss])
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered


# --------------------------------------- fused quantized collective

def test_quant_collective_parity_bitwise():
    """Fused quantize / dequant-reduce-requant vs the dense arm over a
    real 8-way mesh (padding exercised by the un-aligned size)."""
    if len(jax.devices()) < 8:
        pytest.skip('needs 8 devices')
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.compat import shard_map
    from paddle_tpu.ops import collective_ops
    mesh = Mesh(np.array(jax.devices()[:8]), ('dp',))
    x = np.random.RandomState(0).randn(8, 1000).astype('float32')
    x[:, 100:150] = 0.0      # all-zero blocks hit the s>0 guard

    def run(force):
        set_flags({'FLAGS_pallas_force': force,
                   'FLAGS_pallas_quant_collective': True})
        return np.asarray(jax.jit(shard_map(
            lambda v: collective_ops._quant_allreduce(v, 'dp', 8, 256),
            mesh=mesh, in_specs=P('dp'), out_specs=P('dp')))(x))

    dense = run(False)
    fused = run(True)
    assert np.array_equal(dense, fused)


def test_quantize_blocks_bitwise():
    from paddle_tpu.ops.pallas import quant_collective as qc
    flat = np.random.RandomState(0).randn(32, 256).astype('float32')
    flat[3] = 0.0
    qv, s = qc.quantize_blocks(jnp.asarray(flat), True)

    def q(v):
        s = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return (jnp.clip(jnp.rint(v / s), -127, 127).astype(jnp.int8),
                s.astype(jnp.float32))

    qref, sref = jax.jit(q)(jnp.asarray(flat))
    assert np.array_equal(np.asarray(qv), np.asarray(qref))
    assert np.array_equal(np.asarray(s), np.asarray(sref))


def test_comms_plan_fused_quant_admissibility():
    """The acceptance budget: 1.5x payload of headroom.  The legacy
    2.25x temporary estimate rejects the quant arm; the fused-kernel
    0.75x term admits it — and the digest carries the bit so the flip
    retraces exactly once."""
    from paddle_tpu.fluid import comms_plan
    payload = 1 << 20
    set_flags({'FLAGS_comms_quantize': True,
               'FLAGS_comms_hbm_budget_bytes': int(1.5 * (1 << 20)),
               'FLAGS_pallas_quant_collective': True,
               'FLAGS_pallas_force': False})
    assert not comms_plan._fused_quant_available()
    assert comms_plan.quant_hbm_temp(payload) == 2.25 * payload
    rejected = comms_plan.decide(payload, 4, 8)
    assert rejected['arm'] == 'dense'
    d0 = comms_plan.digest()
    assert 'qfuse=0' in d0
    set_flags({'FLAGS_pallas_force': True})
    assert comms_plan._fused_quant_available()
    assert comms_plan.quant_hbm_temp(payload) == 0.75 * payload
    admitted = comms_plan.decide(payload, 4, 8)
    assert admitted['arm'] == 'quant'
    d1 = comms_plan.digest()
    assert 'qfuse=1' in d1 and d0 != d1
    # the flag also kills availability regardless of platform
    set_flags({'FLAGS_pallas_quant_collective': False})
    assert not comms_plan._fused_quant_available()


# -------------------------------- dispatch observability / registry

def test_kernel_registry_contract():
    ks = common.kernels()
    assert set(ks) == {'flash_attention', 'grouped_matmul', 'kda_chunk',
                       'kda_walk', 'quant_collective', 'sinkhorn',
                       'ssd_scan', 'ssm_scan'}
    for name in ks:
        assert ks[name]['dense_fallback'], name


def test_dispatch_reasons_and_statusz():
    set_flags({'FLAGS_pallas_quant_collective': False})
    assert quant_collective.dispatch() == (False, False)
    assert common._LAST['quant_collective']['reason'] == 'flag_off'
    assert monitor.counter_value(
        'pallas/quant_collective/fallback/flag_off') > 0
    from paddle_tpu.fluid import health
    rep = health.statusz()['pallas']
    assert rep and 'quant_collective' in rep['kernels']
    k = rep['kernels']['quant_collective']
    assert k['last']['reason'] == 'flag_off'
    assert k['dense_fallback']


# --------------------------------------------------- progcheck pass

def test_progcheck_programs_with_fused_ops():
    """The static verifier walks a program containing the fused op
    (shape inference runs the real lowering via eval_shape)."""
    from paddle_tpu import models
    seq = flash_attention.FLASH_MIN_SEQ
    cfg = models.bert.BertConfig(
        vocab_size=64, hidden=32, layers=1, heads=2, intermediate=32,
        max_pos=seq, dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        feeds, _, loss = models.bert.build_pretrain(cfg, seq)
        fluid.optimizer.Adagrad(0.05).minimize(loss)
    assert 'fused_multihead_attention' in [
        op.type for op in main.global_block().ops]
    rep = progcheck.verify_program(
        main, feed_names=tuple(feeds), fetch_names=(loss.name,),
        startup_program=startup, level='full', raise_on_error=False)
    assert rep.ok(), rep.format()
