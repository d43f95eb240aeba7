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
from paddle_tpu.ops.pallas import common, embedding, fused_optimizer


_PALLAS_FLAGS = [k for k in _DEFAULTS if k.startswith('FLAGS_pallas_')]


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    set_flags({k: _DEFAULTS[k] for k in _PALLAS_FLAGS})
    set_flags({'FLAGS_comms_quantize': _DEFAULTS['FLAGS_comms_quantize'],
               'FLAGS_comms_hbm_budget_bytes':
               _DEFAULTS['FLAGS_comms_hbm_budget_bytes']})


def _force(on=True):
    set_flags({'FLAGS_pallas_force': on})


# ------------------------------------------- fused optimizer updates

def _opt_ins(n_tensors, seed=0, zero_grad_idx=None):
    rng = np.random.RandomState(seed)
    shapes = [(33, 47), (128,), (5, 8, 13), (257,)][:n_tensors]
    ins = {k: [] for k in ('Param', 'Grad', 'Moment1', 'Moment2',
                           'LearningRate', 'Beta1Pow', 'Beta2Pow')}
    for i, s in enumerate(shapes):
        g = rng.randn(*s).astype('float32')
        if zero_grad_idx == i:
            g[:] = 0.0
        ins['Param'].append(jnp.asarray(rng.randn(*s).astype('float32')))
        ins['Grad'].append(jnp.asarray(g))
        ins['Moment1'].append(jnp.asarray(
            (0.0 if zero_grad_idx == i else 1.0) *
            rng.randn(*s).astype('float32')))
        ins['Moment2'].append(jnp.asarray(
            np.abs(rng.randn(*s)).astype('float32') *
            (0.0 if zero_grad_idx == i else 1.0)))
        ins['LearningRate'].append(jnp.asarray(
            np.float32(0.001 * (i + 1))))
        ins['Beta1Pow'].append(jnp.asarray(np.float32(0.9 ** (i + 1))))
        ins['Beta2Pow'].append(jnp.asarray(np.float32(0.999 ** (i + 1))))
    return ins


@pytest.mark.parametrize('kind', ['adam', 'adamw', 'lamb'])
def test_fused_optimizer_parity(kind):
    """Forced-fused (interpret) vs the per-tensor dense lowerings over
    a 4-tensor run with distinct shapes / lrs / beta powers.  The
    compiled kernel body may contract mul+add into FMAs the dense
    op-by-op chain rounds individually — parity is 1-2 ulp."""
    ins = _opt_ins(4, seed=3)
    attrs = {'beta1': 0.9, 'beta2': 0.999}
    _force(True)
    fused = fused_optimizer.apply(kind, registry.LowerCtx(0), ins, attrs)
    _force(False)
    dense = fused_optimizer._dense(kind, registry.LowerCtx(0), ins, attrs)
    for slot in ('ParamOut', 'Moment1Out', 'Moment2Out',
                 'Beta1PowOut', 'Beta2PowOut'):
        assert len(fused[slot]) == len(dense[slot]) == 4
        for a, b in zip(fused[slot], dense[slot]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-6, atol=3e-7,
                err_msg='%s %s' % (kind, slot))


def test_fused_optimizer_dense_dispatch_bitwise():
    """Off-TPU without force the dispatcher picks the dense fallback,
    which IS the per-tensor lowerings — bitwise, not just close."""
    ins = _opt_ins(3, seed=5)
    out = fused_optimizer.apply('adam', registry.LowerCtx(0), ins, {})
    ref = fused_optimizer._dense('adam', registry.LowerCtx(0), ins, {})
    for slot in ref:
        for a, b in zip(out[slot], ref[slot]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert common._LAST['fused_optimizer']['reason'] == 'off_tpu'


def test_lamb_trust_ratio_edge_cases():
    """The in-kernel per-tensor trust ratio: a tensor whose r-norm is
    zero (zero grad/moments/weight-decay) must take the trust=1 branch
    while its run-mates get ||p||/||r|| — per-tensor, not per-run."""
    ins = _opt_ins(3, seed=7, zero_grad_idx=1)
    attrs = {'weight_decay': 0.0}
    _force(True)
    fused = fused_optimizer.apply('lamb', registry.LowerCtx(0), ins,
                                  attrs)
    _force(False)
    dense = fused_optimizer._dense('lamb', registry.LowerCtx(0), ins,
                                   attrs)
    for a, b in zip(fused['ParamOut'], dense['ParamOut']):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=3e-7)
    # the zero-r tensor is untouched (trust branch, zero update)
    assert np.array_equal(np.asarray(fused['ParamOut'][1]),
                          np.asarray(ins['Param'][1]))


def test_fused_optimizer_below_floor_reason():
    set_flags({'FLAGS_pallas_opt_min_tensors': 8})
    _force(True)
    fused_optimizer.apply('adam', registry.LowerCtx(0), _opt_ins(2), {})
    assert common._LAST['fused_optimizer'] == {
        'path': 'dense', 'reason': 'below_floor', 'interpret': False}


def test_auto_partitioned_is_the_callers_word_and_beats_force():
    """dispatch() probes nothing: a lowering hands it
    ctx.auto_partitioned, which LowerCtx takes from the mesh the GSPMD
    runner publishes while it traces (one device: nothing to
    partition).  Dense then, counted, even under force."""
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
        'pallas/fused_optimizer/fallback/auto_partitioned')
    fused_optimizer.apply('adam', ctx, _opt_ins(2), {})
    assert common._LAST['fused_optimizer'] == {
        'path': 'dense', 'reason': 'auto_partitioned', 'interpret': False}
    assert monitor.counter_value(
        'pallas/fused_optimizer/fallback/auto_partitioned') == before + 1


def test_executor_groups_optimizer_run():
    """An Adam program with several params runs the fused op at the
    executor level and matches the ungrouped lowering bitwise (dense
    dispatch) / at tolerance (forced fused)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[8], dtype='float32')
            h = layers.fc(x, 16, act='relu')
            h = layers.fc(h, 16, act='relu')
            pred = layers.fc(h, 4)
            loss = layers.reduce_mean(pred)
            fluid.optimizer.Adam(1e-2).minimize(loss)
        return main, startup, loss

    feed = {'x': np.random.RandomState(0).randn(4, 8).astype('float32')}

    def run(opt_fuse, force):
        set_flags({'FLAGS_pallas_opt_fuse': opt_fuse,
                   'FLAGS_pallas_force': force})
        main, startup, loss = build()
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            out = [exe.run(main, feed=feed, fetch_list=[loss])[0]
                   for _ in range(3)]
        return np.asarray(out[-1])

    base = run(False, False)
    grouped = run(True, False)
    forced = run(True, True)
    assert np.array_equal(base, grouped)
    np.testing.assert_allclose(forced, base, rtol=2e-5, atol=1e-6)
    assert monitor.counter_value(
        'pallas/fused_optimizer/dispatch_fused') > 0
    assert monitor.counter_value(
        'pallas/fused_optimizer/dispatch_dense') > 0


def test_pallas_flag_flip_rekeys_live_executor():
    """Flipping a FLAGS_pallas_* knob on an ALREADY-COMPILED executor
    must re-dispatch (the per-step executable cache keys on the pallas
    flag tuple); flipping back must be a cache hit, not a retrace."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[8], dtype='float32')
        pred = layers.fc(x, 4)
        loss = layers.reduce_mean(pred)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    feed = {'x': np.random.RandomState(3).randn(4, 8).astype('float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        assert common._LAST['fused_optimizer']['path'] == 'dense'
        set_flags({'FLAGS_pallas_force': True})
        exe.run(main, feed=feed, fetch_list=[loss])
        assert common._LAST['fused_optimizer'] == {
            'path': 'fused', 'reason': 'forced_interpret',
            'interpret': True}
        set_flags({'FLAGS_pallas_force': False})
        lowered = monitor.counter_value('executor/segments_lowered')
        exe.run(main, feed=feed, fetch_list=[loss])
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered


# ------------------------------------------ fused embedding kernels

def test_embedding_lookup_parity_bitwise():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(600, 16).astype('float32'))
    ids = jnp.asarray(rng.randint(0, 600, size=(7, 5)).astype('int64'))
    set_flags({'FLAGS_pallas_embedding': True})
    _force(True)
    fused = embedding.embedding_lookup(w, ids, padding_idx=3)
    _force(False)
    dense = embedding._dense_lookup(w, ids, 3)
    assert np.array_equal(np.asarray(fused), np.asarray(dense))


def test_embedding_lookup_grad_collisions_bitwise():
    """Cotangent scatter with heavily repeated ids: sorted runs
    accumulate in-VMEM; result is bitwise the dense .at[].add."""
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(520, 8).astype('float32'))
    ids = jnp.asarray(
        np.array([0, 5, 5, 5, 2, 519, 2, 5, 0, 0], np.int64))

    def loss(fn, w):
        return jnp.sum(fn(w, ids, -1) ** 2)

    _force(True)
    gf = jax.grad(lambda w: loss(embedding.embedding_lookup, w))(w)
    _force(False)
    gd = jax.grad(lambda w: loss(embedding._dense_lookup, w))(w)
    assert np.array_equal(np.asarray(gf), np.asarray(gd))


def test_embedding_update_collisions_and_padding():
    rng = np.random.RandomState(2)
    v, d = 530, 8
    w = jnp.asarray(rng.randn(v, d).astype('float32'))
    mom = jnp.asarray(np.abs(rng.randn(v, d)).astype('float32'))
    ids = jnp.asarray(
        np.array([7, 7, 7, 1, 0, 529, 1, 7], np.int64))
    g = jnp.asarray(rng.randn(8, d).astype('float32'))
    ins = {'Param': [w], 'Moment': [mom], 'Ids': [ids], 'Grad': [g],
           'LearningRate': [jnp.asarray(np.float32(0.1))]}
    attrs = {'epsilon': 1e-6, 'padding_idx': 1}
    set_flags({'FLAGS_pallas_embedding': True})
    _force(True)
    fused = embedding.apply_update(registry.LowerCtx(0), ins, attrs)
    _force(False)
    dense = embedding.apply_update(registry.LowerCtx(0), ins, attrs)
    for slot in ('ParamOut', 'MomentOut'):
        np.testing.assert_allclose(
            np.asarray(fused[slot][0]), np.asarray(dense[slot][0]),
            rtol=2e-6, atol=2e-6, err_msg=slot)
    # padding rows and untouched rows are bit-identical to the input
    for row in (1, 2, 100):
        assert np.array_equal(np.asarray(fused['ParamOut'][0][row]),
                              np.asarray(w[row]))


def test_adagrad_embedding_rewrite_end_to_end():
    """Embedding + Adagrad: the graph rewrite replaces the dense
    lookup_table_v2_grad scatter + full-table adagrad pair with one
    fused_emb_update op, and training matches the unrewritten program
    bitwise under dense dispatch."""
    def build(rewrite):
        set_flags({'FLAGS_pallas_embedding': rewrite})
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 13
        with fluid.program_guard(main, startup):
            ids = layers.data('ids', shape=[1], dtype='int64')
            emb = layers.embedding(ids, size=[600, 16])
            pred = layers.fc(emb, 4)
            loss = layers.reduce_mean(pred)
            fluid.optimizer.Adagrad(0.05).minimize(loss)
        return main, startup, loss

    main, _, _ = build(True)
    types = [op.type for op in main.global_block().ops]
    assert 'fused_emb_update' in types
    assert 'lookup_table_v2_grad' not in types
    main, _, _ = build(False)
    types = [op.type for op in main.global_block().ops]
    assert 'fused_emb_update' not in types

    feed = {'ids': np.random.RandomState(3).randint(
        0, 600, size=(6, 1)).astype('int64')}

    def run(rewrite, force):
        main, startup, loss = build(rewrite)
        set_flags({'FLAGS_pallas_force': force})
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            return np.asarray(
                [exe.run(main, feed=feed, fetch_list=[loss])[0]
                 for _ in range(4)])

    base = run(False, False)
    rewritten = run(True, False)
    forced = run(True, True)
    assert np.array_equal(base, rewritten)
    np.testing.assert_allclose(forced, base, rtol=2e-5, atol=1e-6)


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
    for name in ('flash_attention', 'fused_optimizer',
                 'embedding_lookup', 'embedding_update',
                 'quant_collective'):
        assert name in ks, name
        assert ks[name]['dense_fallback'], name


def test_dispatch_reasons_and_statusz():
    set_flags({'FLAGS_pallas_opt_fuse': False})
    fused_optimizer.apply('adam', registry.LowerCtx(0), _opt_ins(2), {})
    assert common._LAST['fused_optimizer']['reason'] == 'flag_off'
    assert monitor.counter_value(
        'pallas/fused_optimizer/fallback/flag_off') > 0
    from paddle_tpu.fluid import health
    rep = health.statusz()['pallas']
    assert rep and 'fused_optimizer' in rep['kernels']
    k = rep['kernels']['fused_optimizer']
    assert k['last']['reason'] == 'flag_off'
    assert k['dense_fallback']


# --------------------------------------------------- progcheck pass

def test_progcheck_programs_with_fused_ops():
    """The static verifier walks programs containing each fused op
    (shape inference runs the real lowerings via eval_shape)."""
    # fused_emb_update via the Adagrad rewrite
    set_flags({'FLAGS_pallas_embedding': True})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        ids = layers.data('ids', shape=[1], dtype='int64')
        emb = layers.embedding(ids, size=[600, 16])
        loss = layers.reduce_mean(layers.fc(emb, 4))
        fluid.optimizer.Adagrad(0.05).minimize(loss)
    assert 'fused_emb_update' in [op.type for op in
                                  main.global_block().ops]
    rep = progcheck.verify_program(
        main, feed_names=('ids',), fetch_names=(loss.name,),
        startup_program=startup, level='full', raise_on_error=False)
    assert rep.ok(), rep.format()

    # fused_adam / fused_adamw / fused_lamb as explicit graph ops
    for fused_type in ('fused_adam', 'fused_adamw', 'fused_lamb'):
        main = fluid.Program()
        blk = main.global_block()
        names = {}
        for slot, shape in (('p0', (8, 8)), ('g0', (8, 8)),
                            ('m10', (8, 8)), ('m20', (8, 8)),
                            ('p1', (16,)), ('g1', (16,)),
                            ('m11', (16,)), ('m21', (16,))):
            names[slot] = blk.create_var(
                name=slot, shape=list(shape), dtype='float32',
                persistable=True)
        for slot in ('lr', 'b1p0', 'b2p0', 'b1p1', 'b2p1'):
            names[slot] = blk.create_var(
                name=slot, shape=[1], dtype='float32', persistable=True)
        blk.append_op(
            type=fused_type,
            inputs={'Param': [names['p0'], names['p1']],
                    'Grad': [names['g0'], names['g1']],
                    'Moment1': [names['m10'], names['m11']],
                    'Moment2': [names['m20'], names['m21']],
                    'LearningRate': [names['lr'], names['lr']],
                    'Beta1Pow': [names['b1p0'], names['b1p1']],
                    'Beta2Pow': [names['b2p0'], names['b2p1']]},
            outputs={'ParamOut': [names['p0'], names['p1']],
                     'Moment1Out': [names['m10'], names['m11']],
                     'Moment2Out': [names['m20'], names['m21']],
                     'Beta1PowOut': [names['b1p0'], names['b1p1']],
                     'Beta2PowOut': [names['b2p0'], names['b2p1']]},
            attrs={'beta1': 0.9, 'beta2': 0.999},
            infer_shape=False)
        rep = progcheck.verify_program(main, level='full',
                                       raise_on_error=False)
        assert rep.ok(), '%s: %s' % (fused_type, rep.format())
