"""SP/EP as first-class fluid citizens (round-4 VERDICT item 1):
ring attention and MoE reachable from the Program IR via
layers.context_parallel_attention / layers.moe, compiled through
CompiledProgram.with_mesh onto 'sp'/'ep' axes the way 'dp'/'mp' work —
parity-tested against the parallel/ library path and the dense math,
plus the 3D dp x pp x mp composition from ONE fluid Program
(program_pipeline.build_train_step data_axis/param_specs)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.parallel import mesh as pmesh

B, T, H, D, E, FF = 4, 16, 4, 8, 4, 32
DIM = H * D


def _build_block(seed=5):
    """Transformer-ish block: qkv fc -> context-parallel causal
    attention -> proj -> residual -> MoE FFN -> residual -> mse+aux."""
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
        att = layers.context_parallel_attention(q, k, v, causal=True)
        att = layers.reshape(att, [-1, T, DIM])
        proj = layers.fc(att, size=DIM, num_flatten_dims=2,
                         bias_attr=False)
        h1 = layers.elementwise_add(x, proj)
        mo, aux = layers.moe(h1, num_experts=E, hidden_size=FF,
                             aux_weight=0.01)
        out = layers.elementwise_add(h1, mo)
        mse = layers.reduce_mean(
            layers.square(layers.elementwise_sub(out, y)))
        loss = layers.elementwise_add(mse, aux)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _run_losses(program, startup, loss, feed, steps, compiled=None):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        target = compiled if compiled is not None else program
        out = []
        for _ in range(steps):
            l, = exe.run(target, feed=feed, fetch_list=[loss])
            out.append(float(np.asarray(l).ravel()[0]))
    return out


def test_ring_attention_op_matches_library_and_dense():
    """The fluid op on an 'sp' mesh == parallel.ring_attention ==
    dense reference, same inputs."""
    from paddle_tpu.parallel.ring_attention import (
        ring_attention, reference_attention)
    rng = np.random.RandomState(3)
    q = rng.randn(B, T, H, D).astype('float32')
    k = rng.randn(B, T, H, D).astype('float32')
    v = rng.randn(B, T, H, D).astype('float32')

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv = layers.data('q', shape=[T, H, D], dtype='float32')
        kv = layers.data('k', shape=[T, H, D], dtype='float32')
        vv = layers.data('v', shape=[T, H, D], dtype='float32')
        out = layers.context_parallel_attention(qv, kv, vv, causal=True)

    feed = {'q': q, 'k': k, 'v': v}
    # single device: dense fallback
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        single, = exe.run(main, feed=feed, fetch_list=[out])
    # sp mesh through the SAME program
    mesh = pmesh.create_mesh(dp=2, sp=4)
    comp = fluid.CompiledProgram(main).with_data_parallel().with_mesh(
        mesh)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        sharded, = exe.run(comp, feed=feed, fetch_list=[out])
    # library path on the same mesh
    lib = np.asarray(ring_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), mesh, axis='sp',
                                    causal=True))
    dense = np.asarray(reference_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    np.testing.assert_allclose(single, dense, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sharded, lib, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sharded, dense, rtol=2e-4, atol=2e-5)


def test_moe_op_sharded_matches_library_path():
    """The fluid moe op under an ep mesh == moe_ffn_inner shard_mapped
    with the SAME token layout (dp x (sp,ep) token sharding)."""
    from paddle_tpu.parallel.moe import moe_ffn_inner
    rng = np.random.RandomState(4)
    x = rng.randn(B, T, DIM).astype('float32')
    wg = rng.randn(DIM, E).astype('float32') * 0.1
    w1 = rng.randn(E, DIM, FF).astype('float32') * 0.1
    w2 = rng.randn(E, FF, DIM).astype('float32') * 0.1

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data('x', shape=[T, DIM], dtype='float32')
        mo, aux = layers.moe(xv, num_experts=E, hidden_size=FF,
                             aux_weight=1.0)
    wg_n, w1_n, w2_n = [p.name for p in main.all_parameters()]

    mesh = pmesh.create_mesh(dp=2, sp=2, ep=2)
    comp = fluid.CompiledProgram(main).with_data_parallel().with_mesh(
        mesh)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        sc.set_var(wg_n, wg)
        sc.set_var(w1_n, w1)
        sc.set_var(w2_n, w2)
        got, gaux = exe.run(comp, feed={'x': x}, fetch_list=[mo, aux])

    # library path: same token layout the op uses
    b_loc, t_loc = B // 2, T // (2 * 2)

    def inner(xl, wg_, w1_, w2_):
        o, a = moe_ffn_inner(xl.reshape(b_loc * t_loc, DIM), wg_, w1_,
                             w2_, 'ep', 2.0)
        for ax in mesh.axis_names:
            a = jax.lax.pmean(a, ax)
        return o.reshape(b_loc, t_loc, DIM), a

    from paddle_tpu.compat import shard_map
    f = shard_map(
        inner, mesh=mesh,
        in_specs=(P('dp', ('sp', 'ep'), None), P(), P('ep'), P('ep')),
        out_specs=(P('dp', ('sp', 'ep'), None), P()))
    lib, laux = f(jnp.asarray(x), jnp.asarray(wg), jnp.asarray(w1),
                  jnp.asarray(w2))
    np.testing.assert_allclose(got, np.asarray(lib), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(float(np.asarray(gaux).ravel()[0]),
                               float(laux), rtol=2e-4)


def test_block_trains_same_single_vs_spep_mesh():
    """Same program + same seeds: single-device dense fallbacks and the
    dp2 x sp2 x ep2 sharded path learn the same loss curve (tokens per
    shard match, so capacity semantics agree)."""
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    main, startup, loss = _build_block()
    single = _run_losses(main, startup, loss, feed, 4)
    assert single[-1] < single[0]

    mesh = pmesh.create_mesh(dp=2, sp=2, ep=2)
    main2, startup2, loss2 = _build_block()
    comp = fluid.CompiledProgram(main2).with_data_parallel(
        loss_name=loss2.name).with_mesh(mesh)
    sharded = _run_losses(main2, startup2, loss2, feed, 4,
                          compiled=comp)
    np.testing.assert_allclose(sharded, single, rtol=5e-3, atol=5e-4)


def test_moe_expert_weights_actually_shard_over_ep():
    """The layer-stamped hints must land: after a mesh step, the
    expert weights live sharded over 'ep' (not replicated)."""
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    mesh = pmesh.create_mesh(dp=2, sp=2, ep=2)
    main, startup, loss = _build_block()
    w1_n = next(p.name for p in main.all_parameters()
                if tuple(p.shape) == (E, DIM, FF))
    comp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name).with_mesh(mesh)
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(comp, feed=feed, fetch_list=[loss])
        w1 = sc.find_var(w1_n)  # jax.Array after the mesh step
        spec = w1.sharding.spec
    assert spec[0] == 'ep', spec


def test_3d_dp_pp_mp_through_fluid_program():
    """dp2 x pp2 x mp2 from ONE fluid Program: two Megatron stages
    (column-parallel fc + row-parallel fc + c_allreduce_sum over 'mp')
    cut into a GPipe pipeline, batch sharded over 'dp' — with a numpy
    oracle for the first loss."""
    from paddle_tpu.parallel.program_pipeline import build_train_step
    d, ff, b = 16, 32, 8
    rng = np.random.RandomState(13)
    x_np = rng.randn(b, d).astype('float32')
    y_np = rng.randn(b, d).astype('float32')

    mesh = pmesh.create_mesh(dp=2, mp=2, pp=2)
    pmesh.set_global_mesh(mesh)  # ring 1 -> 'mp'
    mp_ring = list(mesh.axis_names).index('mp')

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 21
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[d], dtype='float32')
        cuts = []
        h = x
        for s in range(2):
            col = layers.fc(h, size=ff, act='tanh', bias_attr=False)
            row = layers.fc(col, size=d, bias_attr=False)
            blk = main.current_block()
            red = blk.create_var(
                name='stage%d_out' % s, dtype='float32',
                shape=(-1, d), stop_gradient=False)
            blk.append_op('c_allreduce_sum', inputs={'X': row},
                          outputs={'Out': red},
                          attrs={'ring_id': mp_ring})
            h = red
            if s == 0:
                cuts.append(red.name)
        out_name = h.name

    pnames = [p.name for p in main.all_parameters()]
    param_specs = {}
    for n in pnames:
        shp = tuple(main.global_block().var(n).shape)
        param_specs[n] = P(None, 'mp') if shp == (d, ff) \
            else P('mp', None)

    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        step, params = build_train_step(
            main, sc, 'x', cuts, out_name,
            lambda o, yy: jnp.mean((o - yy) ** 2), mesh,
            n_microbatches=4, learning_rate=0.2,
            data_axis='dp', param_specs=param_specs)
        ws = {n: np.asarray(fluid.core.as_array(sc.find_var(n)))
              for n in pnames}

    # numpy oracle: allreduce makes each stage tanh(x@W1)@W2 exactly
    # (all_parameters preserves creation order: w1_s0, w2_s0, w1_s1, ...)
    w1s = [n for n in pnames if ws[n].shape == (d, ff)]
    w2s = [n for n in pnames if ws[n].shape == (ff, d)]
    ref = x_np
    for s in range(2):
        ref = np.tanh(ref @ ws[w1s[s]]) @ ws[w2s[s]]
    ref_loss = float(np.mean((ref - y_np) ** 2))

    loss, params = step(params, x_np, y_np)
    loss2, _ = step(params, x_np, y_np)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-4)
    assert float(loss2) < float(loss)


def _build_attn_dropout(seed=9, rate=0.3, use_flash=False):
    """Attention-only program with IN-RING attention-prob dropout
    (round 5): mask drawn at GLOBAL positions so sharded and dense
    paths agree bit-for-bit."""
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
        att = layers.context_parallel_attention(
            q, k, v, causal=True, use_flash=use_flash,
            dropout_rate=rate)
        att = layers.reshape(att, [-1, T, DIM])
        loss = layers.reduce_mean(
            layers.square(layers.elementwise_sub(att, y)))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_ring_attention_dropout_sharded_matches_dense():
    """Round 5: attention-prob dropout under context parallelism —
    the global-position counter-hash mask makes the sp-sharded ring
    and the single-device dense fallback IDENTICAL stochastic
    functions; training losses must match across the mesh boundary."""
    rng = np.random.RandomState(3)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    main, startup, loss = _build_attn_dropout()
    single = _run_losses(main, startup, loss, feed, 4)

    mesh = pmesh.create_mesh(dp=2, sp=4)
    main2, startup2, loss2 = _build_attn_dropout()
    comp = fluid.CompiledProgram(main2).with_data_parallel(
        loss_name=loss2.name).with_mesh(mesh)
    sharded = _run_losses(main2, startup2, loss2, feed, 4,
                          compiled=comp)
    np.testing.assert_allclose(sharded, single, rtol=5e-3, atol=5e-4)


@pytest.mark.usefixtures('pallas_interpret')
def test_ring_flash_attention_dropout_sharded_matches_dense():
    """Same contract with the Pallas flash per-block engine (interpret
    mode on CPU): dropout offsets ride the packed seed operand into
    the kernels."""
    rng = np.random.RandomState(4)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    main, startup, loss = _build_attn_dropout(use_flash=True)
    single = _run_losses(main, startup, loss, feed, 3)

    mesh = pmesh.create_mesh(sp=2)
    main2, startup2, loss2 = _build_attn_dropout(use_flash=True)
    comp = fluid.CompiledProgram(main2).with_data_parallel(
        loss_name=loss2.name).with_mesh(mesh)
    sharded = _run_losses(main2, startup2, loss2, feed, 3,
                          compiled=comp)
    np.testing.assert_allclose(sharded, single, rtol=5e-3, atol=5e-4)


def test_cp_attention_dropout_eval_clone_is_deterministic():
    """for_test clones drop the stochastic mask (prefer_test lowering
    skips dropout): two eval runs produce identical losses."""
    main, startup, loss = _build_attn_dropout(rate=0.5)
    test_prog = main.clone(for_test=True)
    rng = np.random.RandomState(5)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        a, = exe.run(test_prog, feed=feed, fetch_list=[loss])
        b, = exe.run(test_prog, feed=feed, fetch_list=[loss])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_top2_gating_properties():
    """GShard top-2 (round 5): combine weights of an uncapped token
    sum to 1 over its two routes (renormalized pair); under capacity
    pressure second choices drop FIRST; top_k=1 path unchanged."""
    import jax.numpy as jnp
    from paddle_tpu.parallel.moe import topk_gating

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 6).astype('float32'))
    wg = jnp.asarray(rng.randn(6, 4).astype('float32'))

    # generous capacity: nothing drops; each token's combine mass == 1
    d, c, aux = topk_gating(x, wg, 4, capacity=16, top_k=2)
    np.testing.assert_allclose(np.asarray(c.sum(axis=(1, 2))),
                               np.ones(8), rtol=1e-5)
    # each token occupies exactly two dispatch slots
    np.testing.assert_allclose(np.asarray(d.sum(axis=(1, 2))),
                               2 * np.ones(8), rtol=1e-6)
    # tight capacity: total kept slots per expert <= capacity, and the
    # kept mass never exceeds the uncapped mass
    d2, c2, _ = topk_gating(x, wg, 4, capacity=1, top_k=2)
    per_expert = np.asarray(d2.sum(axis=(0, 2)))
    assert (per_expert <= 1 + 1e-6).all(), per_expert
    assert float(c2.sum()) <= float(c.sum()) + 1e-6
    # top_k=1 equals the legacy top1_gating exactly
    from paddle_tpu.parallel.moe import top1_gating
    d1a, c1a, aux1a = topk_gating(x, wg, 4, capacity=4, top_k=1)
    d1b, c1b, aux1b = top1_gating(x, wg, 4, capacity=4)
    np.testing.assert_array_equal(np.asarray(d1a), np.asarray(d1b))
    np.testing.assert_array_equal(np.asarray(c1a), np.asarray(c1b))


def test_moe_top2_sharded_matches_dense():
    """top_k=2 through the fluid op: ep-sharded all_to_all routing ==
    dense fallback at shard-divisible shapes (the top-1 parity
    contract extended to GShard routing)."""
    def build(seed=21):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[T, DIM], dtype='float32')
            y = layers.data('y', shape=[T, DIM], dtype='float32')
            mo, aux = layers.moe(x, num_experts=E, hidden_size=FF,
                                 aux_weight=0.01, top_k=2)
            out = layers.elementwise_add(x, mo)
            mse = layers.reduce_mean(
                layers.square(layers.elementwise_sub(out, y)))
            loss = layers.elementwise_add(mse, aux)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(6)
    feed = {'x': rng.randn(B, T, DIM).astype('float32'),
            'y': rng.randn(B, T, DIM).astype('float32')}
    main, startup, loss = build()
    single = _run_losses(main, startup, loss, feed, 3)

    mesh = pmesh.create_mesh(dp=4, ep=2)
    m2, s2, loss2 = build()
    comp = fluid.CompiledProgram(m2).with_data_parallel(
        loss_name=loss2.name).with_mesh(mesh)
    sharded = _run_losses(m2, s2, loss2, feed, 3, compiled=comp)
    np.testing.assert_allclose(sharded, single, rtol=5e-3, atol=5e-4)
