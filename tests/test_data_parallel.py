"""Data-parallel loss parity: multi-device vs single-device.

Mirrors the reference fixture parallel_executor_test_base.py (compare
ParallelExecutor losses against single-device Executor on the same seed)
and test_dist_base.py:510 (distributed vs local loss parity) — here on the
8-device CPU mesh.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid


def build_model(seed):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        y = fluid.layers.data('y', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, 32, act='relu')
        h2 = fluid.layers.fc(h, 16, act='relu')
        logits = fluid.layers.fc(h2, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
    return main, startup, loss


def make_batches(steps=6, n=16):
    rng = np.random.RandomState(5)
    out = []
    for _ in range(steps):
        x = rng.randn(n, 8).astype('float32')
        y = (np.abs(x).sum(1, keepdims=True) * 2
             ).astype('int64') % 4
        out.append((x, y))
    return out


def train(program_runner, main, startup, loss, batches, opt):
    with fluid.program_guard(main, startup):
        opt.minimize(loss)
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for x, y in batches:
            l, = program_runner(exe, main,
                                {'x': x, 'y': y}, [loss])
            losses.append(float(l))
        pname = main.all_parameters()[0].name
        final_param = np.asarray(scope.find_var(pname))
    return losses, final_param


def _single(exe, main, feed, fetch):
    return exe.run(main, feed=feed, fetch_list=fetch)


def test_gspmd_data_parallel_loss_parity():
    batches = make_batches()
    m1, s1, l1 = build_model(3)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.SGD(0.1))

    m2, s2, l2 = build_model(3)

    compiled_box = {}

    def _parallel(exe, main, feed, fetch):
        if 'cp' not in compiled_box:
            compiled_box['cp'] = fluid.CompiledProgram(
                main).with_data_parallel(loss_name=l2.name)
        return exe.run(compiled_box['cp'], feed=feed, fetch_list=fetch)

    par, par_p = train(_parallel, m2, s2, l2, batches,
                       fluid.optimizer.SGD(0.1))
    np.testing.assert_allclose(ref, par, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_p, par_p, rtol=1e-4, atol=1e-5)
    assert par[-1] < par[0]


@pytest.mark.parametrize('shape', [[16, 24], [6, 40], [5]])
def test_dropout_mask_of_a_sharded_batch_is_the_one_device_mask(shape):
    """The dropout op's draw is a hash of the element's GLOBAL
    position (iotas, which GSPMD shards with the tensor): the mask of
    a batch split over `dp` is the one-device mask of the same batch,
    and the grad op, which the parallel runner lowers apart from the
    op, replays the same bits."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=shape, dtype='float32')
        x.stop_gradient = False
        out = fluid.layers.dropout(
            x, 0.3, dropout_implementation='upscale_in_train')
        loss = fluid.layers.reduce_sum(out)
        fluid.backward.append_backward(loss)
    dx = main._grad_name_map[x.name]
    feed = {'x': np.ones([16] + shape, 'float32')}

    def masks(target):
        kept = []
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            for _ in range(2):
                o, g = exe.run(target, feed=feed, fetch_list=[out, dx])
                np.testing.assert_array_equal(np.asarray(g),
                                              np.asarray(o))
                kept.append(np.asarray(o) != 0)
        return kept

    single = masks(main)
    sharded = masks(fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name))
    assert single[0].shape == tuple([16] + shape)
    assert abs(single[0].mean() - 0.7) < \
        4 * np.sqrt(0.21 / single[0].size)
    assert (single[0] != single[1]).any()
    for a, b in zip(single, sharded):
        np.testing.assert_array_equal(a, b)


def test_fleet_collective_loss_parity():
    from paddle_tpu.fluid.incubate.fleet.collective import fleet, \
        DistributedStrategy
    from paddle_tpu.fluid.incubate.fleet.base import role_maker

    batches = make_batches()
    m1, s1, l1 = build_model(9)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.SGD(0.1))

    m2, s2, l2 = build_model(9)
    fleet.init(role_maker.PaddleCloudRoleMaker())
    with fluid.program_guard(m2, s2):
        opt = fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1), DistributedStrategy())
        opt.minimize(l2)
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(s2)
        for x, y in batches:
            l, = exe.run(m2, feed={'x': x, 'y': y}, fetch_list=[l2])
            losses.append(float(l))
        pname = m2.all_parameters()[0].name
        col_p = np.asarray(scope.find_var(pname))
    # collective mode fetches a device-local loss (2-sample shard, not the
    # global mean) — matching the reference, which fetches trainer-0's
    # loss.  The real invariant is identical parameter updates:
    # allreduced mean grads == single-device full-batch grads.
    np.testing.assert_allclose(ref_p, col_p, rtol=1e-4, atol=1e-5)


def test_fleet_local_sgd_single_process_parity():
    """In-graph LocalSGD (single-process multi-device): local SGD step
    then param averaging == gradient allreduce for SGD (the update is
    linear in the grad), so it must match single-device full batch."""
    from paddle_tpu.fluid.incubate.fleet.collective import fleet, \
        DistributedStrategy
    from paddle_tpu.fluid.incubate.fleet.base import role_maker

    batches = make_batches()
    m1, s1, l1 = build_model(21)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.SGD(0.1))

    m2, s2, l2 = build_model(21)
    fleet.init(role_maker.PaddleCloudRoleMaker())
    strategy = DistributedStrategy()
    strategy.use_local_sgd = True
    with fluid.program_guard(m2, s2):
        opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1),
                                          strategy)
        opt.minimize(l2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(s2)
        for x, y in batches:
            exe.run(m2, feed={'x': x, 'y': y}, fetch_list=[l2])
        pname = m2.all_parameters()[0].name
        lsgd_p = np.asarray(scope.find_var(pname))
    np.testing.assert_allclose(ref_p, lsgd_p, rtol=1e-4, atol=1e-5)


def test_fleet_local_sgd_momentum_parity():
    """Stateful optimizer under in-graph LocalSGD: velocity accumulators
    are averaged alongside params (both are linear in the grad, so this
    equals synchronous momentum = single-device full batch)."""
    from paddle_tpu.fluid.incubate.fleet.collective import fleet, \
        DistributedStrategy
    from paddle_tpu.fluid.incubate.fleet.base import role_maker

    batches = make_batches()
    m1, s1, l1 = build_model(23)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.Momentum(0.1, momentum=0.9))

    m2, s2, l2 = build_model(23)
    fleet.init(role_maker.PaddleCloudRoleMaker())
    strategy = DistributedStrategy()
    strategy.use_local_sgd = True
    with fluid.program_guard(m2, s2):
        opt = fleet.distributed_optimizer(
            fluid.optimizer.Momentum(0.1, momentum=0.9), strategy)
        opt.minimize(l2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(s2)
        for x, y in batches:
            exe.run(m2, feed={'x': x, 'y': y}, fetch_list=[l2])
        pname = m2.all_parameters()[0].name
        lsgd_p = np.asarray(scope.find_var(pname))
    np.testing.assert_allclose(ref_p, lsgd_p, rtol=1e-4, atol=1e-5)


def test_collective_ops_semantics():
    """c_allreduce/c_allgather/c_broadcast inside shard_map match numpy."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.ops import registry

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ('dp',))
    n = len(devs)
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)

    def body(xs):
        ctx = registry.LowerCtx(0)
        ar = registry.get('c_allreduce_sum').fn(
            ctx, {'X': [xs]}, {'ring_id': 0})['Out'][0]
        mx = registry.get('c_allreduce_max').fn(
            ctx, {'X': [xs]}, {'ring_id': 0})['Out'][0]
        ag = registry.get('c_allgather').fn(
            ctx, {'X': [xs]}, {'ring_id': 0, 'nranks': n})['Out'][0]
        bc = registry.get('c_broadcast').fn(
            ctx, {'X': [xs]}, {'ring_id': 0, 'root': 2})['Out'][0]
        return ar, mx, ag, bc

    from paddle_tpu.compat import shard_map
    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P('dp'),),
        out_specs=(P(), P(), P(), P('dp'))))
    ar, mx, ag, bc = f(x)
    np.testing.assert_allclose(np.asarray(ar).reshape(3), x.sum(0))
    np.testing.assert_allclose(np.asarray(mx).reshape(3), x.max(0))
    np.testing.assert_allclose(np.asarray(ag), x)
    np.testing.assert_allclose(np.asarray(bc),
                               np.tile(x[2], (n, 1)))


def test_zero_sharded_optimizer_states_parity():
    """ZeRO-1 weight-update sharding: same losses/params as replicated."""
    batches = make_batches()
    m1, s1, l1 = build_model(21)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.Adam(0.01))

    m2, s2, l2 = build_model(21)
    box = {}

    def _zero(exe, main, feed, fetch):
        if 'cp' not in box:
            box['cp'] = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=l2.name).with_sharded_optimizer_states()
        return exe.run(box['cp'], feed=feed, fetch_list=fetch)

    par, par_p = train(_zero, m2, s2, l2, batches,
                       fluid.optimizer.Adam(0.01))
    np.testing.assert_allclose(ref, par, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_p, par_p, rtol=1e-4, atol=1e-5)


def test_reduce_strategy_maps_to_zero_sharding():
    """BuildStrategy ReduceStrategy.Reduce -> ZeRO-style sharded
    optimizer states (the kReduce param-ownership analog), with full
    loss parity."""
    batches = make_batches()
    m1, s1, l1 = build_model(31)
    ref, ref_p = train(_single, m1, s1, l1, batches,
                       fluid.optimizer.Momentum(0.1, momentum=0.9))

    m2, s2, l2 = build_model(31)
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    box = {}

    def _parallel(exe, main, feed, fetch):
        if 'cp' not in box:
            box['cp'] = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=l2.name, build_strategy=bs)
            assert box['cp']._shard_opt_states_axis is not None
        return exe.run(box['cp'], feed=feed, fetch_list=fetch)

    par, par_p = train(_parallel, m2, s2, l2, batches,
                       fluid.optimizer.Momentum(0.1, momentum=0.9))
    np.testing.assert_allclose(ref, par, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_p, par_p, rtol=1e-4, atol=1e-5)
