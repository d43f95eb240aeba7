"""Elastic resilience plane (fluid/elastic.py + fluid/faultinject.py
+ the rpc/heartbeat retry satellites): crash-consistent manifest-led
checkpoints (kill -9 mid-save leaves a loadable last-good generation,
torn shards refused BY NAME), cross-topology resharding (dp4 -> dp2,
dp2 -> fsdp2 x tp1 on the CPU mesh, parameters bitwise-preserved,
resumed loss trajectories at parity), bounded retry/backoff with
per-call deadlines, and heartbeat miss tolerance."""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import elastic, faultinject, layers, monitor
from paddle_tpu.parallel import plan as ashard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ELASTIC_FLAGS = ('FLAGS_elastic_checkpoint', 'FLAGS_auto_shard',
                 'FLAGS_faultinject', 'FLAGS_elastic_keep_generations',
                 'FLAGS_rpc_backoff_ms', 'FLAGS_rpc_backoff_max_ms')


@pytest.fixture(autouse=True)
def _clean():
    prev = fluid.get_flags(list(ELASTIC_FLAGS))
    monitor.reset()
    elastic.reset()
    faultinject.reset()
    ashard.reset()
    yield
    fluid.set_flags(prev)
    faultinject.reset()
    elastic.reset()
    ashard.reset()
    monitor.reset()


def _build(seed=7, hidden=32, optimizer='adam'):
    from paddle_tpu.fluid import unique_name
    # unique_name.guard(): deterministic param names (fc_0.w_0, ...)
    # regardless of what earlier tests built in this process — the
    # manifest names must match across the save/load (and subprocess)
    # boundary, and the missing-var guard rightly refuses otherwise
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[16], dtype='float32')
            h = layers.fc(x, hidden, act='relu')
            h2 = layers.fc(h, 16)
            loss = layers.reduce_mean(h2)
            if optimizer == 'adam':
                fluid.optimizer.Adam(0.01).minimize(loss)
            else:
                fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(seed=3, n=8):
    return {'x': np.random.RandomState(seed).randn(n, 16)
            .astype('float32')}


def _f(val):
    return float(np.asarray(val).ravel()[0])


# ------------------------------------------------------------ faultinject
def test_faultinject_spec_parse_and_determinism():
    faultinject.configure('a.site:delay:0.001@2;b.site:torn@3+')
    assert faultinject.armed()
    # clause fires on exactly the 2nd hit of a.site
    assert faultinject.check('a.site') is None
    assert faultinject.check('a.site') is None   # delay executed inline
    assert faultinject.fired('a.site') == 1
    assert faultinject.check('a.site') is None
    assert faultinject.fired('a.site') == 1      # @2 exact, not @2+
    # @3+ fires on the 3rd and every later hit, returning the clause
    assert faultinject.check('b.site') is None
    assert faultinject.check('b.site') is None
    c = faultinject.check('b.site')
    assert c is not None and c['action'] == 'torn'
    assert faultinject.check('b.site')['action'] == 'torn'
    assert faultinject.fired('b.site') == 2
    rep = faultinject.report()
    assert rep['armed'] and rep['hits']['a.site'] == 3
    with pytest.raises(ValueError):
        faultinject.configure('missing-action-clause')
    with pytest.raises(ValueError):
        faultinject.configure('site:explode')
    faultinject.reset()
    assert not faultinject.armed()
    assert faultinject.check('a.site') is None


def test_faultinject_exact_clause_beats_open_ended():
    """'rpc.call:delay@1+;rpc.call:fail@3' — the documented combined
    spec: the one-shot exact clause must fire on its hit even though
    an open-ended clause also matches every hit."""
    faultinject.configure('s:delay:0.0@1+;s:fail@3')
    assert faultinject.check('s') is None          # hit 1: delay
    assert faultinject.check('s') is None          # hit 2: delay
    with pytest.raises(ConnectionError):
        faultinject.check('s')                     # hit 3: fail@3
    assert faultinject.check('s') is None          # hit 4: delay again


def test_faultinject_fail_action_raises_transport_error():
    faultinject.configure('x.y:fail@1')
    with pytest.raises(ConnectionError):
        faultinject.check('x.y')
    faultinject.configure('x.y:raise@1')
    with pytest.raises(faultinject.FaultInjected):
        faultinject.check('x.y')


# ----------------------------------------------------- save/load roundtrip
def test_save_load_roundtrip_bitwise_with_adam_state():
    main, startup, loss = _build()
    feed = _feed()
    d = tempfile.mkdtemp(prefix='pt_el_')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        gen = elastic.save_checkpoint(d, main, executor=exe)
        step_at_save = exe._step
        ref = [_f(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]
    assert gen == 1 and elastic.latest_generation(d) == 1
    # fresh process-state: new scope + executor; Adam moments are
    # persistable, so the resumed trajectory must be BITWISE identical
    with fluid.scope_guard(fluid.Scope()):
        exe2 = fluid.Executor(fluid.XLAPlace(0))
        info = elastic.load_checkpoint(d, main, executor=exe2)
        assert info['generation'] == 1
        assert exe2._step == step_at_save
        got = [_f(exe2.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]
    assert got == ref, (got, ref)
    # same topology: every param keeps its grid (zero-wire schedule)
    assert set(info['reshard']['by_kind']) == {'keep'}
    assert info['reshard']['wire_bytes'] == 0


def test_io_wiring_flag_save_and_autodetect_load():
    main, startup, loss = _build(optimizer='sgd')
    feed = _feed()
    d = tempfile.mkdtemp(prefix='pt_el_')
    fluid.set_flags({'FLAGS_elastic_checkpoint': True})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        fluid.io.save_persistables(exe, d, main)
        ref = _f(exe.run(main, feed=feed, fetch_list=[loss])[0])
    assert elastic.is_elastic_store(d)
    # load_persistables detects the store even with the flag OFF
    fluid.set_flags({'FLAGS_elastic_checkpoint': False})
    with fluid.scope_guard(fluid.Scope()):
        exe2 = fluid.Executor(fluid.XLAPlace(0))
        fluid.io.load_persistables(exe2, d, main)
        got = _f(exe2.run(main, feed=feed, fetch_list=[loss])[0])
    assert got == ref


def test_native_save_stays_default_and_atomic():
    """Flag off: save_persistables keeps the one-.npz native format,
    published atomically (no tmp debris)."""
    main, startup, loss = _build(optimizer='sgd')
    d = tempfile.mkdtemp(prefix='pt_el_')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        fluid.io.save_persistables(exe, d, main)
    assert os.path.exists(os.path.join(d, '__model_params__.npz'))
    assert not elastic.is_elastic_store(d)
    assert not [e for e in os.listdir(d) if '.tmp' in e]


# --------------------------------------------------- crash consistency
_CHILD = r'''
import os, sys
import numpy as np
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import elastic, faultinject, layers
main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 7
with fluid.program_guard(main, startup):
    x = layers.data('x', shape=[16], dtype='float32')
    h = layers.fc(x, 32, act='relu')
    h2 = layers.fc(h, 16)
    loss = layers.reduce_mean(h2)
    fluid.optimizer.Adam(0.01).minimize(loss)
feed = {'x': np.random.RandomState(3).randn(8, 16).astype('float32')}
exe = fluid.Executor(fluid.XLAPlace(0))
exe.run(startup)
exe.run(main, feed=feed, fetch_list=[loss])
d = sys.argv[1]
elastic.save_checkpoint(d, main, executor=exe)        # gen 1: clean
exe.run(main, feed=feed, fetch_list=[loss])
faultinject.configure(sys.argv[2])
elastic.save_checkpoint(d, main, executor=exe)        # gen 2: injected
print('SURVIVED')
'''


def _run_child(d, spec):
    return subprocess.run(
        [sys.executable, '-c', _CHILD, d, spec], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))


def test_kill9_mid_save_leaves_loadable_last_good():
    d = tempfile.mkdtemp(prefix='pt_el_')
    p = _run_child(d, 'elastic.shard_write:die@3')
    assert p.returncode == 9, (p.returncode, p.stderr[-1500:])
    assert 'SURVIVED' not in p.stdout
    # the torn save never published: only staging debris, gen 1 intact
    assert elastic.list_generations(d) == [1]
    assert elastic.latest_generation(d) == 1
    elastic.verify_generation(d, 1)
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        info = elastic.load_checkpoint(d, main, executor=exe)
    assert info['generation'] == 1


def test_torn_published_generation_refused_by_name():
    d = tempfile.mkdtemp(prefix='pt_el_')
    p = _run_child(d, 'elastic.shard_write:torn@2')
    assert p.returncode == 0, p.stderr[-1500:]
    assert elastic.list_generations(d) == [1, 2]
    # explicit load of the torn generation names the shard
    with pytest.raises(elastic.ElasticCheckpointError) as ei:
        elastic.verify_generation(d, 2)
    assert ei.value.reason == 'torn_shard'
    assert ei.value.shard and ei.value.shard.endswith('.npy')
    assert ei.value.shard in str(ei.value)
    # default load refuses gen 2 (counted + recorded) and falls back
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        info = elastic.load_checkpoint(d, main, executor=exe)
    assert info['generation'] == 1
    assert monitor.counter_value('elastic/refused_generations') == 1.0
    rep = elastic.report()
    assert rep['refusals'][-1]['reason'] == 'torn_shard'
    assert rep['refusals'][-1]['shard'] == ei.value.shard


def test_every_generation_torn_raises_no_generation():
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        elastic.save_checkpoint(d, main, executor=exe)
    # tear the only generation by hand
    gdir = os.path.join(d, 'gen-00000001')
    shard = [e for e in os.listdir(gdir) if e.endswith('.npy')][0]
    with open(os.path.join(gdir, shard), 'r+b') as f:
        f.truncate(8)
    with pytest.raises(elastic.ElasticCheckpointError) as ei:
        with fluid.scope_guard(fluid.Scope()):
            elastic.load_checkpoint(d, main)
    assert ei.value.reason == 'no_generation'


def test_stale_latest_pointer_neither_wedges_saves_nor_hides_newest():
    """A crash between a generation's rename and the LATEST update
    leaves a stale pointer: saves must keep numbering from the newest
    PUBLISHED generation (not collide), and loads must prefer it."""
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    feed = _feed()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        elastic.save_checkpoint(d, main, executor=exe)
        exe.run(main, feed=feed, fetch_list=[loss])
        elastic.save_checkpoint(d, main, executor=exe)
    with open(os.path.join(d, 'LATEST'), 'w') as f:
        f.write('1')                     # the stale pointer
    assert elastic.latest_generation(d) == 2
    with fluid.scope_guard(fluid.Scope()):
        exe2 = fluid.Executor(fluid.XLAPlace(0))
        info = elastic.load_checkpoint(d, main, executor=exe2)
        assert info['generation'] == 2   # newest, not the pointer
        gen = elastic.save_checkpoint(d, main, executor=exe2)
    assert gen == 3                      # no collision with gen-2


def test_missing_persistable_refused_loudly():
    """A program persistable absent from the checkpoint (optimizer
    switched after the save) must raise, not silently train from
    fresh init — the native load_vars guard, kept."""
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        elastic.save_checkpoint(d, main, executor=exe)
    main2, startup2, loss2 = _build(optimizer='adam')  # adds moments
    with pytest.raises(elastic.ElasticCheckpointError) as ei:
        with fluid.scope_guard(fluid.Scope()):
            exe2 = fluid.Executor(fluid.XLAPlace(0))
            elastic.load_checkpoint(d, main2, executor=exe2)
    assert ei.value.reason == 'missing_var'
    assert 'moment' in str(ei.value)


def test_generations_pruned_to_keep_limit():
    fluid.set_flags({'FLAGS_elastic_keep_generations': 2})
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(4):
            elastic.save_checkpoint(d, main, executor=exe)
    assert elastic.list_generations(d) == [3, 4]
    assert elastic.latest_generation(d) == 4


def test_prune_never_evicts_last_intact_generation():
    """Torn NEWER generations must not count toward the keep limit:
    after two torn saves over one good generation, the good one
    survives pruning and still loads."""
    fluid.set_flags({'FLAGS_elastic_keep_generations': 2})
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        elastic.save_checkpoint(d, main, executor=exe)       # gen 1
        faultinject.configure('elastic.shard_write:torn@1+')
        elastic.save_checkpoint(d, main, executor=exe)       # torn 2
        elastic.save_checkpoint(d, main, executor=exe)       # torn 3
        faultinject.reset()
    assert 1 in elastic.list_generations(d)
    with fluid.scope_guard(fluid.Scope()):
        exe2 = fluid.Executor(fluid.XLAPlace(0))
        info = elastic.load_checkpoint(d, main, executor=exe2)
    assert info['generation'] == 1
    assert monitor.counter_value('elastic/refused_generations') >= 2


# -------------------------------------------------- cross-topology reshard
def _params_bytes(names, scope):
    return {n: np.asarray(scope.find_var(n)).tobytes() for n in names}


def _run_layout(main, startup, loss, feed, layout, ndev, steps,
                ckpt=None, save_at=None, save_dir=None):
    """Train `steps` under an injected auto-shard plan; optionally
    load `ckpt` first / save at step `save_at`.  Returns (losses,
    param bytes AT SAVE TIME (else at end), plan)."""
    plan = ashard.build_plan(main, ndev=ndev, layouts=[layout])
    losses = []
    names = [p.name for p in main.all_parameters()]
    param_bytes = None
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        comp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name,
            places=[fluid.XLAPlace(i) for i in range(ndev)])
        comp._auto_plan = plan
        if ckpt is not None:
            elastic.load_checkpoint(ckpt, main, executor=exe,
                                    plan=plan)
        else:
            exe.run(startup)
        for i in range(steps):
            l, = exe.run(comp, feed=feed, fetch_list=[loss])
            losses.append(_f(l))
            if save_at is not None and i + 1 == save_at:
                elastic.save_checkpoint(save_dir, main, executor=exe)
                param_bytes = _params_bytes(names,
                                            fluid.global_scope())
        if param_bytes is None:
            param_bytes = _params_bytes(names, fluid.global_scope())
    return losses, param_bytes, plan


def test_reshard_dp4_to_dp2_loss_parity():
    fluid.set_flags({'FLAGS_auto_shard': True})
    main, startup, loss = _build()
    feed = _feed(n=8)           # 8 divides every dp extent used here
    d = tempfile.mkdtemp(prefix='pt_el_')
    pre, saved_params, _ = _run_layout(
        main, startup, loss, feed, (4, 1, 1), 4, 4, save_at=2,
        save_dir=d)
    # resume at dp2: parameters bitwise-preserved through the reshard,
    # trajectory at parity with the dp4 continuation (float summation
    # order differs across device counts), and bitwise-REPRODUCIBLE —
    # two resumes from the same generation agree exactly
    got1, p1, _ = _run_layout(main, startup, loss, feed, (2, 1, 1), 2,
                              2, ckpt=d)
    got2, p2, _ = _run_layout(main, startup, loss, feed, (2, 1, 1), 2,
                              2, ckpt=d)
    assert got1 == got2
    assert p1.keys() == p2.keys()
    np.testing.assert_allclose(got1, pre[2:], rtol=2e-5, atol=1e-7)
    # the loaded (pre-training) params equal the saved ones bitwise:
    # verify via a zero-step load
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        plan2 = ashard.build_plan(main, ndev=2, layouts=[(2, 1, 1)])
        elastic.load_checkpoint(d, main, executor=exe, plan=plan2)
        loaded = _params_bytes(saved_params.keys(),
                               fluid.global_scope())
    assert loaded == saved_params


def test_reshard_dp2_to_fsdp2_tp1_loss_parity():
    fluid.set_flags({'FLAGS_auto_shard': True})
    main, startup, loss = _build(hidden=64)
    feed = _feed(n=8)
    d = tempfile.mkdtemp(prefix='pt_el_')
    pre, saved_params, _ = _run_layout(
        main, startup, loss, feed, (2, 1, 1), 2, 4, save_at=2,
        save_dir=d)
    got1, p1, plan_b = _run_layout(main, startup, loss, feed,
                                   (1, 2, 1), 2, 2, ckpt=d)
    got2, p2, _ = _run_layout(main, startup, loss, feed, (1, 2, 1), 2,
                              2, ckpt=d)
    assert plan_b.layout == (1, 2, 1)
    assert any(s is not None for s in plan_b.specs.values())
    assert got1 == got2                      # bitwise-reproducible
    np.testing.assert_allclose(got1, pre[2:], rtol=2e-5, atol=1e-7)
    # reshard preserved every parameter bitwise.  The dp2 source is
    # genuinely sharded (ZeRO moments + the dp-propagated param
    # updates live split over 'dp'), so the synthesized schedule
    # includes real collective steps: row-halves -> column-halves is
    # the general ppermute re-cut, moments coarsen via allgather
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        planb = ashard.build_plan(main, ndev=2, layouts=[(1, 2, 1)])
        info = elastic.load_checkpoint(d, main, executor=exe,
                                       plan=planb)
        loaded = _params_bytes(saved_params.keys(),
                               fluid.global_scope())
    assert loaded == saved_params
    kinds = set(info['reshard']['by_kind'])
    assert kinds <= {'keep', 'slice', 'allgather', 'ppermute'}
    assert info['src_layout'] == {'dp': 2}
    assert monitor.counter_value('elastic/reshard_params') > 0


def test_reshard_fsdp4_to_fsdp2_allgather_schedule():
    """A genuinely sharded source coarsening onto fewer shards: the
    schedule names allgather steps with nonzero wire bytes, predicted
    seconds are recorded, and values stay bitwise."""
    fluid.set_flags({'FLAGS_auto_shard': True})
    main, startup, loss = _build(hidden=64)
    feed = _feed(n=8)
    d = tempfile.mkdtemp(prefix='pt_el_')
    _pre, saved_params, _ = _run_layout(
        main, startup, loss, feed, (1, 4, 1), 4, 3, save_at=3,
        save_dir=d)
    m = elastic.read_manifest(d, 1)
    assert any(len(r['shards']) == 4 for r in m['params'].values())
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        plan2 = ashard.build_plan(main, ndev=2, layouts=[(1, 2, 1)])
        info = elastic.load_checkpoint(d, main, executor=exe,
                                       plan=plan2)
        loaded = _params_bytes(saved_params.keys(),
                               fluid.global_scope())
    assert loaded == saved_params
    assert info['reshard']['by_kind'].get('allgather', 0) > 0
    assert info['reshard']['wire_bytes'] > 0
    assert info['reshard']['measured_s'] > 0
    assert monitor.gauge_value(
        'elastic/reshard_measured_seconds') > 0


def test_resume_warms_compile_cache_zero_retraces():
    """resume() drives Executor.warmup through the persistent compile
    cache: steps after the warmup lower nothing."""
    main, startup, loss = _build(optimizer='sgd')
    feed = _feed()
    d = tempfile.mkdtemp(prefix='pt_el_')
    cache = tempfile.mkdtemp(prefix='pt_el_cc_')
    fluid.set_flags({'FLAGS_compile_cache_dir': cache})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            elastic.save_checkpoint(d, main, executor=exe)
            ref = _f(exe.run(main, feed=feed, fetch_list=[loss])[0])
        with fluid.scope_guard(fluid.Scope()):
            exe2 = fluid.Executor(fluid.XLAPlace(0))
            info = elastic.resume(
                exe2, d, main,
                feed_shapes={'x': feed['x']}, fetch_list=[loss])
            assert info.get('warmed')
            lowered = monitor.counter_value('executor/segments_lowered')
            got = _f(exe2.run(main, feed=feed, fetch_list=[loss])[0])
            assert monitor.counter_value(
                'executor/segments_lowered') == lowered
        assert got == ref
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': ''})
        from paddle_tpu.fluid import compile_cache
        compile_cache.reset_plane()


@pytest.mark.parametrize('layout, hidden', [((2, 1, 1), 40),
                                            ((1, 2, 1), 48)],
                         ids=['from_dp2', 'from_fsdp2'])
def test_resume_on_another_topology_cold_then_warm_counts(
        layout, hidden, tmp_path, monkeypatch):
    """What the elastic start-up record was worth, as counts: a
    generation saved on two devices resumes on one; the first such
    process compiles what it warms and hits nothing on disk, the second
    hits once per stored executable and compiles nothing, and neither
    lowers anything after its warm-up.  Both continue bit for bit
    alike."""
    import jax
    from paddle_tpu.fluid import compile_cache
    fluid.set_flags({'FLAGS_auto_shard': True})
    main, startup, loss = _build(hidden=hidden)
    feed = _feed(n=8)
    store = str(tmp_path / 'store')
    _run_layout(main, startup, loss, feed, layout, 2, 2, save_at=2,
                save_dir=store)
    fluid.set_flags({'FLAGS_auto_shard': False})
    # JAX's own cache beside the segment store, as in
    # test_compile_cache.py: "cold" must not depend on earlier runs
    xla = str(tmp_path / 'xla')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', xla)
    jax.config.update('jax_compilation_cache_dir', xla)
    compile_cache.reset_plane()
    fluid.set_flags({'FLAGS_compile_cache_dir': str(tmp_path / 'cc')})
    keys = ('aot_compiles', 'compile_cache_disk_hit',
            'compile_cache_disk_writes')

    def process():
        before = {k: monitor.counter_value('executor/' + k)
                  for k in keys}
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            info = elastic.resume(exe, store, main,
                                  feed_shapes={'x': feed['x']},
                                  fetch_list=[loss])
            assert info.get('warmed') and info['src_layout']
            lowered = monitor.counter_value('executor/segments_lowered')
            losses = [_f(exe.run(main, feed=feed,
                                 fetch_list=[loss])[0])
                      for _ in range(3)]
            after_warmup = monitor.counter_value(
                'executor/segments_lowered') - lowered
        # a process that ends joins its compile pool: a warmed
        # executable is stored after its future resolves
        ended = compile_cache.reset_plane()
        if ended is not None and ended._pool is not None:
            ended._pool.shutdown(wait=True)
        return losses, after_warmup, {
            k: monitor.counter_value('executor/' + k) - v
            for k, v in before.items()}

    try:
        cold_losses, cold_after, cold = process()
        assert cold['aot_compiles'] >= 1
        assert cold['compile_cache_disk_writes'] == cold['aot_compiles']
        assert cold['compile_cache_disk_hit'] == 0
        assert cold_after == 0
        warm_losses, warm_after, warm = process()
        assert warm['compile_cache_disk_hit'] == cold['aot_compiles']
        assert warm['aot_compiles'] == 0
        assert warm['compile_cache_disk_writes'] == 0
        assert warm_after == 0
        assert warm_losses == cold_losses
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': ''})
        compile_cache.reset_plane()
        jax.config.update('jax_compilation_cache_dir', None)


# ------------------------------------------------------- retry/backoff
def test_retry_backoff_and_deadline():
    from paddle_tpu.distributed.rpc_ps import PsClient, \
        RpcDeadlineError
    import socket
    # a port with nothing listening: connect fails fast; the client
    # must retry with backoff and raise RpcDeadlineError
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    fluid.set_flags({'FLAGS_rpc_backoff_ms': 10,
                     'FLAGS_rpc_backoff_max_ms': 40})
    before = monitor.counter_value('rpc/retries')
    c = PsClient('127.0.0.1:%d' % port, deadline_ms=300, retry_times=2)
    t0 = time.monotonic()
    with pytest.raises(RpcDeadlineError):
        c.pull_dense('w')
    wall = time.monotonic() - t0
    assert monitor.counter_value('rpc/retries') - before == 2
    h = monitor.histogram_value('rpc/backoff_seconds')
    assert h and h['count'] >= 2 and h['sum'] > 0
    # bounded: two backoffs capped at 40ms each + fast connect refusals
    assert wall < 5.0
    assert monitor.counter_value('rpc/deadline_errors') >= 1


def test_backoff_bounds_and_jitter():
    from paddle_tpu.distributed.rpc_ps import _backoff_seconds
    fluid.set_flags({'FLAGS_rpc_backoff_ms': 100,
                     'FLAGS_rpc_backoff_max_ms': 400})
    for attempt, cap in ((1, 0.1), (2, 0.2), (3, 0.4), (9, 0.4)):
        for _ in range(16):
            b = _backoff_seconds(attempt)
            assert 0.5 * cap <= b <= cap, (attempt, b, cap)
    fluid.set_flags({'FLAGS_rpc_backoff_ms': 0})
    assert _backoff_seconds(5) == 0.0


def test_faultinject_rpc_delay_counts_against_deadline():
    """An injected per-call delay exercises the real deadline path:
    the call still completes (delay < deadline) and the injection is
    counted."""
    pytest.importorskip('ctypes')
    from paddle_tpu.distributed.rpc_ps import PsServer, PsClient
    try:
        srv = PsServer()
    except Exception:
        pytest.skip('native runtime unavailable')
    try:
        faultinject.configure('rpc.call:delay:0.05@1')
        c = PsClient(srv.endpoint)
        w = np.ones(4, 'float32')
        t0 = time.monotonic()
        c.init_dense('w', w)
        assert time.monotonic() - t0 >= 0.05
        assert faultinject.fired('rpc.call') == 1
        np.testing.assert_allclose(c.pull_dense('w'), w)
        c.close()
    finally:
        srv.stop()


def test_rejoin_trainer_readmission():
    from paddle_tpu.distributed.rpc_ps import PsServer
    try:
        srv = PsServer()
    except Exception:
        pytest.skip('native runtime unavailable')
    d = tempfile.mkdtemp(prefix='pt_el_')
    main, startup, loss = _build(optimizer='sgd')
    feed = _feed()
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            elastic.save_checkpoint(d, main, executor=exe)
            ref = _f(exe.run(main, feed=feed, fetch_list=[loss])[0])
        # the restarted trainer re-registers the slot and resumes
        # from the last-good generation
        with fluid.scope_guard(fluid.Scope()):
            exe2 = fluid.Executor(fluid.XLAPlace(0))
            info, hb = elastic.rejoin_trainer(
                srv.endpoint, trainer_id=0, dirname=d, program=main,
                executor=exe2, timeout=5.0, interval=0.05)
            assert info is not None and info['generation'] == 1
            got = _f(exe2.run(main, feed=feed, fetch_list=[loss])[0])
            hb.stop()
        assert got == ref
        assert monitor.counter_value('elastic/readmissions') >= 1
        from paddle_tpu.distributed.rpc_ps import PsClient
        c = PsClient(srv.endpoint)
        assert 0 in c.query_trainers()
        c.close()
    finally:
        srv.stop()


# --------------------------------------------------- heartbeat tolerance
def test_heartbeat_requires_consecutive_misses():
    from paddle_tpu.distributed.heartbeat import HeartBeatMonitor
    lost = []
    mon = HeartBeatMonitor(workers=1, timeout=0.08, check_interval=0.03,
                           misses=3,
                           on_lost=lambda w, a: lost.append(w))
    mon.start()
    try:
        mon.update(0)
        # one expired check is NOT death: beat again right after the
        # timeout first elapses -> flap, not loss
        time.sleep(0.13)
        mon.update(0)
        assert mon.lost_workers() == []
        # silence long enough for >= 3 consecutive expired checks
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline and not mon.lost_workers():
            time.sleep(0.03)
        assert mon.lost_workers() == [0]
        assert lost == [0]
        # re-admission: a restarted worker's first beat reclaims the
        # slot and is counted
        before = monitor.counter_value('elastic/readmissions')
        mon.update(0)
        assert mon.lost_workers() == []
        assert monitor.counter_value('elastic/readmissions') == \
            before + 1
        assert monitor.counter_value('elastic/heartbeat_flaps') >= 1
    finally:
        mon.stop()


def test_heartbeat_misses_flag_default():
    from paddle_tpu.distributed.heartbeat import HeartBeatMonitor
    mon = HeartBeatMonitor(workers=1, timeout=1.0)
    assert mon.misses == int(
        fluid.get_flags(['FLAGS_heartbeat_misses'])
        ['FLAGS_heartbeat_misses'])


# ------------------------------------------------------------- /statusz
def test_statusz_elastic_section_and_report():
    from paddle_tpu.fluid import health
    main, startup, loss = _build(optimizer='sgd')
    feed = _feed()
    d = tempfile.mkdtemp(prefix='pt_el_')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        elastic.save_checkpoint(d, main, executor=exe)
    with fluid.scope_guard(fluid.Scope()):
        exe2 = fluid.Executor(fluid.XLAPlace(0))
        elastic.load_checkpoint(d, main, executor=exe2)
    sz = health.statusz()
    sec = sz['elastic']
    assert sec is not None
    assert sec['last_generation'] == 1.0
    assert sec['last_save']['generation'] == 1
    assert sec['last_load']['generation'] == 1
    rs = sec['last_load']['reshard']
    for k in ('by_kind', 'predicted_s', 'measured_s',
              'pred_over_measured', 'staging_waves'):
        assert k in rs, rs
    assert 'retries' in sec['rpc']
    assert 'armed' in sec['faultinject']
    json.dumps(sz)              # the whole report stays JSON-able


def test_spec_jsonable_roundtrip():
    from jax.sharding import PartitionSpec as P
    for spec in (None, P('dp'), P(('fsdp', 'mp'), None),
                 P(None, 'mp')):
        doc = elastic.spec_to_jsonable(spec)
        json.dumps(doc)
        back = elastic.spec_from_jsonable(doc)
        assert (back is None and spec is None) or \
            tuple(back) == tuple(spec)
