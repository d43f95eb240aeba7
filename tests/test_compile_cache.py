"""AOT compile plane: content-addressed segment fingerprints, the
persistent on-disk executable store, background warmup, and the LRU
caps on the in-memory caches (fluid/compile_cache.py + executor.py)."""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (compile_cache, layers, monitor,
                              unique_name)
from paddle_tpu.fluid import executor as executor_mod


@pytest.fixture
def plane_dir(tmp_path, monkeypatch):
    """A fresh cache dir + a fresh plane, restored afterwards so the
    rest of the suite keeps the plane-off fast path.  JAX's own cache
    is placed under the same tmp dir the way a deployment would place
    it (JAX_COMPILATION_CACHE_DIR, inherited by child processes; jax
    read the variable at import, so this process is told directly):
    the checkout's shared .jax_cache would make "cold" depend on what
    earlier suite runs compiled."""
    import jax
    d = str(tmp_path / 'ccache')
    xla = str(tmp_path / 'xla')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', xla)
    jax.config.update('jax_compilation_cache_dir', xla)
    compile_cache.reset_plane()
    fluid.set_flags({'FLAGS_compile_cache_dir': d})
    try:
        yield d
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': ''})
        compile_cache.reset_plane()
        jax.config.update('jax_compilation_cache_dir', None)


def _prog(seed, width=4):
    """Identical programs on demand: unique_name.guard() resets the
    process-global name counters, so a rebuild names its vars exactly
    like a fresh process would — the executable interface (pytree
    keys) matches and fingerprints collide on purpose."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[8], dtype='float32')
            h = layers.fc(x, width, act='relu')
            loss = layers.reduce_mean(h)
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _xs(n=4):
    return np.random.RandomState(0).randn(n, 8).astype('float32')


def _run_steps(main, startup, loss, xs, steps=3):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        return [np.asarray(exe.run(main, feed={'x': xs},
                                   fetch_list=[loss])[0])
                for _ in range(steps)]


def _seg_entries(d):
    return sorted(os.listdir(os.path.join(d, 'segments')))


def test_disk_roundtrip_second_process_zero_retraces(plane_dir):
    """Process 1 populates the store; 'process 2' (fresh plane, fresh
    name scope — the in-process stand-in two real subprocesses exercise
    in tools/check_compile_cache.py) must run entirely from disk: hits
    > 0, zero retraces, bit-identical trajectory."""
    xs = _xs()
    ref = _run_steps(*_prog(101), xs=xs)
    entries = _seg_entries(plane_dir)
    assert entries, 'first process wrote no cache entries'
    assert monitor.counter_value('executor/aot_compiles') > 0

    compile_cache.reset_plane()
    lower0 = monitor.counter_value('executor/segments_lowered')
    hit0 = monitor.counter_value('executor/compile_cache_disk_hit')
    got = _run_steps(*_prog(101), xs=xs)
    assert monitor.counter_value(
        'executor/compile_cache_disk_hit') - hit0 >= len(entries)
    assert monitor.counter_value(
        'executor/segments_lowered') - lower0 == 0
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


_DISK_COUNTERS = ('aot_compiles', 'compile_cache_disk_hit',
                  'compile_cache_disk_miss', 'compile_cache_disk_writes',
                  'segments_lowered')


def _two_segment_prog(seed, width):
    """_prog with a host op in the middle: two device segments."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[8], dtype='float32')
            h = layers.fc(x, width, act='relu')
            mid = main.current_block().create_var(
                name='cc_mid', shape=[-1, width], dtype='float32')
            layers.py_func(lambda a: a, h, mid)
            loss = layers.reduce_mean(layers.fc(mid, 4))
    return main, startup, loss


# a width a case: a program some earlier case of this process compiled
# is handed over in memory, and its executable cannot be stored again
@pytest.mark.parametrize('build, width, warmup', [
    (_prog, 5, False), (_prog, 6, True), (_two_segment_prog, 7, False)],
    ids=['lazy', 'warmup', 'two_segments_lazy'])
def test_cold_and_warm_process_counts_equal_the_entries(
        plane_dir, build, width, warmup):
    """What the cold / warm / warm+warmup start-up records were worth:
    a cold process compiles, misses and writes once per store entry;
    a warm one (fresh plane, same store) hits once per entry and
    compiles, misses, writes and lowers nothing, whether its
    executables are asked for by the first step or by warmup()."""
    xs = _xs()

    def process():
        before = {k: monitor.counter_value('executor/' + k)
                  for k in _DISK_COUNTERS}
        main, startup, loss = build(131, width)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            if warmup:
                exe.warmup(main, feed_shapes={'x': xs},
                           fetch_list=[loss], wait=True)
            out = [np.asarray(exe.run(main, feed={'x': xs},
                                      fetch_list=[loss])[0])
                   for _ in range(3)]
        # a process that ends joins its compile pool: a warmed
        # executable is stored after its future resolves
        ended = compile_cache.reset_plane()
        if ended is not None and ended._pool is not None:
            ended._pool.shutdown(wait=True)
        return out, {k: monitor.counter_value('executor/' + k) - v
                     for k, v in before.items()}

    ref, cold = process()
    n = len(_seg_entries(plane_dir))
    assert n >= 2       # the startup program's and the step's
    assert cold['aot_compiles'] == n
    assert cold['compile_cache_disk_miss'] == n
    assert cold['compile_cache_disk_writes'] == n
    assert cold['compile_cache_disk_hit'] == 0

    got, warm = process()
    assert len(_seg_entries(plane_dir)) == n
    assert warm['compile_cache_disk_hit'] == n
    assert warm['aot_compiles'] == 0
    assert warm['compile_cache_disk_miss'] == 0
    assert warm['compile_cache_disk_writes'] == 0
    assert warm['segments_lowered'] == 0
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_identical_program_shares_executable_in_memory(plane_dir):
    """Two content-identical programs in ONE process share the
    executable through the fingerprint map — no second compile."""
    xs = _xs()
    ref = _run_steps(*_prog(102), xs=xs)
    aot0 = monitor.counter_value('executor/aot_compiles')
    mem0 = monitor.counter_value('executor/compile_cache_memory_hit')
    got = _run_steps(*_prog(102), xs=xs)
    assert monitor.counter_value('executor/aot_compiles') == aot0
    assert monitor.counter_value(
        'executor/compile_cache_memory_hit') > mem0
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_fingerprint_invalidation_axes():
    """The fingerprint must move when anything that changes the
    lowering moves: flags, boundary shapes, dtypes, jax version —
    and must NOT move on volatile attrs (op callstacks)."""
    main, startup, loss = _prog(103)
    exe = fluid.Executor(fluid.XLAPlace(0))
    plan = exe._get_plan(main, ('x',), (loss.name,))
    seg = [it for it in plan
           if isinstance(it, executor_mod._Segment)][0]
    specs = ((('x', (4, 8), '<f4'),), ())
    base_flags = executor_mod._lowering_flag_items(False, True)

    def fp(specs=specs, flags=base_flags, donate=True, purpose='aot'):
        return compile_cache.fingerprint(seg.ops, specs, flags,
                                         donate=donate, purpose=purpose)

    base = fp()
    assert base == fp()  # deterministic
    # flags that change lowering: prefer_test / whole_program_grad /
    # auto layout / conv precision
    assert fp(flags=executor_mod._lowering_flag_items(True, True)) \
        != base
    assert fp(flags=executor_mod._lowering_flag_items(False, False)) \
        != base
    assert fp(flags=executor_mod._lowering_flag_items(
        False, True, auto=True)) != base
    # boundary shape / dtype
    assert fp(specs=((('x', (8, 8), '<f4'),), ())) != base
    assert fp(specs=((('x', (4, 8), '<f2'),), ())) != base
    # donation + executable family
    assert fp(donate=False) != base
    assert fp(purpose='jit') != base
    # volatile attrs must NOT move it
    saved = seg.ops[0].attrs.get('__op_callstack__')
    seg.ops[0].attrs['__op_callstack__'] = ['somewhere:1 (else)']
    try:
        assert fp() == base
    finally:
        seg.ops[0].attrs['__op_callstack__'] = saved
    # op content MUST move it
    seg.ops[0].attrs['__fp_probe__'] = 1
    try:
        assert fp() != base
    finally:
        del seg.ops[0].attrs['__fp_probe__']


def test_fingerprint_keys_on_jax_version(monkeypatch):
    main, startup, loss = _prog(104)
    exe = fluid.Executor(fluid.XLAPlace(0))
    plan = exe._get_plan(main, ('x',), (loss.name,))
    seg = [it for it in plan
           if isinstance(it, executor_mod._Segment)][0]
    flags = executor_mod._lowering_flag_items(False, True)
    base = compile_cache.fingerprint(seg.ops, (), flags)
    real = compile_cache._env_key()
    monkeypatch.setattr(compile_cache, '_env_key',
                        lambda: real[:1] + ('99.99.99',) + real[2:])
    assert compile_cache.fingerprint(seg.ops, (), flags) != base


def test_corrupted_entry_recompiles_never_crashes(plane_dir):
    xs = _xs()
    ref = _run_steps(*_prog(105), xs=xs)
    seg_dir = os.path.join(plane_dir, 'segments')
    entries = _seg_entries(plane_dir)
    assert entries
    # truncate one entry, fill another (or the same) with garbage
    with open(os.path.join(seg_dir, entries[0]), 'r+b') as f:
        f.truncate(16)
    with open(os.path.join(seg_dir, entries[-1]), 'wb') as f:
        f.write(b'not a cache entry at all')
    compile_cache.reset_plane()
    corrupt0 = monitor.counter_value('executor/compile_cache_corrupt')
    got = _run_steps(*_prog(105), xs=xs)
    assert monitor.counter_value(
        'executor/compile_cache_corrupt') > corrupt0
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)
    # a further restart is clean: the poisoned entries were either
    # rewritten (verified round-trippable) or unlinked — they are
    # never served corrupt twice
    compile_cache.reset_plane()
    c1 = monitor.counter_value('executor/compile_cache_corrupt')
    got2 = _run_steps(*_prog(105), xs=xs)
    assert monitor.counter_value(
        'executor/compile_cache_corrupt') == c1
    for r, g in zip(ref, got2):
        np.testing.assert_array_equal(r, g)


def test_flag_toggle_compiles_fresh_executable(plane_dir):
    """Toggling a lowering-changing flag after the first compile must
    land on a DIFFERENT cache entry (the silent-stale-executable
    failure mode), and both settings must keep working."""
    xs = _xs()
    _run_steps(*_prog(106), xs=xs)
    n_entries = len(_seg_entries(plane_dir))
    prev = fluid.flags.get_flag('FLAGS_whole_program_grad')
    fluid.set_flags({'FLAGS_whole_program_grad': not prev})
    try:
        got = _run_steps(*_prog(106), xs=xs)
        assert np.isfinite(np.asarray(got)).all()
        assert len(_seg_entries(plane_dir)) > n_entries
    finally:
        fluid.set_flags({'FLAGS_whole_program_grad': prev})


def test_shape_change_compiles_fresh_executable(plane_dir):
    xs4, xs6 = _xs(4), _xs(6)
    main, startup, loss = _prog(107)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': xs4}, fetch_list=[loss])
        n4 = len(_seg_entries(plane_dir))
        out, = exe.run(main, feed={'x': xs6}, fetch_list=[loss])
        assert np.isfinite(np.asarray(out)).all()
        assert len(_seg_entries(plane_dir)) > n4


def test_warmup_matches_lazy_bit_for_bit(plane_dir):
    xs = _xs()
    # lazy path, fresh dir half A: plane is ACTIVE here too (dir set),
    # so this also proves warmup-compiled executables == run-compiled
    ref = _run_steps(*_prog(108), xs=xs)
    compile_cache.reset_plane()
    fluid.set_flags({'FLAGS_compile_cache_dir':
                     plane_dir + '_warmed'})
    main, startup, loss = _prog(108)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        res = exe.warmup(main, feed_shapes={'x': ((4, 8), 'float32')},
                         fetch_list=[loss], wait=True)
        assert res.submitted >= 1
        assert res.done()
        got = [np.asarray(exe.run(main, feed={'x': xs},
                                  fetch_list=[loss])[0])
               for _ in range(3)]
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)
    assert monitor.histogram_value('executor/warmup_seconds')


def test_warmup_memory_only_without_dir():
    """warmup() without a cache dir still primes the process (memory
    plane): the first run's segments come from the warmup futures."""
    compile_cache.reset_plane()
    try:
        xs = _xs()
        ref = _run_steps(*_prog(109), xs=xs)  # plane off: legacy path
        compile_cache.reset_plane()
        main, startup, loss = _prog(109)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            aot0 = monitor.counter_value('executor/aot_compiles')
            res = exe.warmup(main, feed_shapes={'x': xs},
                             fetch_list=[loss], wait=True)
            assert res.submitted >= 1
            assert monitor.counter_value(
                'executor/aot_compiles') > aot0
            got = [np.asarray(exe.run(main, feed={'x': xs},
                                      fetch_list=[loss])[0])
                   for _ in range(3)]
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r, g)
    finally:
        compile_cache.reset_plane()


def test_warmup_skips_host_cut_segments():
    """Segments downstream of a host op (whose outputs only a real
    step can shape) are skipped, not mis-compiled."""
    compile_cache.reset_plane()
    try:
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = layers.data('x', shape=[4], dtype='float32')
                y = layers.scale(x, scale=2.0)
                mid = main.current_block().create_var(
                    name='wu_mid', shape=[-1, 4], dtype='float32')
                layers.py_func(lambda a: a + 1.0, y, mid)
                z = layers.scale(mid, scale=3.0)
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            res = exe.warmup(main,
                             feed_shapes={'x': ((2, 4), 'float32')},
                             fetch_list=[z], wait=True)
            # segment 1 (scale before py_func) compiles; segment 2
            # reads the host op's output -> skipped
            assert res.submitted == 1
            assert res.skipped == 1
            xv = np.ones((2, 4), 'float32')
            got, = exe.run(main, feed={'x': xv}, fetch_list=[z])
            np.testing.assert_allclose(np.asarray(got),
                                       (xv * 2 + 1) * 3, rtol=1e-6)
    finally:
        compile_cache.reset_plane()


def test_segment_cache_lru_eviction(plane_dir):
    """Per-shape AOT entries are LRU-capped: cycling more shapes than
    the cap evicts (counted) and re-running an evicted shape still
    computes correctly (recompile or plane re-load)."""
    prev = fluid.flags.get_flag('FLAGS_segment_cache_capacity')
    fluid.set_flags({'FLAGS_segment_cache_capacity': 2})
    try:
        main, startup, loss = _prog(110)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            ev0 = monitor.counter_value(
                'executor/segment_cache_evictions')
            first = None
            for n in (2, 3, 4, 5):
                out, = exe.run(main, feed={'x': _xs(n)},
                               fetch_list=[loss])
                if first is None:
                    first = np.asarray(out)
            assert monitor.counter_value(
                'executor/segment_cache_evictions') > ev0
            # the evicted first shape still runs and agrees (params
            # moved since, so just require finite + same shape)
            again, = exe.run(main, feed={'x': _xs(2)},
                             fetch_list=[loss])
            assert np.isfinite(np.asarray(again)).all()
    finally:
        fluid.set_flags({'FLAGS_segment_cache_capacity': prev})


def test_plan_cache_lru_eviction():
    prev = fluid.flags.get_flag('FLAGS_plan_cache_capacity')
    fluid.set_flags({'FLAGS_plan_cache_capacity': 2})
    try:
        main, startup, loss = _prog(111)
        xs = _xs()
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            ev0 = monitor.counter_value(
                'executor/plan_cache_evictions')
            # three distinct plan keys under a cap of 2: two fetch
            # sets on this executor + one from a second executor (the
            # key includes the executor identity)
            exe_b = fluid.Executor(fluid.XLAPlace(0))
            exe.run(main, feed={'x': xs}, fetch_list=[loss])
            exe.run(main, feed={'x': xs}, fetch_list=[])
            out, = exe_b.run(main, feed={'x': xs},
                             fetch_list=[loss.name])
            assert monitor.counter_value(
                'executor/plan_cache_evictions') > ev0
            assert len(main._exec_cache) <= 2
            assert np.isfinite(np.asarray(out)).all()
    finally:
        fluid.set_flags({'FLAGS_plan_cache_capacity': prev})


def test_compiled_step_reuses_jit_across_identical_programs():
    """Executor.compile: repeated CALLS never re-trace (jit-backed),
    and a second CompiledStep of a content-identical program reuses
    the first one's jit through the plane (the run/compile shared
    fingerprint surface)."""
    compile_cache.reset_plane()
    try:
        def build():
            with unique_name.guard():
                main, startup = fluid.Program(), fluid.Program()
                main.random_seed = startup.random_seed = 3
                with fluid.program_guard(main, startup):
                    x = layers.data('x', shape=[6], dtype='float32')
                    y = layers.fc(x, 3, act='tanh')
            return main, startup, y

        main, startup, y = build()
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            step = exe.compile(main, feed_names=('x',),
                               fetch_names=(y.name,))
            # inference program: params are pure INPUTS (nothing is
            # updated in place), so they ride in `data`
            scope = fluid.global_scope()
            data = {n: fluid.core.as_array(scope.find_var(n))
                    for n in step.input_names if n != 'x'}
            data['x'] = np.ones((2, 6), 'float32')
            state = {n: fluid.core.as_array(scope.find_var(n))
                     for n in step.state_names}
            out1 = step(0, state, data)
            out2 = step(1, state, data)
            np.testing.assert_array_equal(np.asarray(out1[y.name]),
                                          np.asarray(out2[y.name]))
        mem0 = monitor.counter_value(
            'executor/compile_cache_memory_hit')
        main2, startup2, y2 = build()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup2)
            step2 = exe.compile(main2, feed_names=('x',),
                                fetch_names=(y2.name,))
            assert step2._jitted is step._jitted
        assert monitor.counter_value(
            'executor/compile_cache_memory_hit') > mem0
    finally:
        compile_cache.reset_plane()


def test_compiled_step_composes_under_jit():
    """Under an outer trace the CompiledStep must fall back to the raw
    traceable fn (no nested-jit recompilation surprises, grads flow)."""
    import jax
    import jax.numpy as jnp
    compile_cache.reset_plane()
    try:
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 5
            with fluid.program_guard(main, startup):
                x = layers.data('x', shape=[4], dtype='float32')
                y = layers.fc(x, 2)
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            step = exe.compile(main, feed_names=('x',),
                               fetch_names=(y.name,))
            scope = fluid.global_scope()
            params = {n: np.asarray(fluid.core.as_array(
                scope.find_var(n)))
                for n in step.input_names if n != 'x'}
            xv = np.ones((2, 4), 'float32')

            def call(p):
                d = dict(p)
                d['x'] = xv
                return step(0, {}, d)[y.name]

            eager = call(params)

            def f(p):
                return jnp.sum(call(p))

            g = jax.grad(f)(params)
            assert set(g) == set(params)
            jitted_out = jax.jit(call)(params)
            np.testing.assert_allclose(np.asarray(jitted_out),
                                       np.asarray(eager), rtol=1e-6)
    finally:
        compile_cache.reset_plane()


def test_lru_cache_semantics():
    ev_key = 'test/lru_evictions_%d' % os.getpid()
    c = compile_cache.LRUCache(2, ev_key)
    c['a'] = 1
    c['b'] = 2
    assert c.get('a') == 1          # refresh: 'a' becomes MRU
    c['c'] = 3                      # evicts 'b'
    assert 'b' not in c and 'a' in c and 'c' in c
    assert monitor.counter_value(ev_key) == 1
    assert sorted(c.keys()) == ['a', 'c']
    assert len(c) == 2
    c.clear()
    assert len(c) == 0
    unbounded = compile_cache.LRUCache(0)
    for i in range(100):
        unbounded[i] = i
    assert len(unbounded) == 100


def test_fetch_set_keys_executable_identity(plane_dir):
    """The check_grad two-fetch pattern with the plane ACTIVE: the
    same program planned for the analytic-grad fetch set and then for
    the loss fetch set shares its op list between the two segments,
    but each exports DIFFERENT vars.  The fingerprint folds the
    segment's output_names in, so the second plan compiles its own
    executable instead of taking a content-addressed hit on the
    first's (which returns the wrong vars — 'fetch var not
    produced')."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from op_test import OpTest
    ot = OpTest()
    ot.grad_atol = ot.grad_rtol = 2e-2
    ot.check_grad(
        'sum',
        {'X': [('x0', np.random.RandomState(7).rand(3, 4)
                .astype('float32')),
               ('x1', np.random.RandomState(8).rand(3, 4)
                .astype('float32'))]},
        attrs={}, out_slot='Out')
    # and the distinct executables both landed in the store
    assert len(_seg_entries(plane_dir)) >= 2


def test_listener_splits_built_from_loaded_and_times_the_stages(
        tmp_path):
    """JAX's own duration events, folded into fluid.monitor: one
    program the compiler builds, then the same program served from the
    persistent cache after jit's in-memory caches are dropped."""
    import jax
    import jax.numpy as jnp
    names = ['compile/%s_%s' % (stage, kind)
             for stage in ('trace', 'lower', 'backend_built',
                           'backend_loaded')
             for kind in ('seconds', 'count')]

    def delta(before):
        return {n: monitor.counter_value(n) - before[n] for n in names}

    compile_cache.listen()      # the package does this at import
    settings = {'jax_compilation_cache_dir': str(tmp_path / 'xla'),
                'jax_persistent_cache_min_compile_time_secs': 0.0,
                'jax_persistent_cache_min_entry_size_bytes': -1}
    was = {k: getattr(jax.config, k) for k in settings}
    for k, v in settings.items():
        jax.config.update(k, v)
    try:
        def f(x):
            return jnp.tanh(x) @ x + 23.0

        x = jnp.ones((16, 16))
        before = {n: monitor.counter_value(n) for n in names}
        jax.jit(f)(x).block_until_ready()
        built = delta(before)
        assert built['compile/backend_built_count'] == 1
        assert built['compile/backend_loaded_count'] == 0
        assert built['compile/backend_built_seconds'] > 0
        assert built['compile/trace_count'] >= 1
        assert built['compile/lower_count'] == 1
        assert built['compile/trace_seconds'] > 0
        assert built['compile/lower_seconds'] > 0

        jax.clear_caches()
        before = {n: monitor.counter_value(n) for n in names}
        jax.jit(f)(x).block_until_ready()
        loaded = delta(before)
        assert loaded['compile/backend_built_count'] == 0
        assert loaded['compile/backend_loaded_count'] == 1
        assert loaded['compile/backend_loaded_seconds'] > 0
        assert loaded['compile/lower_count'] == 1
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
