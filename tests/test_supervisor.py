"""Self-healing supervisor (fluid/supervisor.py) + hung-step watchdog
+ serving deadline shedding + the rejoin-backoff satellite.

The decision-table tests drive the controller with SCRIPTED peer-view
sequences (injected heartbeat-loss signals) and call ``_tick()``
directly, so every decision is deterministic: a flap that recovers
under the miss threshold must not reshard; a death + rejoin race must
resolve to exactly ONE recovery action; checkpoint backpressure must
never overlap saves; a frozen controller (FLAGS_supervisor=0) must log
intents without acting."""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (elastic, faultinject, layers, monitor,
                              supervisor)

SUP_FLAGS = ('FLAGS_supervisor', 'FLAGS_supervisor_checkpoint_steps',
             'FLAGS_supervisor_rejoin_wait_s', 'FLAGS_step_timeout_s',
             'FLAGS_faultinject', 'FLAGS_elastic_checkpoint',
             'FLAGS_elastic_keep_generations', 'FLAGS_trace')


@pytest.fixture(autouse=True)
def _clean():
    prev = fluid.get_flags(list(SUP_FLAGS))
    monitor.reset()
    supervisor.reset()
    elastic.reset()
    faultinject.reset()
    yield
    fluid.set_flags(prev)
    supervisor.reset()
    faultinject.reset()
    elastic.reset()
    monitor.reset()


def _build(seed=7):
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[8], dtype='float32')
            y = layers.data('y', shape=[1], dtype='float32')
            h = layers.fc(x, 16, act='relu')
            pred = layers.fc(h, 1)
            loss = layers.reduce_mean(layers.square(
                layers.elementwise_sub(pred, y)))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _batch(s, n=8):
    rng = np.random.RandomState(100 + s)
    x = rng.randn(n, 8).astype('float32')
    return x, (x.sum(1, keepdims=True) * 0.5).astype('float32')


def _f(val):
    return float(np.asarray(val).ravel()[0])


class _Peers(object):
    """Scripted peer view: a mutable {rank: state} the tests step
    through injected heartbeat-loss sequences."""

    def __init__(self, *ranks):
        self.state = {r: dict(up=True, ready=True, misses=0,
                              was_up=True, confirmed_down=False,
                              endpoint='scripted')
                      for r in ranks}

    def __call__(self):
        return {r: dict(v) for r, v in self.state.items()}

    def set(self, rank, **kw):
        self.state[rank].update(kw)


def _mk_sup(store, peers=None, price=None, **kw):
    """A Supervisor WITHOUT a controller thread: tests drive _tick()
    by hand so every decision lands deterministically."""
    kw.setdefault('checkpoint_steps', 0)
    sup = supervisor.Supervisor(store, peers=peers or _Peers('1'),
                                price=price, **kw)
    return sup


def _kinds(decs=None):
    return [(d['kind'], d['choice']) for d in
            (decs if decs is not None else supervisor.decisions())]


# ------------------------------------------------------ decision table
def test_flap_under_threshold_never_triggers_recovery():
    peers = _Peers('1')
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'), peers=peers)
    # injected loss sequence: two consecutive misses (threshold 3),
    # then recovery — the aggregator counts a flap, never a death
    for misses in (1, 2):
        peers.set('1', up=False, misses=misses)
        sup._tick()
    peers.set('1', up=True, misses=0)
    monitor.add('elastic/heartbeat_flaps')   # the aggregator's count
    sup._tick()
    kinds = _kinds()
    assert ('heartbeat_flap', 'tolerate') in kinds
    assert not any(k in ('death', 'recovery') for k, _c in kinds)
    assert sup._pending_recovery is None
    assert monitor.counter_value('supervisor/deaths_confirmed') == 0


def test_confirmed_death_cheap_reshard_degrades_immediately():
    peers = _Peers('1')
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'), peers=peers,
                  price=lambda: 0.001, rejoin_wait_s=5.0)
    peers.set('1', up=False, misses=3, confirmed_down=True)
    sup._tick()
    assert ('death', 'degrade_to_survivors') in _kinds()
    assert sup._pending_recovery is not None
    assert monitor.counter_value('supervisor/deaths_confirmed') == 1
    # further ticks with the worker still down do not re-decide
    sup._tick()
    sup._tick()
    assert monitor.counter_value('supervisor/deaths_confirmed') == 1


def test_death_rejoin_race_resolves_to_one_recovery_action():
    # pricing says the reshard costs MORE than the budget -> the
    # controller waits; the worker rejoins inside the budget -> the
    # ONLY recovery action is the readmission, never a reshard
    peers = _Peers('1')
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'), peers=peers,
                  price=lambda: 100.0, rejoin_wait_s=30.0)
    peers.set('1', up=False, misses=3, confirmed_down=True)
    sup._tick()
    assert ('death', 'wait_for_rejoin') in _kinds()
    assert sup.state == 'waiting_rejoin'
    assert sup._pending_recovery is None
    # the race: the worker answers again on the same tick the budget
    # would also be checked — readmission must win and close the
    # incident with exactly one action
    peers.set('1', up=True, misses=0, confirmed_down=False)
    sup._tick()
    kinds = _kinds()
    assert ('rejoin', 'readmit') in kinds
    assert ('death', 'degrade_after_wait') not in kinds
    assert ('death', 'degrade_to_survivors') not in kinds
    assert sup._pending_recovery is None
    assert sup.state == 'idle'
    # budget expiry later cannot fire a second action
    sup._wait_deadline = None
    sup._tick()
    recovery_actions = [k for k in _kinds()
                        if k in (('rejoin', 'readmit'),
                                 ('death', 'degrade_after_wait'))]
    assert recovery_actions == [('rejoin', 'readmit')]


def test_wait_budget_expiry_degrades_exactly_once():
    peers = _Peers('1')
    clock = [0.0]
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'), peers=peers,
                  price=lambda: 100.0, rejoin_wait_s=2.0,
                  clock=lambda: clock[0])
    peers.set('1', up=False, misses=3, confirmed_down=True)
    sup._tick()
    assert sup.state == 'waiting_rejoin'
    clock[0] = 5.0     # past the budget, worker still dead
    sup._tick()
    sup._tick()
    assert _kinds().count(('death', 'degrade_after_wait')) == 1
    assert sup._pending_recovery is not None


def test_frozen_controller_logs_intents_without_acting():
    fluid.set_flags({'FLAGS_supervisor': False})
    peers = _Peers('1')
    calls = []
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'), peers=peers,
                  price=lambda: 0.0, rejoin_wait_s=5.0,
                  checkpoint_steps=1,
                  save_fn=lambda *a: calls.append(a) or 1)
    peers.set('1', up=False, misses=3, confirmed_down=True)
    sup._tick()
    decs = supervisor.decisions()
    assert any(d['kind'] == 'death' for d in decs)
    assert all(d['acted'] is False and d['frozen'] for d in decs)
    assert sup._pending_recovery is None          # intent only
    assert monitor.counter_value('supervisor/frozen_intents') >= 1
    # checkpoint cadence: intent logged, no save executed
    import types
    sup.maybe_checkpoint(types.SimpleNamespace(_step=5))
    assert calls == []
    assert any(d['kind'] == 'checkpoint' and not d['acted']
               for d in supervisor.decisions())


# -------------------------------------------------- checkpoint plane
def test_checkpoint_backpressure_never_overlaps_saves():
    store = tempfile.mkdtemp(prefix='pt_sup_')
    inflight = [0]
    peak = [0]
    done = []

    def slow_save(dirname, program, scope, shim):
        inflight[0] += 1
        peak[0] = max(peak[0], inflight[0])
        time.sleep(0.15)
        inflight[0] -= 1
        done.append(shim._step)
        return len(done)

    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        sup = supervisor.attach(store, program=main, executor=exe,
                                checkpoint_steps=1, save_fn=slow_save,
                                start=False)
        try:
            for s in range(8):
                x, y = _batch(s)
                exe.run(main, feed={'x': x, 'y': y},
                        fetch_list=[loss])
            t = sup._save_thread
            if t is not None:
                t.join(timeout=10)
        finally:
            supervisor.detach()
    assert peak[0] == 1, 'two saves overlapped'
    assert monitor.counter_value('supervisor/checkpoint_deferred') > 0
    assert any(d['kind'] == 'checkpoint' and
               d['choice'] == 'deferred_backpressure'
               for d in supervisor.decisions())
    assert len(done) >= 1


def test_cadence_stretches_when_save_wall_approaches_interval():
    store = tempfile.mkdtemp(prefix='pt_sup_')
    clock = [0.0]

    def slow_save(dirname, program, scope, shim):
        time.sleep(0.002)    # >> half the scripted 1e-3s trigger gap
        return 1

    sup = _mk_sup(store, checkpoint_steps=2, save_fn=slow_save,
                  clock=lambda: clock[0])
    main, startup, loss = _build()
    sup._program = main
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        sup._scope = fluid.global_scope()
        exe.run(startup)
        import types
        # first trigger establishes the reference wall; second arrives
        # only 1e-3 "seconds" later so even a fast save exceeds half
        # the gap -> the cadence must double
        sup.maybe_checkpoint(types.SimpleNamespace(_step=2))
        sup._save_thread.join(10)
        clock[0] = 1e-3
        sup.maybe_checkpoint(types.SimpleNamespace(_step=4))
        sup._save_thread.join(10)
    assert monitor.counter_value('supervisor/cadence_stretched') >= 1
    assert sup._cadence >= 4
    assert any(d['kind'] == 'cadence_stretched'
               for d in supervisor.decisions())


def test_torn_checkpoint_detected_and_resaved():
    store = tempfile.mkdtemp(prefix='pt_sup_')
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        x, y = _batch(0)
        exe.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
        sup = supervisor.attach(store, program=main, executor=exe,
                                checkpoint_steps=1, start=False)
        try:
            # tear the first shard of the FIRST generation: the
            # supervisor's post-save verification must catch the
            # digest mismatch and resave immediately
            faultinject.configure('elastic.shard_write:torn@1')
            sup.maybe_checkpoint(exe)
            sup._save_thread.join(30)
        finally:
            supervisor.detach()
    assert monitor.counter_value('supervisor/checkpoint_torn') == 1
    decs = supervisor.decisions()
    assert any(d['kind'] == 'checkpoint_torn' and
               d['choice'] == 'resave' and
               d.get('info', {}).get('shard') for d in decs)
    # the resaved generation is intact and loadable
    gen = elastic.latest_generation(store)
    elastic.verify_generation(store, gen)


def test_double_torn_checkpoint_gives_up_loudly():
    # the resave itself tears (persistent bitrot / open-ended torn
    # clause): the supervisor must SAY so, not log a good checkpoint
    store = tempfile.mkdtemp(prefix='pt_sup_')
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        sup = supervisor.attach(store, program=main, executor=exe,
                                checkpoint_steps=1, start=False)
        try:
            faultinject.configure('elastic.shard_write:torn@1+')
            sup.maybe_checkpoint(exe)
            sup._save_thread.join(30)
        finally:
            supervisor.detach()
    assert monitor.counter_value('supervisor/checkpoint_torn') == 2
    kinds = _kinds()
    assert ('checkpoint_torn', 'resave') in kinds
    assert ('checkpoint_torn', 'gave_up') in kinds
    assert ('checkpoint', 'take') not in kinds


def test_hooks_pinned_to_attached_executor():
    # a second executor in the process (serving dispatcher, bench)
    # must neither drive the cadence nor execute a pending recovery
    store = tempfile.mkdtemp(prefix='pt_sup_')
    main, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        sup = supervisor.attach(store, program=main, executor=exe,
                                checkpoint_steps=1, start=False)
        try:
            other = fluid.Executor(fluid.XLAPlace(0))
            sup._pending_recovery = {'why': 'test'}
            x, y = _batch(0)
            # the UNattached executor's run must not recover or save
            other.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
            assert sup._pending_recovery is not None
            assert monitor.counter_value(
                'supervisor/checkpoints_taken') == 0
            sup._pending_recovery = None
        finally:
            supervisor.detach()


def test_recovery_end_to_end_bounded_lost_work():
    # keep every generation: the replay below resumes the RECOVERY
    # generation by number after the soak wrote newer ones
    fluid.set_flags({'FLAGS_elastic_keep_generations': 32})
    store = tempfile.mkdtemp(prefix='pt_sup_')
    peers = _Peers('1')
    main, startup, loss = _build()
    cadence = 3
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        # This program's step is a millisecond and a generation's write
        # is not: on a loaded host the write outlasts a cadence, and
        # the controller then, rightly, defers the next save or doubles
        # the cadence, and "lost <= 3" no longer describes it.  Give
        # the soak the regime the bound is about: a step is a minute on
        # the controller's clock, and every write ends before the next
        # step begins.
        sup = supervisor.attach(store, program=main, executor=exe,
                                checkpoint_steps=cadence, peers=peers,
                                price=lambda: 0.0, rejoin_wait_s=5.0,
                                clock=lambda: 60.0 * exe._step,
                                start=False)
        try:
            losses = {}
            recovered = []
            target = 12
            while exe._step < target:
                s = exe._step
                x, y = _batch(s)
                try:
                    l, = exe.run(main, feed={'x': x, 'y': y},
                                 fetch_list=[loss])
                    losses[exe._step] = _f(l)
                except supervisor.Recovered as e:
                    recovered.append(e)
                    continue
                t = sup._save_thread
                if t is not None:
                    t.join(30)
                if exe._step == 8 and not recovered:
                    peers.set('1', up=False, misses=3,
                              confirmed_down=True)
                    sup._tick()     # controller confirms + schedules
            assert len(recovered) == 1
            e = recovered[0]
            assert e.lost_steps <= cadence
            assert exe._step >= target
            # detach BEFORE the replay: the replay executor must not
            # feed the same controller
            supervisor.detach()
            # post-recovery trajectory reproducible: resume the same
            # generation in a fresh scope and replay — bitwise equal
            replay = {}
            with fluid.scope_guard(fluid.Scope()):
                exe2 = fluid.Executor(fluid.XLAPlace(0))
                elastic.load_checkpoint(store, main, executor=exe2,
                                        generation=e.generation)
                while exe2._step < target:
                    s = exe2._step
                    x, y = _batch(s)
                    l, = exe2.run(main, feed={'x': x, 'y': y},
                                  fetch_list=[loss])
                    replay[exe2._step] = _f(l)
            for s in replay:
                assert np.float32(replay[s]).tobytes() == \
                    np.float32(losses[s]).tobytes(), \
                    'step %d diverged' % s
        finally:
            supervisor.detach()
    assert monitor.counter_value('supervisor/recoveries') == 1
    assert any(d['kind'] == 'recovery' and d['choice'] == 'recovered'
               for d in supervisor.decisions())


# ------------------------------------------------------------ watchdog
def test_guard_dispatch_times_out_with_named_segment():
    t0 = time.perf_counter()
    with pytest.raises(supervisor.StepTimeoutError) as ei:
        supervisor.guard_dispatch(lambda: time.sleep(3.0),
                                  'seg:fc_0.w_0', 0.2, step=7)
    wall = time.perf_counter() - t0
    assert wall < 0.4                      # < 2x the deadline
    assert ei.value.segment == 'seg:fc_0.w_0'
    assert 'fc_0.w_0' in str(ei.value)
    assert monitor.counter_value('executor/step_timeouts') == 1


def test_guard_dispatch_transparent_for_results_and_errors():
    assert supervisor.guard_dispatch(lambda: {'a': 1}, 's', 5.0) == \
        {'a': 1}
    with pytest.raises(KeyError):
        supervisor.guard_dispatch(lambda: {}['x'], 's', 5.0)
    assert monitor.counter_value('executor/step_timeouts') == 0


def test_injected_stall_converts_to_timeout_in_real_executor():
    # the watchdog acceptance: an injected dispatch stall becomes a
    # named StepTimeoutError + flight dump in < 2x the deadline
    fluid.set_flags({'FLAGS_step_timeout_s': 0.3, 'FLAGS_trace': True})
    from paddle_tpu.fluid import trace
    trace.enable()
    main, startup, loss = _build()
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            x, y = _batch(0)
            exe.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
            # arm AFTER warmup: which calls count as guarded
            # (site-consulting) dispatches depends on whether the AOT
            # compile plane is active in this process, so the clause
            # is configured once the next dispatch is steady-state
            # under either mode
            faultinject.configure('executor.dispatch:stall:5@1')
            t0 = time.perf_counter()
            with pytest.raises(supervisor.StepTimeoutError) as ei:
                exe.run(main, feed={'x': x, 'y': y},
                        fetch_list=[loss])
            wall = time.perf_counter() - t0
        assert wall < 0.6                   # < 2x FLAGS_step_timeout_s
        assert ei.value.dump_path and os.path.exists(
            ei.value.dump_path)
        assert monitor.counter_value('executor/step_timeouts') == 1
        assert faultinject.fired('executor.dispatch') == 1
    finally:
        fluid.set_flags({'FLAGS_step_timeout_s': 0.0,
                         'FLAGS_trace': False})
        trace.disable()


def test_collective_stall_converts_to_timeout_in_parallel_runner():
    # the satellite's named vehicle: 'collective.dispatch:stall' on a
    # dp2 CompiledProgram — a straggling collective blocked past the
    # deadline must become a StepTimeoutError, not a hang
    fluid.set_flags({'FLAGS_step_timeout_s': 0.4})
    main, startup, loss = _build()
    comp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name,
        places=[fluid.XLAPlace(i) for i in range(2)])
    x, y = _batch(0)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            exe.run(comp, feed={'x': x, 'y': y}, fetch_list=[loss])
            faultinject.configure('collective.dispatch:stall:5@1')
            t0 = time.perf_counter()
            with pytest.raises(supervisor.StepTimeoutError) as ei:
                exe.run(comp, feed={'x': x, 'y': y},
                        fetch_list=[loss])
            assert time.perf_counter() - t0 < 0.8   # < 2x deadline
        assert 'ops@' in ei.value.segment
        assert monitor.counter_value('executor/step_timeouts') == 1
        assert faultinject.fired('collective.dispatch') == 1
    finally:
        fluid.set_flags({'FLAGS_step_timeout_s': 0.0})


def test_hung_step_with_supervisor_recovers_from_last_good():
    store = tempfile.mkdtemp(prefix='pt_sup_')
    fluid.set_flags({'FLAGS_step_timeout_s': 0.3})
    main, startup, loss = _build()
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            # as in the recovery soak above: a step is a minute on the
            # controller's clock and every write ends before the next
            # step, or a loaded host doubles the cadence under the bound
            sup = supervisor.attach(store, program=main, executor=exe,
                                    checkpoint_steps=2, start=False,
                                    clock=lambda: 60.0 * exe._step)
            faultinject.configure('executor.dispatch:stall:5@4')
            losses = 0
            recovered = []
            while exe._step < 8:
                x, y = _batch(exe._step)
                try:
                    exe.run(main, feed={'x': x, 'y': y},
                            fetch_list=[loss])
                    losses += 1
                    if sup._save_thread is not None:
                        sup._save_thread.join(30)
                except supervisor.StepTimeoutError:
                    continue    # next run() executes the recovery
                except supervisor.Recovered as e:
                    recovered.append(e)
                    continue
            assert recovered, 'timeout never converted to recovery'
            assert recovered[0].lost_steps <= 2
            assert any(d['kind'] == 'hung_step' for d in
                       supervisor.decisions())
    finally:
        supervisor.detach()
        fluid.set_flags({'FLAGS_step_timeout_s': 0.0})


# ----------------------------------------------- serving deadline shed
def test_serving_sheds_expired_requests_instead_of_dispatching():
    from paddle_tpu.fluid import serving
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[4], dtype='float32')
            out = layers.fc(x, 4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    try:
        srv.add_program('t', main, ['x'], [out], scope=scope)
        # stall the dispatcher behind a lock-step: submit while the
        # dispatcher thread is NOT yet running, with an
        # already-expired deadline — _take_batch must shed it
        feed = {'x': np.ones((2, 4), 'float32')}
        fut = srv.submit('t', feed, deadline_s=1e-6)
        time.sleep(0.01)
        with pytest.raises(serving.DeadlineExpired):
            fut.result(timeout=10)
        assert monitor.counter_value('serving/shed_expired') == 1
        # an un-deadlined request still serves
        res = srv.submit('t', feed).result(timeout=30)
        assert res[0].shape == (2, 4)
        # requests served after the shed: the shed never wedged the
        # dispatcher or leaked into a batch
        assert monitor.counter_value('serving/requests') == 2
    finally:
        srv.close()


def test_serving_admission_rejects_expired_deadline():
    """A non-positive deadline fails fast AT ADMISSION — the request
    never queues, never reaches the dispatcher."""
    from paddle_tpu.fluid import serving
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[4], dtype='float32')
            out = layers.fc(x, 4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    try:
        srv.add_program('t', main, ['x'], [out], scope=scope)
        feed = {'x': np.ones((2, 4), 'float32')}
        for dl in (0.0, -1.0):
            fut = srv.submit('t', feed, deadline_s=dl)
            assert fut.done()          # failed at admission, no queue
            with pytest.raises(serving.DeadlineExpired):
                fut.result(timeout=0)
        assert monitor.counter_value('serving/shed_expired') == 2
        # nothing was admitted: the tenant queue never saw them
        assert len(srv._tenants['t'].pending) == 0
        # a live deadline still serves
        res = srv.submit('t', feed, deadline_s=60.0).result(timeout=30)
        assert res[0].shape == (2, 4)
    finally:
        srv.close()


def test_serving_degraded_sheds_and_flips_readiness():
    from paddle_tpu.fluid import serving
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        with fluid.program_guard(main, startup):
            x = layers.data('x', shape=[4], dtype='float32')
            out = layers.fc(x, 4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
    srv = serving.ServingExecutor(max_batch=8, executor=exe)
    try:
        t = srv.add_program('t', main, ['x'], [out], scope=scope)
        t.warmed = True
        ready, reasons = serving.readiness()
        assert ready is True
        serving.enter_degraded('supervisor recovery: test')
        try:
            ready, reasons = serving.readiness()
            assert ready is False
            assert any('degraded' in r for r in reasons)
            fut = srv.submit('t', {'x': np.ones((2, 4), 'float32')})
            with pytest.raises(serving.ServingDegraded):
                fut.result(timeout=5)
            assert monitor.counter_value('serving/shed_degraded') == 1
        finally:
            serving.exit_degraded()
        ready, _ = serving.readiness()
        assert ready is True
    finally:
        srv.close()


# ------------------------------------------------- rejoin backoff fix
def test_rejoin_trainer_retries_transient_connection_refusal():
    # the aggregator/pserver restarts exactly when a trainer rejoins:
    # the first admission attempts are REFUSED, then the endpoint
    # comes back — rejoin_trainer must retry under its own timeout
    # through the rpc_ps backoff policy, not die on the first refusal
    from paddle_tpu.distributed import rpc_ps
    calls = {'n': 0}

    class FlakyHB(object):
        def __init__(self, endpoint, trainer_id, timeout=None,
                     interval=None):
            calls['n'] += 1
            if calls['n'] < 3:
                raise ConnectionRefusedError(
                    'injected: endpoint not listening yet')
            self.endpoint = endpoint
            self.trainer_id = trainer_id

        def stop(self):
            pass

    orig = rpc_ps.TrainerHeartbeat
    rpc_ps.TrainerHeartbeat = FlakyHB
    try:
        info, hb = elastic.rejoin_trainer('127.0.0.1:1', trainer_id=0,
                                          timeout=10.0)
        assert info is None and hb.trainer_id == 0
        assert calls['n'] == 3
        assert monitor.counter_value('elastic/rejoin_retries') == 2
        assert monitor.counter_value('elastic/readmissions') == 1
    finally:
        rpc_ps.TrainerHeartbeat = orig


def test_rejoin_trainer_raises_after_deadline():
    from paddle_tpu.distributed import rpc_ps

    class DeadHB(object):
        def __init__(self, *a, **k):
            raise ConnectionRefusedError('injected: still down')

    orig = rpc_ps.TrainerHeartbeat
    rpc_ps.TrainerHeartbeat = DeadHB
    try:
        t0 = time.perf_counter()
        with pytest.raises(ConnectionError):
            elastic.rejoin_trainer('127.0.0.1:1', trainer_id=0,
                                   timeout=0.3)
        assert time.perf_counter() - t0 < 5.0
    finally:
        rpc_ps.TrainerHeartbeat = orig


# --------------------------------------------------------- observability
def test_statusz_supervisor_section_json_able():
    import json
    store = tempfile.mkdtemp(prefix='pt_sup_')
    peers = _Peers('1')
    sup = supervisor.attach(store, program=_build()[0], peers=peers,
                            price=lambda: 0.0, start=False)
    try:
        peers.set('1', up=False, misses=3, confirmed_down=True)
        sup._tick()
        from paddle_tpu.fluid import health
        doc = health.statusz()
        section = doc['supervisor']
        assert section is not None
        assert section['active'] is True
        assert section['controller']['store_dir'] == \
            os.path.abspath(store)
        assert any(d['kind'] == 'death' for d in section['decisions'])
        json.dumps(section)     # the HTTP handler's contract
    finally:
        supervisor.detach()


def test_decision_log_bounded():
    sup = _mk_sup(tempfile.mkdtemp(prefix='pt_sup_'))
    for i in range(supervisor._DECISIONS_CAP + 20):
        sup._decide('checkpoint', 'take', n=i)
    decs = supervisor.decisions()
    assert len(decs) == supervisor._DECISIONS_CAP
    assert decs[-1]['info']['n'] == supervisor._DECISIONS_CAP + 19


def test_disabled_watchdog_costs_one_flag_read():
    # FLAGS_step_timeout_s=0 must keep the plain dispatch path: no
    # guard threads are created
    main, startup, loss = _build()
    import threading as _th
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        x, y = _batch(0)
        exe.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
        before = {t.name for t in _th.enumerate()}
        for s in range(3):
            exe.run(main, feed={'x': x, 'y': y}, fetch_list=[loss])
        after = {t.name for t in _th.enumerate()}
    assert not any(n.startswith('pt_step_guard')
                   for n in after - before)
    assert monitor.counter_value('executor/step_timeouts') == 0
