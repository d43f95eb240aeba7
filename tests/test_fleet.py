"""fluid.fleet: SLO-aware serving fleet over N ServingExecutor
replicas.

Covers the fleet-plane contract: router placement is sticky (a
tenant's warmed ladder keeps paying off), a firing SLO objective on
one class sheds the OTHER classes while the protected class keeps
serving, eviction picks the priced-cheapest candidate with the whole
candidate table in the decision log, migration lands bitwise-equal on
the target with zero post-warmup retraces, the freeze/revert contract
(FLAGS_fleet=0 logs intents without acting; revert() restores the
as-registered placements even frozen), and the /statusz fleet section
is JSON-able."""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (fleet, health, layers, memviz, monitor,
                              serving, slo, timeseries)


@pytest.fixture(autouse=True)
def _clean():
    yield
    fluid.set_flags({'FLAGS_fleet': True,
                     'FLAGS_fleet_interval_s': 1.0,
                     'FLAGS_fleet_imbalance_depth': 8,
                     'FLAGS_fleet_shed_mode': 'shed',
                     'FLAGS_fleet_defer_close_wait_s': 0.02,
                     'FLAGS_fleet_rewarmup_default_s': 1.0,
                     'FLAGS_slo_hysteresis': 3,
                     'FLAGS_timeseries': False})
    fleet.reset()
    timeseries.reset()
    slo.reset()
    monitor.reset()


@pytest.fixture
def exe():
    return fluid.Executor(fluid.XLAPlace(0))


def _build_mlp(width=16, seed=5, in_w=8):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[in_w], dtype='float32')
        h = layers.fc(x, width, act='relu')
        y = layers.fc(h, 6, act='softmax')
    return main_p, startup, y


def _make_fleet(exe, replicas=2, tenants=(('a', 16, 'interactive'),
                                          ('b', 24, 'batch'))):
    fl = fleet.Fleet()
    for i in range(replicas):
        fl.add_replica('r%d' % i,
                       serving.ServingExecutor(max_batch=4,
                                               executor=exe))
    built = {}
    for i, (name, width, cls) in enumerate(tenants):
        mp, sp, y = _build_mlp(width=width, seed=5 + i)
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(sp)
        fl.register_tenant(name, mp, ['x'], [y], scope=sc,
                           slo_class=cls)
        built[name] = (mp, sc, y)
    return fl, built


class TestRouter:
    def test_placement_spreads_and_sticks(self, exe):
        fl, _ = _make_fleet(exe, replicas=2)
        fl.warmup(wait=True)
        # the second tenant lands on the emptier replica (scored, not
        # first-fit)
        placed = fl.placement()
        assert set(placed.values()) == {'r0', 'r1'}
        # sticky: repeated submits never move the tenant
        rng = np.random.RandomState(0)
        before = dict(placed)
        for _ in range(6):
            rows = int(rng.randint(1, 4))
            xv = rng.randn(rows, 8).astype('float32')
            fl.submit('a', {'x': xv}).result(120)
        assert fl.placement() == before
        # every request was served by the placed replica
        rep = fl.replica(before['a']).resident_report()
        trep = [t for t in rep['tenants'] if t['tenant'] == 'a'][0]
        assert trep['requests_served'] == 6
        assert monitor.counter_value('fleet/routed_requests') == 6
        # every placement decision logged the per-replica signals
        places = [d for d in fleet.decisions() if d['kind'] == 'place']
        assert len(places) == 2
        for d in places:
            assert set(d['info']['signals']) == {'r0', 'r1'}
            assert d['acted']

    def test_unplaced_tenant_rejected(self, exe):
        fl, _ = _make_fleet(exe, replicas=1)
        with pytest.raises(KeyError):
            fl.submit('nope', {'x': np.zeros((1, 8), 'float32')})


class TestClassPolicy:
    def _fire(self, fl):
        """Drive a declared objective to 'firing' through the real
        sampling cadence (the fleet tick rides the same sample)."""
        fluid.set_flags({'FLAGS_slo_hysteresis': 1,
                         'FLAGS_fleet_interval_s': 0.0})
        slo.declare('fleet/_test_breach value < 1', name='fleet-obj')
        fl.protect_class('interactive', 'fleet-obj')
        monitor.add('fleet/_test_breach', 100)
        timeseries.sample(now=1000.0)   # sample -> slo eval -> tick
        timeseries.sample(now=1002.0)
        assert [o['state'] for o in slo.objectives()] == ['firing']

    def test_firing_objective_sheds_other_class_only(self, exe):
        fl, _ = _make_fleet(exe, replicas=1)
        fl.warmup(wait=True)
        self._fire(fl)
        xv = np.random.RandomState(0).randn(2, 8).astype('float32')
        # the batch class fails fast; interactive keeps serving
        with pytest.raises(serving.ServingDegraded):
            fl.submit('b', {'x': xv}).result(10)
        out, = fl.submit('a', {'x': xv}).result(120)
        assert np.asarray(out).shape == (2, 6)
        assert monitor.counter_value('serving/shed_class') >= 1
        sheds = [d for d in fleet.decisions()
                 if d['kind'] == 'class_shed']
        assert sheds and sheds[-1]['choice']['class'] == 'batch'
        # resolution restores the shed class
        slo.clear()
        fl.tick(now=2000.0)
        out, = fl.submit('b', {'x': xv}).result(120)
        assert np.asarray(out).shape == (2, 6)
        assert any(d['kind'] == 'class_restore'
                   for d in fleet.decisions())
        assert monitor.counter_value('fleet/class_restored') == 1

    def test_defer_mode_widens_close_wait_instead(self, exe):
        fluid.set_flags({'FLAGS_fleet_shed_mode': 'defer',
                         'FLAGS_fleet_defer_close_wait_s': 0.5})
        fl, _ = _make_fleet(exe, replicas=1)
        fl.warmup(wait=True)
        self._fire(fl)
        srv = fl.replica(fl.placement('b'))
        assert srv._tenants['b'].close_wait_s == 0.5
        assert srv._tenants['a'].close_wait_s is None
        # deferred, not shed: the batch class still serves
        xv = np.random.RandomState(0).randn(2, 8).astype('float32')
        out, = fl.submit('b', {'x': xv}).result(120)
        assert np.asarray(out).shape == (2, 6)
        slo.clear()
        fl.tick(now=2000.0)
        assert srv._tenants['b'].close_wait_s is None

    def test_frozen_class_policy_logs_intent_only(self, exe):
        fl, _ = _make_fleet(exe, replicas=1)
        fl.warmup(wait=True)
        fluid.set_flags({'FLAGS_slo_hysteresis': 1,
                         'FLAGS_fleet_interval_s': 0.0})
        slo.declare('fleet/_test_breach value < 1', name='fleet-obj')
        fl.protect_class('interactive', 'fleet-obj')
        monitor.add('fleet/_test_breach', 100)
        fluid.set_flags({'FLAGS_fleet': 0})   # freeze FIRST
        timeseries.sample(now=1000.0)         # fires the objective
        fl.tick(now=1002.0)
        sheds = [d for d in fleet.decisions()
                 if d['kind'] == 'class_shed']
        assert sheds and sheds[-1]['frozen'] \
            and not sheds[-1]['acted']
        # nothing actually shed
        xv = np.random.RandomState(0).randn(2, 8).astype('float32')
        out, = fl.submit('b', {'x': xv}).result(120)
        assert np.asarray(out).shape == (2, 6)


class TestPricedEviction:
    def test_evict_picks_cheapest_with_full_table(self, exe):
        # 'big' frees ~30x the residency of 'small' for the same
        # re-warmup wall: cheapest per byte freed, so churn evicts it
        fl, _ = _make_fleet(
            exe, replicas=1,
            tenants=(('small', 8, 'batch'), ('big', 256, 'batch')))
        memviz.live_census()        # pricing reads the newest census
        assert fl.price_move('big')['cost_per_byte'] \
            < fl.price_move('small')['cost_per_byte']
        assert fl.evict(why='test-churn') == 'big'
        assert monitor.counter_value('fleet/evictions') == 1
        d = [x for x in fleet.decisions() if x['kind'] == 'evict'][-1]
        # the whole candidate table is priced in the log
        table = {c['tenant']: c for c in d['info']['candidates']}
        assert set(table) == {'small', 'big'}
        assert all(c['residency_bytes'] > 0 and c['rewarmup_s'] > 0
                   for c in table.values())
        assert d['info']['why'] == 'test-churn'
        # the evicted tenant is gone from the route table
        assert fl.placement('big') is None
        with pytest.raises(KeyError):
            fl.submit('big', {'x': np.zeros((1, 8), 'float32')})

    def test_frozen_evict_is_intent_only(self, exe):
        fl, _ = _make_fleet(exe, replicas=1)
        fluid.set_flags({'FLAGS_fleet': 0})
        assert fl.evict(why='frozen') is None
        d = [x for x in fleet.decisions() if x['kind'] == 'evict'][-1]
        assert d['frozen'] and not d['acted']
        assert monitor.counter_value('fleet/frozen_intents') == 1
        assert set(fl.placement()) == {'a', 'b'}


class TestMigration:
    def test_migrate_bitwise_equal_zero_retrace(self, exe):
        fl, _ = _make_fleet(exe, replicas=2,
                            tenants=(('a', 16, 'interactive'),))
        fl.warmup(wait=True)
        src = fl.placement('a')
        rng = np.random.RandomState(1)
        feeds = [rng.randn(r, 8).astype('float32')
                 for r in (1, 3, 2, 4)]
        before = [np.asarray(fl.submit('a', {'x': xv}).result(120)[0])
                  for xv in feeds]
        tgt = fl.migrate('a', why='test')
        assert tgt is not None and tgt != src
        assert fl.placement('a') == tgt
        # post-migration traffic must not retrace: the target ladder
        # was pre-warmed through the persistent compile cache
        lowered0 = monitor.counter_value('executor/segments_lowered')
        after = [np.asarray(fl.submit('a', {'x': xv}).result(120)[0])
                 for xv in feeds]
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered0
        rep = fl.replica(tgt).resident_report()
        trep = [t for t in rep['tenants'] if t['tenant'] == 'a'][0]
        assert trep['retraces'] == 0
        # bitwise: the scope moved with the tenant, the per-bucket
        # executables come from the same compile cache
        for b, a in zip(before, after):
            assert np.array_equal(b, a)
        # the source no longer holds the tenant
        assert all(t['tenant'] != 'a' for t in
                   fl.replica(src).resident_report()['tenants'])
        # priced and logged, with the measured warmup wall
        d = [x for x in fleet.decisions()
             if x['kind'] == 'migrate'][-1]
        assert d['acted']
        assert d['info']['priced']['measured_warmup_s'] >= 0
        assert d['info']['from'] == src and d['info']['to'] == tgt
        assert monitor.counter_value('fleet/migrations') == 1

    @pytest.mark.parametrize('hops', [2, 3])
    def test_migrations_count_and_stay_bitwise_retrace_free(self, exe,
                                                            hops):
        """A tenant moved `hops` times between two replicas (there and
        back again): every hop is counted once, acted on and lands on
        the other replica, and the traffic after each hop answers bit
        for bit as before the first, lowering nothing."""
        fl, _ = _make_fleet(exe, replicas=2,
                            tenants=(('a', 16, 'interactive'),))
        fl.warmup(wait=True)
        rng = np.random.RandomState(2)
        feeds = [rng.randn(r, 8).astype('float32') for r in (2, 1, 4)]

        def answers():
            return [np.asarray(fl.submit('a', {'x': xv}).result(120)[0])
                    for xv in feeds]

        before = answers()
        lowered0 = None
        for hop in range(hops):
            src = fl.placement('a')
            tgt = fl.migrate('a', why='hop %d' % hop)
            assert tgt is not None and tgt != src
            if lowered0 is None:
                # the first hop warmed the other replica's ladder
                lowered0 = monitor.counter_value(
                    'executor/segments_lowered')
            for b, a in zip(before, answers()):
                assert np.array_equal(b, a)
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered0
        assert monitor.counter_value('fleet/migrations') == hops
        acted = [d for d in fleet.decisions()
                 if d['kind'] == 'migrate' and d['acted']]
        assert len(acted) == hops
        for name in ('r0', 'r1'):
            held = [t for t in fl.replica(name).resident_report()
                    ['tenants'] if t['tenant'] == 'a']
            assert len(held) == (name == fl.placement('a'))
            assert all(t['retraces'] == 0 for t in held)

    def test_frozen_migrate_is_intent_only(self, exe):
        fl, _ = _make_fleet(exe, replicas=2,
                            tenants=(('a', 16, 'interactive'),))
        fl.warmup(wait=True)
        src = fl.placement('a')
        fluid.set_flags({'FLAGS_fleet': 0})
        assert fl.migrate('a', why='frozen') is None
        assert fl.placement('a') == src
        d = [x for x in fleet.decisions()
             if x['kind'] == 'migrate'][-1]
        assert d['frozen'] and not d['acted']
        assert 'priced' in d['info']


class TestFreezeRevert:
    def test_frozen_placement_is_static(self, exe):
        fluid.set_flags({'FLAGS_fleet': 0})
        fl, _ = _make_fleet(exe, replicas=2)
        # frozen: everything lands on the static first replica, the
        # scored choice only logged
        assert set(fl.placement().values()) == {'r0'}
        places = [d for d in fleet.decisions() if d['kind'] == 'place']
        assert all(d['choice']['why'] == 'frozen_static'
                   for d in places)

    def test_revert_restores_base_placements(self, exe):
        fl, _ = _make_fleet(exe, replicas=2,
                            tenants=(('a', 16, 'interactive'),))
        fl.warmup(wait=True)
        base = fl.placement('a')
        fl.migrate('a', why='test')
        assert fl.placement('a') != base
        # revert works even frozen — it IS the escape hatch
        fluid.set_flags({'FLAGS_fleet': 0})
        restored = fl.revert()
        assert restored['migrations'] == 1
        assert fl.placement('a') == base
        assert monitor.counter_value('fleet/reverts') == 1
        # the reverted route still serves, zero-retrace
        lowered0 = monitor.counter_value('executor/segments_lowered')
        xv = np.random.RandomState(0).randn(2, 8).astype('float32')
        out, = fl.submit('a', {'x': xv}).result(120)
        assert np.asarray(out).shape == (2, 6)
        assert monitor.counter_value(
            'executor/segments_lowered') == lowered0


class TestSurface:
    def test_statusz_fleet_section_jsonable(self, exe):
        fl, _ = _make_fleet(exe, replicas=2)
        doc = health.statusz()
        sec = doc['fleet']
        assert sec is not None
        json.dumps(sec)          # JSON-able end to end
        body = sec['fleets'][0]
        assert set(body['replicas']) == {'r0', 'r1'}
        assert set(body['placements']) == {'a', 'b'}
        assert body['classes'] == {'a': 'interactive', 'b': 'batch'}
        assert sec['decisions_total'] == 2
        assert sec['enabled']
        # no fleet -> section withheld (a plain trainer pays nothing)
        fleet.reset()
        assert health.statusz()['fleet'] is None

    def test_tick_rides_sampling_cadence(self, exe):
        fl, _ = _make_fleet(exe, replicas=1)
        fluid.set_flags({'FLAGS_fleet_interval_s': 10.0})
        timeseries.sample(now=5000.0)
        assert monitor.counter_value('fleet/ticks') == 1
        timeseries.sample(now=5001.0)   # throttled
        assert monitor.counter_value('fleet/ticks') == 1
        timeseries.sample(now=5011.0)
        assert monitor.counter_value('fleet/ticks') == 2
        assert monitor.counter_value('fleet/tick_errors') == 0
