"""fluid.timeseries — windowed history.

The acceptance contract: window math survives the ugly inputs real
jobs produce — counter resets from a restarted worker (the post-reset
value IS the delta, prometheus rate() semantics), gauge gaps from a
dead worker's missed heartbeats (reported as holes, never bridged),
empty windows (None, not a crash); the sampler appends one point a
series and pulls in no plane above it; the exposition linter rejects
the per-bucket-count histogram rendering; rate_limited_dump claims
atomically."""

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import (health, monitor, supervisor,
                              timeseries, trace)


@pytest.fixture(autouse=True)
def _clean():
    yield
    fluid.set_flags({'FLAGS_timeseries': False,
                     'FLAGS_timeseries_window': 512,
                     'FLAGS_timeseries_sample_steps': 1})
    timeseries.reset()
    supervisor.reset()
    trace.reset()
    monitor.reset()


# ------------------------------------------------------- window math
class TestWindowMath:
    def test_counter_reset_is_delta_not_negative(self):
        # 10, 25, 40, restart -> 5, 20: the reset interval contributes
        # the post-reset cumulative (5), never -35
        pts = [(0.0, 0, 10.0), (1.0, 1, 25.0), (2.0, 2, 40.0),
               (3.0, 3, 5.0), (4.0, 4, 20.0)]
        deltas = [d for _t, _s, d in timeseries.counter_deltas(pts)]
        assert deltas == [15.0, 15.0, 5.0, 15.0]
        assert timeseries.counter_resets(pts) == 1
        # rate spans the whole window with the reset-aware total
        assert timeseries.rate_per_s(pts) == pytest.approx(50.0 / 4.0)

    def test_rate_needs_two_points_and_elapsed_time(self):
        assert timeseries.rate_per_s([]) is None
        assert timeseries.rate_per_s([(1.0, 0, 5.0)]) is None
        assert timeseries.rate_per_s([(1.0, 0, 5.0),
                                      (1.0, 1, 9.0)]) is None

    def test_gauge_gaps_counted_not_bridged(self):
        pts = [(0.0, 0, 4.0), (1.0, None, None), (2.0, None, None),
               (3.0, 3, 8.0)]
        st = timeseries.gauge_stats(pts)
        assert st['gaps'] == 2 and st['n'] == 2
        assert st['min'] == 4.0 and st['max'] == 8.0 and st['last'] == 8.0

    def test_gauge_stats_empty(self):
        st = timeseries.gauge_stats([(0.0, None, None)])
        assert st['last'] is None and st['n'] == 0 and st['gaps'] == 1

    def test_percentile_interpolates_and_pins_overflow(self):
        edges = (1.0, 2.0, 4.0)
        # 4 obs in (1, 2]: p50 lands mid-bucket
        assert timeseries.percentile_from_counts(
            edges, [0, 4, 0, 0], 0.5) == pytest.approx(1.5)
        # all overflow: the honest answer is the last finite edge
        assert timeseries.percentile_from_counts(
            edges, [0, 0, 0, 7], 0.99) == 4.0
        assert timeseries.percentile_from_counts(edges, [0, 0, 0, 0],
                                                 0.5) is None

    def test_hist_window_subtracts_cumulative_state(self):
        edges = (1.0, 2.0)
        # cumulative (count, sum, buckets) at window start and end:
        # the window saw 3 obs totalling 4.5, all in (1, 2]
        pts = [(0.0, 0, 10, 8.0, (10, 0, 0)),
               (5.0, 5, 13, 12.5, (10, 3, 0))]
        hw = timeseries.hist_window(edges, pts)
        assert hw['count'] == 3
        assert hw['sum'] == pytest.approx(4.5)
        assert hw['mean'] == pytest.approx(1.5)
        assert 1.0 <= hw['percentiles']['p50'] <= 2.0

    def test_hist_window_reset_falls_back_to_end_state(self):
        edges = (1.0,)
        pts = [(0.0, 0, 50, 50.0, (50, 0)),
               (5.0, 5, 4, 2.0, (4, 0))]    # restarted mid-window
        hw = timeseries.hist_window(edges, pts)
        assert hw['count'] == 4 and hw['sum'] == pytest.approx(2.0)

    def test_hist_window_empty(self):
        hw = timeseries.hist_window((1.0,), [])
        assert hw['count'] == 0 and hw['mean'] is None
        assert hw['percentiles']['p99'] is None

    def test_downsample_keeps_last_per_bucket(self):
        pts = [(t * 0.1, t, float(t)) for t in range(40)]
        ds = timeseries.downsample(pts, 1.0)
        assert len(ds) == 4
        assert [p[2] for p in ds] == [9.0, 19.0, 29.0, 39.0]
        assert timeseries.downsample(pts, 0) == pts

    def test_spark_normalizes(self):
        s = timeseries.spark([1, 2, 3, 4, 5, 6, 7, 8])
        assert s[0] == u'▁' and s[-1] == u'█' and len(s) == 8
        assert timeseries.spark([None, None]) == ''
        assert timeseries.spark([3.0, 3.0]) == u'▁▁'


# ----------------------------------------------------- live sampling
class TestSampling:
    def test_maybe_sample_off_by_default(self):
        monitor.add('demo/c', 5)
        assert timeseries.maybe_sample(step=1) is False
        assert timeseries.report()['samples'] == 0

    def test_sample_appends_one_point_per_registry_entry(self):
        fluid.set_flags({'FLAGS_timeseries': True})
        monitor.add('demo/c', 5)
        monitor.set_gauge('demo/g', 2.0)
        monitor.observe('demo/h', 0.01)
        assert timeseries.maybe_sample(step=1) is True
        monitor.add('demo/c', 3)
        assert timeseries.maybe_sample(step=2) is True
        doc = timeseries.window('demo/c')
        assert doc['kind'] == 'counter' and doc['n'] == 2
        assert doc['derived']['total_delta'] == pytest.approx(3.0)
        assert timeseries.window('demo/g')['kind'] == 'gauge'
        hdoc = timeseries.window('demo/h')
        assert hdoc['kind'] == 'hist' and hdoc['edges']
        # points carry (ts, step, value)
        assert doc['points'][0][1] == 1 and doc['points'][1][1] == 2

    def test_sample_is_one_point_a_series_and_imports_nothing(self):
        # the sampler is the base of the telemetry: a sample touches
        # the registry and its own rings, never a plane above it
        import sys
        fluid.set_flags({'FLAGS_timeseries': True})
        monitor.add('demo/c')
        monitor.set_gauge('demo/g', 1.0)
        monitor.observe('demo/h', 0.01)
        before = set(sys.modules)
        assert timeseries.maybe_sample(step=1) is True
        grown = {m for m in set(sys.modules) - before
                 if m.startswith('paddle_tpu.fluid')}
        assert grown == set()
        lens = {n: timeseries.window(n)['n'] for n in timeseries.names()}
        assert {'demo/c', 'demo/g', 'demo/h'} <= set(lens)
        assert set(lens.values()) == {1}

    def test_sample_stride(self):
        fluid.set_flags({'FLAGS_timeseries': True,
                         'FLAGS_timeseries_sample_steps': 4})
        monitor.add('demo/c')
        assert timeseries.maybe_sample(step=3) is False
        assert timeseries.maybe_sample(step=4) is True
        # heartbeat-source samples ignore the step stride
        assert timeseries.maybe_sample(source='heartbeat') is True

    def test_window_bounded_by_flag(self):
        fluid.set_flags({'FLAGS_timeseries': True,
                         'FLAGS_timeseries_window': 8})
        for i in range(30):
            monitor.add('demo/c')
            timeseries.sample(step=i)
        assert timeseries.window('demo/c')['n'] == 8

    def test_window_unknown_series_and_empty_window(self):
        fluid.set_flags({'FLAGS_timeseries': True})
        assert timeseries.window('no/such') is None
        monitor.add('demo/c')
        timeseries.sample(step=1, now=100.0)
        doc = timeseries.window('demo/c', seconds=5, now=1000.0)
        assert doc['n'] == 0 and doc['derived']['rate_per_s'] is None
        assert doc['derived']['total_delta'] == 0

    def test_job_history_and_gap_markers(self):
        st = {'counters': {'w/c': 5.0}, 'gauges': {'w/g': 1.0},
              'hists': {}}
        timeseries.job_sample(1, st, now=10.0)
        st2 = {'counters': {'w/c': 9.0}, 'gauges': {'w/g': 2.0},
               'hists': {}}
        timeseries.job_sample(1, st2, now=11.0)
        # dead worker: two missed heartbeats leave explicit holes in
        # its GAUGE series (counters stay cumulative)
        assert timeseries.job_gap(1, now=12.0) == 1
        assert timeseries.job_gap(1, now=13.0) == 1
        assert timeseries.job_gap(7, now=12.0) == 0   # never seen
        doc = timeseries.window('w/g', rank=1)
        assert doc['derived']['gaps'] == 2
        assert doc['derived']['last'] == 2.0
        cdoc = timeseries.window('w/c', rank=1)
        assert cdoc['n'] == 2 and cdoc['derived']['total_delta'] == 4.0
        assert timeseries.job_ranks() == ['1']

    def test_http_query_surfaces(self):
        fluid.set_flags({'FLAGS_timeseries': True})
        monitor.add('demo/c')
        timeseries.sample(step=1)
        code, doc = timeseries.http_query({})
        assert code == 200 and 'demo/c' in doc['series']
        code, doc = timeseries.http_query({'name': 'demo/c',
                                           'point': '1'})
        assert code == 200 and len(doc['point']) == 3
        code, doc = timeseries.http_query({'name': 'no/such'})
        assert code == 404 and doc['series']
        code, doc = timeseries.http_query({'name': 'demo/c',
                                           'points': 'nan-ish'})
        assert code == 400

    def test_statusz_rollup_renders_rows(self):
        fluid.set_flags({'FLAGS_timeseries': True})
        for i in range(6):
            monitor.add('executor/run_calls')
            monitor.set_gauge('demo/g', float(i))
            timeseries.sample(step=i, now=100.0 + i)
        roll = timeseries.statusz_rollup()
        names = [r['name'] for r in roll['series']]
        # preferred ordering puts executor series first
        assert names[0] == 'executor/run_calls'
        assert all(r['spark'] for r in roll['series'])


# ----------------------------------------------------- exposition lint
class TestPromLint:
    def test_live_exposition_is_clean(self):
        monitor.add('demo/c')
        monitor.observe('demo/h', 0.01)
        monitor.observe('demo/h', 99.0)    # overflow bucket populated
        assert health.prom_lint(monitor.prometheus_text()) == []

    def test_per_bucket_counts_rejected(self):
        text = '\n'.join([
            '# HELP m demo', '# TYPE m histogram',
            'm_bucket{le="0.1"} 5',
            'm_bucket{le="1"} 2',          # decrease: per-bucket form
            'm_bucket{le="+Inf"} 1',
            'm_sum 1.5', 'm_count 8', ''])
        problems = health.prom_lint(text)
        assert any('not cumulative' in p for p in problems)

    def test_finite_bucket_above_inf_rejected(self):
        text = '\n'.join([
            '# HELP m demo', '# TYPE m histogram',
            'm_bucket{le="0.1"} 0',
            'm_bucket{le="1"} 7',
            'm_bucket{le="+Inf"} 7',
            'm_sum 1.5', 'm_count 9', ''])
        problems = health.prom_lint(text)
        assert any('+Inf bucket 7 != _count' in p for p in problems)
        text = text.replace('m_count 9', 'm_count 7').replace(
            'm_bucket{le="+Inf"} 7', 'm_bucket{le="+Inf"} 7\n'
            'm_bucket{le="2"} 9')
        problems = health.prom_lint(text)
        assert any('out of order' in p for p in problems)

    def test_job_merged_render_stays_cumulative(self):
        st = {'counters': {}, 'gauges': {},
              'hists': {'demo/h': {'edges': [0.1, 1.0],
                                   'counts': [2, 3, 1],
                                   'sum': 4.0, 'count': 6}}}
        text = health.render_merged([('0', st), ('1', st)])
        assert health.prom_lint(text) == []
        assert 'le="+Inf"} 12' in text


# ----------------------------------------------------- rate_limited_dump
class TestRateLimitedDump:
    def test_claims_once_per_interval(self, tmp_path):
        fluid.set_flags({'FLAGS_trace_dir': str(tmp_path)})
        trace.enable()
        assert trace.rate_limited_dump('t/key', 3600.0,
                                       tag='rld') is not None
        before = monitor.counter_value('trace/dumps_suppressed')
        assert trace.rate_limited_dump('t/key', 3600.0) is None
        assert monitor.counter_value('trace/dumps_suppressed') == \
            before + 1
        # a different key has its own claim
        assert trace.rate_limited_dump('t/other', 3600.0,
                                       tag='rld2') is not None

    def test_interval_zero_never_limits(self, tmp_path):
        fluid.set_flags({'FLAGS_trace_dir': str(tmp_path)})
        trace.enable()
        assert trace.rate_limited_dump('t/key', 0.0,
                                       tag='a') is not None
        assert trace.rate_limited_dump('t/key', 0.0,
                                       tag='b') is not None

    def test_reset_rate_limits_reopens(self, tmp_path):
        fluid.set_flags({'FLAGS_trace_dir': str(tmp_path)})
        trace.enable()
        assert trace.rate_limited_dump('m/key', 3600.0,
                                       tag='x') is not None
        assert trace.rate_limited_dump('m/key', 3600.0) is None
        trace.reset_rate_limits('m/')
        assert trace.rate_limited_dump('m/key', 3600.0,
                                       tag='y') is not None
