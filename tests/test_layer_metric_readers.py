"""The per-layer metric readers this repo's program feeds
(``benchmark/layer_metrics/``): device time by fluid op type from the
profiler's scope table, the executor's host phases, set-up seconds by
stage.  Each on a synthetic reduced trace whose answers are known, and
each returns nothing where there is nothing to read."""

import importlib.util
import os

import pytest

from benchmark.lib import host_phases, scope_time
from benchmark.lib import trace_reduce as tr
from paddle_tpu.fluid import monitor, profiler

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')

DEVICE = ('optimizer_ms', 'embedding_ms', 'matmul_ms', 'attention_ms',
          'conv_bn_ms', 'unscoped_ms')
HOST = ('exec_bind_ms', 'exec_place_ms', 'exec_dispatch_ms',
        'exec_release_ms', 'exec_unspanned_ms')
SETUP = ('setup_import_s', 'setup_trace_s', 'setup_backend_s')


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        'reader_' + name.replace('-', '_'),
        os.path.join(BENCH, 'layer_metrics', name + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _op(name, start_us, dur_us, kind=tr.OTHER):
    return tr.Op(name, start_us * 1e3, (start_us + dur_us) * 1e3, kind)


# chip 0 over two steps, us; while.1 nests two ops and owns 2 us itself
OPS = [_op('while.1', 0, 12), _op('fusion.1', 0, 4), _op('fusion.2', 4, 6),
       _op('fused_adam.1', 12, 10, tr.MOSAIC), _op('fusion.3', 22, 3),
       _op('fusion.4', 25, 5), _op('all-reduce.9', 30, 7, tr.COLLECTIVE),
       _op('copy.5', 37, 1), _op('fusion.6', 38, 8), _op('fusion.7', 46, 2)]
TABLE = {'while.1': None, 'fusion.1': 'mul', 'fusion.2': 'matmul_grad',
         'fused_adam.1': 'fused_adam', 'fusion.3': 'fused_adam/pack',
         'fusion.4': 'lookup_table_v2_grad', 'all-reduce.9': None,
         'copy.5': None, 'fusion.6': 'conv2d',
         'fusion.7': 'fused_multihead_attention_grad'}
# ms per step: (4 + 6) / 2 us ... over 2 steps
EXPECTED = {'optimizer_ms': (10 + 3) / 2e3, 'embedding_ms': 5 / 2e3,
            'matmul_ms': (4 + 6) / 2e3, 'attention_ms': 2 / 2e3,
            'conv_bn_ms': 8 / 2e3, 'unscoped_ms': (2 + 1) / 2e3}


def _span(name, start_us, dur_us):
    return tr.Span(name, start_us * 1e3, (start_us + dur_us) * 1e3)


# two quiet runs and the fetching run, us.  run#0 [0, 100): bind 10,
# place_state 20 + place_data 5, dispatch 30 (a nested span inside it
# adds nothing), state_release 5 -> 30 unspanned.  run#1 [200, 320):
# bind 20, feed_h2d 10, dispatch 40, state_release 10, host_op 15 -> 25
SPANS = [
    _span('bench/run#0', 0, 100), _span('executor/bind', 5, 10),
    _span('executor/place_state', 15, 20),
    _span('executor/place_data', 35, 5), _span('executor/dispatch', 45, 30),
    _span('executor/dispatch', 50, 10),
    _span('executor/state_release', 80, 5),
    _span('bench/run#1', 200, 120), _span('executor/bind', 205, 20),
    _span('executor/feed_h2d', 225, 10), _span('executor/dispatch', 240, 40),
    _span('executor/state_release', 285, 10),
    _span('executor/host_op', 300, 15),
    _span('bench/fetch_run#2', 400, 500),
    _span('executor/fetch_d2h', 450, 400),
]
HOST_EXPECTED = {'exec_bind_ms': 0.015, 'exec_place_ms': 0.0175,
                 'exec_dispatch_ms': 0.035, 'exec_release_ms': 0.0075,
                 'exec_unspanned_ms': 0.0275}


@pytest.fixture
def reduced():
    return tr.Reduced({0: tr.DeviceTimeline(OPS)}, SPANS, steps=2)


@pytest.mark.parametrize('name', DEVICE + HOST + SETUP)
def test_nothing_to_read_nothing_returned(name):
    assert _reader(name).read(None, {}) is None


@pytest.mark.parametrize('name', DEVICE)
def test_device_time_by_fluid_op(name, reduced, monkeypatch):
    monkeypatch.setattr(profiler, 'scope_tables',
                        lambda: {'jit_segment_x': [TABLE]})
    run = {}
    assert _reader(name).read(reduced, run) == pytest.approx(EXPECTED[name])
    if name == 'unscoped_ms':
        note = run['notes']['unscoped_ms']
        for scope in ('fused_adam/pack', 'matmul_grad', '(unscoped)',
                      'while.1', 'copy.5'):
            assert scope in note
        assert 'all-reduce.9' not in note


def test_the_six_never_exceed_the_non_collective_device_time(
        reduced, monkeypatch):
    monkeypatch.setattr(profiler, 'scope_tables',
                        lambda: {'jit_segment_x': [TABLE]})
    run = {}
    total = sum(_reader(n).read(reduced, run) for n in DEVICE)
    chip = reduced.first
    assert total == pytest.approx(reduced.per_step_ms(
        chip.kind_ns(tr.OTHER) + chip.kind_ns(tr.MOSAIC)))
    # the table was built once for the six
    assert set(run) == {'scope_time', 'setup_totals', 'notes'}


def test_ops_go_to_the_table_of_the_module_run_they_fall_in():
    quiet = dict(TABLE)
    fetch = dict(TABLE, **{'fusion.1': 'softmax', 'extra.1': 'mean'})
    ops = [_op('fusion.1', 0, 4), _op('fusion.2', 4, 6),
           _op('fusion.1', 20, 4), _op('fusion.2', 24, 6),
           _op('extra.1', 30, 1)]
    runs = [(0.0, 10e3, 'jit_segment_x(11)'), (20e3, 31e3,
                                              'jit_segment_x(12)')]
    by_scope, unscoped = scope_time.reduce_by_scope(
        tr.DeviceTimeline(ops), runs, {'jit_segment_x': [fetch, quiet]},
        profiler)
    assert by_scope == {'mul': 4e3, 'softmax': 4e3, 'matmul_grad': 12e3,
                        'mean': 1e3}
    assert not unscoped


def test_on_the_chip_a_trace_without_module_runs_is_an_error(
        reduced, monkeypatch, tmp_path):
    """Off a TPU the pieces are one group (the tests above); on one,
    ops that cannot be told apart by program must not be guessed."""
    import jax
    from benchmark import run as harness
    monkeypatch.setattr(profiler, 'scope_tables',
                        lambda: {'jit_segment_x': [TABLE]})
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(harness, 'OUT_DIR', str(tmp_path))
    cell = type('Cell', (), {'name': 'some_cell'})
    with pytest.raises(FileNotFoundError):
        _reader('matmul_ms').read(reduced, {'cell': cell})
    # the trace is where trace_block leaves it, and has no module line
    where = tmp_path / 'trace' / 'some_cell' / 'plugins' / 'profile' / 'x'
    where.mkdir(parents=True)
    (where / 'host.xplane.pb').write_bytes(b'')
    plane = type('Plane', (), {'name': '/device:TPU:0', 'lines': []})
    monkeypatch.setattr(tr, 'load', lambda path: type(
        'Profile', (), {'planes': [plane]}))
    with pytest.raises(RuntimeError, match='XLA Modules'):
        _reader('matmul_ms').read(reduced, {'cell': cell})


def test_the_table_build_stays_out_of_the_setup_seconds(
        reduced, monkeypatch):
    """Building the table lowers and compiles again, which fires the
    events ``compile/*`` counts: set-up's totals are taken before it,
    whichever reader runs first."""
    values = {'compile/trace_seconds': 11.0, 'compile/lower_seconds': 4.0,
              'compile/trace_count': 9.0, 'compile/lower_count': 5.0,
              'compile/backend_built_seconds': 12.5,
              'compile/backend_built_count': 1.0}
    monkeypatch.setattr(monitor, 'counter_value',
                        lambda name, default=0.0: values.get(name, default))

    def tables():
        values['compile/trace_seconds'] += 1000.0
        values['compile/backend_loaded_seconds'] = 1000.0
        values['compile/backend_loaded_count'] = 2.0
        return {'jit_segment_x': [TABLE]}
    monkeypatch.setattr(profiler, 'scope_tables', tables)
    run = {'setup_seconds': 50.0}
    assert _reader('matmul_ms').read(reduced, run) is not None
    assert values['compile/trace_seconds'] == 1011.0
    assert _reader('setup_trace_s').read(reduced, run) == 15.0
    assert _reader('setup_backend_s').read(reduced, run) == 12.5
    assert 'loaded from the persistent cache 0.00 s in 0' in \
        run['notes']['setup_backend_s']


def test_a_program_without_the_table_gives_nothing(reduced, monkeypatch):
    monkeypatch.delattr(profiler, 'scope_tables')
    assert all(_reader(n).read(reduced, {}) is None for n in DEVICE)


@pytest.mark.parametrize('name', HOST)
def test_host_phases_of_the_quiet_runs(name, reduced):
    run = {}
    assert _reader(name).read(reduced, run) == \
        pytest.approx(HOST_EXPECTED[name])
    if name == 'exec_unspanned_ms':
        assert 'host_op 0.007' in run['notes']['exec_unspanned_ms']


def test_host_phases_sum_to_the_annotation(reduced):
    for annotation, spans in host_phases.quiet_runs(reduced):
        one = tr.Reduced(reduced.devices, [annotation] + spans, steps=1)
        named = sum(_reader(n).read(one, {}) for n in HOST)
        other = host_phases.phase_ms(one, ['host_op', 'fetch_d2h'])
        assert named + other == pytest.approx(
            (annotation.end - annotation.start) / 1e6)


def test_no_executor_span_no_host_phase():
    bare = tr.Reduced({0: tr.DeviceTimeline(OPS)},
                      [_span('bench/run#0', 0, 100)], steps=1)
    assert all(_reader(n).read(bare, {}) is None for n in HOST)


def test_setup_seconds_come_from_the_compile_plane_counters(monkeypatch):
    values = {'compile/trace_seconds': 11.0, 'compile/lower_seconds': 4.0,
              'compile/trace_count': 9.0, 'compile/lower_count': 5.0,
              'compile/backend_built_seconds': 12.5,
              'compile/backend_built_count': 1.0,
              'compile/backend_loaded_seconds': 7.5,
              'compile/backend_loaded_count': 4.0}
    monkeypatch.setattr(monitor, 'counter_value',
                        lambda name, default=0.0: values.get(name, default))
    monkeypatch.setattr(monitor, 'gauge_value',
                        lambda name, default=0.0: 3.25)
    run = {'setup_seconds': 50.0}
    assert _reader('setup_import_s').read(None, run) == 3.25
    assert _reader('setup_trace_s').read(None, run) == 15.0
    assert _reader('setup_backend_s').read(None, run) == 20.0
    assert 'built 12.50 s in 1 programs' in run['notes']['setup_backend_s']
    assert 'loaded from the persistent cache 7.50 s in 4' in \
        run['notes']['setup_backend_s']
    # a program that counts none of this (a parent of PR 23)
    values.clear()
    monkeypatch.setattr(monitor, 'gauge_value',
                        lambda name, default=0.0: default)
    assert all(_reader(n).read(None, {'setup_seconds': 50.0}) is None
               for n in SETUP)
