"""CPU rehearsal of the whole harness: ``run.py``'s control flow at the
tiny presets beside this file, with the TPU requirement, the table of
peaks and the device-plane finder stubbed HERE and nowhere else.  What
a rehearsal prints is never a measurement; these tests check keys,
control flow and that new cells, configurations and per-layer metrics
are picked up as new files.
"""

import hashlib
import importlib.util
import json
import os
import shutil
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
PRESETS = os.path.join(HERE, 'presets')
CONTRACT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def _cpu_op_planes(profile):
    """The CPU backend writes its ops on host threads, each with an
    ``hlo_op`` stat: present them as chip 0's op line so the reduction
    runs end to end here."""
    from benchmark.lib import trace_reduce
    events = [ev for plane in profile.planes for line in plane.lines
              for ev in line.events if 'hlo_op' in dict(ev.stats)]
    line = types.SimpleNamespace(name=trace_reduce.OP_LINE, events=events)
    return {0: types.SimpleNamespace(name='/device:TPU:0', lines=[line])}


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """A copy of ``benchmark/`` with the presets laid over it and a
    manifest of its own; -> (run module of the copy, its root)."""
    root = str(tmp_path / 'checkout')
    copy = os.path.join(root, 'benchmark')
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests'))
    for sub in ('configs', 'workloads'):
        for name in os.listdir(os.path.join(PRESETS, sub)):
            shutil.copy(os.path.join(PRESETS, sub, name),
                        os.path.join(copy, sub, name))
    shutil.copy(os.path.join(PRESETS, 'BENCHMARK.json'),
                os.path.join(root, 'BENCHMARK.json'))
    spec = importlib.util.spec_from_file_location(
        'rehearsed_run', os.path.join(copy, 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmark.lib import peaks, trace_reduce
    monkeypatch.setattr(run, 'ACCELERATORS', ('tpu', 'cpu'))
    monkeypatch.setitem(peaks.CHIP_PEAKS, 'cpu', (1.0, 1.0))
    monkeypatch.setattr(trace_reduce, 'device_planes', _cpu_op_planes)
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                       str(tmp_path / 'jax_cache'))
    return run, root


def _last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


def _names(root, group, cell):
    manifest = json.load(open(os.path.join(root, 'BENCHMARK.json')))
    return {m['name'] for m in manifest[group]
            if cell in m.get('workloads', [cell])}


@pytest.mark.parametrize('cell,chips', [('tiny_bert', 1),
                                        ('tiny_resnet', 1),
                                        ('tiny_bert_dp4', 4)])
def test_window_run_prints_the_contract_line(harness, capsys, cell,
                                             chips):
    run, root = harness
    assert run.main(['--workload', cell, '--seed', '3', '--seconds',
                     '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0 and line['attempted'] % 3 == 0
    assert set(line['metrics']) == _names(root, 'end_to_end', cell)
    assert all(m['value'] > 0 for n, m in line['metrics'].items()
               if n != 'peak_hbm')      # the CPU reports no memory_stats
    assert line['device']['count'] == chips
    assert line['device']['platform'] == 'cpu'


@pytest.mark.parametrize('cell', ['tiny_bert', 'tiny_bert_dp4'])
def test_traced_run_prints_layer_metrics_and_breakdown(harness, capsys,
                                                       cell):
    run, root = harness
    assert run.main(['--workload', cell, '--seed', '0', '--seconds', '1',
                     '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True and line['attempted'] == 3
    allowed = _names(root, 'per_layer', cell)
    assert set(line['metrics']) <= allowed
    # what needs a Mosaic call or a collective in the trace may be left
    # out off-chip; the rest is always there
    assert {'host_run_ms', 'setup_compiles', 'xla_ops_ms', 'pallas_ms',
            'pallas_fused_calls', 'device_idle'} <= set(line['metrics'])
    assert 0 < line['device']['busy_s'] <= line['device']['window_s']
    assert 0 < len(line['breakdown']['device_ops']) <= 10
    assert len(line['breakdown']['idle_gaps']) <= 10
    assert all(isinstance(n, str) and s >= 0 for group in
               line['breakdown'].values() for n, s in group)


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result(
        harness, capsys, monkeypatch):
    run, _ = harness
    monkeypatch.setattr(run, 'ACCELERATORS', ('tpu',))
    with pytest.raises(SystemExit) as exit_info:
        run.main(['--workload', 'tiny_bert', '--seed', '0', '--seconds',
                  '1', '--trace', '0'])
    assert exit_info.value.code not in (0, None)
    assert '{' not in capsys.readouterr().out


def test_fewer_chips_than_the_cell_asks_for_exits_nonzero(harness, capsys):
    run, root = harness
    path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(path))
    for w in manifest['workloads']:
        w['chips'] = 8 if w['name'] == 'tiny_bert_dp4' else w['chips']
    json.dump(manifest, open(path, 'w'))
    with pytest.raises(SystemExit) as exit_info:
        run.main(['--workload', 'tiny_bert_dp4', '--seed', '0',
                  '--seconds', '1', '--trace', '0'])
    assert exit_info.value.code not in (0, None)
    assert '{' not in capsys.readouterr().out


def _digests(root):
    out = {}
    for directory, _, files in os.walk(os.path.join(root, 'benchmark')):
        if '__pycache__' in directory:
            continue
        for name in files:
            path = os.path.join(directory, name)
            with open(path, 'rb') as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_cell_config_and_layer_metric_are_new_files_only(harness,
                                                             capsys):
    """A fifth cell, a third configuration and a new per-layer metric:
    three new files plus manifest entries, no edit to a file that is
    there."""
    run, root = harness
    before = _digests(root)
    bench = os.path.join(root, 'benchmark')
    config = json.load(open(os.path.join(bench, 'configs',
                                         'bert-tiny.json')))
    config['published']['num_hidden_layers'] = 1
    json.dump(config, open(os.path.join(bench, 'configs',
                                        'bert-one-layer.json'), 'w'))
    traffic = json.load(open(os.path.join(bench, 'workloads',
                                          'tiny_s64.json')))
    traffic.update(seq_len=32, batch_per_chip=6)
    json.dump(traffic, open(os.path.join(bench, 'workloads',
                                         'tiny_s32.json'), 'w'))
    with open(os.path.join(bench, 'layer_metrics', 'ops_per_step.py'),
              'w') as f:
        f.write("LAYER = 'device'\nUNIT = 'count'\nMOVES = 'throughput'"
                "\n\n\ndef read(trace, run):\n"
                "    return len(trace.first.ops) / trace.steps\n")
    path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(path))
    manifest['configs'].append(
        {'name': 'bert-one-layer', 'source': 'preset', 'reduced': [],
         'file': 'benchmark/configs/bert-one-layer.json', 'why': 'test'})
    manifest['workloads'].append(
        {'name': 'one_layer_s32', 'config': 'bert-one-layer',
         'traffic': 'tiny_s32', 'chips': 1, 'why': 'test'})
    manifest['per_layer'].append(
        {'name': 'ops_per_step', 'unit': 'count', 'better': 'lower',
         'source': 'device_trace', 'layer': 'device',
         'moves': 'throughput', 'workloads': ['one_layer_s32']})
    json.dump(manifest, open(path, 'w'))

    assert run.main(['--workload', 'one_layer_s32', '--seed', '5',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert line['correct'] is True
    assert line['metrics']['ops_per_step']['value'] > 0
    assert line['metrics']['ops_per_step']['unit'] == 'count'
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before
    assert len(after) == len(before) + 3
    # an older cell does not report the new cell's metric
    assert run.main(['--workload', 'tiny_bert', '--seed', '5',
                     '--seconds', '1', '--trace', '1']) == 0
    assert 'ops_per_step' not in _last_line(capsys)['metrics']


@pytest.mark.parametrize('cell_name', ['tiny_bert', 'tiny_resnet'])
def test_reference_agrees_with_the_zoo_program_and_sees_a_wrong_weight(
        harness, cell_name):
    """The plain reference against the zoo's for_test program at the
    tiny preset; the check is not vacuous: one weight scaled by 2%
    behind the program's back is seen."""
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    cell_name)
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        small = {k: v[:2] for k, v in host.items()}
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        # read the scope after the run: a run donates the state it holds
        scope = fluid.global_scope()
        weight = params[-2]                 # the last layer's matrix
        wrong = [fluid.core.as_array(scope.find_var(p)) *
                 (1.02 if p == weight else 1.0) for p in params]
        want = float(cell.family.reference_loss(cell.config, cell.traffic,
                                                wrong, small))
        assert abs(got - want) > cell.family.REFERENCE_RTOL * abs(want)


def test_same_seed_same_inputs_other_seed_other_inputs(harness):
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_bert')
    first, again, other = (cell.family.batch(cell.config, cell.traffic,
                                             cell.batch, seed)
                           for seed in (7, 7, 8))
    assert all((first[k] == again[k]).all() for k in first)
    assert (first['src_ids'] != other['src_ids']).any()
