"""CPU rehearsal of the memory split (PR 52): a traced run of the tiny
presets with the real manifest's six ``hbm_*`` entries appended prints
every one of them, the identity their definitions promise holds to the
byte, the notes name the table's point and who raised the allocator's
marks, and a program without the tables (a parent of that PR) leaves
them out without raising.  Keys and control flow only: what a rehearsal
prints is never a measurement (the CPU backend reports no
``memory_stats()``, so ``peak_hbm`` reads 0 here)."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

_spec = importlib.util.spec_from_file_location(
    'rehearsal_base', os.path.join(HERE, 'test_rehearsal.py'))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
harness = _base.harness         # the fixture: a copy of benchmark/ + presets

NEW = ['hbm_args_gb', 'hbm_temp_gb', 'hbm_residual_gb', 'hbm_unwalked_gb',
       'hbm_code_mb', 'hbm_outside_step_gb']


def _real_entries():
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    return [m for m in manifest['per_layer'] if m['name'] in NEW]


def _with_the_new_entries(root):
    path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(path))
    manifest['per_layer'] += _real_entries()
    json.dump(manifest, open(path, 'w'))


def test_the_manifest_holds_the_six_as_the_issue_names_them():
    entries = _real_entries()
    assert [m['name'] for m in entries] == NEW     # appended, in order
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    assert manifest['per_layer'][-6:] == entries
    layers = {m['name']: m['layer'] for m in entries}
    assert layers == {
        'hbm_args_gb': 'executor', 'hbm_temp_gb': 'op lowerings',
        'hbm_residual_gb': 'op lowerings',
        'hbm_unwalked_gb': 'compile plane', 'hbm_code_mb': 'compile plane',
        'hbm_outside_step_gb': 'device'}
    for m in entries:
        assert m['moves'] == 'peak_hbm' and 'workloads' not in m
        assert m['source'] == 'program_counter' and m['better'] == 'lower'
        path = os.path.join(ROOT, 'benchmark', 'layer_metrics',
                            m['name'] + '.py')
        spec = importlib.util.spec_from_file_location(m['name'], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert (module.UNIT, module.LAYER, module.MOVES) == (
            m['unit'], m['layer'], m['moves'])


@pytest.mark.parametrize('cell', ['tiny_bert', 'tiny_bert_dp4'])
def test_traced_run_prints_the_split_and_it_sums_to_the_peak(
        harness, capsys, cell):
    run, root = harness
    _with_the_new_entries(root)
    assert run.main(['--workload', cell, '--seed', '3000000052',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line['correct'] is True
    got = {k: v['value'] for k, v in line['metrics'].items()}
    units = {k: v['unit'] for k, v in line['metrics'].items()}
    assert set(NEW) <= set(got)
    assert units['hbm_code_mb'] == 'MB' and units['hbm_temp_gb'] == 'GB'
    assert got['hbm_args_gb'] > 0 and got['hbm_temp_gb'] > 0
    assert got['hbm_code_mb'] >= 0 and got['hbm_residual_gb'] > 0

    notes = {l.split('] ', 1)[1].split(': ', 1)[0]: l for l in out
             if '] hbm_' in l}
    assert set(NEW) <= set(notes)
    # the identity, to the byte: the noted outputs are in MB to one
    # place, so it is taken from the library the readers read
    from benchmark.lib import memory_split
    from paddle_tpu.fluid import memviz
    step = max((r for r in memviz.report(limit=1 << 20)
                if r['program'] == memviz.high_water()[
                    'first_runs'][-1]['program']),
               key=lambda r: r['argument_bytes'] + r['temp_bytes'])
    outputs = step['output_bytes'] - step['alias_bytes']
    peak = line['device']['memory_peak_bytes']
    total = (got['hbm_args_gb'] * 1e9 + got['hbm_temp_gb'] * 1e9 +
             got['hbm_code_mb'] * 1e6 + got['hbm_outside_step_gb'] * 1e9 +
             outputs)
    assert abs(total - peak) < 1.0
    assert got['hbm_args_gb'] * 1e9 == pytest.approx(
        step['argument_bytes'], abs=0.5)
    assert got['hbm_temp_gb'] * 1e9 == pytest.approx(
        step['temp_bytes'], abs=0.5)
    # the walk stands beside the compiler's figure
    assert got['hbm_unwalked_gb'] * 1e9 == pytest.approx(
        step['temp_bytes'] - step['temp_peak']['bytes'])
    assert abs(got['hbm_unwalked_gb']) < 0.5 * got['hbm_temp_gb']
    assert 'estimated' not in step           # the mesh row is the real one
    assert 'a fluid op named for' in notes['hbm_residual_gb']
    assert 'first runs in order: 0 ' in notes['hbm_outside_step_gb']
    # no allocator marks on the CPU: the remainder stays in one piece
    assert 'no marks to split it by' in notes['hbm_outside_step_gb']
    assert 'param ' in notes['hbm_args_gb']
    # start-up, the for_test clone, the step that fetches, the quiet one
    assert len(memviz.high_water()['first_runs']) >= 4
    assert memory_split.name_of(step) in notes['hbm_code_mb']


def test_the_remainder_splits_by_the_marks_of_the_chip_at_the_peak(
        monkeypatch):
    """``hbm_outside_step_gb`` is a remainder of two parts of opposite
    sign; its note keeps them apart by the two marks of the chip whose
    sum is the run's peak."""
    import jax
    from benchmark.lib import memory_split

    class Chip:
        def __init__(self, in_use, reserved):
            self.stats = {'peak_bytes_in_use': in_use,
                          'peak_bytes_reserved': reserved}

        def memory_stats(self):
            return self.stats

    monkeypatch.setattr(jax, 'local_devices',
                        lambda: [Chip(5e9, 1e9), Chip(3e9, 9e9)])
    assert memory_split._marks_at(12e9) == (3e9, 9e9)
    assert memory_split._marks_at(None) == (None, None)
    monkeypatch.setattr(jax, 'local_devices', lambda: [Chip(0, 0)])
    Chip.memory_stats = lambda self: None            # the CPU's answer
    assert memory_split._marks_at(0) == (None, None)
    path = os.path.join(ROOT, 'benchmark', 'layer_metrics',
                        'hbm_outside_step_gb.py')
    spec = importlib.util.spec_from_file_location('outside', path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    text = reader._parts({'outside_in_use_bytes': 39.3e6,
                          'reserved_less_temp_bytes': -710.7e6})
    assert 'truly outside) 39.3 + reserved less temp' in text
    assert text.endswith('-710.7')


def test_a_program_without_the_tables_leaves_the_six_out(
        harness, capsys, monkeypatch):
    """A parent of this PR has no ``memviz.build_tables``: the line has
    every other metric and none of these, and nothing raises."""
    from paddle_tpu.fluid import memviz
    monkeypatch.delattr(memviz, 'build_tables')
    run, root = harness
    _with_the_new_entries(root)
    assert run.main(['--workload', 'tiny_bert', '--seed', '3000000053',
                     '--seconds', '1', '--trace', '1']) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line['correct'] is True
    assert not set(NEW) & set(line['metrics'])
    assert 'device_idle' in line['metrics']
