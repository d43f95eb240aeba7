"""CPU rehearsal of the per-layer metrics that read the program's cost
table (PR 34): a traced run of the tiny presets with the real manifest's
four entries appended prints each where it is declared and nowhere
else, and the cost table as a note.  Keys and control flow only: what a
rehearsal prints is never a measurement (the stubbed peaks are 1 TFLOP/s
and 1 GB/s, so a share here says nothing)."""

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

NEW = ['matmul_roofline', 'conv_roofline', 'collective_mb',
       'collective_calls']
# the real cells' stand-ins among the presets; a cell with none is left out
STAND_IN = {'bert_base_s2048': 'tiny_bert', 'bert_base_s128': 'tiny_bert',
            'bert_base_s512_b48': 'tiny_bert',
            'bert_base_s128_dp4': 'tiny_bert_dp4',
            'resnet50_train': 'tiny_resnet'}


def _with_the_cost_entries(root):
    path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(path))
    real = {m['name']: m for m in json.load(open(os.path.join(
        ROOT, 'BENCHMARK.json')))['per_layer']}
    for name in NEW:
        entry = dict(real[name])
        entry['workloads'] = sorted({STAND_IN[w] for w in entry['workloads']
                                     if w in STAND_IN})
        manifest['per_layer'].append(entry)
    json.dump(manifest, open(path, 'w'))
    return manifest


@pytest.mark.parametrize('cell,declared', [
    ('tiny_bert_dp4', {'matmul_roofline', 'collective_mb',
                       'collective_calls'}),
    ('tiny_resnet', {'conv_roofline'})])
def test_traced_run_prints_the_cost_metrics_where_declared(
        harness, capsys, cell, declared):
    run, root = harness
    manifest = _with_the_cost_entries(root)
    assert {m['name'] for m in manifest['per_layer'] if m['name'] in NEW
            and cell in m['workloads']} == declared
    assert run.main(['--workload', cell, '--seed', '2147483999',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line['correct'] is True
    got = {k: v for k, v in line['metrics'].items() if k in NEW}
    assert set(got) == declared
    assert all(v['value'] > 0 for v in got.values())
    units = {m['name']: m['unit'] for m in manifest['per_layer']}
    assert all(v['unit'] == units[k] for k, v in got.items())
    notes = '\n'.join(out)
    assert 'roofline: cost by fluid op, chip 0, per step, against' in notes
    assert 'longest instructions' in notes
    if cell == 'tiny_bert_dp4':
        assert 'collective_mb: collectives of chip 0' in notes
        assert 'hold a dot / convolution' in notes
