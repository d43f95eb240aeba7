"""CPU rehearsal of the per-layer metrics the program itself feeds (PR
23): a traced run of the tiny presets with the real manifest's new
``per_layer`` entries appended to the presets' manifest prints every one
of them that applies to the cell, the by-scope table as a note, and the
sums the definitions promise.  Keys and control flow only: what a
rehearsal prints is never a measurement."""

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

# the real cells' stand-ins among the presets
STAND_IN = {'bert_base_s2048': 'tiny_bert', 'bert_base_s128': 'tiny_bert',
            'bert_base_s128_dp4': 'tiny_bert_dp4',
            'resnet50_train': 'tiny_resnet'}
SCOPED = ['optimizer_ms', 'embedding_ms', 'matmul_ms', 'attention_ms',
          'conv_bn_ms', 'unscoped_ms']
PHASES = ['exec_bind_ms', 'exec_place_ms', 'exec_dispatch_ms',
          'exec_release_ms', 'exec_unspanned_ms']


def _with_the_real_entries(root):
    path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(path))
    have = {m['name'] for m in manifest['per_layer']}
    for entry in json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))[
            'per_layer']:
        if entry['name'] not in have:
            entry = dict(entry)
            if 'workloads' in entry:
                entry['workloads'] = sorted(
                    {STAND_IN[w] for w in entry['workloads']})
            manifest['per_layer'].append(entry)
    json.dump(manifest, open(path, 'w'))
    return manifest


@pytest.mark.parametrize('cell', ['tiny_bert_dp4', 'tiny_resnet'])
def test_traced_run_prints_the_program_fed_metrics(harness, capsys, cell):
    run, root = harness
    manifest = _with_the_real_entries(root)
    assert run.main(['--workload', cell, '--seed', '3000000000',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line['correct'] is True
    got = {k: v['value'] for k, v in line['metrics'].items()}
    applies = {m['name'] for m in manifest['per_layer']
               if cell in m.get('workloads', [cell])}
    assert set(got) >= applies - {'flash_roofline', 'collective_exposed_ms'}
    assert ('conv_bn_ms' in got) == (cell == 'tiny_resnet')
    assert ('attention_ms' in got) == ('attention_ms' in applies)
    # the split never exceeds the device time it splits
    scoped = sum(got[n] for n in SCOPED if n in got)
    assert scoped <= (got['xla_ops_ms'] + got['pallas_ms']) * (1 + 1e-9)
    assert (got['conv_bn_ms'] if cell == 'tiny_resnet'
            else got['matmul_ms']) > 0
    # the five phases make up the host's clock around a quiet run (the
    # annotation is a little wider than the clock inside it)
    assert sum(got[n] for n in PHASES) == pytest.approx(
        got['host_run_ms'], rel=0.25)
    if cell == 'tiny_bert_dp4':
        assert got['exec_place_ms'] > 0
    assert all(got[n] > 0 for n in ('setup_import_s', 'setup_trace_s',
                                    'setup_backend_s'))
    notes = '\n'.join(out)
    assert 'device time by fluid op, chip 0' in notes
    assert 'setup_backend_s: built' in notes
