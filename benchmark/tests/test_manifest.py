"""``BENCHMARK.json`` and the files its names point at agree."""

import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
MANIFEST = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')


def _reader(directory, name):
    spec = importlib.util.spec_from_file_location(
        'reader_' + name, os.path.join(BENCH, directory, name + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize('entry', MANIFEST['per_layer'],
                         ids=lambda e: e['name'])
def test_every_layer_metric_has_a_reader_that_agrees(entry):
    reader = _reader('layer_metrics', entry['name'])
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
        (entry['layer'], entry['unit'], entry['moves'])
    assert entry['moves'] in {m['name'] for m in MANIFEST['end_to_end']}
    # nothing to read -> nothing returned, for what comes from a trace
    if entry['source'] == 'device_trace':
        assert reader.read(None, {}) is None


@pytest.mark.parametrize('cell', MANIFEST['workloads'],
                         ids=lambda w: w['name'])
def test_every_cell_points_at_its_files(cell):
    configs = {c['name']: c for c in MANIFEST['configs']}
    config = json.load(open(os.path.join(
        ROOT, configs[cell['config']]['file'])))
    traffic = json.load(open(os.path.join(
        BENCH, 'workloads', cell['traffic'] + '.json')))
    for directory, name in (('families', config['family']),
                            ('layouts', traffic['layout'])):
        assert os.path.exists(os.path.join(BENCH, directory, name + '.py'))
    assert traffic['batch_per_chip'] % traffic['batch_granularity'] == 0
    assert cell['chips'] in (1, 4) and len(cell['why']) <= 200
    assert (cell['chips'] == 4) == (traffic['layout'] != 'single')


@pytest.mark.parametrize('entry', MANIFEST['end_to_end'],
                         ids=lambda e: e['name'])
def test_every_end_to_end_metric_has_a_reader_that_agrees(entry):
    assert _reader('end_to_end', entry['name']).UNIT == entry['unit']
    assert entry['source'] in ('host_clock', 'device_trace')


def test_names_units_and_shares_are_within_the_contract():
    names = [e['name'] for group in ('configs', 'workloads', 'end_to_end',
                                     'per_layer')
             for e in MANIFEST[group]]
    assert all(NAME.match(n) for n in names)
    for group in ('end_to_end', 'per_layer'):
        assert len({m['name'] for m in MANIFEST[group]}) == \
            len(MANIFEST[group])
        for m in MANIFEST[group]:
            assert re.match(r'^[A-Za-z0-9_/%.-]{1,16}$', m['unit'])
            assert m['better'] in ('lower', 'higher')
    four = [w for w in MANIFEST['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(MANIFEST['workloads']) // 4)
    assert {m['name'] for m in MANIFEST['end_to_end']} >= {'setup_s'}
    assert all(0.01 <= m['bound'] <= 0.1 for m in MANIFEST['end_to_end'])
    # a full check of 24 cells fits the driver's budget
    seconds = MANIFEST['run_seconds']
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 180 + 1200 <= 43200


def test_the_harness_sets_no_flags_and_names_no_cell():
    source = open(os.path.join(BENCH, 'run.py')).read()
    assert 'FLAGS_' not in source and 'set_flags' not in source
    for entry in MANIFEST['workloads'] + MANIFEST['configs'] + \
            MANIFEST['per_layer'] + MANIFEST['end_to_end']:
        assert "'%s'" % entry['name'] not in source
