"""CPU rehearsal of the harness on the Phi-4-mini-flash family: the
``phi4flash`` family file, its configuration layout (the catalog's keys
as run beside the public code's default sizes), the FLOP counts and the
per-layer readers this family brought, at the tiny preset in
``presets_phi4flash/``.  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_phi4flash')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('ssm_scan_ms', 'ssm_scan_roofline', 'ssm_chunks',
               'ssm_state_mb', 'diff_flash_roofline')
CELL = 'phi4_mini_flash_s8192'


@pytest.fixture
def harness(tmp_path, monkeypatch):
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
        'rehearsed_run_phi4flash', os.path.join(copy, 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmark.lib import peaks, trace_reduce
    monkeypatch.setattr(run, 'ACCELERATORS', ('tpu', 'cpu'))
    monkeypatch.setitem(peaks.CHIP_PEAKS, 'cpu', (1.0, 1.0))
    monkeypatch.setattr(trace_reduce, 'device_planes', _cpu_op_planes)
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                       str(tmp_path / 'jax_cache'))
    return run, root


def test_window_run_prints_the_contract_line(harness, capsys):
    run, _ = harness
    assert run.main(['--workload', 'tiny_phi4flash', '--seed',
                     '2147483659', '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    flash share is left out); what is read from the program's scope and
    cost tables and its gauges is there, the scan's time, share, chunk
    trips and kept states among them: 96 tokens are one chunk, three
    Mamba layers, each scanned forward, once more in its recompute
    group's second forward, and in reverse; a [2, 4, 128] float32 state
    a layer."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_phi4flash', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'ssm_scan_ms', 'ssm_scan_roofline', 'ssm_chunks',
            'ssm_state_mb', 'short_conv_ms', 'short_conv_roofline',
            'causal_attention_ms', 'window_attention_ms', 'matmul_ms',
            'matmul_roofline', 'optimizer_ms',
            'unscoped_ms'} <= set(got)
    assert got['ssm_scan_ms']['value'] > 0
    assert got['ssm_scan_roofline']['value'] > 0
    assert got['ssm_chunks']['value'] == 3 * 1 * 3
    assert abs(got['ssm_state_mb']['value'] -
               3 * 2 * 4 * 128 * 4 / 1e6) < 1e-9
    assert got['short_conv_ms']['value'] > 0
    assert 0 < got['window_attention_ms']['value'] < \
        got['causal_attention_ms']['value']
    assert 'diff_flash_roofline' not in got     # no kernel off-chip


def test_reference_agrees_and_sees_each_part(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss; then a
    bfloat16 scan state, a dropped D * x, a window of a key fewer, the
    lambdas left at lam0 and bfloat16 throughout: each has to miss the
    tolerance, by one of the cell's limits.  The zoo's reference (the
    same equations, unblocked) agrees with the family's copy."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import phi4flash as zoo_reference
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_phi4flash')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert sorted(host) == ['ids', 'labels']
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    assert s['layer_types'] == [
        'mamba', 'sliding_attention', 'mamba', 'sliding_attention',
        'mamba', 'full_attention', 'gmu', 'cross_attention']
    assert params == cell.family.parameter_names(cell.config,
                                                 cell.traffic)
    small = {k: v[:1] for k, v in host.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        scope = fluid.global_scope()
        rng = np.random.RandomState(5)
        for p in params:
            shape = tuple(fluid.core.as_array(scope.find_var(p)).shape)
            what = p.rsplit('.', 1)[1]
            if what == 'a_log':
                continue
            elif what == 'b_dt':
                w = rng.uniform(-4, 0, shape)
            elif what in ('g', 'subln_g', 'd'):
                w = 1 + 0.3 * rng.randn(*shape)
            elif what in ('lq1', 'lk1', 'lq2', 'lk2'):
                w = 0.5 * rng.randn(*shape)
            elif len(shape) == 1:
                w = 0.3 * rng.randn(*shape)
            elif what in ('conv_w', 'embed_tokens'):
                w = rng.randn(*shape)
            else:
                w = rng.randn(*shape) / np.sqrt(shape[0])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(**kw):
        want = float(cell.family.reference_loss(
            cell.config, cell.traffic, weights, small, **kw))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    assert off(state_dtype=jnp.bfloat16) > 10 * rtol
    assert off(dtype=jnp.bfloat16) > 100 * rtol
    for part in ('skip', 'lambda', 'window_511'):
        assert off(without=(part,)) > 100 * rtol, part
    # one layer's lambda alone (layer 3's, a windowed one)
    one = [0 * w if p.startswith('phi4flash.3.') and
           p.rsplit('.', 1)[1] in ('lq1', 'lq2') else w
           for p, w in zip(params, weights)]
    want = float(cell.family.reference_loss(cell.config, cell.traffic,
                                            one, small))
    assert abs(got - want) / abs(want) > 10 * rtol
    sizes = zoo_reference.sizes_of(cell.family._zoo_config(
        cell.config, cell.traffic))
    zoo = float(zoo_reference.loss(dict(zip(params, weights)),
                                   small['ids'], small['labels'],
                                   sizes=sizes))
    assert abs(got - zoo) <= rtol * abs(zoo)


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Phi-4-mini-flash forward per token as cut (8 layers, 25008
    rows, s8192), by hand.  Matmul parameters: Mamba 2560 x 10240 +
    5120 x 192 + 160 x 5120 + 5120 x 2560 = 41,123,840; attention with
    its own K/V 2560 x 5120 + 2560 x 2560 = 19,660,800; cross 2 x 2560 x
    2560 = 13,107,200; GMU 2 x 2560 x 5120 = 26,214,400; MLP 3 x 2560 x
    10240 = 78,643,200; table 2560 x 25008 = 64,020,480.  Pairs a token:
    the band (512 x 513 / 2 + 7680 x 512) / 8192 = 496.03125, the causal
    half 4096.5; 40 heads x 2 x (64 + 128) = 15,360 FLOPs a pair."""
    from benchmark.families import phi4flash
    from benchmark.lib import phi4flash_flops as count
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'phi-4-mini-flash.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's8192_b1.json')))
    sizes = phi4flash.sizes(config, traffic)
    assert sizes['layer_types'] == [
        'mamba', 'sliding_attention', 'mamba', 'sliding_attention',
        'mamba', 'full_attention', 'gmu', 'cross_attention']
    parameters = count.matmul_parameters(sizes)
    assert parameters == {
        'mamba': 41123840, 'sliding_attention': 19660800,
        'full_attention': 19660800, 'cross_attention': 13107200,
        'gmu': 26214400, 'mlp': 78643200, 'head': 64020480}
    matmuls = 3 * 41123840 + 3 * 19660800 + 13107200 + 26214400 + \
        8 * 78643200 + 64020480
    assert matmuls == 914841600
    pairs = 15360 * (2 * 496.03125 + 2 * 4096.5)
    assert phi4flash.flops_per_item(config, traffic) == \
        3 * (2 * matmuls + pairs)
    # every parameter: the model card's count at the published depth
    # and rows, the issue's 915 M as cut
    assert abs(count.parameter_count(sizes, 32, 200064) - 3.85e9) < 3.85e7
    assert abs(count.parameter_count(sizes) - 915.1e6) < 0.005 * 915.1e6
    flops, nbytes = count.scan_train_cost(1, 8192, 5120, 16)
    wide, narrow, states = 8192 * 5120, 8192 * 16, 32 * 5120 * 16 * 4
    assert flops == 3 * wide * (7 * 16 + 3)
    assert nbytes == wide * (5 * 2 + 3 * 4) + 6 * narrow * 2 + \
        2 * states + 5120 * 17 * 4
    # a function of the SHAPES and the nominal chunk only
    assert count.scan_train_cost(1, 8192, 5120, 16, chunk=256) == \
        (flops, nbytes)
    band, full = (count.diff_flash_train_cost(1, 40, 20, 8192, 64, w)
                  for w in (512, 0))
    assert band[0] == 2 * 40 * (512 * 513 // 2 + 7680 * 512) * \
        (4 * 64 + 3 * 128)
    assert full[0] == 2 * 40 * (8192 * 8193 // 2) * (4 * 64 + 3 * 128)
    assert band[1] == full[1] == 3 * 8192 * 2 * (
        40 * (64 + 128) + 20 * 64 + 10 * 128)
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (8, 32), 'vocab_size': (25008, 200064)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    assert config['vocab_size'] * 8 == published['vocab_size']
    assert config['num_hidden_layers_published'] == 32
    assert config['vocab_size_published'] == 200064
    assert config['head_dim'] * config['num_attention_heads'] == \
        config['hidden_size']
    assert config['mamba_dt_rank'] == -(-config['hidden_size'] // 16)
    assert set(config['assumed']) >= {
        'mamba', 'attention_bias', 'differential', 'mlp', 'startup',
        'layer_rule', 'head_dim', 'optimizer'}
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'phi-4-mini-flash'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"Phi-4-mini-flash-reasoning"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source'] == entry['source']
    cells = [w for w in manifest['workloads']
             if w['config'] == 'phi-4-mini-flash']
    assert [(w['name'], w['traffic'], w['chips']) for w in cells] == \
        [(CELL, 's8192_b1', 1)]
    declared = {m['name'] for m in manifest['per_layer']
                if CELL in m.get('workloads', ())}
    assert declared == set(NEW_READERS) | {
        'matmul_roofline', 'causal_attention_ms', 'window_attention_ms',
        'short_conv_ms', 'short_conv_roofline'}
    from paddle_tpu.fluid import monitor
    monitor.reset()             # no program: the gauges are not there
    for name in NEW_READERS:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
