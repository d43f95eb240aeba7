"""CPU rehearsal of the harness on the routed-decoder family: the
``olmoe`` family file, its configuration layout, the causal traffic
and the per-layer readers this family brought, at the tiny preset in
``presets_olmoe/`` (a manifest and presets of this file's own; the
BERT / ResNet rehearsal's are untouched).  Nothing printed here is a
measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_olmoe')
NEW_READERS = {'moe_experts_ms', 'moe_route_ms', 'moe_expert_roofline',
               'causal_attention_ms', 'causal_flash_roofline',
               'norm_rope_ms', 'moe_load_max'}


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
        'rehearsed_run_olmoe', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_olmoe', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call:
    ``causal_flash_roofline`` and ``causal_attention_ms`` may be left
    out); what is read from the program's scope table and its gauge
    is there."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_olmoe', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'moe_experts_ms', 'moe_route_ms', 'moe_expert_roofline',
            'norm_rope_ms', 'moe_load_max', 'matmul_ms', 'optimizer_ms',
            'embedding_ms', 'unscoped_ms'} <= set(got)
    assert got['moe_experts_ms']['value'] > 0
    assert got['moe_route_ms']['value'] > 0
    assert got['norm_rope_ms']['value'] > 0
    assert got['optimizer_ms']['value'] > 0       # adamw is counted
    assert 1.0 <= got['moe_load_max']['value'] <= 8.0
    assert 'causal_flash_roofline' not in got     # no kernel off-chip


def test_reference_agrees_and_sees_a_dropped_auxiliary_loss(harness):
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_olmoe')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert (host['labels'][:, -1] == -1).all()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        small = {k: v[:1] for k, v in host.items()}
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        scope = fluid.global_scope()
        weights = [fluid.core.as_array(scope.find_var(p)) for p in params]
    rtol = cell.family.REFERENCE_RTOL
    family = cell.family
    for dropped in ('AUX_WEIGHT', 'Z_WEIGHT'):
        kept = getattr(family, dropped)
        setattr(family, dropped, 0.0)
        try:
            want = float(family.reference_loss(cell.config, cell.traffic,
                                               weights, small))
        finally:
            setattr(family, dropped, kept)
        assert abs(got - want) > rtol * abs(want), dropped


def test_flops_by_hand_and_the_new_readers_find_nothing_without_a_trace():
    """OLMoE-1B-7B forward per token at depth 1, s4096, by hand: q, k,
    v, o 4 * 2 * 2048^2 = 33,554,432; causal scores + context 2 * 4096
    * 2048 = 16,777,216; router 2 * 2048 * 64 = 262,144; eight experts
    8 * 3 * 2 * 2048 * 1024 = 100,663,296; head 2 * 2048 * 50304 =
    206,045,184: 357,302,272."""
    from benchmark.families import olmoe
    from benchmark.lib import decoder_flops
    assert decoder_flops.routed_decoder_forward_flops_per_token(
        1, 2048, 1024, 64, 8, 4096, 50304) == 357302272
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'olmoe-1b-7b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b2.json')))
    assert olmoe.flops_per_item(config, traffic) == 3 * 357302272
    # grouped matmuls of one layer at S*k = 3 * 4096 * 8 rows
    flops, nbytes = decoder_flops.grouped_gated_mlp_train_cost(
        98304, 2048, 1024, 64)
    assert flops == 9 * 2 * 98304 * 2048 * 1024
    assert nbytes == 2 * (9 * 64 * 2048 * 1024 + 6 * 98304 * 2048 +
                          12 * 98304 * 1024)
    # the catalog's keys, as run, at the file's top level
    published = config['published']
    assert {k: config[k] for k in published
            if k != 'num_hidden_layers'} == \
        {k: v for k, v in published.items() if k != 'num_hidden_layers'}
    assert (config['num_hidden_layers'],
            published['num_hidden_layers']) == (1, 16)
    for name in NEW_READERS - {'moe_load_max'}:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
