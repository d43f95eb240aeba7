"""CPU rehearsal of the harness on the Laguna family: the ``laguna``
family file, its configuration layout (per-layer lists kept whole, the
held experts beside the router's published width), the banded traffic
and the per-layer readers this family brought, at the tiny preset in
``presets_laguna/``.  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_laguna')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('window_attention_ms', 'window_flash_roofline',
               'gqa_causal_flash_roofline', 'moe_held_share')


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
        'rehearsed_run_laguna', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_laguna', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    two roofline shares are left out); what is read from the program's
    scope table and its gauges is there, the windowed calls' time apart
    from the whole attention's."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_laguna', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'moe_experts_ms', 'moe_route_ms', 'norm_rope_ms',
            'moe_load_max', 'moe_held_share', 'causal_attention_ms',
            'window_attention_ms', 'matmul_ms', 'optimizer_ms',
            'unscoped_ms'} <= set(got)
    assert 0 < got['window_attention_ms']['value'] < \
        got['causal_attention_ms']['value']
    assert got['moe_experts_ms']['value'] > 0
    # 4 of 16 experts held: a quarter of the pairs where the routing is
    # even, never all and never none on 128 tokens x 4 choices
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert 'window_flash_roofline' not in got     # no kernel off-chip
    assert 'gqa_causal_flash_roofline' not in got


def test_reference_agrees_and_sees_what_the_config_settles(harness):
    """The family's own reference against the f32 for_test program on
    seeded weights, and the same reference with one published number
    changed: each has to land outside the tolerance."""
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_laguna')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        small = {k: v[:1] for k, v in host.items()}
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        scope = fluid.global_scope()
        weights = [fluid.core.as_array(scope.find_var(p)) for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(**changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(config, cell.traffic,
                                                weights, small))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    # Normal(0.02) weights at width 64 leave the loss nearly flat, so
    # what a wrong reading moves is small; each still clears the limit
    assert off(sliding_window=17) > rtol
    assert off(experts_held=[0, 4]) > rtol
    assert off(moe_routed_scaling_factor=1.0) > rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Laguna-S-2.1 forward per token as cut (5 layers, s4096), by
    hand.  Full layer (48 heads): q and o 2 * 2 * 3072 * 6144 =
    75,497,472; k and v 2 * 2 * 3072 * 1024 = 12,582,912; gate 2 *
    3072 * 48 = 294,912; scores + context 4 * 6144 * 2048.5 =
    50,343,936: 138,719,232.  Sliding layer (72 heads): 113,246,208 +
    12,582,912 + 442,368 + 4 * 9216 * (512 * 513 / 2 + 3584 * 512) /
    4096 = 17,697,024: 143,968,512.  Dense MLP 6 * 3072 * 12288 =
    226,492,416.  Sparse: router 2 * 3072 * 256 = 1,572,864; shared 6
    * 3072 * 1024 = 18,874,368; routed 10 * 8 / 256 of that =
    5,898,240: 26,345,472.  Head 2 * 3072 * 12544 = 77,070,336."""
    from benchmark.families import laguna
    from benchmark.lib import laguna_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'laguna-s-2.1.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    assert laguna_flops.visible_pairs(4096) == 4096 * 4097 // 2
    assert laguna_flops.visible_pairs(4096, 512) == \
        512 * 513 // 2 + 3584 * 512
    assert laguna_flops.visible_pairs(4096, 4096) == 4096 * 4097 // 2
    full = laguna_flops.attention_forward_flops_per_token(
        3072, 48, 8, 128, 4096)
    sliding = laguna_flops.attention_forward_flops_per_token(
        3072, 72, 8, 128, 4096, 512)
    assert (full, sliding) == (138719232, 143968512)
    want = 2 * full + 3 * sliding + 226492416 + 4 * 26345472 + 77070336
    assert want == 1118288640
    assert laguna.flops_per_item(config, traffic) == 3 * want
    flops, nbytes = laguna_flops.grouped_flash_train_cost(
        1, 72, 8, 4096, 128, 512)
    assert flops == 7 * 2 * 72 * (512 * 513 // 2 + 3584 * 512) * 128
    assert nbytes == 6 * 80 * 4096 * 128 * 2
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the three cuts; the per-layer lists whole
    published = config['published']
    cut = {'num_hidden_layers': (5, 48), 'num_experts': (8, 256),
           'vocab_size': (12544, 100352)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'laguna-s-2.1'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['num_experts_published'] == published['num_experts']
    assert config['experts_held'] == [0, config['num_experts']]
    assert laguna_flops.layers_of(laguna.sizes(config, traffic)) == [
        ('full_attention', 48, 'dense'),
        ('sliding_attention', 72, 'sparse'),
        ('sliding_attention', 72, 'sparse'),
        ('sliding_attention', 72, 'sparse'),
        ('full_attention', 48, 'sparse')]
    for name in NEW_READERS:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        if name != 'moe_held_share':
            assert reader.read(None, {}) is None
