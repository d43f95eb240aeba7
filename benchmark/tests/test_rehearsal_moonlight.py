"""CPU rehearsal of the harness on the Moonlight family: the
``moonlight`` family file, its configuration layout (the held experts
beside the router's published width, the two assumed numbers of the
choice bias), the traffic and the per-layer readers this family
brought, at the tiny preset in ``presets_moonlight/``.  Nothing printed
here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_moonlight')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('mla_flash_roofline', 'moe_bias_max')


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
        'rehearsed_run_moonlight', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_moonlight', '--seed',
                     '2147483659', '--seconds', '0.5', '--trace',
                     '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    roofline share is left out); what is read from the program's scope
    table and its gauges is there, the choice bias's among them."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_moonlight', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'moe_experts_ms', 'moe_route_ms', 'norm_rope_ms',
            'moe_load_max', 'moe_held_share', 'moe_bias_max',
            'causal_attention_ms', 'matmul_ms', 'optimizer_ms',
            'unscoped_ms'} <= set(got)
    assert got['causal_attention_ms']['value'] > 0
    assert got['moe_experts_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    # Normal(0, 0.05) over 16 experts and two layers, moved by 0.001 a
    # step for a handful of steps
    assert 0.03 < got['moe_bias_max']['value'] < 0.3
    assert 'mla_flash_roofline' not in got        # no kernel off-chip


def test_reference_agrees_and_sees_what_the_config_settles(harness):
    """The family's own reference against the f32 for_test program on
    seeded weights (the choice bias among them, in creation order),
    and the same reference with one published number changed: each has
    to land outside the tolerance."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_moonlight')
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
    biases = [i for i, w in enumerate(weights) if w.shape == (16,)]
    assert len(biases) == 2 and all(
        np.abs(weights[i]).max() > 0 for i in biases)

    def off(weights=weights, **changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(config, cell.traffic,
                                                weights, small))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    # Normal(0.02) weights at width 64 leave the loss nearly flat, so
    # what a wrong reading moves is small; each still clears the limit
    assert off(experts_held=[0, 4]) > rtol
    assert off(routed_scaling_factor=1.0) > rtol
    assert off(num_experts_per_tok=3) > rtol
    # and a reference that leaves the bias out picks other experts
    no_bias = [0 * w if i in biases else w for i, w in enumerate(weights)]
    assert off(no_bias) > rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Moonlight-16B-A3B forward per token as cut (6 layers, s8192), by
    hand.  Attention: Wq 2 * 2048 * 3072 = 12,582,912; Wkva 2 * 2048 *
    576 = 2,359,296; Wkvb 2 * 512 * 4096 = 4,194,304; Wo 2 * 2048 *
    2048 = 8,388,608; scores + context 2 * 16 * 320 * 4096.5 =
    41,948,160: 69,473,280.  Dense MLP 6 * 2048 * 11264 = 138,412,032.
    Sparse: router 2 * 2048 * 64 = 262,144; shared 6 * 2048 * 2816 =
    34,603,008; routed 6 * 8 / 64 of 6 * 2048 * 1408 = 12,976,128:
    47,841,280.  Head 2 * 2048 * 20480 = 83,886,080."""
    from benchmark.families import moonlight
    from benchmark.lib import moonlight_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'moonlight-16b-a3b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's8192_b1.json')))
    sizes = moonlight.sizes(config, traffic)
    assert moonlight_flops.visible_pairs(8192) == 8192 * 8193 // 2
    assert moonlight_flops.attention_forward_flops_per_token(
        sizes, 8192) == 69473280
    layers = config['num_hidden_layers']
    want = layers * 69473280 + 138412032 + (layers - 1) * 47841280 + \
        83886080
    assert moonlight.flops_per_item(config, traffic) == 3 * want
    flops, nbytes = moonlight_flops.latent_flash_train_cost(
        1, 16, 8192, 192, 128)
    assert flops == 2 * 16 * (8192 * 8193 // 2) * (4 * 192 + 3 * 128)
    assert nbytes == 6 * 16 * 8192 * 2 * (192 + 128)
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the three cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (layers, 27), 'n_routed_experts': (8, 64),
           'vocab_size': (20480, 163840)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'moonlight-16b-a3b'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['n_routed_experts_published'] == \
        published['n_routed_experts']
    assert config['experts_held'] == [0, config['n_routed_experts']]
    for name in NEW_READERS:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        if name != 'moe_bias_max':
            assert reader.read(None, {}) is None
