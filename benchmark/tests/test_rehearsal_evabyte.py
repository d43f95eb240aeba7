"""CPU rehearsal of the harness on the EvaByte family: the ``evabyte``
family file, its configuration layout, the FLOP counts and the
per-layer readers this family brought, at the tiny preset in
``presets_evabyte/`` (hidden 64, 4 heads of 16, windows of 32 over
chunks of 4, three heads of prediction, 128 bytes a sequence: four
windows, so both attention streams run).  Nothing printed here is a
measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_evabyte')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('eva_attention_ms', 'eva_remote_ms',
               'eva_local_flash_roofline', 'eva_remote_flash_roofline',
               'eva_remote_share')


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
        'rehearsed_run_evabyte', os.path.join(copy, 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmark.lib import peaks, trace_reduce
    monkeypatch.setattr(run, 'ACCELERATORS', ('tpu', 'cpu'))
    monkeypatch.setitem(peaks.CHIP_PEAKS, 'cpu', (1.0, 1.0))
    monkeypatch.setattr(trace_reduce, 'device_planes', _cpu_op_planes)
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                       str(tmp_path / 'jax_cache'))
    return run, root


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                       name + '.py'))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def test_window_run_prints_the_contract_line(harness, capsys):
    run, _ = harness
    assert run.main(['--workload', 'tiny_evabyte', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    three readers of kernel calls find nothing and are left out, each
    for that reason); what is read from the program's scope table and
    its gauges is there: the layer's time by op type, the remote
    stream's share of the pairs."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_evabyte', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'eva_attention_ms', 'eva_remote_share', 'norm_rope_ms',
            'matmul_ms', 'optimizer_ms', 'embedding_ms',
            'unscoped_ms'} <= set(got)
    assert got['eva_attention_ms']['value'] > 0
    # four windows of 32 over chunks of 4, a head and sequence: local
    # 4 * 32 * 33 / 2 = 2112 pairs, remote 32 * 8 * (1 + 2 + 3) = 1536
    assert got['eva_remote_share']['value'] == \
        pytest.approx(1536 / (1536 + 2112))
    for kernels_only in ('eva_remote_ms', 'eva_local_flash_roofline',
                         'eva_remote_flash_roofline'):
        assert kernels_only not in got      # no Mosaic call off-chip
    # the layer's parts, named in the reader's note
    assert 'eva_attention_ms: ms a step by part' in out
    for part in ('attention_merge', 'eva_chunk_summary', 'remote',
                 'fused_multihead_attention'):
        assert part in out.split('eva_attention_ms: ms a step')[1] \
            .splitlines()[0]
    assert 'eva/remote_weight_mean' in out


def test_reference_agrees_and_every_part_of_the_mathematics_moves_it(
        harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then each
    part read the OTHER way: the summaries' mu left out, phi left out
    (uniform pooling), a head of the eight dropped from the loss (its
    matrix zeroed), a window or chunk of another size, the gain read
    without its unit offset.  Each has to miss the tolerance by
    orders of magnitude, and so does the reference in bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_evabyte')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    for i in range(3):      # head i's labels: the ids shifted by 1 + i
        assert (host['labels'][:, :127 - i, i] ==
                host['ids'][:, 1 + i:]).all()
        assert (host['labels'][:, 127 - i:, i] == -1).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    small = {k: v[:1] for k, v in host.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        scope = fluid.global_scope()
        rng = np.random.RandomState(5)
        for p in params:
            shape = tuple(fluid.core.as_array(scope.find_var(p)).shape)
            if len(shape) == 1:
                w = 0.5 * rng.randn(*shape)         # gain offsets
            elif shape == (s['num_attention_heads'], s['head_dim']) or \
                    shape[0] == s['vocab_size']:
                w = rng.randn(*shape)               # phi, mu, embedding
            else:
                w = rng.randn(*shape) / np.sqrt(shape[0])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(weights=weights, dtype=None, **changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(
            config, cell.traffic, weights, small, dtype=dtype))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    pooled = [i for i, w in enumerate(weights)
              if w.shape == (s['num_attention_heads'], s['head_dim'])]
    assert pooled == [5, 6, 16, 17]         # phi, mu of the two layers

    def zeroed(which):
        return [0 * w if i in which else w for i, w in enumerate(weights)]

    assert off(zeroed(pooled[1::2])) > 100 * rtol       # no mu
    assert off(zeroed(pooled[0::2])) > 100 * rtol       # no phi
    assert off(zeroed([len(weights) - 1])) > 100 * rtol     # a head less
    assert off(window_size=64) > 100 * rtol
    assert off(chunk_size=8) > 100 * rtol
    gains = [i for i, w in enumerate(weights) if w.ndim == 1]
    assert off([w - 1 if i in gains else w
                for i, w in enumerate(weights)]) > 100 * rtol
    assert off(dtype=jnp.bfloat16) > 100 * rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """EvaByte forward per byte as cut (four layers, s4096), by hand.
    A layer: q, k, v, o 4 * 2 * 4096 * 4096 = 134,217,728; MLP 3 * 2 *
    4096 * 11008 = 270,532,608; scores + context over the visible
    pairs: local 2 * 2048 * 2049 / 2 = 4,196,352 and remote 2048 * 128 =
    262,144 pairs a head and sequence, 4,458,496 / 4096 = 1088.5 a
    byte, times 2 * 2 * 32 * 128 = 17,833,984.  Heads 8 * 2 * 4096 * 320
    = 20,971,520.  The tiny preset by the same rule."""
    from benchmark.families import evabyte
    from benchmark.lib import evabyte_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'evabyte-6.5b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    sizes = evabyte.sizes(config, traffic)
    assert sizes['head_dim'] == 128
    assert evabyte_flops.local_pairs(4096, 2048) == 4196352
    assert evabyte_flops.remote_pairs(4096, 2048, 16) == 262144
    assert evabyte_flops.remote_pairs(2048, 2048, 16) == 0
    # the published 32768 positions: the remote stream is 0.94 of local
    assert round(evabyte_flops.remote_pairs(32768, 2048, 16) /
                 evabyte_flops.local_pairs(32768, 2048), 2) == 0.94
    want = 4 * (134217728 + 270532608 + 17833984) + 20971520
    assert evabyte.flops_per_item(config, traffic) == 3 * want
    assert round(3 * want * 4096 / 1e12, 1) == 21.0     # the issue's
    attention = 4 * 17833984 / want
    assert round(100 * attention, 1) == 4.2
    tiny = json.load(open(os.path.join(PRESETS, 'configs',
                                       'evabyte-tiny.json')))
    tiny_traffic = json.load(open(os.path.join(
        PRESETS, 'workloads', 'tiny_s128_eva.json')))
    pairs = (4 * 32 * 33 // 2 + 32 * 8 * 6) / 128
    by_hand = 2 * (8 * 64 * 64 + 6 * 64 * 96 + 2 * 2 * 4 * 16 * pairs) + \
        3 * 2 * 64 * 41
    assert evabyte.flops_per_item(tiny, tiny_traffic) == 3 * by_hand
    # the streams' costs: seven matmuls a visible pair, q / o / do / dq
    # at full length, the summaries a sixteenth of that
    flops, nbytes = evabyte_flops.local_train_cost(1, 32, 4096, 128, 2048)
    assert flops == 7 * 2 * 32 * 4196352 * 128
    assert nbytes == 12 * 32 * 4096 * 128 * 2
    flops, nbytes = evabyte_flops.remote_train_cost(1, 32, 4096, 128,
                                                    2048, 16)
    assert flops == 7 * 2 * 32 * 262144 * 128
    assert nbytes == (6 + 6 / 16) * 32 * 4096 * 128 * 2
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the depth, no width among the cuts
    published = config['published']
    assert {k: config[k] for k in published
            if k != 'num_hidden_layers'} == \
        {k: v for k, v in published.items() if k != 'num_hidden_layers'}
    assert (config['num_hidden_layers'],
            published['num_hidden_layers'],
            config['num_hidden_layers_published']) == (4, 32, 32)
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'evabyte-6.5b'][0]
    assert entry['reduced'] == ['num_hidden_layers']
    assert len(entry['source']) <= 200 and \
        entry['source'].startswith(config['source'])
    for item in ('phi_logit_unscaled', 'mu_after_pooling',
                 'summaries_pool_rotated_keys', 'phi_mu_startup',
                 'prediction_heads', 'optimizer', 'initializer'):
        assert item in config['assumed']
    row = [json.loads(line) for line in open(
        '/opt/skills/guides/model-configs/architectures.jsonl')
        if '"EvaByte"' in line] if os.path.exists(
        '/opt/skills/guides/model-configs/architectures.jsonl') else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
    cell = [w for w in manifest['workloads']
            if w['name'] == 'evabyte_6b5_s4096'][0]
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        ('evabyte-6.5b', 's4096_b1', 1) and len(cell['why']) <= 200
    declared = {m['name']: m for m in manifest['per_layer']}
    for name in NEW_READERS:
        reader = _reader(name)
        assert declared[name]['workloads'] == ['evabyte_6b5_s4096']
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            declared[name]['layer'], declared[name]['unit'],
            declared[name]['moves'])
        if declared[name]['source'] == 'device_trace':
            assert reader.read(None, {}) is None
    for name in ('matmul_roofline', 'norm_rope_ms'):
        assert declared[name]['workloads'][-1] == 'evabyte_6b5_s4096'


def test_the_share_reader_returns_nothing_without_its_gauges(monkeypatch):
    """A parent of this PR sets no ``eva/`` gauge: the reader leaves
    the metric out and does not raise."""
    from paddle_tpu.fluid import monitor
    monkeypatch.setattr(monitor, '_gauges', {})
    assert _reader('eva_remote_share').read(None, {}) is None
