"""CPU rehearsal of the harness on the SDAR family: the ``sdar`` family
file (a batch that is a CORRUPTION, an item that is a data token, a
reference over ONE dense [2L, 2L] mask), its configuration layout, the
FLOP counts and the per-layer readers the cell is listed under, at the
tiny preset in ``presets_sdar/``.  Nothing printed here is a
measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_sdar')
ROOT = os.path.dirname(BENCH)
CELL = 'sdar_30b_a3b_s4096'
SHARED = ('moe_experts_ms', 'moe_route_ms', 'moe_load_max',
          'moe_held_share', 'norm_rope_ms', 'matmul_roofline')
NEW = ('bd_attention_ms', 'bd_flash_roofline', 'bd_tiles_visited')


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
        'rehearsed_run_sdar', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_sdar', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs: no Mosaic call and
    no tile is walked, so the kernels' two metrics are left out (the
    next test reads them from a trace and a gauge made by hand); what
    is read from the program's scope table is there, the three parts
    of block diffusion's attention and their merge among it."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_sdar', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert set(SHARED) | {'bd_attention_ms'} <= set(got)
    assert {'matmul_ms', 'optimizer_ms', 'unscoped_ms'} <= set(got)
    assert got['bd_attention_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert not {'bd_flash_roofline', 'bd_tiles_visited'} & set(got)
    from paddle_tpu.fluid import monitor
    # 2 sequences of 64 in blocks of 4: 64^2 pairs a layer with both
    # copies, 64 x 60 / 2 in the last
    assert monitor.gauge_value('sdar/visible_pairs') == \
        2 * (2 * 64 * 64 + 64 * 60 // 2)
    assert 0.2 < monitor.gauge_value('sdar/masked_share') < 0.8


class _Chip(object):
    def __init__(self, ops):
        self.ops = ops

    def matching_ns(self, pattern, kind):
        import re
        return sum(ns for name, k, ns in self.ops
                   if k == kind and re.search(pattern, name))


class _Trace(object):
    """What the two kernel readers take of a reduced trace."""
    steps = 2

    def __init__(self, ops):
        self.first = _Chip(ops)

    def per_step_ms(self, ns):
        return ns / 1e6 / self.steps


def test_the_kernels_metrics_from_a_trace_and_a_gauge_made_by_hand(
        harness):
    """``bd_flash_roofline`` over Mosaic calls named after the
    ``block<n>_<relation>`` scopes and no others, with the FLOPs of the
    mask's visible pairs; ``bd_tiles_visited`` from the gauge the
    lowerings sum into."""
    from benchmark.lib import peaks, sdar_flops
    from benchmark.lib.trace_reduce import MOSAIC, OTHER
    from paddle_tpu.fluid import monitor
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import flash_attention as fa
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_sdar')
    measured = {'cell': cell, 'device_kind': 'cpu'}
    roofline, tiles = (_reader(n) for n in NEW[1:])
    trace = _Trace([('block4_strict/pallas_call', MOSAIC, 4e9),
                    ('block4_causal/pallas_call', MOSAIC, 3e9),
                    ('fused_multihead_attention/x', MOSAIC, 5e9),
                    ('block4_causal/fusion', OTHER, 9e9)])
    s = cell.family.sizes(cell.config, cell.traffic)
    flops, nbytes = sdar_flops.block_flash_train_cost(s, 2, 64)
    # three layers: two run both calls, the last the strict one alone
    assert flops == 7 * 2 * 2 * 4 * 16 * (2 * 64 * 68 // 2 +
                                          3 * 64 * 60 // 2)
    assert nbytes == 5 * 6 * (4 + 2) * 2 * 64 * 16 * 2
    peak_flops, peak_bytes = peaks.chip_peak('cpu')
    want = 100.0 * max(flops / peak_flops, nbytes / peak_bytes) / \
        (7e9 / 1e9 / 2)
    assert roofline.read(trace, measured) == pytest.approx(want)
    assert roofline.read(_Trace([('fused_multihead_attention/x', MOSAIC,
                                  5e9)]), measured) is None
    assert roofline.read(None, measured) is None
    monitor.reset()
    assert tiles.read(None, {}) is None
    registry.begin_trace()
    fa._count_tiles(8, 128, 128, (4, 1), (64, 64))
    fa._count_tiles(8, 128, 128, (4, 0), (64, 32), passes=2)
    # block-causal: 1 + 2 tiles of 64 x 64; strict: 2 + 4 of 64 x 32
    assert tiles.read(None, {}) == 8 * 3 + 8 * 2 * 6


def test_reference_agrees_and_sees_what_the_config_leaves_open(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then each
    reading the catalog's row does not settle, and each size of the
    share, read the OTHER way.  Each has to miss the tolerance."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_sdar')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert sorted(host) == ['ids', 'noisy_ids', 'pos_ids', 'weights']
    s = cell.family.sizes(cell.config, cell.traffic)
    mask_id = s['vocab_size'] - 1
    assert host['ids'].max() < mask_id
    masked = host['weights'] > 0
    assert (host['noisy_ids'][masked] == mask_id).all()
    assert (host['noisy_ids'][~masked] == host['ids'][~masked]).all()
    assert (host['pos_ids'][:, :64] == host['pos_ids'][:, 64:]).all()
    assert cell.family.items_per_sample(cell.config, cell.traffic) == 64
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
                w = 1 + 0.5 * rng.randn(*shape)         # gains
            elif shape[0] == s['vocab_size']:
                w = rng.randn(*shape)
            elif shape == (s['hidden_size'], s['num_experts_published']):
                w = 4 * rng.randn(*shape) / np.sqrt(shape[0])
            else:
                w = rng.randn(*shape) / np.sqrt(shape[-2])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(feed=small, **changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(config, cell.traffic,
                                                weights, feed))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    assert off(experts_held=[0, 4]) > 100 * rtol
    assert off(num_experts_per_tok=2) > 100 * rtol
    # top-3 of 12 with 4 held: few tokens hold a renormalised gate
    # (1.5e-4 here; tests/test_sdar.py has the mutation at its own
    # tolerance)
    assert off(norm_topk_prob=False) > 3 * rtol
    assert off(assumed=dict(cell.config['assumed'],
                            block_length={'value': 8})) > 100 * rtol
    assert off(rope_theta=10000) > 100 * rtol
    assert off(dict(small, pos_ids=np.arange(128, dtype='int32')[None])) \
        > 100 * rtol
    assert off(dict(small, weights=(small['weights'] > 0).astype(
        'float32'))) > 100 * rtol
    assert off(dict(small, ids=np.roll(small['ids'], -1, 1))) > 100 * rtol
    # and the zoo's reference is the same function
    from paddle_tpu.models.reference import sdar as zoo_reference
    cfg = cell.family._zoo_config(cell.config, cell.traffic)
    want = float(zoo_reference.loss(
        weights, {k: jnp.asarray(v) for k, v in small.items()},
        layers=cfg.layers, head_dim=cfg.head_dim, top_k=cfg.top_k,
        block=cfg.block_length, first=cfg.experts_held[0],
        eps=cfg.rms_eps, theta=cfg.rope_theta))
    assert abs(got - want) <= rtol * abs(want)


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """SDAR-30B-A3B-Chat forward as cut (layers 0 to 5, 4096 data
    tokens), by hand.  A position's token-wise products in a layer: q
    and output projections 2 x 2 x 2048 x 4096 = 33,554,432; k and v 2
    x 2 x 2048 x 512 = 4,194,304; router 2 x 2048 x 128 = 524,288; the
    ONE expected held expert (8 x 16 / 128) 6 x 2048 x 768 = 9,437,184.
    Attention, a head: clean over clean 4096 x 4100 / 2 = 8,396,800
    pairs, corrupted over clean 4096 x 4092 / 2 = 8,380,416, corrupted
    over its own block 4096 x 4 = 16,384; 2 x 2 x 32 x 128 = 16,384
    FLOPs a pair.  A layer that runs both copies: 8192 x 47,710,208 +
    16,384 x 16,793,600; the last: 4096 x 47,710,208 + 4096 x
    4,194,304 (the clean rows' keys and values) + 16,384 x 8,396,800.
    Head 4096 x 2 x 2048 x 18,992."""
    from benchmark.families import sdar
    from benchmark.lib import sdar_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'sdar-30b-a3b-chat.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    sizes = sdar.sizes(config, traffic)
    assert (sizes['block_length'], sizes['t_min']) == (4, 0.001)
    assert sdar_flops.row_forward_flops(sizes) == \
        (33554432, 4194304, 524288 + 9437184)
    assert sdar_flops.visible_pairs(4096, 4) == (8396800, 8380416, 16384)
    whole = 8192 * 47710208 + 16384 * 16793600
    last = 4096 * 47710208 + 4096 * 4194304 + 16384 * 8396800
    head = 4096 * 2 * 2048 * 18992
    want = 5 * whole + last + head
    assert want == 3998748770304
    assert sdar_flops.forward_flops_per_sequence(sizes, 4096) == want
    assert sdar.flops_per_item(config, traffic) == 3 * want / 4096
    assert sdar.items_per_sample(config, traffic) == 4096
    # the roofline reader's count: L^2 pairs a head in five layers, the
    # strict half in the sixth, seven matmuls of 2 x 128 a pair
    flops, nbytes = sdar_flops.block_flash_train_cost(sizes, 1, 4096)
    assert flops == 7 * 2 * 32 * 128 * (5 * 4096 * 4096 + 8380416)
    assert nbytes == 11 * 6 * 36 * 4096 * 128 * 2
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (6, 48), 'num_experts': (16, 128),
           'vocab_size': (18992, 151936)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    assert (config['hidden_size'], config['moe_intermediate_size'],
            config['head_dim'], config['num_attention_heads'],
            config['num_key_value_heads'],
            config['num_experts_per_tok']) == (2048, 768, 128, 32, 4, 8)
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'sdar-30b-a3b-chat'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['num_experts_published'] == published['num_experts']
    assert config['experts_held'] == [0, config['num_experts']]
    assert '8 chips' in config['deployment']
    assert {'block_length', 't_min', 'objective', 'qk_norm', 'mask_id',
            'router', 'final_norm', 'last_layer', 'optimizer',
            'recompute_groups', 'embed_std', 'qk_gain'} <= \
        set(config['assumed'])
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"SDAR-30B-A3B-Chat"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
        assert entry['source'].startswith(config['source'] + ' ')
    cells = [w for w in manifest['workloads']
             if w['config'] == 'sdar-30b-a3b-chat']
    assert [(w['name'], w['traffic'], w['chips']) for w in cells] == \
        [(CELL, 's4096_b1', 1)]
    listed = [m['name'] for m in manifest['per_layer']
              if CELL in m.get('workloads', ())]
    assert sorted(listed) == sorted(SHARED + NEW)
    for m in manifest['per_layer']:
        if m['name'] in NEW:
            assert m['workloads'] == [CELL] and m['moves'] == 'throughput'
    from paddle_tpu.fluid import monitor
    monitor.reset()             # no program: the gauge is not there
    for name in NEW:
        assert _reader(name).read(None, {}) is None
