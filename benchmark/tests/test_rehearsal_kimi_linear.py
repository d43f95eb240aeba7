"""CPU rehearsal of the harness on the Kimi Linear family: the
``kimi_linear`` family file, its configuration layout (layers numbered
from 1, the published ``linear_attn_config`` whole, the held experts
beside the router's published width), the FLOP counts and the
per-layer readers the cell is listed under, at the tiny preset in
``presets_kimi_linear/``.  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_kimi_linear')
ROOT = os.path.dirname(BENCH)
CELL = 'kimi_linear_48b_s8192'
LISTED = ('moe_experts_ms', 'moe_route_ms', 'moe_load_max',
          'moe_held_share', 'moe_bias_max', 'causal_attention_ms',
          'mla_flash_roofline', 'norm_rope_ms', 'matmul_roofline',
          'short_conv_ms', 'short_conv_roofline', 'kda_ms',
          'kda_roofline', 'kda_chunks')


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
        'rehearsed_run_kimi_linear', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_kimi', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    latent flash share is left out); what is read from the program's
    scope and cost tables and its gauges is there, the delta rule's
    time inside its recompute groups, its share and its chunk steps
    among them: 96 tokens are 2 chunks of 64, four delta-rule layers,
    each scanned forward, forward again by its group (the last block
    is none) and in reverse."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_kimi', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert set(LISTED) - {'mla_flash_roofline'} <= set(got)
    assert {'matmul_ms', 'optimizer_ms', 'unscoped_ms'} <= set(got)
    assert got['kda_ms']['value'] > 0
    assert 0 < got['kda_roofline']['value']
    assert got['kda_chunks']['value'] == (3 * 3 + 2) * 2
    assert got['short_conv_ms']['value'] > 0
    assert got['causal_attention_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert 'mla_flash_roofline' not in got      # no kernel off-chip


def _wrong_ways(weights, s):
    """{reading: weights that compute the model the OTHER way}: each
    has to miss the tolerance."""
    import numpy as np
    d = s['linear_attn_config']['head_dim']
    heads = s['linear_attn_config']['num_heads']
    taps = s['linear_attn_config']['short_conv_kernel_size']
    rank, rope = s['kv_lora_rank'], s['qk_rope_head_dim']
    filters = [i for i, w in enumerate(weights)
               if w.shape == (heads * d, taps)]
    decays = [i for i, w in enumerate(weights)
              if w.shape == (d, heads * d)]     # Wf_up, then Wg_up
    wkva = [i for i, w in enumerate(weights)
            if w.shape == (s['hidden_size'], rank + rope)]
    assert (len(filters), len(decays), len(wkva)) == (12, 8, 1)

    def swapped(indices, change):
        return [change(w) if i in indices else w
                for i, w in enumerate(weights)]

    def per_head(w):
        w = w.reshape(d, heads, d)
        return np.repeat(w[:, :, :1], d, 2).reshape(d, heads * d)

    def no_shared_key(w):
        w = w.copy()
        w[:, rank:] = 0
        return w

    return {
        'taps in the other order': swapped(filters, lambda w: w[:, ::-1]),
        'decay per head, not per channel':
            swapped(decays[0::2], per_head),
        'the shared key slice dropped from the product':
            swapped(wkva, no_shared_key),
    }


def test_reference_agrees_and_sees_what_the_config_leaves_open(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then each
    reading the catalog's row does not settle read the OTHER way: the
    taps in the other order, the decay per head, the shared key slice
    dropped or ROTATED, beta WITH Solar's 2, a wrong expert share, no
    choice bias, a dropped 2.446.  Each has to miss the tolerance."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import kimi_linear as zoo_reference
    from paddle_tpu.models.reference import moonlight as moon_reference
    from paddle_tpu.models.reference import solar_open2 as solar_reference
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_kimi')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert sorted(host) == ['ids', 'labels']
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    assert s['layer_types'] == ['kda', 'kda', 'kda', 'full_attention',
                                'kda']
    assert s['mlp_layer_types'] == ['dense'] + ['sparse'] * 4
    assert (s['num_hidden_layers'], s['layers_held']) == (1, 5)
    width = s['hidden_size']
    linear = s['linear_attn_config']
    d, heads, taps = linear['head_dim'], linear['num_heads'], \
        linear['short_conv_kernel_size']
    small = {k: v[:1] for k, v in host.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        scope = fluid.global_scope()
        rng = np.random.RandomState(5)
        for p in params:
            shape = tuple(fluid.core.as_array(scope.find_var(p)).shape)
            if shape == (s['num_experts_published'],):
                w = 0.3 * rng.randn(*shape)             # choice bias
            elif shape == (heads,):
                w = np.log(rng.uniform(1, 16, shape))   # A_log
            elif shape == (heads * d,):
                w = rng.uniform(-3, 1, shape)           # dt_bias
            elif len(shape) == 1:
                w = 1 + 0.5 * rng.randn(*shape)         # gains
            elif shape == (heads * d, taps) or \
                    shape[0] == s['vocab_size']:
                w = rng.randn(*shape)
            elif shape == (width, s['num_experts_published']):
                w = 4 * rng.randn(*shape) / np.sqrt(width)
            else:
                w = rng.randn(*shape) / np.sqrt(shape[-2])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(weights=weights, **changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(config, cell.traffic,
                                                weights, small))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    for reading, other in _wrong_ways(weights, s).items():
        # the latent layer is one of five and 8 of its 24 key features
        assert off(other) > (10 if 'key' in reading else 100) * rtol, \
            reading
    assert off(experts_held=[0, 4]) > 100 * rtol
    assert off(num_experts_per_token=2) > 100 * rtol
    assert off(routed_scaling_factor=1.0) > 100 * rtol
    biases = [i for i, w in enumerate(weights)
              if w.shape == (s['num_experts_published'],)]
    assert len(biases) == 4
    no_bias = [0 * w if i in biases else w for i, w in enumerate(weights)]
    assert off(no_bias) > 10 * rtol

    # the readings no weight expresses, through the zoo's reference
    # (the same equations: it agrees with the family's copy first)
    sizes = zoo_reference.sizes_of(cell.family._zoo_config(
        cell.config, cell.traffic))
    trainable = [w for i, w in enumerate(weights) if i not in biases]
    held_biases = [weights[i] for i in biases]

    def zoo_off():
        want = float(zoo_reference.loss(
            trainable, held_biases, small['ids'], small['labels'],
            sizes=sizes))
        return abs(got - want) / abs(want)

    assert zoo_off() <= rtol
    # beta WITH the factor 2 (a Solar-shaped layer)
    real_inputs = solar_reference.kda_inputs
    try:
        def doubled(*args):
            q, k, v, a, beta = real_inputs(*args)
            return q, k, v, a, 2.0 * beta
        solar_reference.kda_inputs = doubled
        assert zoo_off() > 100 * rtol
    finally:
        solar_reference.kda_inputs = real_inputs
    # the shared key slice and the queries' last 64 ROTATED (a
    # Moonlight-shaped layer)
    real_attention = zoo_reference.nope_attention
    try:
        def rotated(u, *w, **kw):
            b, t, _ = u.shape
            return moon_reference.attention(
                u, jnp.broadcast_to(jnp.arange(t), (b, t)), *w[:5],
                dict(w[5], rope_theta=s['rope_theta']))
        zoo_reference.nope_attention = rotated
        # 8 of 24 features of one layer of five over 96 positions: the
        # operator alone moves by 5% (tests/test_kimi_linear.py)
        assert zoo_off() > 3 * rtol
    finally:
        zoo_reference.nope_attention = real_attention


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Kimi-Linear-48B-A3B forward per token as cut (layers 1 to 5,
    s8192), by hand.  Delta-rule operator: Wq, Wk, Wv, Wo 4 x 2 x 2304
    x 4096 = 75,497,472; two low-rank gates 2 x 2 x (2304 x 128 + 128 x
    4096) = 3,276,800; beta 2 x 2304 x 32 = 147,456; the recurrence
    8,937,472 a chunk of 64 and head, over 64 tokens x 32 heads =
    4,468,736 a token: 83,390,464.  Latent operator: Wq 2 x 2304 x
    6144 = 28,311,552; Wkva 2 x 2304 x 576 = 2,654,208; Wkvb 2 x 512 x
    8192 = 8,388,608; Wo 2 x 4096 x 2304 = 18,874,368; scores +
    context 2 x 32 x 320 x 4096.5 = 83,896,320: 142,125,056.  Dense
    MLP 6 x 2304 x 9216 = 127,401,984.  Sparse MLP: router 2 x 2304 x
    256 = 1,179,648; shared 6 x 2304 x 1024 = 14,155,776; routed 8 x 8
    / 256 = 0.25 of that = 3,538,944: 18,874,368.  Head 2 x 2304 x
    20480 = 94,371,840."""
    from benchmark.families import kimi_linear
    from benchmark.lib import kimi_linear_flops, moonlight_flops, \
        solar_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'kimi-linear-48b-a3b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's8192_b1.json')))
    sizes = kimi_linear.sizes(config, traffic)
    assert sizes['layer_types'] == ['kda', 'kda', 'kda',
                                    'full_attention', 'kda']
    assert sizes['mlp_layer_types'] == ['dense'] + ['sparse'] * 4
    # what `mla_flash_roofline.py` multiplies one layer's calls by, and
    # what `kda_roofline.py` counts
    assert (sizes['num_hidden_layers'], sizes['layers_held']) == (1, 5)
    assert sum(k == solar_flops.KDA for k in sizes['layer_types']) == 4
    delta = kimi_linear_flops.operator_forward_flops_per_token(
        sizes, 'kda', 8192)
    latent = kimi_linear_flops.operator_forward_flops_per_token(
        sizes, 'full_attention', 8192)
    assert (delta, latent) == (83390464, 142125056)
    dense = kimi_linear_flops.mlp_forward_flops_per_token(sizes, 'dense')
    sparse = kimi_linear_flops.mlp_forward_flops_per_token(sizes,
                                                           'sparse')
    assert (dense, sparse) == (127401984, 18874368)
    want = 4 * delta + latent + dense + 4 * sparse + 94371840
    assert want == 772958208
    assert kimi_linear.flops_per_item(config, traffic) == 3 * want
    # the two hand counts the readers take, at this cell's shapes
    flops, _ = solar_flops.kda_train_cost(1, 8192, 32, 128)
    assert flops == 3 * 128 * 32 * 8937472
    flops, nbytes = moonlight_flops.latent_flash_train_cost(
        1, 32, 8192, 192, 128)
    assert flops == 2 * 32 * (8192 * 8193 // 2) * (4 * 192 + 3 * 128)
    assert nbytes == 6 * 32 * 8192 * 2 * 320
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (5, 27), 'num_experts': (8, 256),
           'vocab_size': (20480, 163840)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    assert (config['hidden_size'], config['intermediate_size'],
            config['moe_intermediate_size'], config['kv_lora_rank'],
            config['qk_nope_head_dim'], config['qk_rope_head_dim'],
            config['v_head_dim'], config['num_attention_heads'],
            config['num_experts_per_token'],
            config['routed_scaling_factor']) == \
        (2304, 9216, 1024, 512, 128, 64, 128, 32, 8, 2.446)
    assert (config['linear_attn_config']['num_heads'],
            config['linear_attn_config']['head_dim']) == (32, 128)
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'kimi-linear-48b-a3b'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['num_experts_published'] == published['num_experts']
    assert config['experts_held'] == [0, config['num_experts']]
    assert '32 chips' in config['deployment']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"Kimi-Linear-48B-A3B-Instruct"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
        assert entry['source'].startswith(config['source'] + ' ')
    cells = [w for w in manifest['workloads']
             if w['config'] == 'kimi-linear-48b-a3b']
    assert [(w['name'], w['traffic'], w['chips']) for w in cells] == \
        [(CELL, 's8192_b1', 1)]
    listed = [m['name'] for m in manifest['per_layer']
              if CELL in m.get('workloads', ())]
    assert sorted(listed) == sorted(LISTED)
    from paddle_tpu.fluid import monitor
    monitor.reset()             # no program: the gauge is not there
    for name in ('kda_ms', 'kda_roofline', 'kda_chunks',
                 'mla_flash_roofline'):
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
