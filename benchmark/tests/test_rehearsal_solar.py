"""CPU rehearsal of the harness on the Solar Open 2 family: the
``solar_open2`` family file, its configuration layout (the held heads
of both layer kinds beside the router's published width and the held
experts, the nested ``linear_attn_config``), the FLOP counts and the
per-layer readers this family brought, at the tiny preset in
``presets_solar/``.  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_solar')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('kda_ms', 'kda_roofline', 'kda_chunks')


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
        'rehearsed_run_solar', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_solar', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    flash share is left out); what is read from the program's scope and
    cost tables and its gauges is there, the delta rule's time, share
    and chunk steps among them: 96 tokens are 2 chunks of 64, three
    layers, each scanned forward and in reverse."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_solar', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'kda_ms', 'kda_roofline', 'kda_chunks', 'short_conv_ms',
            'short_conv_roofline', 'moe_experts_ms', 'moe_route_ms',
            'norm_rope_ms', 'moe_load_max', 'moe_held_share',
            'moe_bias_max', 'causal_attention_ms', 'matmul_ms',
            'matmul_roofline', 'optimizer_ms',
            'unscoped_ms'} <= set(got)
    assert got['kda_ms']['value'] > 0
    assert got['kda_roofline']['value'] > 0
    assert got['kda_chunks']['value'] == 3 * 2 * 2
    assert got['short_conv_ms']['value'] > 0
    assert got['causal_attention_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert 'gqa_causal_flash_roofline' not in got   # no kernel off-chip


def _wrong_ways(weights, s):
    """{reading: weights or a config change that computes the model the
    OTHER way}: each has to miss the tolerance."""
    import numpy as np
    d = s['linear_attn_config']['head_dim']
    heads = s['linear_attn_config']['num_heads']
    taps = s['linear_attn_config']['short_conv_kernel_size']
    filters = [i for i, w in enumerate(weights)
               if w.shape == (heads * d, taps)]
    decays = [i for i, w in enumerate(weights)
              if w.shape == (d, heads * d)]     # Wf_up, then Wg_up
    assert (len(filters), len(decays)) == (9, 6)

    def swapped(indices, change):
        return [change(w) if i in indices else w
                for i, w in enumerate(weights)]

    def per_head(w):
        # one decay a HEAD: every channel of a head takes the head's
        # first channel's projection
        w = w.reshape(d, heads, d)
        return np.repeat(w[:, :, :1], d, 2).reshape(d, heads * d)

    return {
        'taps in the other order': swapped(filters, lambda w: w[:, ::-1]),
        'decay per head, not per channel':
            swapped(decays[0::2], per_head),
    }


def test_reference_agrees_and_sees_what_the_config_leaves_open(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then each
    reading the catalog's row does not settle read the OTHER way: beta
    without its 2, the decay per head instead of per channel, the taps
    in the other order, the gate before the norm, a wrong held head
    range; and a wrong expert share.  Each has to miss the tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import solar_open2 as zoo_reference
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_solar')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert sorted(host) == ['ids', 'labels']
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    assert s['layer_types'] == ['full_attention', 'kda', 'kda', 'kda']
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
            if shape == (s['n_routed_experts_published'],):
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
            elif shape == (width, s['n_routed_experts_published']):
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
        assert off(other) > 100 * rtol, reading
    # beta without its 2 (kda_allow_neg_eigval read as false)
    assert off(kda_allow_neg_eigval=False) > 100 * rtol
    assert off(experts_held=[0, 4]) > 100 * rtol
    assert off(num_experts_per_tok=2) > 100 * rtol
    biases = [i for i, w in enumerate(weights)
              if w.shape == (s['n_routed_experts_published'],)]
    assert len(biases) == 4
    no_bias = [0 * w if i in biases else w for i, w in enumerate(weights)]
    assert off(no_bias) > 10 * rtol

    # the readings no weight expresses, through the zoo's reference
    # (the same equations: it agrees with the family's copy first)
    sizes = zoo_reference.sizes_of(cell.family._zoo_config(
        cell.config, cell.traffic))
    trainable = [w for i, w in enumerate(weights) if i not in biases]
    held_biases = [weights[i] for i in biases]

    def zoo_off(params=trainable):
        want = float(zoo_reference.loss(
            params, held_biases, small['ids'], small['labels'],
            sizes=sizes))
        return abs(got - want) / abs(want)

    assert zoo_off() <= rtol
    # the gate BEFORE the norm: rms_norm(o * gate) for rms_norm(o) * gate
    real_norm = zoo_reference.rms_norm
    real_operator = zoo_reference.kda_operator
    try:
        def gate_first(u, *w):
            (wq, fq, wk, fk, wv, fv, wf_down, wf_up, a_log, dt_bias, wb,
             g_o, wg_down, wg_up, wo, sz) = w[:16]
            b, t, _ = u.shape
            o = zoo_reference.kda_recurrence(*zoo_reference.kda_inputs(
                u, wq, fq, wk, fk, wv, fv, wf_down, wf_up, a_log,
                dt_bias, wb, sz))
            gate = jax.nn.sigmoid((u @ wg_down) @ wg_up).reshape(o.shape)
            return real_norm(o * gate, g_o, sz['rms_eps']).reshape(
                b, t, -1) @ wo
        zoo_reference.kda_operator = gate_first
        assert zoo_off() > 100 * rtol
    finally:
        zoo_reference.kda_operator = real_operator
    # a wrong held head range: the delta-rule heads' A_log handed in
    # the order of another share (heads 1, 2, 0 for 0, 1, 2)
    a_logs = [i for i, w in enumerate(trainable) if w.shape == (heads,)]
    assert len(a_logs) == 3
    rolled = [np.roll(w, 1) if i in a_logs else w
              for i, w in enumerate(trainable)]
    assert zoo_off(rolled) > 100 * rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Solar-Open2-250B forward per token as cut (layers 0 to 3, 8 of
    64 heads, s4096), by hand.  Softmax operator: Wq, Wgate, Wo 3 x 2 x
    4096 x 1024 = 25,165,824; Wk, Wv 2 x 2 x 4096 x 128 = 2,097,152;
    scores + context 2 x 2 x 8 x 128 x 2048.5 = 8,390,656: 35,653,632.
    Delta-rule operator: Wq, Wk, Wv, Wo 4 x 2 x 4096 x 1024 =
    33,554,432; two low-rank gates 2 x 2 x (4096 x 128 + 128 x 1024) =
    2,621,440; beta 2 x 4096 x 8 = 65,536; the recurrence a chunk of 64
    and head: scores 2 x 2 x 2080 x 128 = 1,064,960, solve 2 x 2048 x
    256 = 1,048,576, state 6 x 64 x 16384 = 6,291,456, B U 2 x 2080 x
    128 = 532,480: 8,937,472, over 64 tokens x 8 heads = 1,117,184 a
    token: 37,358,592.  MLP: router 2 x 4096 x 320 = 2,621,440; shared
    6 x 4096 x 1280 = 31,457,280; routed 8 x 8 / 320 = 0.2 of that =
    6,291,456: 40,370,176.  Head 2 x 4096 x 24576 = 201,326,592."""
    from benchmark.families import solar_open2
    from benchmark.lib import laguna_flops, solar_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'solar-open2-250b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    sizes = solar_open2.sizes(config, traffic)
    assert solar_flops.layers_run(sizes) == sizes['layer_types'] == \
        ['full_attention', 'kda', 'kda', 'kda']
    assert solar_flops.kda_chunk_forward_flops(128) == 8937472
    softmax = solar_flops.operator_forward_flops_per_token(
        sizes, 'full_attention', 4096)
    delta = solar_flops.operator_forward_flops_per_token(
        sizes, 'kda', 4096)
    assert (softmax, delta) == (35653632, 37358592)
    want = softmax + 3 * delta + 4 * 40370176 + 201326592
    assert solar_open2.flops_per_item(config, traffic) == 3 * want
    flops, nbytes = solar_flops.kda_train_cost(1, 4096, 8, 128)
    tensor, states = 4096 * 8 * 128, 64 * 8 * 128 * 128 * 4
    assert flops == 3 * 64 * 8 * 8937472
    assert nbytes == 11 * tensor * 2 + 3 * tensor * 4 + \
        3 * 4096 * 8 * 2 + 2 * states
    # a function of the SHAPES and the nominal chunk only
    assert solar_flops.kda_train_cost(1, 4096, 8, 128, chunk=64) == \
        (flops, nbytes)
    # the grouped flash calls' count takes head counts and width from
    # this family's sizes (the reader `gqa_causal_flash_roofline` uses)
    assert laguna_flops.layers_of(sizes)[0] == ('full_attention', 8,
                                                'sparse')
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (4, 48), 'n_routed_experts': (8, 320),
           'vocab_size': (24576, 196608), 'num_attention_heads': (8, 64),
           'num_key_value_heads': (1, 8)}
    nested = 'linear_attn_config'
    assert {k: config[k] for k in published
            if k not in cut and k != nested} == \
        {k: v for k, v in published.items()
         if k not in cut and k != nested}
    assert {k: (config[k], published[k]) for k in cut} == cut
    assert dict(published[nested], num_heads=8) == config[nested]
    assert published[nested]['num_heads'] == 64
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'solar-open2-250b'][0]
    assert sorted(entry['reduced']) == sorted(list(cut) + [nested])
    assert config['n_routed_experts_published'] == \
        published['n_routed_experts']
    assert config['experts_held'] == [0, config['n_routed_experts']]
    assert config['head_shards'] * config['num_attention_heads'] == \
        published['num_attention_heads']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"Solar-Open2-250B"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
        assert entry['source'].startswith(config['source'] + ' ')
    cells = [w for w in manifest['workloads']
             if w['config'] == 'solar-open2-250b']
    assert [(w['name'], w['traffic'], w['chips']) for w in cells] == \
        [('solar_open2_250b_s4096', 's4096_b1', 1)]
    from paddle_tpu.fluid import monitor
    monitor.reset()             # no program: the gauge is not there
    for name in NEW_READERS:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
