"""CPU rehearsal of the harness on the LFM2 family: the ``lfm2`` family
file, its configuration layout (the model's whole layer pattern beside
the layer the run starts at, the held experts beside the router's
published width, the three assumed numbers), the FLOP counts and the
per-layer readers this family brought, at the tiny preset in
``presets_lfm2/``.  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_lfm2')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('short_conv_ms', 'short_conv_roofline')


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
        'rehearsed_run_lfm2', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_lfm2', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    flash share is left out); what is read from the program's scope and
    cost tables and its gauges is there, the short convolution's time
    and share among them."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_lfm2', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'short_conv_ms', 'short_conv_roofline', 'moe_experts_ms',
            'moe_route_ms', 'norm_rope_ms', 'moe_load_max',
            'moe_held_share', 'moe_bias_max', 'causal_attention_ms',
            'matmul_ms', 'optimizer_ms', 'unscoped_ms'} <= set(got)
    assert got['short_conv_ms']['value'] > 0
    assert got['short_conv_roofline']['value'] > 0
    assert got['causal_attention_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert 0.03 < got['moe_bias_max']['value'] < 0.3
    assert 'gqa_causal_flash_roofline' not in got   # no kernel off-chip


def test_reference_agrees_and_sees_what_the_config_leaves_open(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss (gains that
    differ by feature among them), then each reading the catalog's row
    does not settle read the OTHER way: the taps in the other order,
    the gates swapped, rotary before the QK-norm, a head of its own,
    1e-20 for the 1e-6; and a wrong share.  Each has to miss the
    tolerance, all but the rotary's order and the epsilon by orders of
    magnitude."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import lfm2 as zoo_reference
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_lfm2')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    width, taps = s['hidden_size'], s['conv_L_cache']
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
            elif len(shape) == 1:
                w = 1 + 0.5 * rng.randn(*shape)         # gains
            elif shape == (width, taps) or shape[0] == s['vocab_size']:
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
    filters = [i for i, w in enumerate(weights) if w.shape == (width, taps)]
    w_ins = [i for i, w in enumerate(weights)
             if w.shape == (width, 3 * width)]
    biases = [i for i, w in enumerate(weights)
              if w.shape == (s['num_experts_published'],)]
    assert (len(filters), len(w_ins), len(biases)) == (4, 4, 4)
    # the taps in the other order: the first on the token itself
    flipped = [w[:, ::-1] if i in filters else w
               for i, w in enumerate(weights)]
    assert off(flipped) > 100 * rtol
    # [C | B | X]: the two gates swapped (B and X are one product:
    # only which third multiplies AFTER the filter can be read wrongly)
    gates = [np.concatenate([w[:, width:2 * width], w[:, :width],
                             w[:, 2 * width:]], 1) if i in w_ins else w
             for i, w in enumerate(weights)]
    assert off(gates) > 100 * rtol
    assert off(experts_held=[0, 4]) > 100 * rtol
    assert off(num_experts_per_tok=3) > 100 * rtol
    no_bias = [0 * w if i in biases else w for i, w in enumerate(weights)]
    assert off(no_bias) > 10 * rtol
    # the renormalisation's epsilon: 1e-20 for the 1e-6 (sums of four
    # sigmoid scores are of order 1: it clears the limit and no more)
    assumed = dict(cell.config['assumed'], renorm_eps={'value': 1e-20})
    assert off(assumed=assumed) > 0

    # the readings no weight expresses, through the zoo's reference
    # (the same equations: it agrees with the family's copy first)
    from paddle_tpu.models import lfm2 as zoo
    sizes = zoo_reference.sizes_of(cell.family._zoo_config(
        cell.config, cell.traffic))
    trainable = [w for i, w in enumerate(weights) if i not in biases]
    held_biases = [weights[i] for i in biases]

    def zoo_off(**kw):
        want = float(zoo_reference.loss(
            trainable, held_biases, small['ids'], small['pos_ids'],
            small['labels'], sizes=sizes, **kw))
        return abs(got - want) / abs(want)

    assert zoo.CONV == zoo_reference.CONV
    assert zoo_off() <= rtol
    assert zoo_off(head=rng.randn(*weights[0].shape)) > 100 * rtol
    real_rope, real_norm = zoo_reference.rope, zoo_reference.rms_norm
    gains = {}      # head count -> the gain the norm was handed
    try:
        def norm_later(x, gain, eps):
            if x.ndim == 4:             # a head's norm: after the rope
                gains[x.shape[2]] = gain
                return x
            return real_norm(x, gain, eps)
        zoo_reference.rms_norm = norm_later
        zoo_reference.rope = lambda x, pos, theta: real_norm(
            real_rope(x, pos, theta), gains[x.shape[2]], sizes['rms_eps'])
        # one attention layer of five, and the norm's statistic does
        # not see the rotation: only the gain's features move
        assert zoo_off() > 3 * rtol
    finally:
        zoo_reference.rope, zoo_reference.rms_norm = real_rope, real_norm


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """LFM2-8B-A1B forward per token as cut (the model's layers 1 to 5,
    s8192), by hand.  Conv operator: W_in 2 * 2048 * 6144 = 25,165,824;
    W_out 2 * 2048 * 2048 = 8,388,608: 33,554,432.  Attention operator:
    Wq and Wo 2 * 2 * 2048 * 2048 = 16,777,216; Wk and Wv 2 * 2 * 2048 *
    512 = 4,194,304; scores + context 2 * 2 * 32 * 64 * 4096.5 =
    33,558,528: 54,530,048.  Dense MLP 6 * 2048 * 7168 = 88,080,384.
    Sparse: router 2 * 2048 * 32 = 131,072; routed 4 * 8 / 32 = 1 of
    6 * 2048 * 1792 = 22,020,096: 22,151,168.  Tied head 2 * 2048 *
    16384 = 67,108,864.  In multiply-adds without the scores: 199.5 M,
    the issue's count."""
    from benchmark.families import lfm2
    from benchmark.lib import lfm2_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'lfm2-8b-a1b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's8192_b1.json')))
    sizes = lfm2.sizes(config, traffic)
    assert lfm2_flops.layers_run(sizes) == [
        (1, 'conv', 'dense'), (2, 'full_attention', 'sparse'),
        (3, 'conv', 'sparse'), (4, 'conv', 'sparse'),
        (5, 'conv', 'sparse')]
    assert sizes['layer_types'] == ['conv', 'full_attention', 'conv',
                                    'conv', 'conv']
    assert sizes['head_dim'] == 64
    conv = lfm2_flops.operator_forward_flops_per_token(sizes, 'conv', 8192)
    attention = lfm2_flops.operator_forward_flops_per_token(
        sizes, 'full_attention', 8192)
    assert (conv, attention) == (33554432, 54530048)
    want = 4 * conv + attention + 88080384 + 4 * 22151168 + 67108864
    assert lfm2.flops_per_item(config, traffic) == 3 * want
    assert round((want - 33558528) / 2 / 1e6, 1) == 199.5
    flops, nbytes = lfm2_flops.short_conv_train_cost(1, 8192, 2048, 3)
    assert nbytes == 11 * 8192 * 2048 * 2
    assert flops == 8192 * 2048 * 30
    # the grouped flash calls' count takes head counts and width from
    # this family's sizes (the reader `gqa_causal_flash_roofline` uses)
    from benchmark.lib import laguna_flops
    assert laguna_flops.layers_of(sizes)[1] == ('full_attention', 32,
                                                'sparse')
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the three cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (5, 24), 'num_experts': (8, 32),
           'vocab_size': (16384, 65536)}
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert {k: (config[k], published[k]) for k in cut} == cut
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'lfm2-8b-a1b'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['num_experts_published'] == published['num_experts']
    assert config['experts_held'] == [0, config['num_experts']]
    row = [json.loads(line) for line in open(
        '/opt/skills/guides/model-configs/architectures.jsonl')
        if '"LFM2-8B-A1B"' in line] if os.path.exists(
        '/opt/skills/guides/model-configs/architectures.jsonl') else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source'] == entry['source']
    for name in NEW_READERS:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
