"""CPU rehearsal of the harness on the Nemotron-H family: the
``nemotron_h`` family file, its configuration layout (the pattern
string's first letters, the held experts beside the router's published
width), the FLOP and byte counts and the per-layer readers the cell is
listed under, at the tiny preset in ``presets_nemotron_h/``.  Nothing
printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_nemotron_h')
ROOT = os.path.dirname(BENCH)
CELL = 'nemotron3_nano_30b_s8192'
NEW = ('ssd_ms', 'ssd_roofline', 'ssd_chunks', 'ssd_state_mb')
LISTED = NEW + (
    'moe_experts_ms', 'moe_route_ms', 'moe_load_max', 'moe_held_share',
    'moe_bias_max', 'causal_attention_ms', 'gqa_causal_flash_roofline',
    'norm_rope_ms', 'matmul_roofline', 'short_conv_ms',
    'short_conv_roofline')


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
        'rehearsed_run_nemotron_h', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_nemotron', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_four_new_metrics_and_the_breakdown(
        harness, capsys):
    """Off the chip the dense attention chain runs (no Mosaic call: the
    GQA flash share is left out); what is read from the program's scope
    and cost tables and its gauges is there, the scan's time inside its
    recompute groups, its share of the hand count and its chunk steps
    among them: 96 tokens are 3 chunks of 32, two Mamba-2 layers, both
    in groups: forward, forward again, reverse; each keeps 2 sequences
    x 3 chunks of a [8, 8, 6] float32 state."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_nemotron', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    assert line['breakdown']['device_ops']
    got = line['metrics']
    assert set(LISTED) - {'gqa_causal_flash_roofline'} <= set(got)
    assert {'matmul_ms', 'optimizer_ms', 'unscoped_ms'} <= set(got)
    assert got['ssd_ms']['value'] > 0
    assert 0 < got['ssd_roofline']['value']
    assert got['ssd_chunks']['value'] == 2 * 3 * 3
    assert got['ssd_state_mb']['value'] == pytest.approx(
        2 * 2 * 3 * 8 * 8 * 6 * 4 / 1e6)
    assert got['short_conv_ms']['value'] > 0
    assert got['causal_attention_ms']['value'] > 0
    assert 0.0 < got['moe_held_share']['value'] < 1.0
    assert 'gqa_causal_flash_roofline' not in got   # no kernel off-chip


def test_reference_agrees_and_sees_what_the_config_leaves_open(harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then the model
    read the OTHER way: the taps in the other order, one decay for all
    heads, B and C of the wrong group, no skip, a wrong expert share, a
    dropped 2.5, no choice bias, another top-k.
    Each has to miss the tolerance; the zoo's reference agrees with the
    family's copy."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import nemotron_h as zoo_reference
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_nemotron')
    _, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert sorted(host) == ['ids', 'labels']
    assert (host['labels'][:, :-1] == host['ids'][:, 1:]).all()
    assert host['ids'].max() < cell.config['vocab_size']
    s = cell.family.sizes(cell.config, cell.traffic)
    assert s['layer_types'] == ['mamba', 'moe', 'mamba', 'full_attention',
                                'moe']
    assert (s['num_hidden_layers'], s['layers_held']) == (5, 5)
    zoo = cell.family._zoo_config(cell.config, cell.traffic)
    from paddle_tpu.models import nemotron_h
    labels = [label for label, _ in nemotron_h.parameter_specs(zoo)]
    assert len(labels) == len(params)
    small = {k: v[:1] for k, v in host.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        scope = fluid.global_scope()
        rng = np.random.RandomState(5)
        for p, label in zip(params, labels):
            shape = tuple(fluid.core.as_array(scope.find_var(p)).shape)
            what = label.split('.')[-1]
            if what == 'choice_bias':
                w = 0.3 * rng.randn(*shape)
            elif what == 'a_log':
                w = np.log(rng.uniform(1, 16, shape))
            elif what == 'dt_bias':
                w = rng.uniform(-3, 1, shape)
            elif len(shape) == 1 or what == 'norm_g':
                w = 1 + 0.5 * rng.randn(*shape)
            elif what in ('conv_w', 'embedding'):
                w = rng.randn(*shape)
            elif what == 'router':
                w = 4 * rng.randn(*shape) / np.sqrt(shape[0])
            else:
                w = rng.randn(*shape) / np.sqrt(shape[-2])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]

    def off(weights=weights, **changed):
        """-> how far the program lies from the number the family
        answers, in units of the tolerance it set for that answer."""
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(config, cell.traffic,
                                                weights, small))
        return abs(got - want) / abs(want) / cell.family.REFERENCE_RTOL

    def swapped(what, change):
        return [change(w) if label.endswith(what) else w
                for w, label in zip(weights, labels)]

    inner = s['mamba_num_heads'] * s['mamba_head_dim']
    bc = s['n_groups'] * s['ssm_state_size']

    def other_groups(w):            # B and C of the two groups swapped
        w = w.copy()
        for at in (inner, inner + bc):
            block = w[at:at + bc].copy()
            w[at:at + bc] = np.roll(block, s['ssm_state_size'], axis=0)
        return w

    assert off() <= 1
    wrong = {
        'taps in the other order':
            swapped('conv_w', lambda w: w[:, ::-1]),
        'one decay for all heads':
            swapped('a_log', lambda w: 0 * w + w[0]),
        'B and C of the other group': [
            other_groups(w) if label.endswith(('conv_w', 'conv_b')) else w
            for w, label in zip(weights, labels)],
        'no skip': swapped('mamba.d', lambda w: 0 * w),
        'no choice bias': swapped('choice_bias', lambda w: 0 * w),
    }
    for reading, other in wrong.items():
        assert off(other) > 10, reading
    assert off(experts_held=[0, 4]) > 100
    assert off(num_experts_per_tok=2) > 100
    assert off(routed_scaling_factor=1.0) > 100
    want = float(zoo_reference.loss(
        weights, small, pattern=zoo.pattern, head_dim=zoo.head_dim,
        top_k=zoo.top_k, first=zoo.experts_held[0],
        routed_scale=zoo.routed_scale, eps=zoo.rms_eps))
    assert abs(got - want) <= cell.family.BASE_RTOL * abs(want)
    # the rule itself: no choice within the margin on these weights, so
    # the number is the loss and the limit the base; with EVERY choice
    # called undecided the span holds the loss of the pass that takes
    # them all the other way, and the answer is the span's middle
    plain, low, high, undecided = (float(x) for x in
                                   cell.family.reference_readings(
        cell.config, cell.traffic, weights, small))
    assert (low, high, undecided) == (0.0, 0.0, 0.0)
    assert plain == pytest.approx(want, rel=1e-6)
    plain, low, high, undecided = (float(x) for x in
                                   cell.family.reference_readings(
        cell.config, cell.traffic, weights, small, tie_margin=10.0))
    assert undecided == 2 * small['ids'].size       # two routed layers
    assert low < 0 < high and plain == pytest.approx(want, rel=1e-6)
    middle, rtol = cell.family.allowed(plain, low, high)
    for end in (low, high):
        at = abs(plain + end - float(middle)) / abs(float(middle))
        assert rtol - 2 * cell.family.BASE_RTOL < at < rtol
    assert cell.family.allowed(10.0, 0.0, 0.0) == \
        (10.0, cell.family.BASE_RTOL)


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Nemotron-3-Nano-30B-A3B forward per token as cut (MEMEM*EME,
    s8192), by hand.  Mamba-2 mixer: W_in 2 x 2688 x 10304 =
    55,394,304; W_out 2 x 4096 x 2688 = 22,020,096; the recurrence in
    128-token chunks: a chunk's 128 x 129 / 2 = 8256 pairs x 2 x (8 x
    128 + 64 x 64) = 84,541,440, over 128 tokens 660,480 a token, plus
    write and read 2 x 2 x 64 x 64 x 128 = 2,097,152: 80,172,032.
    Attention: Wq, Wo 2 x 2 x 2688 x 4096 = 44,040,192; Wk, Wv 2 x 2 x
    2688 x 256 = 2,752,512; scores + context 2 x 2 x 32 x 128 x 4096.5 =
    67,117,056: 113,909,760.  Routed layer: router 2 x 2688 x 128 =
    688,128; shared 2 x 2 x 2688 x 3712 = 39,911,424; routed 6 x 8 /
    128 = 0.375 of 2 x 2 x 2688 x 1856 = 7,483,392: 48,082,944.  Head 2
    x 2688 x 16384 = 88,080,384."""
    from benchmark.families import nemotron_h
    from benchmark.lib import nemotron_h_flops as count
    config = json.load(open(os.path.join(
        BENCH, 'configs', 'nemotron-3-nano-30b-a3b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's8192_b1.json')))
    sizes = nemotron_h.sizes(config, traffic)
    assert sizes['layer_types'] == [
        'mamba', 'moe', 'mamba', 'moe', 'mamba', 'full_attention', 'moe',
        'mamba', 'moe']
    assert (sizes['num_hidden_layers'], sizes['layers_held']) == (9, 9)
    assert count.ssd_chunk_forward_flops(8192, 64, 64, 8, 128, 128) == \
        64 * 84541440 + 8192 * 2097152
    want = 4 * 80172032 + 113909760 + 4 * 48082944 + 88080384
    assert want == 715010048
    assert count.forward_flops_per_token(sizes, 8192) == want
    assert nemotron_h.flops_per_item(config, traffic) == 3 * want
    # the hand count the reader takes, at this cell's shapes: x and y
    # [8192, 4096] and B, C [8192, 1024] in bfloat16, delta [8192, 64]
    # float32, 64 boundary states of 2 MB each way
    flops, nbytes = count.ssd_train_cost(1, 8192, 64, 64, 8, 128, 128)
    assert flops == 3 * (64 * 84541440 + 8192 * 2097152)
    wide, narrow, steps = 8192 * 4096 * 2, 8192 * 1024 * 2, 8192 * 64 * 4
    boundary = 64 * 64 * 64 * 128 * 4
    assert boundary == 134217728
    assert nbytes == (2 * wide + 2 * narrow + steps + boundary) + \
        (3 * wide + 4 * narrow + 2 * steps + boundary + 512)
    # the issue's arithmetic: the cut and the whole model
    assert count.parameter_count(sizes) == 666962944
    assert round(count.parameter_count(
        sizes, config['published']['hybrid_override_pattern'], 128,
        131072) / 1e9, 2) == 31.58
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the cuts, no width among them
    published = config['published']
    cut = {'num_hidden_layers': (9, 52), 'n_routed_experts': (8, 128),
           'vocab_size': (16384, 131072)}
    same = [k for k in published if k not in cut]
    assert {k: config[k] for k in same} == {k: published[k] for k in same}
    assert {k: (config[k], published[k]) for k in cut} == cut
    # the pattern stays whole in the file; `sizes` runs its first nine
    assert sizes['hybrid_override_pattern_published'] == \
        published['hybrid_override_pattern']
    assert sizes['hybrid_override_pattern'] == \
        published['hybrid_override_pattern'][:9] == 'MEMEM*EME'
    assert (config['hidden_size'], config['mamba_num_heads'],
            config['mamba_head_dim'], config['n_groups'],
            config['ssm_state_size'], config['chunk_size'],
            config['conv_kernel'], config['num_attention_heads'],
            config['num_key_value_heads'], config['head_dim'],
            config['moe_intermediate_size'],
            config['moe_shared_expert_intermediate_size'],
            config['num_experts_per_tok'],
            config['routed_scaling_factor']) == \
        (2688, 64, 64, 8, 128, 128, 4, 32, 2, 128, 1856, 3712, 6, 2.5)
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'nemotron-3-nano-30b-a3b'][0]
    assert sorted(entry['reduced']) == sorted(cut)
    assert config['n_routed_experts_published'] == \
        published['n_routed_experts']
    assert config['experts_held'] == [0, config['n_routed_experts']]
    assert '16 chips' in config['deployment']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
        assert entry['source'].startswith(config['source'] + ' ')
    cells = [w for w in manifest['workloads']
             if w['config'] == 'nemotron-3-nano-30b-a3b']
    assert [(w['name'], w['traffic'], w['chips']) for w in cells] == \
        [(CELL, 's8192_b1', 1)]
    assert (len(manifest['workloads']), len(manifest['configs']),
            sum(w['chips'] == 4 for w in manifest['workloads'])) == \
        (18, 14, 2)
    listed = [m['name'] for m in manifest['per_layer']
              if CELL in m.get('workloads', ())]
    assert sorted(listed) == sorted(LISTED)
    from paddle_tpu.fluid import monitor
    monitor.reset()             # no program: the gauges are not there
    for name in NEW:
        spec = importlib.util.spec_from_file_location(
            'reader_' + name, os.path.join(BENCH, 'layer_metrics',
                                           name + '.py'))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert reader.read(None, {}) is None
