"""CPU rehearsal of the harness on the Xing4.0 family: the ``xing4``
family file, its configuration layout, the FLOP and byte counts and the
per-layer readers this family brought, at the tiny preset in
``presets_xing4/`` (hidden 64, 4 streams, 4 heads, a dense and a sparse
layer + the prediction module's, experts 2 .. 5 of 8 held, 128 tokens a
sequence).  Nothing printed here is a measurement."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_xing4')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('mhc_ms', 'mhc_roofline', 'mhc_stochastic_err',
               'mtp_loss_share')
CELL = 'xing4_29b_s4096'


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
        'rehearsed_run_xing4', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_xing4', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """What is read from the program's tables and gauges is there: the
    hyper-connection ops' time with its parts, its share of the hand
    count's roofline, H_res's distance from the doubly stochastic
    matrices, the module's share of the loss; the ops inside the
    recompute groups still land under their own fluid op.  Off the chip
    the dense attention chain runs: no Mosaic call, so the latent
    flash roofline's reader finds nothing and is left out."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_xing4', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert set(NEW_READERS) | {
        'norm_rope_ms', 'matmul_ms', 'optimizer_ms', 'embedding_ms',
        'moe_experts_ms', 'moe_route_ms', 'moe_load_max',
        'moe_held_share', 'moe_bias_max', 'causal_attention_ms',
        'unscoped_ms'} <= set(got)
    assert 'mla_flash_roofline' not in got      # no Mosaic call off-chip
    assert got['mhc_ms']['value'] > 0
    assert got['mhc_roofline']['value'] > 0
    assert 0 < got['mhc_stochastic_err']['value'] < 1e-3
    # random weights: both cross-entropies near ln 97, the share near
    # 0.3 / 1.3
    assert 0.2 < got['mtp_loss_share']['value'] < 0.26
    assert 'by part: maps' in out and 'write_back' in out
    assert 'mhc/sinkhorn_iters 20' in out and 'mtp/loss' in out


def test_reference_agrees_and_every_part_of_the_mathematics_moves_it(
        harness):
    """The family's own reference against the f32 for_test program on
    weights large enough that every part moves the loss, then each
    part changed in the reference: a Sinkhorn loop cut short, the
    clamp, static maps (phi zero), YaRN's softmax scale and table, the
    module's weight, its input token, a wrong held range.  Each has to
    miss the tolerance many times over (the hyper-connections average
    the streams: a changed part moves this tiny model's loss by less
    than it moves Moonlight's), and so does the reference in
    bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_xing4')
    main, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert (host['labels'][:, :127] == host['ids'][:, 1:]).all()
    assert (host['labels_mtp'][:, :126] == host['ids'][:, 2:]).all()
    assert (host['labels'][:, 127] == -1).all()
    assert (host['labels_mtp'][:, 126:] == -1).all()
    assert host['ids'].max() < cell.config['vocab_size']
    # embedding; a dense layer 18, a sparse one 23; final norm and
    # head; the module's two norms, W_eh and sparse layer
    assert len(params) == 1 + 18 + 23 + 2 + 3 + 23 == len(set(params))
    ops = [op.type for op in test.global_block().ops]
    assert ops.count('hyper_connection_pre') == 6
    assert ops.count('lookup_table_v2') == 2
    small = {k: v[:1] for k, v in host.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        assert run.reference_check(cell, exe, test, loss, params, host)
        scope = fluid.global_scope()
        rng = np.random.RandomState(5)
        for p in params:
            shape = tuple(fluid.core.as_array(scope.find_var(p)).shape)
            if shape == (cell.config['n_routed_experts_published'],):
                w = 0.3 * rng.randn(*shape)         # a choice bias
            elif len(shape) == 1:
                w = 1 + 0.2 * rng.randn(*shape)
            elif shape[0] == cell.config['vocab_size'] or \
                    shape == (4 * 64, 24):  # phi is stored at unit size
                w = rng.randn(*shape)
            else:
                w = rng.randn(*shape) / np.sqrt(shape[-2])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(dtype=None, weights=weights, feed=small, assumed=None,
            **changed):
        config = dict(cell.config, **changed)
        if assumed:
            config['assumed'] = dict(config['assumed'], **assumed)
        want = float(cell.family.reference_readings(
            config, cell.traffic, weights, feed, dtype=dtype)[0])
        return abs(got - want) / abs(want)

    assert off() <= rtol
    # the loop converges geometrically: a loss can tell a loop of one
    # or three normalisations from the twenty, not the twentieth from
    # the nineteenth (H_res moves by less than float32 resolves)
    assert off(hc_sinkhorn_iters=1) > 10 * rtol
    assert off(hc_sinkhorn_iters=3) > rtol
    assert off(mhc_h_res_clamp_max=0.5) > 5 * rtol
    static = [0 * w if w.shape == (4 * 64, 24) else w for w in weights]
    assert off(weights=static) > 20 * rtol
    scaling = cell.config['rope_scaling']
    assert off(rope_scaling=dict(scaling, mscale_all_dim=0)) > 20 * rtol
    assert off(rope_scaling=dict(scaling, factor=1.0001)) > 20 * rtol
    assert off(assumed={'mtp_weight': {'value': 0.0}}) > 20 * rtol
    assert off(experts_held=[0, 4]) > 20 * rtol
    assert off(routed_scaling_factor=1.0) > 20 * rtol
    shifted = dict(small, labels=np.where(
        small['labels'] >= 0, (small['labels'] + 1) % 97, -1))
    assert off(feed=shifted) > 20 * rtol
    assert off(dtype=jnp.bfloat16) > 5 * rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Xing4.0 forward per token as cut (five layers + the module,
    s4096), by hand.  Attention's projections: 2 x (3584 x 768 + 768 x
    32 x 192 + 3584 x 576 + 512 x 32 x 256 + 32 x 128 x 3584) =
    56,819,712; scores + context over the visible pairs, 2048.5 a
    token, times 2 x 32 x 320 = 41,953,280; the two maps of a layer
    2 x 2 x 14336 x 24 = 1,376,256.  Dense MLP 6 x 3584 x 9216 =
    198,180,864.  Sparse: the router 2 x 3584 x 64 = 458,752, the
    shared expert 6 x 3584 x 1024 = 22,020,096, half a routed expert
    11,010,048.  A head product 2 x 3584 x 16384 = 117,440,512; W_eh
    2 x 7168 x 3584 = 51,380,224."""
    from benchmark.families import xing4
    from benchmark.lib import xing_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'xing4.0-29b-a4b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    sizes = xing4.sizes(config, traffic)
    assert (sizes['layers_held'], sizes['num_nextn_predict_layers'],
            sizes['num_hidden_layers']) == (5, 1, 6)
    attention = 56819712 + 41953280 + 1376256
    assert xing_flops.attention_forward_flops_per_token(sizes, 4096) + \
        2 * xing_flops.maps_forward_flops_per_token(sizes) == attention
    sparse = 458752 + 22020096 + 11010048
    assert xing_flops.sparse_mlp_forward_flops_per_token(sizes) == sparse
    parts = xing_flops.forward_parts_per_token(sizes, 4096)
    assert parts['main'] == 5 * attention + 198180864 + 4 * sparse + \
        117440512
    assert parts['module'] == attention + sparse + 51380224 + 117440512
    want = parts['main'] + parts['module']
    assert xing4.flops_per_item(config, traffic) == 3 * want
    # the issue's "about 3.0 GFLOP a token in matmuls + 0.75 in
    # attention": 3 x forward
    scores = 6 * 41953280
    assert round(3 * scores / 1e9, 2) == 0.76
    assert round((3 * want - 3 * scores) / 1e9, 1) == 3.0
    assert round(100 * parts['module'] / want) == 24
    # the hyper-connections by the hand count: 12 operators, 4096
    # tokens, (14 + 23) x 3584 bf16 elements a token + the maps:
    # forward and backward, no forward run again (that would be 14 of
    # 14 + 23 more: what the recompute groups pay)
    assert xing_flops.operators(sizes) == 12
    flop, byte = xing_flops.mhc_train_cost(4096, 4, 3584)
    stream = 4096 * 3584 * 2 * (14 + 23)
    assert byte == stream + 4096 * 24 * 4 * 2 * 3 + 14336 * 24 * 4 * 3
    assert flop == 3 * 4096 * 2 * 14336 * 24
    assert round(12 * byte / 1e9, 1) == 13.1     # 16 ms at 819 GB/s
    assert round(xing_flops.mhc_forward_share(4), 3) == round(14 / 37., 3)
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the four cuts, no width among them
    published = config['published']
    cut = ('num_hidden_layers', 'n_routed_experts', 'vocab_size',
           'first_k_dense_replace')
    assert {k: config[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert [(config[k], published[k]) for k in cut] == [
        (5, 40), (8, 64), (16384, 131072), (1, 2)]
    assert config['experts_held'] == [0, 8] and \
        config['n_routed_experts_published'] == 64
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs']
             if c['name'] == 'xing4.0-29b-a4b'][0]
    assert entry['reduced'] == list(cut)
    assert len(entry['source']) <= 200 and len(entry['why']) <= 200 and \
        entry['source'] == config['source']
    for item in ('hyper_connections', 'hc_alpha_init', 'hc_phi_std',
                 'hc_pre_init', 'hc_post_init', 'hc_res_init', 'mtp', 'mtp_weight', 'bias_update_rate',
                 'bias_init_std', 'rope', 'optimizer'):
        assert item in config['assumed']
    assert len(config['reduced']) == 4
    for key in ('deployment', 'expert_load'):
        assert config[key]
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"Xing4.0-29B-A4B"' in line] \
        if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
    cell = [w for w in manifest['workloads'] if w['name'] == CELL][0]
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        ('xing4.0-29b-a4b', 's4096_b1', 1) and len(cell['why']) <= 200
    assert len(manifest['workloads']) >= 14 and \
        len(manifest['configs']) >= 10 and \
        sum(w['chips'] == 4 for w in manifest['workloads']) == 2
    declared = {m['name']: m for m in manifest['per_layer']}
    for name in NEW_READERS:
        reader = _reader(name)
        assert declared[name]['workloads'] == [CELL]
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            declared[name]['layer'], declared[name]['unit'],
            declared[name]['moves'])
        if declared[name]['source'] == 'device_trace':
            assert reader.read(None, {}) is None
    for name in ('moe_experts_ms', 'moe_route_ms', 'moe_load_max',
                 'moe_held_share', 'moe_bias_max', 'causal_attention_ms',
                 'mla_flash_roofline', 'norm_rope_ms', 'matmul_roofline'):
        assert CELL in declared[name]['workloads']


def test_the_gauge_readers_return_nothing_without_their_gauges(
        monkeypatch):
    """A parent of this PR sets no ``mhc/`` or ``mtp/`` gauge: the
    readers leave the metrics out and do not raise."""
    from paddle_tpu.fluid import monitor
    monkeypatch.setattr(monitor, '_gauges', {})
    assert _reader('mhc_stochastic_err').read(None, {}) is None
    assert _reader('mtp_loss_share').read(None, {}) is None


def test_the_trace_readers_return_nothing_where_no_such_op_ran(
        monkeypatch):
    """A traced run of a program without the ops (every accepted cell,
    and a parent of this PR in them): the scope table gives the ops no
    time and both readers leave their metric out."""
    from benchmark.lib import scope_time
    monkeypatch.setattr(scope_time, 'per_step_ms',
                        lambda trace, run, belongs: 0.0)
    trace = object()        # anything that is not None
    for name in ('mhc_ms', 'mhc_roofline'):
        assert _reader(name).read(trace, {}) is None
