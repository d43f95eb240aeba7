"""CPU rehearsal of the harness on the Ouro family: the ``ouro`` family
file, its configuration layout, the FLOP counts and the per-layer
readers this family brought, at the tiny preset in ``presets_ouro/``
(hidden 64, 4 heads of 16, two layers applied four times, 128 tokens a
sequence).  Nothing printed here is a measurement."""

import importlib.util
import json
import math
import os
import shutil

import pytest

from benchmark.tests.test_rehearsal import (BENCH, CONTRACT_KEYS, HERE,
                                            _cpu_op_planes, _last_line)

PRESETS = os.path.join(HERE, 'presets_ouro')
ROOT = os.path.dirname(BENCH)
NEW_READERS = ('loop_ms', 'loop_forward_share', 'loop_trips',
               'exit_entropy')


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
        'rehearsed_run_ouro', os.path.join(copy, 'run.py'))
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
    assert run.main(['--workload', 'tiny_ouro', '--seed', '2147483659',
                     '--seconds', '0.5', '--trace', '0']) == 0
    line = _last_line(capsys)
    assert set(line) == CONTRACT_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert {'throughput', 'mfu', 'setup_s'} <= set(line['metrics'])


def test_traced_run_reports_the_family_s_layer_metrics(harness, capsys):
    """What is read from the program's tables and gauges is there: the
    loop bodies' time by side, the trips, the exit distribution's
    entropy; the ops inside the loop's bodies still land under their
    own fluid op (matmul, norm and rotary, attention).  Off the chip
    the dense attention chain runs: no Mosaic call, so the flash
    roofline's reader finds nothing and is left out."""
    run, _ = harness
    assert run.main(['--workload', 'tiny_ouro', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert {'loop_ms', 'loop_forward_share', 'loop_trips',
            'exit_entropy', 'norm_rope_ms', 'matmul_ms', 'optimizer_ms',
            'embedding_ms', 'unscoped_ms'} <= set(got)
    assert 'causal_flash_roofline' not in got   # no Mosaic call off-chip
    assert got['loop_trips']['value'] == 4
    assert 0 < got['exit_entropy']['value'] <= math.log(4) + 1e-6
    assert got['loop_ms']['value'] > 0
    assert 0 < got['loop_forward_share']['value'] < 100
    # the loop's bodies hold the matmuls and the norms
    assert got['matmul_ms']['value'] > 0
    assert got['norm_rope_ms']['value'] > 0
    assert 'loop bodies, chip 0: forward' in out
    assert 'ouro/exit_mass_last' in out


def test_reference_agrees_and_every_part_of_the_mathematics_moves_it(
        harness):
    """The family's own reference against the f32 for_test program (the
    ``lax.while_loop`` lowering) on weights large enough that every
    part moves the loss, then each part left out of the reference: the
    post-operator norms, the norm between passes, the gate, the
    entropy term, a pass fewer.  Each has to miss the tolerance by
    orders of magnitude, and so does the reference in bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    run, root = harness
    cell = run.Cell(json.load(open(os.path.join(root, 'BENCHMARK.json'))),
                    'tiny_ouro')
    main, startup, test, loss, params = run.build_programs(cell, seed=4)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, 4)
    assert (host['labels'][:, :127] == host['ids'][:, 1:]).all()
    assert (host['labels'][:, 127] == -1).all()
    assert host['ids'].max() < cell.config['vocab_size']
    # ONE while op over the stack; each layer's parameters once
    assert [op.type for op in main.global_block().ops].count('while') == 1
    assert len(params) == 1 + 11 * 2 + 4 == len(set(params))
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
                w = 1 + 0.3 * rng.randn(*shape) if shape[0] > 1 \
                    else 0.3 * rng.randn(*shape)
            elif shape[0] == cell.config['vocab_size']:
                w = rng.randn(*shape)
            else:
                w = rng.randn(*shape) / np.sqrt(shape[0])
            scope.set_var(p, jnp.asarray(w.astype('float32')))
        got = run.scalar(exe.run(test, feed=small, fetch_list=[loss]))
        weights = [np.asarray(fluid.core.as_array(scope.find_var(p)))
                   for p in params]
    rtol = cell.family.REFERENCE_RTOL

    def off(dtype=None, without=(), **changed):
        config = dict(cell.config, **changed)
        want = float(cell.family.reference_loss(
            config, cell.traffic, weights, small, dtype=dtype,
            without=without))
        return abs(got - want) / abs(want)

    assert off() <= rtol
    for part in ('post_norms', 'norm_between', 'gate', 'entropy'):
        assert off(without=(part,)) > 100 * rtol, part
    assert off(total_ut_steps=3) > 100 * rtol
    assert off(entropy_weight=0.05) > 100 * rtol
    assert off(dtype=jnp.bfloat16) > 100 * rtol


def test_flops_by_hand_the_file_and_readers_without_a_trace():
    """Ouro forward per token as cut (four layers, four passes, s4096),
    by hand.  A layer-pass: q, k, v, o 4 * 2 * 2048 * 2048 =
    33,554,432; MLP 3 * 2 * 2048 * 5632 = 69,206,016; scores + context
    over the visible pairs, 4096 * 4097 / 2 = 8,390,656 a head and
    sequence, 2048.5 a token, times 2 * 2 * 16 * 128 = 16,781,312.  A
    pass's exit: the head 2 * 2048 * 49152 = 201,326,592 and the gate
    4,096."""
    from benchmark.families import ouro
    from benchmark.lib import ouro_flops
    config = json.load(open(os.path.join(BENCH, 'configs',
                                         'ouro-2.6b.json')))
    traffic = json.load(open(os.path.join(BENCH, 'workloads',
                                          's4096_b1.json')))
    sizes = ouro.sizes(config, traffic)
    assert (sizes['layers_held'], sizes['total_ut_steps'],
            sizes['num_hidden_layers']) == (4, 4, 16)
    assert ouro_flops.causal_pairs(4096) == 8390656
    layer_pass = 33554432 + 69206016 + 16781312
    assert ouro_flops.layer_pass_flops_per_token(sizes, 4096) == \
        layer_pass == 119541760
    assert ouro_flops.exit_flops_per_token(sizes) == 201326592 + 4096
    want = 4 * (4 * layer_pass + 201330688)
    assert ouro.flops_per_item(config, traffic) == 3 * want
    assert round(3 * want / 1e9, 2) == 8.15         # the issue's 8.16
    assert round(100 * 4 * 201330688 / want) == 30  # the head's share
    tiny = json.load(open(os.path.join(PRESETS, 'configs',
                                       'ouro-tiny.json')))
    tiny_traffic = json.load(open(os.path.join(
        PRESETS, 'workloads', 'tiny_s128_ouro.json')))
    by_hand = 4 * (2 * (8 * 64 * 64 + 6 * 64 * 96 +
                        2 * 2 * 4 * 16 * 64.5) + 2 * 64 * 98)
    assert ouro.flops_per_item(tiny, tiny_traffic) == 3 * by_hand
    # the catalog's keys, as run, at the file's top level: everything
    # as published but the depth, no width among the cuts
    published = config['published']
    assert {k: config[k] for k in published
            if k != 'num_hidden_layers'} == \
        {k: v for k, v in published.items() if k != 'num_hidden_layers'}
    assert (config['num_hidden_layers'],
            published['num_hidden_layers'],
            config['num_hidden_layers_published']) == (4, 48, 48)
    assert config['total_ut_steps'] == 4 and config['vocab_size'] == 49152
    manifest = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    entry = [c for c in manifest['configs'] if c['name'] == 'ouro-2.6b'][0]
    assert entry['reduced'] == ['num_hidden_layers']
    assert len(entry['source']) <= 200 and len(entry['why']) <= 200 and \
        entry['source'].startswith(config['source'])
    for item in ('loop', 'sandwich_norms', 'exit_gate', 'loss',
                 'initializer', 'fp32', 'recompute', 'optimizer'):
        assert item in config['assumed']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    row = [json.loads(line) for line in open(catalog)
           if '"Ouro-2.6B"' in line] if os.path.exists(catalog) else []
    if row:
        assert row[0]['config'] == published
        assert row[0]['source_url'] == config['source']
    cell = [w for w in manifest['workloads']
            if w['name'] == 'ouro_2b6_s4096'][0]
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        ('ouro-2.6b', 's4096_b1', 1) and len(cell['why']) <= 200
    assert len(manifest['workloads']) == 13 and \
        len(manifest['configs']) == 9 and \
        sum(w['chips'] == 4 for w in manifest['workloads']) == 2
    declared = {m['name']: m for m in manifest['per_layer']}
    for name in NEW_READERS:
        reader = _reader(name)
        assert declared[name]['workloads'] == ['ouro_2b6_s4096']
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            declared[name]['layer'], declared[name]['unit'],
            declared[name]['moves'])
        if declared[name]['source'] == 'device_trace':
            assert reader.read(None, {}) is None
    for name in ('matmul_roofline', 'causal_attention_ms',
                 'causal_flash_roofline', 'norm_rope_ms'):
        assert declared[name]['workloads'][-1] == 'ouro_2b6_s4096'


def test_the_gauge_readers_return_nothing_without_their_gauges(
        monkeypatch):
    """A parent of this PR sets no ``loop/`` or ``ouro/`` gauge: the
    readers leave the metrics out and do not raise."""
    from paddle_tpu.fluid import monitor
    monkeypatch.setattr(monitor, '_gauges', {})
    assert _reader('loop_trips').read(None, {}) is None
    assert _reader('exit_entropy').read(None, {}) is None


def test_the_loop_readers_return_nothing_without_the_program_s_table(
        monkeypatch):
    """A parent of this PR has no ``profiler.loop_tables``: the trace
    readers leave their metrics out and do not raise."""
    from paddle_tpu.fluid import profiler
    monkeypatch.delattr(profiler, 'loop_tables')
    trace = object()        # anything that is not None
    for name in ('loop_ms', 'loop_forward_share'):
        assert _reader(name).read(trace, {}) is None
