"""fluid.profiler's scope table: which fluid op each instruction of a
compiled segment's optimised HLO was lowered from, and the xplane
loader that reads a capture through it."""

import collections

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache, profiler

OPS = {'mul', 'relu', 'adam', 'fused_adam', 'lookup_table_v2', 'softmax'}

# two modules of one name (a segment planned for two fetch lists); the
# second holds one instruction more and gives 'fusion.1' another scope
HLO = '''HloModule jit_segment_mul_x4, is_scheduled=true, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%%fused_dot (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %%p0 = f32[8,8]{1,0} parameter(0)
  %%p1 = f32[8,8]{1,0} parameter(1)
  %%max.1 = f32[8,8]{1,0} maximum(%%p0, %%p1), metadata={op_name="jit(segment_mul_x4)/relu/max"}
  %%dot.2 = f32[8,8]{1,0} dot(%%max.1, %%p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(segment_mul_x4)/transpose(jvp(mul))/dot_general"}
  ROOT %%add.3 = f32[8,8]{1,0} add(%%dot.2, %%p0), metadata={op_name="jit(segment_mul_x4)/jvp(softmax)/add"}
}

%%fused_root (p0.1: f32[8,8]) -> f32[8,8] {
  %%p0.1 = f32[8,8]{1,0} parameter(0)
  %%neg.4 = f32[8,8]{1,0} negate(%%p0.1), metadata={op_name="jit(segment_mul_x4)/mul"}
  ROOT %%exp.5 = f32[8,8]{1,0} exponential(%%neg.4), metadata={op_name="jit(segment_mul_x4)/jvp(softmax)/exp"}
}

%%fused_tuple (p0.2: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %%p0.2 = f32[8,8]{1,0} parameter(0)
  %%abs.6 = f32[8,8]{1,0} abs(%%p0.2)
  %%mul.7 = f32[8,8]{1,0} multiply(%%abs.6, %%p0.2), metadata={op_name="jit(segment_mul_x4)/fused_adam/pack/mul"}
  %%copy.8 = f32[8,8]{1,0} copy(%%mul.7)
  ROOT %%tuple.9 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%%copy.8, %%abs.6)
}

%%fused_bare (p0.3: f32[8,8]) -> f32[8,8] {
  %%p0.3 = f32[8,8]{1,0} parameter(0)
  ROOT %%copy.10 = f32[8,8]{1,0} copy(%%p0.3)
}

ENTRY %%main.20 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %%Arg_0.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="state['w']"}
  %%fusion.1 = f32[8,8]{1,0} fusion(%%Arg_0.1, %%Arg_0.1), kind=kOutput, calls=%%fused_dot, metadata={op_name="jit(segment_mul_x4)/jvp(softmax)/add"}
  %%fusion.2 = f32[8,8]{1,0} fusion(%%fusion.1), kind=kLoop, calls=%%fused_root
  %%fusion.3 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%%fusion.2), kind=kLoop, calls=%%fused_tuple
  %%get-tuple-element.11 = f32[8,8]{1,0} get-tuple-element(%%fusion.3), index=0
  %%fusion.4 = f32[8,8]{1,0} fusion(%%get-tuple-element.11), kind=kLoop, calls=%%fused_bare, metadata={op_name="jit(segment_mul_x4)/lookup_table_v2/jit(_take)/gather"}
  %%fusion.5 = f32[8,8]{1,0} fusion(%%fusion.4), kind=kLoop, calls=%%fused_bare
  %%custom-call.12 = f32[8,8]{1,0} custom-call(%%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(segment_mul_x4)/fused_adam/pallas_call"}
  %%copy.13 = f32[8,8]{1,0} copy(%%custom-call.12)%s
  ROOT %%multiply.14 = f32[8,8]{1,0} multiply(%%copy.13, %%copy.13), metadata={op_name="jit(segment_mul_x4)/mul"}
}
'''
QUIET = HLO % ''
FETCH = (HLO % '\n  %extra.99 = f32[8,8]{1,0} negate(%copy.13), '
         'metadata={op_name="jit(segment_mul_x4)/relu/neg"}').replace(
             'calls=%fused_dot,', 'calls=%fused_root,')


def test_hand_written_hlo_exercises_the_rule():
    module, table = profiler.hlo_scopes(QUIET, op_types=OPS)
    assert module == 'jit_segment_mul_x4'
    # a fusion counts to the dot it holds (here: backward code jax
    # derived inside the scope), not to its root or its own op_name
    assert table['fusion.1'] == 'mul_grad'
    # else to its root; 'jit(...)/mul' ends in the primitive mul: the
    # last component is never a scope
    assert table['fusion.2'] == 'softmax'
    # a tuple root stands for the nearest operand that has a scope,
    # and a plain named scope under the op's is kept
    assert table['fusion.3'] == 'fused_adam/pack'
    # nothing inside carries a scope: the fusion's own op_name decides
    assert table['fusion.4'] == 'lookup_table_v2'
    # an instruction with no fluid scope counts to none
    assert table['fusion.5'] is None and table['copy.13'] is None
    assert table['Arg_0.1'] is None
    assert table['multiply.14'] is None
    assert table['custom-call.12'] == 'fused_adam'
    # what a trace cannot name is left out
    assert 'dot.2' not in table and 'tuple.9' not in table


def test_two_modules_with_one_instruction_name():
    quiet = profiler.hlo_scopes(QUIET, op_types=OPS)[1]
    fetch = profiler.hlo_scopes(FETCH, op_types=OPS)[1]
    assert quiet['fusion.1'] == 'mul_grad' and fetch['fusion.1'] == 'softmax'
    ran_quiet = ['fusion.1', 'fusion.2', 'copy.13', 'multiply.14']
    assert profiler.pick_table([quiet, fetch], ran_quiet) is quiet
    assert profiler.pick_table([quiet, fetch],
                               ran_quiet + ['extra.99']) is fetch
    assert profiler.pick_table([fetch], ran_quiet) is fetch
    assert profiler.pick_table(None, ran_quiet) == {}


def test_trace_ops_go_to_the_program_whose_run_holds_them():
    """One place assigns a trace's ops to programs: the 'XLA Modules'
    line gives the runs, an op belongs to the run that holds it, and
    each program's ops read the one table that knows most of them."""
    import types
    quiet = profiler.hlo_scopes(QUIET, op_types=OPS)[1]
    fetch = profiler.hlo_scopes(FETCH, op_types=OPS)[1]
    module = 'jit_segment_mul_x4'

    def event(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)
    plane = types.SimpleNamespace(lines=[
        types.SimpleNamespace(name='XLA Ops', events=[event('x', 0, 1)]),
        types.SimpleNamespace(name='XLA Modules', events=[
            event(module + '(12)', 200, 50), event(module + '(11)', 0, 100)])])
    runs = profiler.module_runs(plane)
    assert runs == [(0.0, 100.0, module + '(11)'),
                    (200.0, 250.0, module + '(12)')]
    assert [profiler.program_at(runs, t) for t in (0, 100, 150, 225, 300)] \
        == [module + '(11)', module + '(11)', '', module + '(12)', '']
    ops = [(module + '(11)', 'fusion.1'), (module + '(12)', 'fusion.1'),
           (module + '(12)', 'extra.99'), (module + '(11)', 'copy.13'),
           ('other_module(3)', 'fusion.1'), (module + '(11)', 'fusion.2')]
    tables = {module: [fetch, quiet]}
    assert profiler.instruction_scopes(ops, tables) == [
        'mul_grad', 'softmax', fetch['extra.99'], None, None,
        quiet['fusion.2']]
    # no module line (off the chip): every table is a candidate
    assert profiler.module_runs(types.SimpleNamespace(lines=[])) == []
    assert profiler.instruction_scopes(
        [('', 'fusion.1'), ('', 'fusion.2'), ('', 'copy.13'),
         ('', 'multiply.14')], tables)[0] == 'mul_grad'


@pytest.mark.parametrize('op_name,scope', [
    ('jit(segment_x)/mul/dot_general', 'mul'),
    ('jit(segment_wpg_x)/jvp(mul)/dot_general', 'mul'),
    ('jit(segment_wpg_x)/transpose(jvp(mul))/dot_general', 'mul_grad'),
    ('jit(segment_x)/mul_grad/dot_general', 'mul_grad'),
    ('jit(segment_x)/mul#7/dot_general', None),  # one rule: no suffixes
    ('jit(segment_x)/fused_adam/unpack/slice', 'fused_adam/unpack'),
    ('jit(segment_x)/lookup_table_v2/jit(_take)/gather',
     'lookup_table_v2'),
    ('jit(segment_x)/jit(relu)/max', None),     # jit's name is no scope
    ('jit(segment_x)/mul', None),               # a primitive, not a scope
    ('reduce_sum', None),
    ('', None),
])
def test_fluid_scope_of_an_op_name(op_name, scope):
    assert profiler.fluid_scope(op_name, OPS) == scope


def test_self_durations_of_a_nest():
    # while [0, 10) holding [1, 4) and [5, 9); a lone op after it
    assert profiler._self_durations(
        [(0, 10), (1, 3), (5, 4), (12, 2)]) == [3, 3, 4, 2]


def _scopes_of_held_programs():
    types = collections.Counter()
    for tables in profiler.scope_tables().values():
        for table in tables:
            types.update(s.split('/')[0] for s in table.values() if s)
    return types


def _train_once(build, feed):
    compile_cache.reset_plane()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        program_types = {op.type for program in (main, startup)
                         for op in program.global_block().ops}
        return program_types, _scopes_of_held_programs()


# ops that move no data of their own once XLA has fused the step
_LAYOUT_ONLY = {'reshape2', 'transpose2', 'unsqueeze2', 'squeeze2',
                'fill_constant', 'scale', 'sum', 'cast', 'accuracy',
                'top_k'}


def _assert_table_covers(program_types, scopes, must_have, absorbed):
    """``absorbed``: op types none of whose instructions kept the name,
    because the rule gives a fusion ONE scope and XLA fused all of the
    op into a neighbour's (a residual add into its convolution), or
    because nothing read the op's result."""
    forward = {t for t in program_types if not t.endswith('_grad')}
    # every other op type of the program that computes appears
    missing = {t for t in forward - _LAYOUT_ONLY if not scopes[t]}
    assert missing == absorbed, (sorted(missing), sorted(scopes))
    assert must_have <= set(scopes), sorted(scopes)
    # and the table invents none: a scope is an op of the program or
    # the backward jax derived inside one
    for t in scopes:
        base = t[:-5] if t.endswith('_grad') else t
        assert base in forward, t


def test_scope_table_of_a_tiny_bert_program():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=100, hidden=32, layers=1, heads=2,
                          intermediate=64, max_pos=32, type_vocab=2,
                          dropout=0.1, attn_dropout=0.1)

    def build():
        _, _, loss = bert.build_pretrain(cfg, 16)
        fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(1e-3),
            use_dynamic_loss_scaling=True).minimize(loss)
        return loss

    n, t = 2, 16
    rng = np.random.RandomState(0)
    feed = {'src_ids': rng.randint(0, 100, (n, t)).astype('int32'),
            'pos_ids': np.tile(np.arange(t, dtype='int32'), (n, 1)),
            'sent_ids': np.zeros((n, t), 'int32'),
            'input_mask': np.ones((n, t), 'float32'),
            'mlm_label': rng.randint(0, 100, (n, t)).astype('int32'),
            'nsp_label': rng.randint(0, 2, (n, 1)).astype('int32')}
    program_types, scopes = _train_once(build, feed)
    _assert_table_covers(
        program_types, scopes,
        {'lookup_table_v2', 'lookup_table_v2_grad', 'mul', 'mul_grad',
         'matmul', 'matmul_grad', 'softmax', 'layer_norm_grad',
         'adam', 'check_finite_and_unscale'},
        absorbed={'elementwise_mul'})


def test_scope_table_of_a_tiny_resnet_program():
    from paddle_tpu.models import resnet

    def build():
        _, _, loss, _ = resnet.build(image_shape=(3, 32, 32), class_dim=10,
                                     depth=18, data_format='NHWC')
        fluid.optimizer.Momentum(0.01, 0.9).minimize(loss)
        return loss

    rng = np.random.RandomState(0)
    feed = {'image': rng.rand(2, 32, 32, 3).astype('float32'),
            'label': rng.randint(0, 10, (2, 1)).astype('int64')}
    program_types, scopes = _train_once(build, feed)
    _assert_table_covers(
        program_types, scopes,
        {'conv2d', 'conv2d_grad', 'batch_norm', 'batch_norm_grad',
         'pool2d', 'pool2d_grad', 'momentum'},
        absorbed={'elementwise_add', 'softmax'})


def test_a_dead_segment_leaves_the_plane_and_a_live_one_needs_no_trace():
    compile_cache.reset_plane()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        loss = fluid.layers.mean(fluid.layers.fc(x, 4, act='relu'))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((2, 8), 'float32')},
                fetch_list=[loss])
        before = fluid.monitor.counter_value('compile/trace_count')
        held = compile_cache.plane().held_hlo()
        # jit's own caches serve the lowering: JAX reports one (cached)
        # trace per program asked for, and none of the jits inside it
        assert fluid.monitor.counter_value('compile/trace_count') == \
            before + len(held) == before + 2
        assert any('jit_segment_mul' in text for _, text in held)
    del main, startup, exe, loss, x
    import gc
    gc.collect()
    assert compile_cache.plane().held_hlo() == []


def test_default_profile_is_attributed_from_the_xplane(tmp_path, capsys):
    """stop_profiler after a 'Default' profile and stop_trace both read
    the .xplane.pb this runtime writes, through the scope table."""
    import json
    compile_cache.reset_plane()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[64], dtype='float32')
        loss = fluid.layers.mean(fluid.layers.fc(x, 64, act='relu'))
    feed = {'x': np.ones((32, 64), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        profiler.start_profiler('All', tracer_option='Default')
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        profiler.stop_profiler('total', str(tmp_path / 'table.txt'))
        recs = profiler.summary_records()
        assert recs['mul']['calls'] >= 3 and recs['mul']['total'] > 0
        logdir = str(tmp_path / 'cap')
        profiler.start_trace(logdir)
        exe.run(main, feed=feed, fetch_list=[loss])
        assert profiler.stop_trace() == logdir
    capsys.readouterr()
    recs = profiler.summary_records()
    assert recs['mul']['calls'] >= 1 and recs['mul']['total'] > 0
    events = json.load(open(str(tmp_path / 'cap' / 'device.trace.json')))
    scoped = [e for e in events['traceEvents']
              if e.get('args', {}).get('tf_op') == 'mul']
    assert scoped and all(e['dur'] >= 0 for e in scoped)
    merged = json.load(open(str(tmp_path / 'table.txt.timeline.json')))
    assert any(e.get('cat') == 'pt_host' for e in merged['traceEvents'])
    profiler.reset_profiler()
