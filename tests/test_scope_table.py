"""fluid.profiler's scope table: which fluid op each instruction of a
compiled segment's optimised HLO was lowered from, and the xplane
loader that reads a capture through it."""

import collections

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache, profiler

OPS = {'mul', 'relu', 'adam', 'fused_adam', 'lookup_table_v2', 'softmax',
       'tanh', 'while'}

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


_WPG = 'jit(segment_wpg_mul_x36)/transpose(jvp(jvp()))/checkpoint/'
_LOOP = ('jit(s)/transpose(jvp(while))/while/body/closed_call/loop_body/'
         'loop_body/checkpoint/')


@pytest.mark.parametrize('op_name,scope,phase', [
    ('jit(segment_x)/mul/dot_general', 'mul', 'forward'),
    ('jit(segment_wpg_x)/jvp(mul)/dot_general', 'mul', 'forward'),
    ('jit(segment_wpg_x)/transpose(jvp(mul))/dot_general', 'mul_grad',
     'backward'),
    ('jit(segment_x)/mul_grad/dot_general', 'mul_grad', 'backward'),
    ('jit(segment_x)/mul#7/dot_general', None, None),  # no suffixes
    ('jit(segment_x)/fused_adam/unpack/slice', 'fused_adam/unpack',
     'forward'),
    # a registered optimizer's op belongs to no pass
    ('jit(segment_x)/adam/mul', 'adam', None),
    ('jit(segment_x)/lookup_table_v2/jit(_take)/gather',
     'lookup_table_v2', 'forward'),
    ('jit(segment_x)/jit(relu)/max', None, None),  # jit's name: no scope
    ('jit(segment_x)/mul', None, None),         # a primitive, not a scope
    ('reduce_sum', None, None),
    ('', None, None),
    # a recompute group: the transpose sits on an earlier, nameless
    # component; the group's backward ...
    (_WPG + 'mul/dot_general', 'mul_grad', 'backward'),
    (_WPG + 'tanh/mul', 'tanh_grad', 'backward'),
    # ... and its second forward, which keeps the forward's name
    (_WPG + 'rematted_computation/mul/dot_general', 'mul', 'recomputed'),
    (_WPG + 'rematted_computation/tanh/tanh', 'tanh', 'recomputed'),
    # a group in the body of a differentiable loop, under the
    # transposed ``while``
    (_LOOP + 'mul/dot_general', 'mul_grad', 'backward'),
    (_LOOP + 'rematted_computation/mul/dot_general', 'mul', 'recomputed'),
    ('jit(s)/jvp(while)/while/body/closed_call/loop_body/mul/dot_general',
     'mul', 'forward'),
    ('jit(s)/transpose(jvp(while))/while/body/dynamic_slice',
     'while_grad/while', 'backward'),
    # a Mosaic call lowered inside a group follows the same components
    (_WPG + 'rematted_computation/softmax/pallas_call', 'softmax',
     'recomputed'),
    (_WPG + 'softmax/pallas_call', 'softmax_grad', 'backward'),
    # an op's own backward rule that runs its forward again is backward
    ('jit(s)/transpose(jvp(softmax))/pallas_call',
     'softmax_grad', 'backward'),
    # a loop a group runs again
    (_WPG + 'rematted_computation/while/body/loop_body/mul/dot_general',
     'mul', 'recomputed'),
])
def test_fluid_scope_of_an_op_name(op_name, scope, phase):
    assert profiler.fluid_scope(op_name, OPS) == scope
    assert profiler.fluid_pass(op_name, OPS) == phase


# a fusion that holds a recomputed dot under a backward root: the dot
# decides scope AND pass; a fusion of elementwise backward code; a
# multi-output fusion whose tuple root stands for a recomputed operand
GROUP_HLO = '''HloModule jit_segment_wpg_mul_x36, is_scheduled=true

%fused_dot (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.2 = f32[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="WPGrematted_computation/mul/dot_general"}
  ROOT %mul.3 = f32[8,8]{1,0} multiply(%dot.2, %p0), metadata={op_name="WPGtanh/mul"}
}

%fused_back (p0.1: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %mul.4 = f32[8,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="WPGtanh/mul"}
}

%fused_tuple (p0.2: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0.2 = f32[8,8]{1,0} parameter(0)
  %tanh.5 = f32[8,8]{1,0} tanh(%p0.2), metadata={op_name="WPGrematted_computation/tanh/tanh"}
  %copy.6 = f32[8,8]{1,0} copy(%tanh.5)
  ROOT %tuple.7 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%copy.6, %p0.2)
}

ENTRY %main.9 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %first.1 = f32[8,8]{1,0} tanh(%Arg_0.1), metadata={op_name="jit(segment_wpg_mul_x36)/jvp(tanh)/tanh"}
  %fusion.1 = f32[8,8]{1,0} fusion(%first.1, %Arg_0.1), kind=kOutput, calls=%fused_dot, metadata={op_name="WPGtanh/mul"}
  %fusion.2 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_back
  %fusion.3 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%first.1), kind=kLoop, calls=%fused_tuple
  %get-tuple-element.8 = f32[8,8]{1,0} get-tuple-element(%fusion.3), index=0
  %adam.1 = f32[8,8]{1,0} add(%fusion.2, %get-tuple-element.8), metadata={op_name="jit(segment_wpg_mul_x36)/adam/add"}
  ROOT %copy.2 = f32[8,8]{1,0} copy(%adam.1)
}
'''.replace('WPG', _WPG)


def test_a_fusion_s_pass_is_that_of_the_instruction_that_decides_its_scope():
    built = profiler._tables(GROUP_HLO, op_types=OPS | {'tanh'})
    assert built.module == 'jit_segment_wpg_mul_x36'
    both = {name: (built.scopes[name], built.passes[name])
            for name in built.scopes}
    # the dot it holds, not its backward root nor its own op_name
    assert both['fusion.1'] == ('mul', 'recomputed')
    assert both['fusion.2'] == ('tanh_grad', 'backward')
    # the tuple root's nearest scoped operand
    assert both['fusion.3'] == ('tanh', 'recomputed')
    assert both['first.1'] == ('tanh', 'forward')
    assert both['adam.1'] == ('adam', None)
    assert both['copy.2'] == (None, None)
    assert set(built.passes) == set(built.scopes) == set(built.costs)
    # the live walk: what the first forward defines and the second
    # forward reads is kept; what the second forward defines is
    # recomputed, a copy of it the compiler made too
    rows = {r['instruction']: r['class']
            for r in profiler.hlo_live(GROUP_HLO, every=True)[1]['every']}
    assert rows['first.1'] == 'residual'
    assert rows['fusion.1'] == 'recomputed'
    assert rows['fusion.3'] == 'recomputed'
    assert rows['fusion.2'] == 'gradient'


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


def _scope_and_pass_of_held_programs():
    """Counter of (scope, pass) over every instruction of every program
    held; the two tables hold the same instructions, and a pass goes
    with its name: ``_grad`` where and only where it is backward."""
    both = collections.Counter()
    scopes, passes = profiler.scope_tables(), profiler.pass_tables()
    assert sorted(scopes) == sorted(passes)
    for module, tables in scopes.items():
        for table, by_pass in zip(tables, passes[module]):
            assert set(table) == set(by_pass)
            both.update((table[name], by_pass[name]) for name in table)
    for scope, phase in both:
        assert phase in (None,) + profiler.PASSES
        if phase is not None:
            assert scope.split('/')[0].endswith('_grad') == \
                (phase == 'backward'), (scope, phase)
    return both


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
        # a program without a recompute group runs nothing twice
        both = _scope_and_pass_of_held_programs()
        assert {phase for _, phase in both} == {None, 'forward', 'backward'}
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


def _group_program():
    """Four fc layers, the middle two one recompute group."""
    x = fluid.layers.data('x', shape=[16], dtype='float32')
    h = fluid.layers.fc(x, 16, act='tanh')
    with fluid.backward.recompute_guard():
        h = fluid.layers.fc(h, 16, act='tanh')
        h = fluid.layers.fc(h, 16, act='relu')
    loss = fluid.layers.mean(fluid.layers.fc(h, 16))
    fluid.optimizer.Adam(1e-3).minimize(loss)
    return loss


def _loop_program():
    """A differentiable ``While`` whose body, tanh(exp(x) x w), is a
    recompute group (as models/ouro.py's blocks are)."""
    layers = fluid.layers
    x = layers.data('x', shape=[16], dtype='float32')
    w = layers.create_parameter([16, 16], 'float32', name='w')
    i = layers.fill_constant([1], 'int64', 0)
    n = layers.fill_constant([1], 'int64', 3)
    going = layers.less_than(i, n)
    state = layers.scale(x, scale=1.0)
    loop = layers.While(going, max_trip_count=3)
    with loop.block():
        with fluid.backward.recompute_guard():
            new = layers.tanh(layers.mul(layers.exp(state), w))
        layers.assign(new, state)
        layers.increment(i, 1.0)
        layers.less_than(i, n, cond=going)
    loss = layers.mean(state)
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def _run_once(build):
    """-> (executor, program, feed, loss) after one step, inside a
    scope of its own, on an emptied compile plane."""
    compile_cache.reset_plane()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = build()
    feed = {'x': np.ones((4, 16), 'float32')}
    exe = fluid.Executor(fluid.XLAPlace(0))
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    return exe, main, feed, loss


def _live_rows_of_the_step():
    (text,) = [text for _, text in compile_cache.plane().held_hlo()
               if 'rematted_computation' in text]
    return profiler.hlo_live(text, every=True)[1]['every']


def test_a_recompute_group_s_three_passes_through_the_executor(tmp_path,
                                                              capsys):
    """What jax names a group's instructions is read off a real
    lowering: a jax that renames ``rematted_computation`` fails here
    and not, silently, in a metric."""
    import importlib.util
    import json
    import os
    with fluid.scope_guard(fluid.Scope()):
        exe, main, feed, loss = _run_once(_group_program)
        both = _scope_and_pass_of_held_programs()
        for wanted in (('mul', 'forward'), ('mul', 'recomputed'),
                       ('mul_grad', 'backward'), ('tanh', 'recomputed'),
                       ('tanh_grad', 'backward'), ('adam', None)):
            assert both[wanted], (wanted, sorted(both, key=str))
        # the group's two products are run a second time, the two
        # outside it are not
        assert both[('mul', 'recomputed')] == 2
        # the live walk: a second forward's buffers are ``recomputed``;
        # the group's input, which the first forward's tanh defined and
        # the second forward reads, is what the group KEEPS
        rows = _live_rows_of_the_step()
        classes = collections.Counter(r['class'] for r in rows)
        assert classes['recomputed'] and classes['residual']
        assert all(not r['op'].split('/')[0].endswith('_grad')
                   for r in rows if r['class'] in ('recomputed', 'residual'))
        assert any(r['op'] == 'tanh' and r['class'] == 'residual'
                   for r in rows)
        # a capture: every instruction event carries its pass and its
        # shapes, and the table ends with the three totals
        logdir = str(tmp_path / 'cap')
        profiler.start_trace(logdir)
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[loss])
        profiler.stop_trace()
    last = profiler.summary_string().splitlines()[-1]
    assert last.startswith('by pass (ms): forward ')
    assert ', recomputed ' in last and ', backward ' in last
    assert 'mul_grad' in profiler.summary_records()
    events = json.load(open(os.path.join(
        logdir, 'device.trace.json')))['traceEvents']
    seen = collections.Counter(
        (e['args']['tf_op'], e['args'].get('pass')) for e in events
        if 'args' in e and 'tf_op' in e['args'])
    assert seen[('mul', 'recomputed')] and seen[('mul_grad', 'backward')]
    assert all('shapes' in e['args'] for e in events
               if e.get('args', {}).get('kind'))
    rows = profiler.instructions_under(events, ['mul'])
    assert {r['pass'] for r in rows} == {'forward', 'recomputed'}
    assert all(r['tf_op'] == 'mul' and r['kind'] == 'dot' and
               r['calls'] >= 2 and r['ms'] > 0 and 'f32[' in r['shapes']
               for r in rows)
    unscoped = profiler.instructions_under(events, [profiler.UNSCOPED])
    assert unscoped and all(r['pass'] is None for r in unscoped)
    assert not {r['name'] for r in rows} & {r['name'] for r in unscoped}
    # tools/timeline.py --scope prints the same rows
    spec = importlib.util.spec_from_file_location(
        'timeline_tool', os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'tools', 'timeline.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    capsys.readouterr()
    assert tool.print_scope(logdir, ['mul', 'mul_grad',
                                     profiler.UNSCOPED], 50) == 0
    printed = capsys.readouterr().out
    assert ' recomputed ' in printed and ' backward ' in printed
    assert ' copy ' in printed
    profiler.reset_profiler()


def test_a_recompute_group_in_a_loop_s_body():
    """Under the transposed ``while`` the same components decide: the
    body's second forward is ``recomputed`` under the forward's name,
    on the loop's backward side."""
    with fluid.scope_guard(fluid.Scope()):
        _run_once(_loop_program)
        both = _scope_and_pass_of_held_programs()
        for wanted in (('mul', 'forward'), ('mul', 'recomputed'),
                       ('exp', 'recomputed'), ('mul_grad', 'backward'),
                       ('tanh_grad', 'backward'), ('sgd', None)):
            assert both[wanted], (wanted, sorted(both, key=str))
        sides = collections.Counter()
        passes, loops = profiler.pass_tables(), profiler.loop_tables()
        for module, tables in passes.items():
            for by_pass, by_side in zip(tables, loops[module]):
                sides.update((by_pass[name], side)
                             for name, side in by_side.items())
        assert sides[('recomputed', 'backward')]
        assert not sides[('recomputed', 'forward')]
        assert sides[('forward', 'forward')]
        classes = collections.Counter(
            r['class'] for r in _live_rows_of_the_step())
        assert classes['recomputed'] and classes['residual']


def test_a_program_without_a_group_has_no_recomputed_pass(tmp_path):
    def build():
        x = fluid.layers.data('x', shape=[16], dtype='float32')
        loss = fluid.layers.mean(fluid.layers.fc(x, 16, act='tanh'))
        fluid.optimizer.SGD(0.1).minimize(loss)
        return loss

    with fluid.scope_guard(fluid.Scope()):
        exe, main, feed, loss = _run_once(build)
        both = _scope_and_pass_of_held_programs()
        assert both[('mul', 'forward')] and both[('mul_grad', 'backward')]
        assert 'recomputed' not in {phase for _, phase in both}
        for _module, live in profiler.live_tables().values():
            assert 'recomputed' not in live['by_class']
        profiler.start_trace(str(tmp_path / 'cap'))
        exe.run(main, feed=feed, fetch_list=[loss])
        profiler.stop_trace()
    assert 'by pass' not in profiler.summary_string()
    profiler.reset_profiler()


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
