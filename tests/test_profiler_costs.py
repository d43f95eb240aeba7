"""fluid.profiler's cost table: the FLOPs and bytes of each instruction
of a compiled segment's optimised HLO, from the same parse as the scope
table, and the rows and events that carry them."""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache, profiler

BF16 = 2

# what each case of the rule looks like in the text the compilers print:
# the TPU's (layouts with tiles, every dot a convolution) and the CPU's
HLO = '''HloModule jit_segment_costs, is_scheduled=true

%add.reduce (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%fused_sliced_dot (p0: bf16[16,64,32], p1: bf16[32,48], p2: s32[]) -> bf16[64,48] {
  %p0 = bf16[16,64,32]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[32,48]{1,0:T(8,128)(2,1)} parameter(1)
  %p2 = s32[]{:T(128)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  %dynamic-slice.1 = bf16[1,64,32]{2,1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p2, %zero, %zero), dynamic_slice_sizes={1,64,32}
  %bitcast.1 = bf16[64,32]{1,0:T(8,128)(2,1)} bitcast(%dynamic-slice.1)
  ROOT %dot.1 = bf16[64,48]{1,0:T(8,128)(2,1)} dot(%bitcast.1, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(segment_costs)/mul/dot_general"}
}

%fused_update (q0: f32[16,64,48], q1: bf16[64,48], q2: s32[]) -> f32[16,64,48] {
  %q0 = f32[16,64,48]{2,1,0:T(8,128)} parameter(0)
  %q1 = bf16[64,48]{1,0:T(8,128)(2,1)} parameter(1)
  %q2 = s32[]{:T(128)} parameter(2)
  %zero.1 = s32[]{:T(128)} constant(0)
  %convert.1 = f32[64,48]{1,0:T(8,128)} convert(%q1)
  %bitcast.2 = f32[1,64,48]{2,1,0:T(8,128)} bitcast(%convert.1)
  ROOT %dynamic-update-slice.1 = f32[16,64,48]{2,1,0:T(8,128)} dynamic-update-slice(%q0, %bitcast.2, %q2, %zero.1, %zero.1)
}

%fused_batched (r0: bf16[192,12,128,64], r1: bf16[192,12,128,64]) -> bf16[192,12,128,128] {
  %r0 = bf16[192,12,128,64]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %r1 = bf16[192,12,128,64]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[192,12,128,128]{3,2,1,0:T(8,128)(2,1)} convolution(%r0, %r1), window={size=192x12 stride=191x11 lhs_dilate=192x12}, dim_labels=01bf_01oi->01bf, metadata={op_name="jit(segment_costs)/matmul/dot_general"}
}

%body (carry: (s32[], f32[16,64,48])) -> (s32[], f32[16,64,48]) {
  %carry = (s32[]{:T(128)}, f32[16,64,48]{2,1,0:T(8,128)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%carry), index=0
  %buffer = f32[16,64,48]{2,1,0:T(8,128)} get-tuple-element(%carry), index=1
  %x.1 = bf16[16,64,32]{2,1,0:T(8,128)(2,1)} constant({...})
  %w.1 = bf16[32,48]{1,0:T(8,128)(2,1)} constant({...})
  %fusion.10 = bf16[64,48]{1,0:T(8,128)(2,1)} fusion(%x.1, %w.1, %i), kind=kOutput, calls=%fused_sliced_dot, metadata={op_name="jit(segment_costs)/while/body/mul/dot_general"}
  %fusion.11 = f32[16,64,48]{2,1,0:T(8,128)} fusion(%buffer, %fusion.10, %i), kind=kLoop, calls=%fused_update
  ROOT %tuple.1 = (s32[]{:T(128)}, f32[16,64,48]{2,1,0:T(8,128)}) tuple(%i, %fusion.11)
}

%cond (carry.1: (s32[], f32[16,64,48])) -> pred[] {
  %carry.1 = (s32[]{:T(128)}, f32[16,64,48]{2,1,0:T(8,128)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%carry.1), index=0
  %n = s32[]{:T(128)} constant(16)
  ROOT %lt = pred[]{:T(512)} compare(%i.1, %n), direction=LT
}

ENTRY %main (Arg_0: bf16[4,8,16,32], Arg_1: bf16[4,8,32,24], Arg_2: bf16[8,30,30,64], Arg_3: bf16[3,3,16,128], Arg_4: f32[1024,256], Arg_5: bf16[256]) -> f32[] {
  %Arg_0 = bf16[4,8,16,32]{3,2,1,0} parameter(0)
  %Arg_1 = bf16[4,8,32,24]{3,2,1,0} parameter(1)
  %Arg_2 = bf16[8,30,30,64]{3,2,1,0} parameter(2)
  %Arg_3 = bf16[3,3,16,128]{3,2,1,0} parameter(3)
  %Arg_4 = f32[1024,256]{1,0:T(8,128)} parameter(4)
  %Arg_5 = bf16[256]{0:T(256)(128)(2,1)} parameter(5)
  %dot.9 = bf16[4,8,16,24]{3,2,1,0} dot(%Arg_0, %Arg_1), lhs_batch_dims={0,1}, lhs_contracting_dims={3}, rhs_batch_dims={0,1}, rhs_contracting_dims={2}, metadata={op_name="jit(segment_costs)/matmul/dot_general"}
  %convolution.9 = bf16[8,15,15,128]{3,2,1,0} convolution(%Arg_2, %Arg_3), window={size=3x3 stride=2x2 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, feature_group_count=4, metadata={op_name="jit(segment_costs)/conv2d/conv_general_dilated"}
  %fusion.12 = bf16[192,12,128,128]{3,2,1,0:T(8,128)(2,1)} fusion(%Arg_0, %Arg_0), kind=kOutput, calls=%fused_batched
  %all-reduce-start.1 = (f32[1024,256]{1,0:T(8,128)}, bf16[256]{0:T(256)(128)(2,1)}) all-reduce-start(%Arg_4, %Arg_5), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.reduce
  %all-reduce-done.1 = (f32[1024,256]{1,0:T(8,128)}, bf16[256]{0:T(256)(128)(2,1)}) all-reduce-done(%all-reduce-start.1)
  %all-gather.2 = f32[4096,256]{1,0:T(8,128)} all-gather(%Arg_4), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
  %custom-call.3 = bf16[4,8,16,32]{3,2,1,0} custom-call(%Arg_0, %Arg_0), custom_call_target="tpu_custom_call", metadata={op_name="jit(segment_costs)/fused_multihead_attention/pallas_call"}
  %copy-start.4 = (f32[1024,256]{1,0:T(8,128)S(1)}, f32[1024,256]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%Arg_4)
  %copy-done.4 = f32[1024,256]{1,0:T(8,128)S(1)} copy-done(%copy-start.4)
  %start = (s32[]{:T(128)}, f32[16,64,48]{2,1,0:T(8,128)}) tuple(%Arg_4, %Arg_4)
  %while.5 = (s32[]{:T(128)}, f32[16,64,48]{2,1,0:T(8,128)}) while(%start), condition=%cond, body=%body
  ROOT %out = f32[] constant(0)
}
'''


@pytest.fixture(scope='module')
def costs():
    module, table = profiler.hlo_costs(HLO)
    assert module == 'jit_segment_costs'
    return table


def test_a_batched_dot_counts_its_result_times_its_contraction(costs):
    cost = costs['dot.9']
    assert (cost.kind, cost.dtype, cost.group) == ('dot', 'bf16', None)
    assert cost.flops == 2 * (4 * 8 * 16 * 24) * 32
    assert cost.bytes == BF16 * (4 * 8 * 16 * 32 + 4 * 8 * 32 * 24 +
                                 4 * 8 * 16 * 24)
    assert cost.shapes == \
        'bf16[4,8,16,32] x bf16[4,8,32,24] -> bf16[4,8,16,24]'


def test_a_strided_grouped_convolution_counts_the_taps_that_land(costs):
    cost = costs['convolution.9']
    assert (cost.kind, cost.dtype) == ('convolution', 'bf16')
    # 30 -> 15 at stride 2 behind one of padding: of 15 x 3 (position,
    # tap) pairs a dimension, the first position's first tap is on
    # padding and no other; 64 input features in 4 groups
    assert cost.flops == 2 * 8 * 128 * (64 // 4) * (15 * 3 - 1) ** 2


def test_a_batch_the_tpu_compiler_wrote_as_space_counts_once(costs):
    # batch dimensions 192 and 12 as spatial ones, dilated so that one
    # tap in 192 x 12 lands: the product's own 2 x b x h x s x s x d
    cost = costs['fusion.12']
    assert cost.kind == 'convolution'
    assert cost.flops == 2 * 192 * 12 * 128 * 128 * 64


def test_a_fusion_costs_its_dot_and_what_it_moves_at_its_boundary(costs):
    cost = costs['fusion.10']
    assert (cost.kind, cost.flops) == ('dot', 2 * 64 * 48 * 32)
    # of the [16, 64, 32] operand the body reads one [1, 64, 32] slice
    assert cost.bytes == BF16 * (64 * 32 + 32 * 48 + 64 * 48) + 4
    # the buffer is updated in place: the update is what is written,
    # the buffer itself is not read
    update = costs['fusion.11']
    assert (update.kind, update.flops) == ('fusion', 0)
    assert update.bytes == BF16 * 64 * 48 + 4 + 4 * 64 * 48


def test_collectives_cost_their_operands_and_know_their_group(costs):
    start = costs['all-reduce-start.1']
    assert (start.kind, start.flops, start.group) == ('collective', 0, 4)
    assert start.bytes == 4 * 1024 * 256 + BF16 * 256    # the tuple, summed
    assert costs['all-reduce-done.1'] is None
    gather = costs['all-gather.2']
    assert (gather.kind, gather.group, gather.bytes) == \
        ('collective', 4, 4 * 1024 * 256)


def test_a_mosaic_call_has_unknown_flops_never_none_at_all(costs):
    cost = costs['custom-call.3']
    assert cost.kind == 'custom-call' and cost.flops is None
    assert cost.bytes == 3 * BF16 * 4 * 8 * 16 * 32


def test_control_flow_costs_nothing_and_its_body_is_costed(costs):
    assert costs['while.5'] is None and costs['start'] is None
    assert 'fusion.10' in costs and costs['lt'].bytes == 4 + 4 + 1
    # an asynchronous copy moves its bytes beside the op line
    assert costs['copy-start.4'] is None and costs['copy-done.4'] is None


def test_bytes_are_those_of_the_main_memory():
    text = HLO.replace(
        '%Arg_1 = bf16[4,8,32,24]{3,2,1,0} parameter(1)',
        '%Arg_1 = bf16[4,8,32,24]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(1)')
    cost = profiler.hlo_costs(text)[1]['dot.9']
    assert cost.bytes == BF16 * (4 * 8 * 16 * 32 + 4 * 8 * 16 * 24)
    assert cost.flops == 2 * (4 * 8 * 16 * 24) * 32


def test_both_tables_hold_the_same_instructions():
    _, scopes = profiler.hlo_scopes(HLO)
    _, table = profiler.hlo_costs(HLO)
    assert set(scopes) == set(table)
    assert scopes['fusion.10'] == 'mul' and scopes['fusion.12'] == 'matmul'


def _taps_one_by_one(n, o, k, stride, lo, dilate, rhs_dilate):
    return sum(
        1 for p in range(o) for t in range(k)
        if 0 <= p * stride + t * rhs_dilate - lo <= (n - 1) * dilate and
        (p * stride + t * rhs_dilate - lo) % dilate == 0)


@pytest.mark.parametrize('case', [
    (224, 112, 7, 2, 3, 1, 1),      # ResNet's stem
    (56, 56, 3, 1, 1, 1, 1),        # 3 x 3, padded
    (28, 56, 3, 1, 1, 2, 1),        # its input gradient at stride 2
    (56, 3, 28, 1, 1, 1, 2),        # its weight gradient at stride 2
    (192, 192, 192, 191, 0, 192, 1),    # a batch written as space
    (192, 192, 192, 192, 191, 191, 1),  # and the other way round
    (1, 56, 56, 1, 55, 1, 1),       # a 1 x 1 with its operands swapped
    (7, 7, 1, 2, 0, 1, 1), (9, 5, 4, 3, 2, 4, 6), (5, 9, 3, 1, -1, 3, 2),
], ids=str)
def test_landing_taps_equal_the_count_one_by_one(case):
    assert profiler._landing_taps(*case) == _taps_one_by_one(*case)


def test_the_padding_share_the_rule_names_for_resnet50():
    """The docstring's 3.45%: a window-times-result count of ResNet-50
    at 224 x 224 against the multiply-adds that land on an element."""
    def conv(n, o, cin, cout, k, stride, pad):
        whole = o * o * k * k * cin * cout
        return whole, whole // (o * k) ** 2 * profiler._landing_taps(
            n, o, k, stride, pad, 1, 1) ** 2
    layers = [conv(224, 112, 3, 64, 7, 2, 3)]
    hw, cin = 56, 64
    for stage, blocks in enumerate([3, 4, 6, 3]):
        mid = 64 * 2 ** stage
        for block in range(blocks):
            stride = 2 if block == 0 and stage else 1
            layers += [conv(hw, hw, cin, mid, 1, 1, 0),
                       conv(hw, hw // stride, mid, mid, 3, stride, 1),
                       conv(hw // stride, hw // stride, mid, 4 * mid, 1, 1, 0)]
            if cin != 4 * mid or stride != 1:
                layers.append(conv(hw // stride, hw // stride, cin,
                                   4 * mid, 1, 1, 0))
            cin, hw = 4 * mid, hw // stride
    whole, landing = (sum(v) for v in zip(*layers))
    assert 1 - landing / whole == pytest.approx(0.0345, abs=0.0002)


# ------------------------------------------------------- live programs
HIDDEN, WIDTH, OUT, BATCH = 64, 96, 32, 16


def _two_layers(optimizer=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[HIDDEN], dtype='float32')
        h = fluid.layers.fc(x, WIDTH, act='relu')
        # squared, or the last bias's gradient is a constant that the
        # compiler folds and no device reduces
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.fc(h, OUT)))
        if optimizer:
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _dot_flops(tables):
    return [sum(c.flops for c in table.values()
                if c is not None and c.kind in ('dot', 'convolution'))
            for candidates in tables.values() for table in candidates]


def test_cost_tables_of_a_live_program_equal_the_hand_count():
    compile_cache.reset_plane()
    main, startup, loss = _two_layers()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((BATCH, HIDDEN), 'float32')},
                fetch_list=[loss])
        tables = profiler.cost_tables()
    assert max(_dot_flops(tables)) == \
        2 * BATCH * HIDDEN * WIDTH + 2 * BATCH * WIDTH * OUT
    held = [c for ts in tables.values() for t in ts for c in t.values()
            if c is not None and c.flops]
    assert {c.dtype for c in held} == {'f32'}


def test_under_a_mesh_the_all_reduce_moves_the_gradients():
    import jax
    from jax.sharding import Mesh
    compile_cache.reset_plane()
    main, startup, loss = _two_layers(optimizer=True)
    mesh = Mesh(np.array(jax.devices()[:4]), ('dp',))
    target = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name).with_mesh(mesh)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(target, feed={'x': np.ones((BATCH, HIDDEN), 'float32')},
                fetch_list=[loss])
        tables = profiler.cost_tables()
    reduced = [c for ts in tables.values() for t in ts for c in t.values()
               if c is not None and c.kind == 'collective']
    assert reduced and {c.group for c in reduced} == {4}
    parameters = HIDDEN * WIDTH + WIDTH + WIDTH * OUT + OUT
    # every parameter's f32 gradient, and the loss GSPMD sums with them
    assert sum(c.bytes for c in reduced) in (4 * parameters,
                                             4 * (parameters + 1))


def test_each_executable_is_printed_and_parsed_once(monkeypatch):
    compile_cache.reset_plane()
    main, startup, loss = _two_layers()
    printed, built = [], []
    text_of, tables_of = compile_cache.CompilePlane._hlo_text, \
        profiler._tables
    monkeypatch.setattr(
        compile_cache.CompilePlane, '_hlo_text',
        staticmethod(lambda key, ex: printed.append(key) or
                     text_of(key, ex)))
    monkeypatch.setattr(
        profiler, '_tables',
        lambda text: built.append(1) or tables_of(text))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        feed = {'x': np.ones((BATCH, HIDDEN), 'float32')}
        exe.run(main, feed=feed, fetch_list=[loss])
        scopes = profiler.scope_tables()
        held = len(printed)
        assert held == len(built) == 2          # startup and main
        costs = profiler.cost_tables()
        profiler.scope_tables()
        assert len(printed) == len(built) == held
        assert {m: [set(t) for t in ts] for m, ts in scopes.items()} == \
            {m: [set(t) for t in ts] for m, ts in costs.items()}
        # a program that comes later is the only one built then
        exe.run(main, feed=feed, fetch_list=[])
        profiler.cost_tables()
        assert len(printed) == len(built) == held + 1
    del main, startup, exe, loss
    import gc
    gc.collect()
    assert profiler.cost_tables() == {}
    assert compile_cache.plane()._built == {}


def test_the_trace_table_and_events_carry_the_costs(tmp_path, capsys):
    compile_cache.reset_plane()
    main, startup, loss = _two_layers()
    feed = {'x': np.ones((BATCH, HIDDEN), 'float32')}
    logdir = str(tmp_path / 'cap')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        profiler.start_trace(logdir)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        profiler.stop_trace()
    row = profiler.summary_records()['mul']
    assert {'calls', 'total', 'max', 'min', 'ave'} < set(row)
    per_run = 2e-9 * BATCH * (HIDDEN * WIDTH + WIDTH * OUT)
    assert row['gflop'] == pytest.approx(3 * per_run)
    assert row['tflops'] == pytest.approx(row['gflop'] / 1e3 / row['total'])
    assert row['gbps'] == pytest.approx(row['mb'] / 1e3 / row['total'])
    table = profiler.summary_string()
    assert table.splitlines()[0].split()[-4:] == \
        ['GFLOP', 'MB', 'TFLOP/s', 'GB/s']
    events = json.load(open(str(tmp_path / 'cap' / 'device.trace.json')))
    dots = [e['args'] for e in events['traceEvents']
            if e.get('args', {}).get('kind') == 'dot']
    assert len(dots) == 6 and all(
        a['tf_op'] == 'mul' and a['gflop'] > 0 and a['mb'] > 0
        for a in dots)
    profiler.reset_profiler()
    assert 'GFLOP' not in profiler.summary_string()
