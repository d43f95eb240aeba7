"""The reduction from a profiler trace to numbers, on hand-made traces
whose answers are known and on a trace recorded on the chip (PR 22,
TPU v5 lite; trimmed to the op lines of its first steps by
``benchmark/tools/trim_trace.py``)."""

import gzip
import os

import pytest

from benchmark.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _profile(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def _plane(name, lines):
    """A plane in XSpace text form; ``lines`` is {line name: [(event
    name, start us, duration us)]}."""
    ids, out = {}, ['planes { name: "%s"' % name]
    for line_name, events in lines.items():
        out.append('lines { name: "%s" timestamp_ns: 0' % line_name)
        for ev_name, start, dur in events:
            out.append('events { metadata_id: %d offset_ps: %d '
                       'duration_ps: %d }'
                       % (ids.setdefault(ev_name, len(ids) + 1),
                          start * 10 ** 6, dur * 10 ** 6))
        out.append('}')
    for n, i in ids.items():
        out.append('event_metadata { key: %d value { id: %d name: "%s" } }'
                   % (i, i, n))
    return '\n'.join(out + ['}'])


# chip 0, times in us:        0         10        20        30        40
#   while.1 (nest)            [=========================)
#     fusion.1                [====)
#     custom-call.2 (Mosaic)       [=========)
#     all-reduce.3                           [====)   .   .
#   (idle 25..30)
#   fusion.4                                           [=========)
# async line: all-reduce-start.7 spans 32..44 us, beside fusion.4 (30..40)
HAND = _plane('/device:TPU:0', {
    'XLA Ops': [('while.1', 0, 25), ('fusion.1', 0, 5),
                ('custom-call.2', 5, 10), ('all-reduce.3', 15, 5),
                ('fusion.4', 30, 10)],
    'Async XLA Ops': [('all-reduce-start.7', 32, 12),
                      ('copy-start.8', 0, 50)],
}) + _plane('/host:CPU', {
    'python': [('bench/run#0', 0, 27), ('bench/fetch_run#1', 27, 20),
               ('unrelated', 0, 100)],
})


@pytest.fixture(scope='module')
def hand():
    return tr.reduce_profile(_profile(HAND), steps=2)


def test_busy_is_the_union_not_the_sum(hand):
    chip = hand.first
    assert sum(o.end - o.start for o in chip.ops) == 55e3   # the sum
    assert tr.length(chip.busy) == 35e3                     # the union
    assert (chip.start, chip.end) == (0.0, 40e3)
    assert hand.busy_ns == 35e3 and hand.window_ns == 40e3


def test_every_instant_goes_to_the_innermost_op(hand):
    chip = hand.first
    # the nest's own 5 us (20..25, nothing inside) count as other XLA
    assert chip.kind_ns(tr.MOSAIC) == 10e3
    assert chip.kind_ns(tr.COLLECTIVE) == 5e3
    assert chip.kind_ns(tr.OTHER) == 5e3 + 5e3 + 10e3
    assert sum(chip.kind_ns(k) for k in tr.KINDS) == tr.length(chip.busy)
    assert chip.self_ns['while.1'] == 5e3
    assert hand.per_step_ms(chip.kind_ns(tr.MOSAIC)) == 0.005


def test_idle_share_and_gaps_labelled_by_the_host_span(hand):
    chip = hand.first
    assert 1 - tr.length(chip.busy) / (chip.end - chip.start) == 0.125
    # the one gap, 25..30 us: its middle lies in both bench spans'
    # neighbourhood; the shortest span covering 27.5 us is fetch_run
    assert tr.idle_gaps(chip, hand.spans) == [['bench/fetch_run#1', 5e-6]]
    assert [s.name for s in hand.spans] == ['bench/run#0',
                                            'bench/fetch_run#1']
    assert tr.idle_gaps(chip, []) == [['no span', 5e-6]]


def test_exposed_collective_time_on_an_overlapping_case(hand):
    chip = hand.first
    # all-reduce.3 on the op line (15..20) is exposed whole; of the
    # async all-reduce (32..44) fusion.4 hides 32..40, so 4 us show;
    # the async copy is no collective and counts nowhere
    assert [o.name for o in chip.async_collectives] == \
        ['all-reduce-start.7']
    assert chip.exposed_collective_ns() == 5e3 + 4e3
    # the async line adds nothing to busy or to the kinds
    assert tr.length(chip.busy) == 35e3
    assert chip.kind_ns(tr.COLLECTIVE) == 5e3


def test_top_ops_are_ranked_by_innermost_time(hand):
    assert tr.top_ops(hand.first, 3) == [
        ['custom-call.2', 10e-6], ['fusion.4', 10e-6], ['fusion.1', 5e-6]]


# names as a TPU trace prints them (whole HLO instructions; shapes cut
# short here) and as other traces do (the instruction's name alone)
@pytest.mark.parametrize('text,name,kind', [
    ('%fused_adam.1 = (f32[32675,32,128]{2,1,0:T(8,128)}) custom-call('
     's32[32675]{0:T(1024)S(1)} %copy-done.691), custom_call_target='
     '"tpu_custom_call", operand_layout_constraints={s32[32675]{0}}',
     'fused_adam.1', tr.MOSAIC),
    ('%jvp_fused_multihead_attention_.12 = (bf16[144,2048,64]{2,1,0:'
     'T(8,128)(2,1)S(1)}) custom-call(bf16[144,2048,64]{2,1,0} '
     '%bitcast.1928), custom_call_target="tpu_custom_call"',
     'jvp_fused_multihead_attention_.12', tr.MOSAIC),
    ('%all-reduce.5 = f32[768]{0:T(1024)} all-reduce(f32[768]{0} '
     '%fusion.9), channel_id=3, replica_groups={{0,1,2,3}}',
     'all-reduce.5', tr.COLLECTIVE),
    ('%all-reduce-start.2 = f32[768,3072]{1,0} all-reduce-start('
     'f32[768,3072]{1,0} %fusion.1), channel_id=1', 'all-reduce-start.2',
     tr.COLLECTIVE),
    ('%ar-done = f32[8]{0} all-reduce-done(f32[8]{0} %ar-start)',
     'ar-done', tr.COLLECTIVE),
    ('%fusion.7 = f32[768]{0} fusion(f32[768]{0} %all-reduce.5), '
     'kind=kLoop, calls=%fused_computation.3', 'fusion.7', tr.OTHER),
    ('%copy-start.526 = (bf16[768]{0:T(1024)(128)(2,1)S(1)}, u32[]{:S(2)})'
     ' copy-start(bf16[768]{0} %get-tuple-element.2054)',
     'copy-start.526', tr.OTHER),
    ('%all-gather-start.4 = ((f32[8]{0}), f32[32]{0}) async-start(f32[8]{0}'
     ' %fusion.2), calls=%async_computation.4', 'all-gather-start.4',
     tr.COLLECTIVE),
    ('%slice-start.619 = ((bf16[768,3072]{1,0:T(8,128)(2,1)}), bf16[192,'
     '3072]{1,0}, s32[]{:S(2)}) async-start(bf16[768,3072]{1,0} %gte.2845)'
     ', calls=%async_computation.619', 'slice-start.619', tr.OTHER),
    ('all-reduce.12', 'all-reduce.12', tr.COLLECTIVE),
    ('all-gather-done', 'all-gather-done', tr.COLLECTIVE),
    ('collective-permute-start.2', 'collective-permute-start.2',
     tr.COLLECTIVE),
    ('custom-call.5', 'custom-call.5', tr.MOSAIC),
    ('all-reduce-scatter-fusion', 'all-reduce-scatter-fusion', tr.OTHER),
    ('dot_general.1', 'dot_general.1', tr.OTHER),
])
def test_parse_op(text, name, kind):
    assert tr.parse_op(text) == (name, kind)


def _recorded(name, steps):
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(HERE, 'traces', name)) as f:
        return tr.reduce_profile(
            ProfileData.from_serialized_xspace(f.read()), steps)


def test_recorded_one_chip_trace():
    """Two steps of bert_base_s2048 (b12) on a TPU v5 lite."""
    trace = _recorded('bert_base_s2048_two_steps.xplane.pb.gz', 2)
    chip = trace.first
    assert list(trace.devices) == [0] and len(chip.ops) == 10130
    # a real step nests too: the sum of durations exceeds the union
    assert sum(o.end - o.start for o in chip.ops) == 812962842.0
    assert tr.length(chip.busy) == trace.busy_ns == 802819272.0
    assert trace.window_ns == 803321758.0
    assert {k: chip.kind_ns(k) for k in tr.KINDS} == {
        tr.MOSAIC: 318795773.0, tr.COLLECTIVE: 0, tr.OTHER: 484023499.0}
    assert sum(chip.kind_ns(k) for k in tr.KINDS) == tr.length(chip.busy)
    # 29 Mosaic calls a step, as the described-chip compile counts
    mosaic = [o.name for o in chip.ops if o.kind == tr.MOSAIC]
    assert len(mosaic) == 2 * 29
    assert {n.rsplit('.', 1)[0] for n in mosaic} == {
        'fused_adam', 'jvp_fused_multihead_attention_',
        'transpose_jvp_fused_multihead_attention__',
        'jvp_lookup_table_v2_', 'transpose_jvp_lookup_table_v2__'}
    # 12 layers x (forward + backward) x 2 steps, 132 ms a step
    assert len([n for n in mosaic if 'fused_multihead_attention' in n]) \
        == 48
    assert chip.matching_ns('fused_multihead_attention', tr.MOSAIC) \
        == 263916638.0
    assert chip.exposed_collective_ns() == 0
    assert tr.top_ops(chip, 1) == [['fused_adam.1', 0.028536284]]
    # the host enqueues ten runs in 41 ms and waits in the last, the
    # one that fetches the loss, while the chip works through them
    gaps = tr.idle_gaps(chip, trace.spans, 5)
    assert gaps[0] == ['bench/fetch_run#10', 1.7808e-05]
    assert {label for label, _ in gaps} <= {s.name for s in trace.spans}
    assert len(trace.spans) == 11


def test_recorded_four_chip_trace():
    """Two steps of bert_base_s128_dp4 (192 per chip), chips 0 and 1 of
    the four: the three-way split with collectives on real links."""
    trace = _recorded('bert_base_s128_dp4_two_steps.xplane.pb.gz', 2)
    assert sorted(trace.devices) == [0, 1]
    chip = trace.first
    assert chip is trace.devices[0] and len(chip.ops) == 8814
    assert {k: chip.kind_ns(k) for k in tr.KINDS} == {
        tr.MOSAIC: 0, tr.COLLECTIVE: 10783316.0, tr.OTHER: 419754263.0}
    assert sum(chip.kind_ns(k) for k in tr.KINDS) == tr.length(chip.busy) \
        == 430537579.0
    # GSPMD combined the 110M parameters' gradients into three
    # synchronous all-reduces a step; nothing runs beside them
    assert sorted(o.name for o in chip.ops if o.kind == tr.COLLECTIVE) == \
        2 * ['all-reduce.153'] + 2 * ['all-reduce.154'] + \
        2 * ['all-reduce.155']
    assert chip.async_collectives == []
    assert chip.exposed_collective_ns() == 10783316.0
    assert trace.per_step_ms(chip.exposed_collective_ns()) == \
        pytest.approx(5.39, abs=0.01)
    # busy is averaged over the chips, the window spans both
    assert trace.busy_ns == (430537579.0 + 430534513.0) / 2
    assert trace.window_ns == 612958902.0 - 181933634.0
    assert tr.top_ops(chip, 1) == [['fusion.1077', 0.012997782]]


def test_a_trace_without_a_device_op_reduces_to_nothing():
    host_only = _plane('/host:CPU', {'python': [('bench/run#0', 0, 5)]})
    assert tr.reduce_profile(_profile(host_only), steps=1) is None


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == \
        [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) \
        == [(0, 2), (3, 8), (22, 29)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
