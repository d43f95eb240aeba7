"""fluid.profiler's live table (PR 52): the walk of a compiled module's
SCHEDULED HLO text that says which temporaries are alive where their
sum is largest, with the fluid op and class of each.  By hand on a
written two-layer train step; against the compiler's own
``temp_size_in_bytes`` on programs compiled here (a train step with a
donated update, a ``lax.scan`` whose residuals are stacked, a
``dynamic-update-slice`` in place) and on a canned text the TPU
compiler printed for a described v5e (``tests/hlo/scan_grad_v5e.txt``:
``copy-start`` / ``slice-start`` pairs, ``AllocateBuffer``, two loops)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, profiler

HERE = os.path.dirname(os.path.abspath(__file__))

# x[64,16] -> mul w1 -> tanh -> mul w2 -> (p - y), its square's
# gradient, both weights' gradients, sgd into the donated weights;
# f32: h, a, da, s, dh 8192 bytes each, p, d, dp, gw1 2048, gw2 1024
_STEP = '''HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }

ENTRY %main.1 (w1: f32[16,32], w2: f32[32,8], x: f32[64,16], y: f32[64,8]) -> (f32[16,32], f32[32,8]) {
  %w1 = f32[16,32]{1,0} parameter(0)
  %w2 = f32[32,8]{1,0} parameter(1)
  %x = f32[64,16]{1,0} parameter(2)
  %y = f32[64,8]{1,0} parameter(3)
  %h = f32[64,32]{1,0} dot(%x, %w1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mul/dot_general"}
  %a = f32[64,32]{1,0} tanh(%h), metadata={op_name="jit(step)/tanh/tanh"}
  %p = f32[64,8]{1,0} dot(%a, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mul/dot_general"}
  %d = f32[64,8]{1,0} subtract(%p, %y), metadata={op_name="jit(step)/elementwise_sub/sub"}
  %dp = f32[64,8]{1,0} add(%d, %d), metadata={op_name="jit(step)/transpose(jvp(square))/mul"}
  %gw2 = f32[32,8]{1,0} dot(%a, %dp), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(mul))/dot_general"}
  %da = f32[64,32]{1,0} dot(%dp, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(step)/transpose(jvp(mul))/dot_general"}
  %s = f32[64,32]{1,0} multiply(%a, %a), metadata={op_name="jit(step)/transpose(jvp(tanh))/mul"}
  %dh = f32[64,32]{1,0} multiply(%da, %s), metadata={op_name="jit(step)/transpose(jvp(tanh))/mul"}
  %nw2 = f32[32,8]{1,0} subtract(%w2, %gw2), metadata={op_name="jit(step)/sgd/sub"}
  %gw1 = f32[16,32]{1,0} dot(%x, %dh), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(mul))/dot_general"}
  %nw1 = f32[16,32]{1,0} subtract(%w1, %gw1), metadata={op_name="jit(step)/sgd/sub"}
  ROOT %out = (f32[16,32]{1,0}, f32[32,8]{1,0}) tuple(%nw1, %nw2)
}
'''

# name: (bytes, dies at, fluid op, class) of every buffer, by hand
_BY_HAND = {
    'h': (8192, 'a', 'mul', 'working'),
    'a': (8192, 's', 'tanh', 'residual'),
    'p': (2048, 'd', 'mul', 'working'),
    'd': (2048, 'dp', 'elementwise_sub', 'residual'),
    'dp': (2048, 'da', 'square_grad', 'working'),
    'gw2': (1024, 'nw2', 'mul_grad', 'gradient'),
    'da': (8192, 'dh', 'mul_grad', 'working'),
    's': (8192, 'dh', 'tanh_grad', 'working'),
    'dh': (8192, 'gw1', 'tanh_grad', 'working'),
    'gw1': (2048, 'nw1', 'mul_grad', 'gradient'),
}


@pytest.mark.parametrize('name', sorted(_BY_HAND) + ['nw1', 'nw2'])
def test_every_buffer_of_a_written_step_by_hand(name):
    module, live = profiler.hlo_live(_STEP, every=True)
    assert module == 'jit_step' and live is not None
    every = {b['instruction']: b for b in live['every']}
    assert set(every) == set(_BY_HAND) | {'nw1', 'nw2'}
    b = every[name]
    assert b['born'] == name            # born where it is defined
    if name in _BY_HAND:
        nbytes, dies, op, kind = _BY_HAND[name]
        assert (b['bytes'], b['dies'], b['op'], b['class'], b['out']) == (
            nbytes, dies, op, kind, False)
    else:                               # the donated weights' new values
        assert b['out'] and b['class'] == 'optimizer' and b['op'] == 'sgd'


def test_the_peak_of_the_written_step():
    _, live = profiler.hlo_live(_STEP)
    # a, gw2, da and s are alive when s is born; one instruction later
    # gw2, da, s and dh are as many bytes: the first stands
    assert (live['bytes'], live['point'], live['op']) == (
        8192 + 1024 + 8192 + 8192, 's', 'tanh_grad')
    assert [b['instruction'] for b in live['buffers']] == [
        'a', 'da', 's', 'gw2']
    assert live['by_class'] == {'residual': 8192, 'working': 16384,
                                'gradient': 1024}
    assert live['by_op'] == {'tanh': 8192, 'mul_grad': 9216,
                             'tanh_grad': 8192}
    # the outputs are the donated arguments' memory, never temporaries
    assert not {'nw1', 'nw2'} & {b['instruction'] for b in live['buffers']}
    assert 'every' not in live


def _walked(fn, args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    _, live = profiler.hlo_live(compiled.as_text(), every=True)
    return compiled.memory_analysis().temp_size_in_bytes, live


def _scan_step(w, x):
    def loss(w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return jnp.sum(h * h)
    return w - 0.1 * jax.grad(loss)(w)


def _update_in_place(buf, y, i):
    y3 = jnp.tanh(y @ y) @ y
    new = jax.lax.dynamic_update_slice(buf, y3[None], (i, 0, 0))
    return jnp.einsum('bij,jk->bik', new, y)


def _train_step_tables():
    """A two-layer fluid train step (Adam, donated state) run once;
    -> (its executable's temp bytes, its live table)."""
    from paddle_tpu.fluid import compile_cache
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data('x', shape=[256], dtype='float32')
        y = layers.data('y', shape=[1], dtype='float32')
        h = layers.fc(layers.fc(x, 512, act='relu'), 512, act='tanh')
        loss = layers.reduce_mean(layers.square(layers.fc(h, 1) - y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    feed = {'x': np.ones((1024, 256), 'float32'),
            'y': np.ones((1024, 1), 'float32')}
    compile_cache.reset_plane()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[])
        held = compile_cache.plane().held_executables()
        tables = profiler.live_tables()
    key, executable, _noted = max(
        held, key=lambda h: h[1].memory_analysis().temp_size_in_bytes)
    return executable.memory_analysis().temp_size_in_bytes, tables[key][1]


@pytest.mark.parametrize('case', ['train_step', 'scan', 'update_in_place'])
def test_the_walk_stands_beside_the_compilers_figure(case):
    if case == 'train_step':
        temp, live = _train_step_tables()
        # the activations kept for the backward pass carry their ops
        assert live['by_class'].get('residual', 0) > 0.3 * live['bytes']
        assert {'mul', 'mul_grad'} & {
            str(op).split('/')[0] for op in live['by_op']}
    elif case == 'scan':
        temp, live = _walked(_scan_step, (
            jnp.zeros((6, 128, 128), jnp.float32),
            jnp.zeros((512, 128), jnp.float32)), donate=(0,))
        # the body's peak stands at the call, over the stacked
        # residuals [6, 512, 128] that are alive around the loop
        assert ' > ' in live['point'] and live['point'].startswith('while')
        assert any(b['shape'] == 'f32[6,512,128]' for b in live['buffers'])
    else:
        temp, live = _walked(_update_in_place, (
            jnp.zeros((16, 256, 256), jnp.float32),
            jnp.zeros((256, 256), jnp.float32), jnp.int32(3)))
        # an argument is never written in place: the update comes out
        # as ONE buffer of the argument's size, not two
        whole = [b for b in live['every'] if b['bytes'] == 16 * 256 * 256 * 4
                 and not b['out']]
        assert len(whole) == 1
    assert live is not None
    assert abs(live['bytes'] - temp) <= 0.10 * temp


def test_a_written_update_of_a_carried_buffer_defines_nothing():
    """In a loop's body the parameter is the caller's memory: a
    ``dynamic-update-slice`` of it (bare, or as a fusion's root) and
    the operands of the root define no buffer."""
    text = '''HloModule m, is_scheduled=true

%fused (p0: f32[4,256], p1: f32[1,256], p2: s32[]) -> f32[4,256] {
  %p0 = f32[4,256]{1,0} parameter(0)
  %p1 = f32[1,256]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  %z = s32[] constant(0)
  ROOT %dus = f32[4,256]{1,0} dynamic-update-slice(%p0, %p1, %p2, %z)
}

%body (c: (s32[], f32[4,256], f32[1,256])) -> (s32[], f32[4,256], f32[1,256]) {
  %c = (s32[], f32[4,256]{1,0}, f32[1,256]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %stack = f32[4,256]{1,0} get-tuple-element(%c), index=1
  %row = f32[1,256]{1,0} get-tuple-element(%c), index=2
  %t = f32[1,256]{1,0} tanh(%row)
  %u = f32[1,256]{1,0} add(%t, %row)
  %put = f32[4,256]{1,0} fusion(%stack, %t, %i), kind=kLoop, calls=%fused
  %one = s32[] constant(1)
  %n = s32[] add(%i, %one)
  ROOT %r = (s32[], f32[4,256]{1,0}, f32[1,256]{1,0}) tuple(%n, %put, %u)
}

%cond (c: (s32[], f32[4,256], f32[1,256])) -> pred[] {
  %c = (s32[], f32[4,256]{1,0}, f32[1,256]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %four = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %four), direction=LT
}

ENTRY %main (x: f32[1,256]) -> f32[4,256] {
  %x = f32[1,256]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %i0 = s32[] copy(%zero)
  %stack0 = f32[4,256]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %x0 = f32[1,256]{1,0} copy(%x)
  %init = (s32[], f32[4,256]{1,0}, f32[1,256]{1,0}) tuple(%i0, %stack0, %x0)
  %loop = (s32[], f32[4,256]{1,0}, f32[1,256]{1,0}) while(%init), condition=%cond, body=%body
  %filled = f32[4,256]{1,0} get-tuple-element(%loop), index=1
  ROOT %res = f32[4,256]{1,0} negate(%filled)
}
'''
    _, live = profiler.hlo_live(text, every=True)
    defined = {b['instruction']: b for b in live['every']}
    # in the body only tanh's result is a temporary: the fusion writes
    # the carried stack, ``u`` and ``n`` are the next trip's carry
    assert [n for n, b in defined.items() if not b['out']] == [
        'i0', 'stack0', 'x0', 't']
    assert defined['t']['dies'] == 'put'
    # around the loop: the counter, the stack and the row; in it: t
    assert live['bytes'] == 4 + 4096 + 1024 + 1024
    assert live['point'] == 'loop > t'


def test_the_canned_tpu_text():
    """What the TPU compiler printed for ``_scan_step`` at [8, 1024,
    1024] x [4096, 1024] on a described v5e, and said of it:
    ``temp_size_in_bytes=336028672``."""
    text = open(os.path.join(HERE, 'hlo', 'scan_grad_v5e.txt')).read()
    module, live = profiler.hlo_live(text, every=True)
    assert module == 'jit_step' and live is not None
    assert abs(live['bytes'] - 336028672) <= 0.01 * 336028672
    # the three stacks the compiler allocates for the loops to fill
    # (AllocateBuffer) hold all but a few bytes of it
    stacks = [b for b in live['buffers'] if b['bytes'] >= 1 << 26]
    assert sorted(b['shape'] for b in stacks) == [
        'bf16[8,4096,1024]', 'f32[8,4096,1024]', 'f32[8,4096,1024]']
    # a copy-start's tuple is (the copy, its operand, a context): only
    # the copy is a buffer, and one put in another memory space (S(1))
    # counts nothing; a slice-start's is ((operands), the slice, ...)
    starts = [b for b in live['every']
              if b['instruction'].startswith(('copy-start', 'slice-start'))]
    assert all(b['shape'].count('[') == 1 for b in starts)
    lines = {l.split(' = ')[0].strip().lstrip('%'): l
             for l in text.splitlines() if ' = ' in l}
    for b in starts:
        assert 'S(1)' not in lines[b['instruction']].split(', ')[0]
    # the same ONE parse: the table rides with the scope and cost tables
    assert profiler._tables(text)[4]['bytes'] == live['bytes']


def test_step_hlo_hash_compare_says_whose_bytes_moved(tmp_path):
    """``tools/step_hlo_hash.py --compare``: equal programs read equal;
    a ``--memory`` entry that moved prints the fields, the classes, the
    fluid ops and the buffers that are among the ten largest of one
    side only."""
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        'step_hlo_hash', os.path.join(os.path.dirname(HERE), 'tools',
                                      'step_hlo_hash.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    _, live = profiler.hlo_live(_STEP)
    from paddle_tpu.fluid import memviz
    peak = memviz.temp_peak(live)
    fields = {'argument_bytes': 100.0, 'output_bytes': 10.0,
              'temp_bytes': 25600.0, 'peak_bytes': 25700.0,
              'generated_code_bytes': 5.0, 'alias_bytes': 10.0,
              'temp_peak': peak}
    grown = json.loads(json.dumps(fields))
    grown['temp_bytes'] += 58.48e6
    grown['temp_peak']['bytes'] += 58.48e6
    grown['temp_peak']['by_class']['working'] += 58.48e6
    grown['temp_peak']['by_op']['fused_multihead_attention_grad'] = 58.48e6
    grown['temp_peak']['top_buffers'].insert(0, {
        'bytes': 58.48e6, 'shape': 'f32[2,12,2048,64]',
        'instruction': 'fusion.9', 'class': 'working',
        'op': 'fused_multihead_attention_grad'})
    parent = {'c/fetch': ['aa', 10, 1], 'c/quiet': ['bb', 9, 1],
              'c/quiet/memory': fields, 'd/quiet': ['cc', 5, 0]}
    tree = {'c/fetch': ['aa', 10, 1], 'c/quiet': ['bd', 12, 2],
            'c/quiet/memory': grown}
    a, b = str(tmp_path / 'a.json'), str(tmp_path / 'b.json')
    json.dump(parent, open(a, 'w'))
    json.dump(tree, open(b, 'w'))
    lines = []
    assert tool.compare(a, a, out=lines.append) == 0
    assert lines == ['c/fetch: equal', 'c/quiet: equal',
                     'c/quiet/memory: equal', 'd/quiet: equal']
    lines = []
    assert tool.compare(a, b, out=lines.append) == 3
    text = '\n'.join(lines)
    assert 'c/fetch: equal' in text and 'd/quiet: only in the parent' in text
    assert 'c/quiet: DIFFERENT (bb -> bd, 9 -> 12 characters, 1 -> 2' in text
    assert 'field temp_bytes: 0.026 -> 58.506 MB (+58.480)' in text
    assert 'class working: 0.016 -> 58.496 MB (+58.480)' in text
    assert 'fluid op fused_multihead_attention_grad: 0.000 -> 58.480' in text
    assert ('among the ten largest: f32[2,12,2048,64] under '
            'fused_multihead_attention_grad (working): 0 -> 1') in text
    assert 'field argument_bytes' not in text       # what stood still


_CALLS = """HloModule step
%fused_computation.1 (p: f32[8,4,128]) -> f32[4,8,128] {
  %p = f32[8,4,128]{2,1,0} parameter(0)
  %t = f32[4,8,128]{2,1,0} transpose(%p), dimensions={1,0,2}
  ROOT %m = f32[4,8,128]{2,1,0} multiply(%t, %t)
}
%fused_computation.2 (p: f32[8,128]) -> f32[8,128] {
  %p.1 = f32[8,128]{1,0} parameter(0)
  ROOT %e = f32[8,128]{1,0} exponential(%p.1)
}
ENTRY %main (a: f32[8,4,128], b: f32[8,128]) -> (f32[8,128], f32[8,128]) {
  %a = f32[8,4,128]{2,1,0} parameter(0)
  %b = f32[8,128]{1,0} parameter(1)
  %fusion.1 = f32[4,8,128]{2,1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8,128]{1,0} fusion(%b), kind=kLoop, calls=%fused_computation.2
  %copy.7 = f32[8,128]{0,1} copy(%b)
  %copy-done.3 = f32[8,128]{1,0:S(1)} copy-done(%b)
  %walk.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) custom-call(%fusion.1, %copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/walk"}
  %chunk.1 = f32[8,128]{1,0} custom-call(%fusion.2, %copy-done.3, %b), custom_call_target="tpu_custom_call"
  %sort.1 = f32[8,128]{1,0} custom-call(%copy.7), custom_call_target="TopK"
  ROOT %out = (f32[8,128]{1,0}, f32[8,128]{1,0}) tuple(%chunk.1, %sort.1)
}
"""


def test_step_hlo_hash_names_the_operands_re_laid_for_a_kernel(tmp_path):
    """``relaid_operands``: of a compiled step's Mosaic calls, those
    that read what a ``copy`` or ``transpose`` made, alone or inside a
    fusion; a fusion without one, the compiler's prefetch pair, a
    parameter and another library's custom call are not named.
    ``--compare`` prints the lines one side has alone."""
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        'step_hlo_hash', os.path.join(os.path.dirname(HERE), 'tools',
                                      'step_hlo_hash.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    relaid = tool.relaid_operands(_CALLS)
    assert relaid == ['walk.1 <- fusion.1 (fusion)',
                      'walk.1 <- copy.7 (copy)']
    fields = {'peak_bytes': 1.0, 'relaid': relaid}
    parent = {'c/quiet/memory': fields}
    tree = {'c/quiet/memory': dict(fields, relaid=relaid[:1])}
    a, b = str(tmp_path / 'a.json'), str(tmp_path / 'b.json')
    json.dump(parent, open(a, 'w'))
    json.dump(tree, open(b, 'w'))
    lines = []
    assert tool.compare(a, b, out=lines.append) == 1
    assert lines == [
        'c/quiet/memory: moved',
        "    a call's operand re-laid on the parent only: "
        'walk.1 <- copy.7 (copy)',
        '    no temp_peak on either side: whose bytes cannot be said']
