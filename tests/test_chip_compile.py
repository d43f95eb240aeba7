"""Ask the TPU's compiler, without a TPU: every kernel common.dispatch()
can select on a chip is compiled ahead of time for a DESCRIBED v5e at
the widths the main path (BERT-base, s2048 b4) uses.  Interpret-mode
parity (test_pallas_kernels.py, test_flash_attention.py) cannot see
what Mosaic refuses — block shapes off the (8, 128) tiling, SMEM/VMEM
over budget — and a compile costs no chip time.

Nothing runs: these tests say "the chip's compiler accepts it", never
"it is right" or "it is fast".  The topology is described inside the
fixture (only the worker that owns this file loads libtpu, and only
once a test here has started); the compiles run in this process.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.fluid import monitor
from paddle_tpu.ops import registry
from paddle_tpu.ops.pallas import (common, flash_attention,
                                   quant_collective)

# BERT-base (models.bert.BASE) at the chip_smoke.py width
VOCAB, MAX_POS, HIDDEN, FFN, LAYERS = 30522, 512, 768, 3072, 12


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep the
    # cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def dp_mesh(topo):
    """The described host's four chips as the benchmark's dp layout
    takes them (benchmark/layouts/dp.py)."""
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ('dp',))


@pytest.fixture
def as_on_tpu(monkeypatch):
    """jax.devices() still answers "cpu" beside a described topology;
    steer the one platform probe so dispatch() takes its chip branch
    (compiled, not interpreted)."""
    monkeypatch.setattr(common, 'on_tpu', lambda: True)


def _compiled(fn, one_chip, *specs, donate=()):
    """fn compiled for the described chip."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    return jax.jit(fn, donate_argnums=donate).lower(
        *jax.tree_util.tree_map(place, specs)).compile()


def _compile(fn, one_chip, *specs):
    """Compile fn for the described chip; returns the number of Mosaic
    kernels in the executable."""
    return _compiled(fn, one_chip, *specs).as_text().count(
        'custom_call_target="tpu_custom_call"')


def _spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compiled_on_chip(kernel):
    assert common._LAST[kernel] == {
        'path': 'fused', 'reason': 'tpu', 'interpret': False}, \
        common._LAST[kernel]


def _one_pass(t, d, dtype, group=1, dv=None, tk=None, lse=False,
              block_k=None):
    """What the one-pass backward's count (common.one_pass_backward_vmem
    at the blocks _flash_bwd hands it) says of a call with no key
    bias: (admitted, the ``vmem_limit_bytes`` it asks Mosaic for)."""
    return common.one_pass_backward_limit(flash_attention._one_pass_vmem(
        t, tk or t, d, dv or d, flash_attention.FUSED_BLOCK_Q,
        block_k or flash_attention.FUSED_BLOCK_K,
        jnp.dtype(dtype).itemsize, group, False, lse))


def _scoped(text):
    """The scoped VMEM of each Mosaic call of an executable, in bytes:
    what the call asked for, or Mosaic's default."""
    import re
    return [int(n) for n in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?'
        r'scoped_memory_configs[^\n]*?"size":"(\d+)"', text)]


def _forward_and_one_pass(text, limit):
    """The executable holds two Mosaic calls, the forward and the
    one-pass backward, and one of them runs under ``limit`` (None:
    Mosaic's default)."""
    scoped = _scoped(text)
    assert len(scoped) == 2 and \
        (limit or common.SCOPED_VMEM_BYTES) in scoped, (scoped, limit)


@pytest.mark.parametrize('b,t,h,d,rate', [
    (4, 2048, 12, 64, 0.0),     # chip_smoke.py / bench_bert_long
    (4, 2048, 12, 64, 0.1),     # in-kernel dropout mask
    (16, 512, 12, 64, 0.0),     # the dispatch floor (FLASH_MIN_SEQ)
    (48, 512, 12, 64, 0.1),     # bert_base_s512_b48's calls
    (4, 2048, 16, 128, 0.0),    # d128: one pass, under Mosaic's default
])
def test_flash_attention_fwd_bwd(one_chip, as_on_tpu, b, t, h, d, rate):
    def step(q, k, v, bias):
        def loss(q, k, v, bias):
            o = flash_attention.flash_attention(
                q, k, v, key_bias=bias, dropout_rate=rate,
                dropout_seed=jnp.uint32(7) if rate else None)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(q, k, v, bias)

    qkv = _spec((b, t, h, d), jnp.bfloat16)
    n = _compile(step, one_chip, qkv, qkv, qkv, _spec((b, t)))
    _compiled_on_chip('flash_attention')
    assert n == 2, n    # forward + one-pass backward


@pytest.mark.parametrize('b,t', [(2, 512), (1, 2048)])
def test_flash_forward_in_float32_at_the_reference_check_shapes(
        one_chip, as_on_tpu, b, t):
    """The benchmark's reference check runs BERT's f32 for_test clone:
    the forward kernel with a key bias, its products at full
    precision (Mosaic's fp32 contract precision)."""
    qkv = _spec((b, t, 12, 64), jnp.float32)
    n = _compile(lambda q, k, v, bias: flash_attention.flash_attention(
        q, k, v, key_bias=bias), one_chip, qkv, qkv, qkv, _spec((b, t)))
    assert n == 1, n


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
def test_causal_flash_at_the_olmoe_cell_shape(one_chip, as_on_tpu, dtype):
    """olmoe_1b7b_s4096: b3 t4096 h16 d128, causal, no key bias, no
    dropout.  The one-pass backward's instance counts 22.5 MB in
    bfloat16 and 42.5 in float32: over Mosaic's default and far under
    the core's 128 MiB, so _flash_bwd picks it and the call asks for
    its count and the headroom: forward + one backward call.
    bfloat16 is the timed train step; float32 (products at full
    precision) is the cell's reference check and ``chip_smoke.py
    --phase olmoe``."""
    b, t, h, d = 3, 4096, 16, 128
    admitted, limit = _one_pass(t, d, dtype)
    assert admitted and limit == int(
        (22.5 if dtype == jnp.bfloat16 else 42.5) * 2 ** 20) + \
        common.VMEM_HEADROOM_BYTES

    def step(q, k, v):
        def loss(q, k, v):
            o = flash_attention.flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    qkv = _spec((b, t, h, d), dtype)
    text = _compiled(step, one_chip, qkv, qkv, qkv).as_text()
    _compiled_on_chip('flash_attention')
    _forward_and_one_pass(text, limit)


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('heads,window', [(72, 512), (48, 0)],
                         ids=['sliding_72', 'full_48'])
def test_banded_grouped_flash_at_the_laguna_cell_shapes(
        one_chip, as_on_tpu, heads, window, dtype):
    """laguna_s21_s4096: b1 t4096 d128, 72 (banded, window 512) or 48
    (full causal) query heads over 8 K/V heads.  The one-pass backward
    at d128 (26.5 MB an instance in bfloat16, 46.5 in float32, asked
    of Mosaic with the headroom): its grid's second axis walks a K/V
    head's group of query heads over the resident K/V rows and sums dK
    and dV into two f32 VMEM scratch buffers; the banded calls take
    512-wide key blocks.  bfloat16 is the timed step, float32 the
    reference check and ``chip_smoke.py --phase laguna``.  The
    windowed calls carry the scope the op lowers them in."""
    import contextlib
    import re

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                with jax.named_scope('window%d' % window) if window \
                        else contextlib.nullcontext():
                    o = flash_attention.flash_attention(
                        q, k, v, causal=True, window=window)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    assert flash_attention._window_blocks((512, 1024), window) == \
        ((512, 512) if window else (512, 1024))
    text = _compiled(step, one_chip, _spec((1, 4096, heads, 128), dtype),
                     _spec((1, 4096, 8, 128), dtype),
                     _spec((1, 4096, 8, 128), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    admitted, limit = _one_pass(4096, 128, dtype, group=heads // 8)
    assert admitted and limit > common.SCOPED_VMEM_BYTES
    _forward_and_one_pass(text, limit)
    assert all(n.startswith('window512') == bool(window)
               for n in names), names


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('h', [16, 32], ids=['moonlight_h16', 'kimi_h32'])
def test_latent_flash_at_the_moonlight_cell_shape(one_chip, as_on_tpu,
                                                  dtype, h):
    """moonlight_16b_s8192: b1 t8192 h16, queries and keys 192 wide
    over values 128 wide, causal; kimi_linear_48b_s8192's one latent
    layer is the same call at 32 heads (a longer grid, an instance's
    VMEM as at 16).  The resident rows of an 8k
    sequence at 192 + 128, which the pipeline keeps twice, are the
    block clamp's whole budget in bfloat16 and twice it in float32:
    the forward asks Mosaic for more scoped VMEM than its default
    (common.scoped_vmem).  In bfloat16, the timed step, the backward
    is the one-pass kernel: 59 MB an instance (rows 192 wide lie in
    256 lanes), 75 asked, of the core's 128.  In float32
    (``chip_smoke.py --phase moonlight``, the cell's reference check)
    the same instance counts 109 MB, over the 100 every call here
    keeps to: dq and dkv, which ask too.  The calls carry the scope
    the op lowers them in."""
    import re
    b, t, d, dv = 1, 8192, 192, 128
    item = jnp.dtype(dtype).itemsize
    admitted, limit = _one_pass(t, d, dtype, dv=dv)
    assert (admitted, limit) == (
        (True, 75 << 20) if dtype == jnp.bfloat16 else (False, 125 << 20))
    blocks = common.block_sizes(t, 512, 1024, d, item, dv)
    assert common.scoped_vmem(t, d, *blocks, item, dv) > \
        common.SCOPED_VMEM_BYTES

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                with jax.named_scope('qk%dv%d' % (d, dv)):
                    o = flash_attention.flash_attention(q, k, v,
                                                        causal=True)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(step, one_chip, _spec((b, t, h, d), dtype),
                     _spec((b, t, h, d), dtype),
                     _spec((b, t, h, dv), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    if admitted:
        _forward_and_one_pass(text, limit)
    else:
        assert len(names) == 3, names
    assert all(n.startswith('qk192v128') for n in names), names


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('window', [512, 0], ids=['window512', 'full'])
def test_differential_flash_at_the_phi4flash_cell_shapes(
        one_chip, as_on_tpu, window, dtype):
    """phi4_mini_flash_s8192: b1 t8192, 40 query heads 64 wide over 20
    K/V heads (keys 64 wide, values 128 wide: a differential pair's
    [v1 | v2]), causal, banded at 512 in the self-decoder and full in
    the full and the cross layer: the first calls at which the
    two-width path (Moonlight's, Xing4's: 192 / 128, one K/V head a
    query head, no window) runs UNDER a window, WITH grouped K/V heads
    and at width 64.  bfloat16 is the timed step, float32
    ``chip_smoke.py --phase phi4flash`` and the cell's reference check.
    Every call is named after the innermost scope, the widths', banded
    or not."""
    import re
    b, t, h, hkv, d, dv = 1, 8192, 40, 20, 64, 128

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                with jax.named_scope('window%d' % window if window
                                     else 'full'):
                    with jax.named_scope('qk%dv%d' % (d, dv)):
                        o = flash_attention.flash_attention(
                            q, k, v, causal=True, window=window)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(step, one_chip, _spec((b, t, h, d), dtype),
                     _spec((b, t, hkv, d), dtype),
                     _spec((b, t, hkv, dv), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(names) in (2, 3), names      # forward + one pass, or dq + dkv
    assert all(n.startswith('qk64v128') for n in names), names


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
def test_grouped_causal_flash_d64_at_the_lfm2_cell_shape(one_chip, as_on_tpu,
                                                         dtype):
    """lfm2_8b_a1b_s8192: b2 t8192, 32 query heads over 8 K/V heads of
    64, causal: grouped K/V heads had run at width 128 only (Laguna),
    width 64 only with as many K/V heads as query heads, not causal,
    at 2048 and under (BERT).  bfloat16 is the timed step, float32
    ``chip_smoke.py --phase lfm2`` and the cell's reference check.
    The backward is the one-pass kernel in both: rows 64 wide lie in
    128 lanes, so an instance counts 47 MB in bfloat16 and 81 in
    float32 (63 and 97 asked).  Every call is named after the op's
    own scope: no window, one width."""
    import re
    b, t, h, hkv, d = 2, 8192, 32, 8, 64

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                o = flash_attention.flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(step, one_chip, _spec((b, t, h, d), dtype),
                     _spec((b, t, hkv, d), dtype),
                     _spec((b, t, hkv, d), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    admitted, limit = _one_pass(t, d, dtype, group=h // hkv)
    assert (admitted, limit) == (
        True, (63 << 20) if dtype == jnp.bfloat16 else (97 << 20))
    _forward_and_one_pass(text, limit)
    assert all('fused_multihead_attention' in n for n in names), names
    # rows 64 wide lie in 128 lanes: two buffers of an 8k sequence's
    # take half of Mosaic's default and the forward asks for more
    # (inside the cell's train step the bfloat16 dkv call was refused
    # at 16.07 of 16 MB without); BERT's, at 2048 keys and under, do
    # not
    item = jnp.dtype(dtype).itemsize
    assert common.scoped_vmem(t, d, 512, 512, item) > \
        common.SCOPED_VMEM_BYTES
    assert common.scoped_vmem(2048, d, 512, 1024, item) is None


@pytest.mark.parametrize('kind', flash_attention.BLOCK_RELATIONS)
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
def test_block_mask_flash_at_the_sdar_cell_shape(one_chip, as_on_tpu,
                                                 dtype, kind):
    """sdar_30b_a3b_s4096: b1, 4096 keys, 32 query heads over 4 K/V
    heads of 128, blocks of 4, through the op (so the calls carry its
    ``block4_<relation>`` scope), WITH the log-sum-exp and a cotangent
    on it as ``layers.block_diffusion_attention`` merges the strict
    part: 'causal' (clean over clean) and 'strict' (corrupted over
    clean) at 4096 queries, the two the cell runs.
    bfloat16 is the timed step, float32 ``chip_smoke.py --phase sdar``
    and the cell's reference check.  The backward is the one-pass
    kernel in both, its dk and dv summed over a group of 8 in VMEM; no
    [T, Tk] tensor is in the program."""
    import re
    from paddle_tpu.ops import fused_ops
    b, t, h, hkv, d, block = 1, 4096, 32, 4, 128, 4
    tk = t
    assert _one_pass(t, d, dtype, tk=tk, lse=True, group=h // hkv)[0]
    registry.begin_trace()      # the sums below start at this program

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                out = fused_ops.fused_multihead_attention(
                    registry.LowerCtx(0),
                    {'Q': [q], 'K': [k], 'V': [v]},
                    {'block_mask': block, 'block_relation': kind,
                     'with_lse': True})
            lse = out['Lse'][0]
            return jnp.sum(out['Out'][0].astype(jnp.float32)) + \
                jnp.sum(jnp.where(jnp.isfinite(lse), lse, 0.0))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(step, one_chip, _spec((b, t, h, d), dtype),
                     _spec((b, tk, hkv, d), dtype),
                     _spec((b, tk, hkv, d), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(names) == 2, names       # forward + one-pass backward
    assert all(n.startswith('block4_' + kind) for n in names), names
    assert not re.search(r'\[(\d+,)*%d,%d\]' % (t, tk), text)
    # the pairs the mask lets through, a head: the strict or the
    # block-causal half of the square
    assert monitor.gauge_value('sdar/visible_pairs') == {
        'causal': tk * (tk + block) // 2,
        'strict': tk * (tk - block) // 2}[kind]


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
def test_own_blocks_at_the_sdar_cell_shape(one_chip, as_on_tpu, dtype):
    """sdar_30b_a3b_s4096's third attention part: the corrupted copy's
    4096 positions over their OWN blocks of 4, folded into the batch
    as ``layers.block_diffusion_attention`` folds them ([1024, 4, 32 |
    4, 128]), through the op under its bare scope, WITH the log-sum-exp
    and a cotangent on it.  The call takes the small-keys arm
    (ops/pallas/small_keys.py), counted once a lowering: one Mosaic
    call forward and one backward under the op's scope and under no
    ``block<n>_`` one (``bd_flash_roofline`` times those), and no
    ``dot`` or ``convolution`` of the dense chain beside them.  The
    calls read HEADS-FIRST operands, [32 | 4, 4096, 128]: from this
    test's [1, 4096, 32, 128] arguments the compiler transposes each
    one; in the cell's step the producers write that layout and none
    is re-laid (``tools/step_hlo_hash.py --memory``'s ``relaid``
    lines, PERF.md section 6, PR 64).  bfloat16 is the timed step,
    float32 the cell's reference check and ``chip_smoke.py --phase
    sdar`` (a float32 backward is refused at Mosaic's default scoped
    VMEM: the calls ask for what ``_vmem_count`` says)."""
    import re
    from paddle_tpu.ops import fused_ops
    n, block, h, hkv, d = 1024, 4, 32, 4, 128
    taken = monitor.counter_value(
        'pallas/flash_attention/dispatch_small_keys') or 0

    def step(q, k, v):
        def loss(q, k, v):
            def fold(x):
                return x.reshape(n, block, x.shape[2], d)
            with jax.named_scope('fused_multihead_attention'):
                out = fused_ops.fused_multihead_attention(
                    registry.LowerCtx(0),
                    {'Q': [fold(q)], 'K': [fold(k)], 'V': [fold(v)]},
                    {'with_lse': True})
            return jnp.sum(out['Out'][0].astype(jnp.float32)) + \
                jnp.sum(jnp.square(out['Lse'][0]))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(step, one_chip, _spec((1, n * block, h, d), dtype),
                     _spec((1, n * block, hkv, d), dtype),
                     _spec((1, n * block, hkv, d), dtype)).as_text()
    assert common._LAST['flash_attention'] == {
        'path': 'fused', 'reason': 'tpu', 'interpret': False,
        'arm': 'small_keys'}
    assert monitor.counter_value(
        'pallas/flash_attention/dispatch_small_keys') == taken + 1
    calls = re.findall(
        r'%(\S+) = \(([^\n]*?)\) custom-call\([^\n]*'
        r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"',
        text)
    assert sorted(re.sub(r'\.\d+$', '', c[0]) for c in calls) == [
        'small_keys_backward', 'small_keys_forward'], calls
    # a device trace's scope table files both under the op
    assert all(re.search(r'fused_multihead_attention\)+/small_keys_',
                         scope) and not re.search(r'block\d+_', scope)
               for _, _, scope in calls), calls
    # heads first: o, dq [32, 4096, 128]; dk, dv [4, 4096, 128]
    name = {jnp.bfloat16: 'bf16', jnp.float32: 'f32'}[dtype]
    assert all(outs.count('%s[%d,%d,%d]' % (name, h, n * block, d)) == 1
               for _, outs, _ in calls), calls
    assert not re.search(r' (dot|convolution)\(', text)
    assert not re.search(r'\[(\d+,)*%d,%d\]' % (block, block), text)
    assert max(_scoped(text)) <= common.VMEM_LIMIT_CAP_BYTES


@pytest.mark.parametrize('dtype,t', [
    (jnp.bfloat16, 4096), (jnp.float32, 4096), (jnp.bfloat16, 32768)],
    ids=['bf16-cell', 'f32-cell', 'bf16-32768'])
def test_both_eva_streams_at_the_evabyte_shapes(one_chip, as_on_tpu,
                                                dtype, t):
    """evabyte_6b5_s4096: b1 t4096, 32 heads of 128, windows of 2048
    over chunks of 16, through the op (so the calls carry its scopes):
    the local stream is causal flash at 2048 keys with the windows
    folded into the batch, WITH its log-sum-exp and a cotangent on it;
    the remote stream runs the kernels at Tk = T / 16 != Tq under the
    coarse mask, named ``remote``.  bfloat16 is the timed step;
    float32 is ``chip_smoke.py --phase evabyte`` and the cell's
    reference check, whose backward calls ask Mosaic for more scoped
    VMEM than its default (refused at 17.99 of 16 MB without).  Both
    streams' backward is the one-pass kernel wherever its instance
    fits what a call may ask for: the local stream's at 2048 keys
    counts 14.4 MB in bfloat16 (under Mosaic's default: the call asks
    for nothing) and 27.4 in float32; the remote stream's holds q and
    dO rows T long over T / 16 summaries.  T = 32768 is the published
    context: 16 windows, 2048 summaries, the remote stream still one
    pass in bfloat16 (80 MB an instance, 96 asked; float32 would count
    138 and run dq + dkv), and no [T, T / 16] tensor in the program."""
    import re
    from paddle_tpu.ops import fused_ops
    b, h, d, window, chunk = 1, 32, 128, 2048, 16
    item = jnp.dtype(dtype).itemsize
    # the blocks: a coarse call's key block is narrowed towards a
    # window's 128 summaries, but not under a quarter of the keys; the
    # backward is the one-pass kernel over rows of two lengths
    block_k = 128 if t == 4096 else 512
    assert flash_attention._window_blocks(
        common.block_sizes(t, 512, 1024, d, item, None, t // chunk), 0,
        (window, window // chunk), t // chunk) == (512, block_k)
    local = _one_pass(window, d, dtype, lse=True)
    assert local[0] and (local[1] is None) == (dtype == jnp.bfloat16), \
        local
    assert _one_pass(t, d, dtype, tk=t // chunk, lse=True,
                     block_k=block_k)[0]

    def run(ins, attrs):
        return fused_ops.fused_multihead_attention(
            registry.LowerCtx(0), {k: [x] for k, x in ins.items()},
            dict(attrs, with_lse=True))

    def step(q, k, v, ks, vs):
        def loss(q, k, v, ks, vs):
            def fold(x):
                return x.reshape(-1, window, h, d)
            with jax.named_scope('fused_multihead_attention'):
                local = run({'Q': fold(q), 'K': fold(k), 'V': fold(v)},
                            {'causal': True})
                remote = run({'Q': q, 'K': ks, 'V': vs},
                             {'coarse_window': window,
                              'coarse_chunk': chunk})
            lse = remote['Lse'][0]
            return sum(jnp.sum(x.astype(jnp.float32)) for x in (
                local['Out'][0], local['Lse'][0], remote['Out'][0],
                jnp.where(jnp.isfinite(lse), lse, 0.0)))
        return jax.grad(loss, (0, 1, 2, 3, 4))(q, k, v, ks, vs)

    full, summaries = _spec((b, t, h, d), dtype), \
        _spec((b, t // chunk, h, d), dtype)
    text = _compiled(step, one_chip, full, full, full, summaries,
                     summaries).as_text()
    _compiled_on_chip('flash_attention')
    names = [re.sub(r'\.\d+$', '', n) for n in re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)]
    remote = [n for n in names if 'remote' in n]
    local = [n for n in names if 'remote' not in n]
    assert all('fused_multihead_attention' in n for n in local), names
    # forward + one-pass backward, both streams
    assert len(remote) == 2 and len(local) == 2, names
    assert not re.search(r'\[(\d+,)*%d,%d\]' % (t, t // chunk), text)
    assert not re.search(r'\[(\d+,)*%d,%d\]' % (window, window), text)
    assert monitor.gauge_value('eva/remote_pairs') == sum(
        window * w * (window // chunk) for w in range(t // window))


@pytest.mark.parametrize('dtype,b,t,h,d,fused', [
    # what the compiler asks for moves with the grid, so the cells'
    # own: bert_base_s2048's fused backward, two [512, 512] tiles a trip
    ('bfloat16', 12, 2048, 12, 64, True),
    # dq + dkv, two [512, 1024] tiles a trip (four: 17.39M of 16M)
    ('bfloat16', 12, 2048, 12, 64, False),
    ('bfloat16', 6, 4096, 12, 64, False),
    # the rows alone fill the VMEM: one tile a trip (two: 16.29M)
    ('bfloat16', 3, 8192, 12, 64, False),
    # d128 with the draw's extra tile: fused, then dq + dkv
    ('bfloat16', 12, 1024, 16, 128, True),
    ('bfloat16', 6, 2048, 16, 128, True),
    # f32 keeps one tile a trip and the blocks it had (two tiles of
    # the fused backward at d128: 17.88M)
    ('float32', 12, 1024, 16, 128, True),
    ('float32', 2, 1024, 4, 128, False),
    # one tile an instance, dO V^T issued early: f32 at the floor
    ('float32', 48, 512, 12, 64, True),
    ('float32', 2, 2048, 4, 128, True),
    ('float32', 2, 2048, 4, 64, True),
    # the shapes ROADMAP S3 (6) listed as refused at Mosaic's default,
    # one pass since the call asks for what its instance counts
    # (common.one_pass_backward_vmem): bf16 at 4096 (23 MB counted, 39
    # asked; the compiler had said 18.2 of 16) and at 8192 (40, 56),
    # two tiles a trip under the raised limit; f32 at the cells' grids
    # (27.5 MB counted, 43.5 asked)
    ('bfloat16', 6, 4096, 12, 64, True),
    ('bfloat16', 3, 8192, 12, 64, True),
    ('float32', 12, 2048, 12, 64, True),
    ('float32', 6, 2048, 16, 128, True),
    # their dq + dkv twins (FUSED_BWD = False; 17.7 and 17.2 of 16 MB
    # when S3 (6) was written) have asked since PR 38
    ('float32', 12, 2048, 12, 64, False),
    ('float32', 6, 2048, 16, 128, False),
])
def test_flash_backward_fits_the_scoped_vmem(one_chip, as_on_tpu,
                                             monkeypatch, dtype, b, t, h,
                                             d, fused):
    """The backward kernels with a key bias and the in-kernel draw at
    the shapes that decide whether an instance may hold a second
    score tile alive (common.room_for_second_tile; flash_attention.
    _second_tile says what it is used for), and at those that decide
    whether the one-pass call has to ask Mosaic for more than its
    default."""
    monkeypatch.setattr(flash_attention, 'FUSED_BWD', fused)

    def step(q, k, v, bias):
        def loss(q, k, v, bias):
            o = flash_attention.flash_attention(
                q, k, v, key_bias=bias, dropout_rate=0.1,
                dropout_seed=jnp.uint32(7))
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(q, k, v, bias)

    qkv = _spec((b, t, h, d), jnp.dtype(dtype))
    n = _compile(step, one_chip, qkv, qkv, qkv, _spec((b, t)))
    _compiled_on_chip('flash_attention')
    assert n >= 2, n


@pytest.mark.parametrize('b,t,forward_mb,backward_mb', [
    (12, 2048, 27.25, 20.5),    # bert_base_s2048: both calls ask
    (48, 512, 13.75, 14.125),   # bert_base_s512_b48: Mosaic's default
])
def test_paired_d64_calls_lower_at_berts_shapes_and_ask_their_count(
        one_chip, as_on_tpu, b, t, forward_mb, backward_mb):
    """BERT's calls (12 heads of 64, key bias, rate 0.1) on [B, T, 768]
    operands as the projections write them: the forward and the
    one-pass backward hold a pair of heads a grid step
    (flash_attention._heads_a_step), compile for the described v5e
    under the scoped VMEM their counts ask for
    (common.two_tiles_vmem, common.one_pass_backward_vmem with
    heads=2; the headroom where the count passes Mosaic's default),
    which gauge vmem_asked_max reports, and no [B, H, T, 64] copy of
    an operand is in the executable."""
    h, d = 12, 64
    item = jnp.dtype(jnp.bfloat16).itemsize
    blocks = flash_attention._block_sizes(
        t, flash_attention.DEFAULT_BLOCK_Q,
        flash_attention.DEFAULT_BLOCK_K, 2 * d, item, 2 * d)
    forward = common.two_tiles_vmem(flash_attention._rows_resident(
        t, 2 * d, *blocks, item, 2 * d), *blocks, item, 2)
    backward = flash_attention._one_pass_vmem(
        t, t, 2 * d, 2 * d, min(t, flash_attention.FUSED_BLOCK_Q),
        min(t, flash_attention.FUSED_BLOCK_K), item, 1, True, False, 2)
    assert (forward, backward) == (forward_mb * 2 ** 20,
                                   backward_mb * 2 ** 20)
    asked = [common.one_pass_backward_limit(n)[1]
             for n in (forward, backward)]

    def step(q, k, v, bias):
        def loss(q, k, v, bias):
            o = flash_attention.flash_attention(
                *(x.reshape(b, t, h, d) for x in (q, k, v)),
                key_bias=bias, dropout_rate=0.1,
                dropout_seed=jnp.uint32(7))
            return jnp.sum(o.reshape(b, t, h * d).astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(q, k, v, bias)

    # the jitted calls keep their trace, and with it the gauge's setter
    flash_attention._fwd_call.clear_cache()
    flash_attention._bwd_call.clear_cache()
    monitor.remove_gauge('pallas/flash_attention/vmem_asked_max')
    before = monitor.counter_value(
        'pallas/flash_attention/layout_paired') or 0
    qkv = _spec((b, t, h * d), jnp.bfloat16)
    text = _compiled(step, one_chip, qkv, qkv, qkv,
                     _spec((b, t))).as_text()
    _compiled_on_chip('flash_attention')
    assert monitor.counter_value(
        'pallas/flash_attention/layout_paired') == before + 1
    # a call that asks runs under its limit, one that does not under
    # what it takes of Mosaic's default
    scoped = _scoped(text)
    assert len(scoped) == 2 and all(
        (n in scoped) if n else min(scoped) <= common.SCOPED_VMEM_BYTES
        for n in asked), (scoped, asked)
    assert monitor.gauge_value('pallas/flash_attention/vmem_asked_max') \
        == max(n or 0 for n in asked)
    assert 'bf16[%d,%d,%d,%d]' % (b, h, t, d) not in text
    assert 'bf16[%d,%d,%d]' % (b * h, t, d) not in text


def test_flash_kernels_are_named_after_the_scope_they_are_lowered_in(
        one_chip, as_on_tpu):
    """A device trace is read by instruction names (the benchmark's
    flash_roofline looks for the fluid op's type among the Mosaic
    calls), and the compiler names a kernel's instruction after the
    innermost scope around it: the executor's jax.named_scope(op.type)
    has to stay that scope, whatever the kernel file wraps its calls
    in (the jitted _fwd_call / _bwd_call are inlined)."""
    import re

    def step(q, k, v, bias):
        def loss(q, k, v, bias):
            with jax.named_scope('fused_multihead_attention'):
                o = flash_attention.flash_attention(
                    q, k, v, key_bias=bias, dropout_rate=0.1,
                    dropout_seed=jnp.uint32(7))
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(q, k, v, bias)

    qkv = _spec((12, 2048, 12, 64), jnp.bfloat16)
    text = _compiled(step, one_chip, qkv, qkv, qkv,
                     _spec((12, 2048))).as_text()
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(re.sub(r'\.\d+$', '', n) for n in names) == [
        'jvp_fused_multihead_attention_',
        'transpose_jvp_fused_multihead_attention__'], names


@pytest.mark.parametrize('shape,attrs,bias,named', [
    # (b, t, h, hkv, d, dv): bert_base_s2048_dp4's own calls, 2 a chip
    ((8, 2048, 12, 12, 64, 64), {'dropout_rate': 0.1}, True,
     'fused_multihead_attention'),
    ((4, 4096, 72, 8, 128, 128), {'causal': True, 'window': 512}, False,
     'window512'),
    ((4, 8192, 16, 16, 192, 128), {'causal': True}, False, 'qk192v128'),
], ids=['bert_s2048_dp4', 'laguna_window', 'moonlight_latent'])
def test_the_wrapped_flash_op_over_a_dp_mesh_of_four(dp_mesh, as_on_tpu,
                                                     shape, attrs, bias,
                                                     named):
    """fused_multihead_attention as the GSPMD runner traces it over the
    four described chips (the mesh and its batch axis published), the
    forward op and then the grad op, which replays the forward inside a
    shard_map of its own: the partitioner accepts the Mosaic calls (it
    refuses a bare one), the replay is merged with the forward op's
    call (one forward call, not two), no [b, h, t, t] tensor is left,
    nothing crosses chips, and the calls keep the name of the scope the
    op is lowered in, which the trace's readers look for."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel import mesh as pmesh
    b, t, h, hkv, d, dv = shape
    dtype = jnp.bfloat16

    def op(*xs):
        ins = dict(zip(('Q', 'K', 'V', 'KeyBias'), ([x] for x in xs)))
        with pmesh.use_trace_mesh(dp_mesh, ('dp',)):
            ctx = registry.LowerCtx(jnp.uint32(0), 3)
            with jax.named_scope('fused_multihead_attention'):
                return registry.get('fused_multihead_attention').fn(
                    ctx, ins, attrs)['Out'][0]

    def step(*xs):
        out = op(*xs)
        _, vjp = jax.vjp(op, *xs)
        return out, vjp((out * 2).astype(out.dtype))

    split = NamedSharding(dp_mesh, P('dp'))
    specs = [jax.ShapeDtypeStruct((b, t, n, w), dtype, sharding=split)
             for n, w in ((h, d), (hkv, d), (hkv, dv))]
    if bias:
        specs.append(jax.ShapeDtypeStruct((b, t), jnp.float32,
                                          sharding=split))
    before = monitor.counter_value(
        'pallas/flash_attention/dispatch_sharded') or 0
    text = jax.jit(step).lower(*specs).compile().as_text()
    _compiled_on_chip('flash_attention')
    assert monitor.counter_value(
        'pallas/flash_attention/dispatch_sharded') == before + 2
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(names) == 2, names   # forward + one-pass backward
    assert {re.sub(r'\.\d+$', '', n) for n in names} == {named}, names
    assert not re.search(r'\[\d+,%d,%d,%d\]' % (h, t, t), text)
    assert not re.search(r'all-reduce|all-gather|all-to-all|'
                         r'collective-permute', text)


def test_grouped_expert_matmuls_at_the_olmoe_cell_shape(one_chip):
    """The dropless MoE layer's grouped gate / up / down matmuls at
    olmoe_1b7b_s4096's 3 * 4096 * 8 routed rows over 64 experts of
    2048 x 1024, bf16, forward and backward: jax.lax.ragged_dot has to
    stay the chip compiler's own grouped matmul (temporaries of a few
    [rows, H] intermediates), not its dense expansion over the groups
    (64 x the rows)."""
    from paddle_tpu.parallel import moe
    rows, d, hidden, experts = 3 * 4096 * 8, 2048, 1024, 64

    def step(x, sizes, gate, up, down):
        def loss(x, gate, up, down):
            return jnp.sum(moe.grouped_expert_mlp(
                x, sizes, (gate, up), down,
                low_precision=True).astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(x, gate, up, down)

    wide = _spec((experts, d, hidden), jnp.bfloat16)
    compiled = _compiled(step, one_chip, _spec((rows, d), jnp.bfloat16),
                         _spec((experts,), jnp.int32), wide, wide,
                         _spec((experts, hidden, d), jnp.bfloat16))
    assert compiled.memory_analysis().temp_size_in_bytes < \
        16 * rows * d * 2


@pytest.mark.parametrize('rows,d,hidden', [
    (49152, 2048, 1408), (32768, 3072, 1024)], ids=['moonlight', 'laguna'])
def test_a_held_layers_experts_pass_over_no_whole_buffer(one_chip, rows,
                                                         d, hidden):
    """One held layer's ``moe_experts`` and its gradient op at the two
    held cells' shapes (8 experts held, bf16 rows under AMP's casts of
    f32 weights): whatever has a [rows, .] result, in any computation
    of the module, is a grouped-matmul call, a loop handing on its
    carry, or a trip's write of its chunk into the carry: no fusion
    over the buffer, no ``add`` of two cotangents, no ``copy`` of a
    carry at a loop's entry or in its body.  What runs between the
    products runs inside the three loops, a chunk a trip."""
    import re
    lower = registry.get('moe_experts').fn
    grad = registry.grad_op_def(registry.get('moe_experts')).fn
    attrs = {'experts_held': (0, 8), '__amp__': True}

    def step(x, sizes, gate, up, down, dout):
        ins = {'Rows': [x], 'GroupSizes': [sizes], 'WGate': [gate],
               'WUp': [up], 'WDown': [down]}
        with jax.named_scope('moe_experts'):
            out = lower(None, ins, attrs)['Out'][0]
        with jax.named_scope('moe_experts_grad'):
            grads = grad(None, dict(ins, **{'GRAD::Out': [dout]}), attrs)
        return out, grads

    buffer = _spec((rows, d), jnp.bfloat16)
    wide = _spec((8, d, hidden))
    text = _compiled(step, one_chip, buffer, _spec((8,), jnp.int32), wide,
                     wide, _spec((8, hidden, d)), buffer).as_text()
    # copy-start / copy-done: the compiler's own prefetch of a product's
    # operand into its 128 MiB of fast memory, where that is free (here,
    # alone; in no cell's step)
    idle = {'parameter', 'tuple', 'get-tuple-element', 'bitcast', 'while',
            'custom-call', 'opt-barrier', 'dynamic-update-slice',
            'copy-start', 'copy-done'}
    passes = [
        line.strip()[:160] for line in text.split('\n')
        if re.search(r' = \(?(\w+\[[\d,]*\]\S* )*\w+\[%d,' % rows, line)
        and re.search(r' ([\w\-]+)\(', line.split(' = ', 1)[1]).group(1)
        not in idle]
    assert not passes, passes
    assert text.count(' while(') == 3 and ' conditional(' not in text
    assert text.count('custom_call_target="tpu_custom_call"') >= 11


def test_lookup_table_and_its_gradient_hold_no_kernel(one_chip):
    """lookup_table_v2 and its gradient at the largest table a cell
    has (olmoe_1b7b_s4096: 12,288 tokens into 50304 x 2048) compile
    for the described chip with no kernel of ours."""
    lower = registry.get('lookup_table_v2').fn

    def step(w, ids):
        def loss(w):
            out = lower(registry.LowerCtx(0), {'W': [w], 'Ids': [ids]},
                        {'padding_idx': -1})['Out'][0]
            return jnp.sum(out ** 2)
        return jax.value_and_grad(loss)(w)

    text = _compiled(step, one_chip, _spec((50304, 2048)),
                     _spec((3, 4096), jnp.int32)).as_text()
    assert 'tpu_custom_call' not in text


def test_a_program_with_an_embedding_holds_no_mosaic_call(one_chip,
                                                          as_on_tpu):
    """layers.embedding over BERT's word table under Adagrad, the
    whole train step as Executor.run would jit it, compiled for the
    described chip: no kernel and no dispatch decision."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data('ids', shape=[128], dtype='int64')
        emb = fluid.layers.embedding(ids, size=[VOCAB, HIDDEN],
                                     padding_idx=0)
        loss = fluid.layers.reduce_mean(fluid.layers.square(emb))
        fluid.optimizer.Adagrad(0.1).minimize(loss)
    before = monitor.flat()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        step = exe.compile(main, feed_names=['ids'],
                           fetch_names=[loss.name])
        scope = fluid.global_scope()

        def held(n):
            v = fluid.core.as_array(scope.find_var(n))
            return _spec(v.shape, v.dtype)

        state = {n: held(n) for n in step.state_names}
        data = {n: _spec((8, 128), jnp.int32) if n == 'ids' else held(n)
                for n in step.input_names}
    text = _compiled(step.fn, one_chip, _spec((), jnp.int32), state,
                     data, donate=(1,)).as_text()
    assert 'tpu_custom_call' not in text
    assert {k: v for k, v in monitor.flat().items()
            if k.startswith('pallas/') and v != before.get(k, 0)} == {}


def _bert_base_param_shapes():
    """The parameter list Adam sees for BERT-base pretrain (word /
    position / sentence tables, 12 encoder layers, pooler + MLM + NSP
    heads) — 110M elements, so the block->tensor map the kernel keeps
    in SMEM is at its real length."""
    shapes = [(VOCAB, HIDDEN), (MAX_POS, HIDDEN), (2, HIDDEN),
              (HIDDEN,), (HIDDEN,)]
    for _ in range(LAYERS):
        shapes += [(HIDDEN, HIDDEN), (HIDDEN,)] * 4
        shapes += [(HIDDEN, FFN), (FFN,), (FFN, HIDDEN), (HIDDEN,)]
        shapes += [(HIDDEN,)] * 4
    return shapes + [(HIDDEN, HIDDEN), (HIDDEN,), (HIDDEN, HIDDEN),
                     (HIDDEN,), (HIDDEN,), (HIDDEN,), (VOCAB,),
                     (HIDDEN, 2), (2,)]


@pytest.mark.parametrize('kind', ['adam', 'lamb'])
def test_per_tensor_optimizer_over_bert_base(one_chip, kind):
    """The optimizer is no kernel: each parameter's registered lowering,
    state donated.  For the chip that compiles to no Mosaic call and to
    temporaries far under one copy of the 110M parameters (lamb keeps
    one tensor's update at a time for its norms)."""
    shapes = _bert_base_param_shapes()
    lower = registry.get(kind).fn

    def step(state, g, lr):
        outs = [lower(registry.LowerCtx(0),
                      {'Param': [p], 'Grad': [gi], 'Moment1': [m1],
                       'Moment2': [m2], 'LearningRate': [lr],
                       'Beta1Pow': [b1p], 'Beta2Pow': [b2p]}, {})
                for (p, m1, m2, b1p, b2p), gi in zip(state, g)]
        return [tuple(o[k][0] for k in (
            'ParamOut', 'Moment1Out', 'Moment2Out', 'Beta1PowOut',
            'Beta2PowOut')) for o in outs]

    state = [(_spec(s), _spec(s), _spec(s), _spec((1,)), _spec((1,)))
             for s in shapes]
    compiled = _compiled(step, one_chip, state,
                         [_spec(s) for s in shapes], _spec((1,)),
                         donate=(0,))
    assert 'tpu_custom_call' not in compiled.as_text()
    param_bytes = 4 * sum(int(np.prod(s)) for s in shapes)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < param_bytes / 2, mem
    assert mem.alias_size_in_bytes >= 3 * param_bytes, mem


def test_quant_collective_tiles(one_chip):
    # an FFN weight's gradient (768 x 3072) in FLAGS_comms_quant_block
    # = 256 rows, and its reduce over 4 peers' chunks
    block, peers = 256, 4
    nb = HIDDEN * FFN // block
    n = _compile(lambda x: quant_collective.quantize_blocks(x, False),
                 one_chip, _spec((nb, block)))
    assert n == 1, n
    cb = nb // peers
    n = _compile(
        lambda q, s: quant_collective.dequant_reduce_requant(q, s, False),
        one_chip, _spec((peers, cb, block), jnp.int8),
        _spec((peers, cb, 1)))
    assert n == 1, n


@pytest.mark.parametrize('t,heads,dk,dv,dtype', [
    (4096, 8, 128, 128, 'float32'),     # solar_open2_250b_s4096: 64 x 8
    (8192, 32, 128, 128, 'float32'),    # kimi_linear_48b_s8192: 128 x 32
    (8192, 32, 128, 128, 'bfloat16'),   # the same as AMP hands it over
    (24, 3, 128, 128, 'float32'),   # less than a chunk: one of two sub-chunks
    (100, 2, 256, 256, 'bfloat16'),     # a masked tail, two lane tiles
    (4096, 8, 128, 64, 'float32'),      # dv no lane tile: all of it XLA's
])
def test_the_delta_rule_compiles_its_score_and_walk_kernels(
        one_chip, as_on_tpu, t, heads, dk, dv, dtype):
    """``kda_attention``'s forward + backward at the two cells' layer
    shapes (and at a short and a ragged length; q, k, v, beta float32
    and bfloat16, the log decays float32): both dispatches answer fused,
    the executable holds FIVE Mosaic calls (the preparation's forward
    and the forward walk; the preparation's forward again, the reverse
    walk and the preparation's backward), each under the name its module
    gives it, and NO ``while``: the chunks are walked inside two calls,
    and no call holds more VMEM than ``kda_walk``'s count says or asks
    Mosaic for any.  What the preparation's calls read of q, k, v and a
    is the [B, T, H x d] view of what the op was handed (a parameter
    re-laid here, the producer's own result in a cell's step), never a
    chunked, transposed or padded copy.  Where a value's width is off
    the lanes neither kernel's layout holds: no Mosaic call and the two
    scans' ``while``s.  On the fused path no buffer of the step is a
    [.., SUB, SUB, dk] or [.., n_sub, n_sub, SUB, dk] decay block; on
    either the preparation holds no loop and no solve."""
    import re
    from paddle_tpu.ops import kda_ops
    from paddle_tpu.ops.pallas import kda_walk

    def step(q, k, v, a, beta, probe):
        out, pull = jax.vjp(kda_ops.gated_delta_rule, q, k, v, a, beta)
        return (out,) + pull(probe)

    dtype = jnp.dtype(dtype)
    wide, rows = _spec((1, t, heads, dk), dtype), _spec((1, t, heads), dtype)
    values = _spec((1, t, heads, dv), dtype)
    text = _compiled(step, one_chip, wide, wide, values,
                     _spec((1, t, heads, dk)), rows, values).as_text()
    fused = dv % 128 == 0
    for kernel in ('kda_chunk', 'kda_walk'):
        if fused:
            _compiled_on_chip(kernel)
        else:
            assert common._LAST[kernel] == {
                'path': 'dense', 'reason': 'layout', 'interpret': False}
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (5 if fused else 0)
    # (whole axes only: the compiler moves a [64, 8, 64, dk] operand in
    # four [64, 8, 16, dk] slices, which are no blocks)
    blocks = re.findall(
        r'f32\[[\d,]*(?:16,16|(?<!\d)\d,\d,16),%d\]' % dk, text)
    assert not blocks or not fused, sorted(set(blocks))
    assert len(re.findall(r' while\(', text)) == \
        (0 if fused or t <= 64 else 2)
    for opcode in ('triangular-solve', 'InvertDiagBlocksLowerTriangular'):
        assert opcode not in text, opcode
    if fused:
        names = re.findall(
            r'op_name="[^"]*(kda_(?:walk|chunk)_\w+?)\)*/pallas_call"', text)
        assert sorted(set(names)) == [
            'kda_chunk_backward', 'kda_chunk_forward',
            'kda_walk_forward', 'kda_walk_reverse'], names
        read = re.findall(
            r'custom-call\(([^)]*)\), custom_call_target="tpu_custom_call"'
            r'[^\n]*kda_chunk_', text)
        assert len(read) == 3
        for operands in read:
            names = [x.strip().lstrip('%') for x in operands.split(',')][:4]
            shapes = [re.search(r'%%%s = \w+\[([\d,]*)\]' % re.escape(x),
                                text).group(1) for x in names]
            assert shapes == ['1,%d,%d' % (t, heads * d)
                              for d in (dk, dk, dv, dk)], (names, shapes)
        used = [int(n) for n in re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*?'
            r'"used_scoped_memory_configs":\[\{[^}]*?"size":"(\d+)"', text)]
        count = kda_walk.reverse_vmem(
            kda_walk.heads_a_step(heads),
            kda_ops._layout(t, kda_ops.CHUNK)[0], dk, dv)
        assert len(used) == 5 and max(used) <= 1.1 * count <= \
            common.SCOPED_VMEM_BYTES, (used, count)


@pytest.mark.parametrize('rows,experts,d,hidden,held', [
    (98304, 64, 2048, 1024, None),      # olmoe_1b7b_s4096
    (32768, 8, 3072, 1024, (0, 8)),     # laguna_s21_s4096
    (49152, 8, 2048, 1408, (0, 8)),     # moonlight_16b_s8192
    (32768, 8, 2048, 1792, (0, 8)),     # lfm2_8b_a1b_s8192
    (32768, 8, 4096, 1280, (0, 8)),     # solar_open2_250b_s4096
], ids=['olmoe', 'laguna', 'moonlight', 'lfm2', 'solar'])
def test_the_experts_products_compile_as_our_kernels(
        one_chip, as_on_tpu, rows, experts, d, hidden, held):
    """``moe_experts`` and its gradient op at the five routed cells'
    shapes under AMP (bf16 rows, f32 weights cast inside): the
    dispatch answers fused, every grouped product is a Mosaic call of
    ours (3 forward; 6 backward, or 8 with the held MLP's recompute)
    and none is left to the compiler (``ragged-dot``); each call asks
    Mosaic for the VMEM a whole expert's matrix takes, under the
    cap."""
    lower = registry.get('moe_experts').fn
    grad = registry.grad_op_def(registry.get('moe_experts')).fn
    attrs = {'__amp__': True}
    if held:
        attrs['experts_held'] = held
    monitor.set_gauge('pallas/grouped_matmul/vmem_asked_max', 0)

    def step(x, sizes, gate, up, down, dout):
        ins = {'Rows': [x], 'GroupSizes': [sizes], 'WGate': [gate],
               'WUp': [up], 'WDown': [down]}
        out = lower(None, ins, attrs)['Out'][0]
        return out, grad(None, dict(ins, **{'GRAD::Out': [dout]}), attrs)

    buffer = _spec((rows, d), jnp.bfloat16)
    wide = _spec((experts, d, hidden))
    text = _compiled(step, one_chip, buffer, _spec((experts,), jnp.int32),
                     wide, wide, _spec((experts, hidden, d)),
                     buffer).as_text()
    _compiled_on_chip('grouped_matmul')
    assert 'ragged-dot' not in text
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (11 if held else 9)
    asked = monitor.gauge_value('pallas/grouped_matmul/vmem_asked_max')
    assert common.SCOPED_VMEM_BYTES < asked <= common.VMEM_LIMIT_CAP_BYTES


@pytest.mark.parametrize('tokens', [
    4096,           # xing4_29b_s4096: four tiles of 1024 tokens
    128 * 23,       # no whole tiles: ONE block of 23 rows, the largest
                    # the gate admits (tests/test_sinkhorn_kernel.py)
    128,            # one row of lanes
])
def test_the_sinkhorn_projection_compiles_its_two_calls(one_chip, as_on_tpu,
                                                        tokens):
    """``hyper_connection_ops.project`` forward + backward on a [4, 4,
    tokens] float32 matrix, 20 trips: the dispatch answers fused, the
    executable holds TWO Mosaic calls (the forward; the backward, which
    runs the trips again over its VMEM scratch), neither asks Mosaic for
    more than its default scoped VMEM, what the compiler says the
    backward call uses is the kernel module's count (or less: a tile of
    one row lies in no 8 sublanes), and no loop of the program is
    left."""
    import re
    from paddle_tpu.ops import hyper_connection_ops as hc_ops
    from paddle_tpu.ops.pallas import sinkhorn

    def step(m, d_out):
        out, pull = jax.vjp(lambda m: hc_ops.project(m, 20, 1e-6), m)
        return (out,) + pull(d_out)

    rows = tokens // sinkhorn.LANES
    assert sinkhorn.backward_vmem(4, 20, rows) <= common.VMEM_BUDGET_BYTES
    m = _spec((4, 4, tokens))
    text = _compiled(step, one_chip, m, m).as_text()
    _compiled_on_chip('sinkhorn')
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert ' while(' not in text
    call = r'custom_call_target="tpu_custom_call"[^\n]*?'
    asked = re.findall(call + r'"scoped_memory_configs":\[([^\]]*)\]', text)
    assert asked == ['', ''], asked
    used = [int(n) for n in re.findall(
        call + r'"used_scoped_memory_configs":\[\{[^}]*?"size":"(\d+)"',
        text)]
    assert len(used) == 2 and \
        max(used) <= 1.02 * sinkhorn.backward_vmem(4, 20, rows), used


@pytest.mark.parametrize('b,t,d,dtype', [
    (1, 8192, 5120, jnp.bfloat16),  # phi4_mini_flash_s8192 under AMP
    (1, 8192, 5120, jnp.float32),   # its for_test reference check
    (2, 300, 1024, jnp.bfloat16),   # a batch, a tail that fills no chunk
])
def test_the_selective_scan_compiles_its_two_calls(one_chip, as_on_tpu,
                                                   b, t, d, dtype):
    """``selective_scan`` forward + backward at the Phi-4-mini-flash
    cell's layer shape ([1, 8192, 5120] x 16 states, bfloat16 x, B, C
    beside float32 steps) and at a short ragged batch: the dispatch
    answers fused, the executable holds TWO Mosaic calls (the forward
    walk; the reverse walk, which runs each chunk forward again over
    its VMEM scratch) and no loop; the reverse call asks Mosaic for the
    kernel module's count and the headroom, and what the compiler says
    it uses stays under what it asked; the float32 steps and their
    gradient reach the calls as they lie (no copy of a [T, D] float32
    array is made for them)."""
    import re
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.ops.pallas import ssm_scan

    def step(*args):
        out, pull = jax.vjp(ssm_ops.selective_scan, *args[:6])
        return (out,) + pull(args[6])

    wide, steps = _spec((b, t, d), dtype), _spec((b, t, d))
    narrow = _spec((b, t, 16), dtype)
    text = _compiled(step, one_chip, wide, steps, _spec((d, 16)), narrow,
                     narrow, _spec((d,)), wide).as_text()
    _compiled_on_chip('ssm_scan')
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert ' while(' not in text
    size = ssm_scan.layout(t, ssm_ops.CHUNK)[0]
    count = ssm_scan.backward_vmem(size, 16, jnp.dtype(dtype).itemsize)
    assert max(_scoped(text)) == count + common.VMEM_HEADROOM_BYTES \
        <= common.VMEM_LIMIT_CAP_BYTES
    used = [int(n) for n in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?'
        r'"used_scoped_memory_configs":\[\{[^}]*?"size":"(\d+)"', text)]
    assert len(used) == 2 and max(used) <= 1.02 * count, (used, count)
    if t % 8 == 0:
        copies = re.findall(r'= f32\[[\d,]*\]\S* copy\(', text)
        big = [c for c in copies if
               np.prod([int(n) for n in re.findall(r'\d+', c)[1:]]) >=
               b * t * d]
        assert not big, big


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
def test_the_chunked_scan_and_gqa_flash_at_the_nemotron_cell_shapes(
        one_chip, as_on_tpu, dtype):
    """nemotron3_nano_30b_s8192's two new shapes, forward + backward for
    the described chip (bfloat16 is the timed step, float32 ``chip_smoke.py
    --phase nemotron_h`` and the cell's reference check).  ``ssd_scan`` at
    one Mamba-2 layer's [1, 8192, 64, 64] over 8 groups of 128 states in
    64 chunks of 128: the dispatch answers fused, the forward and the
    backward are ONE Mosaic call each, named after the op's own scope,
    no loop walks the chunks through HBM, no float32 array of all 64 x
    64 chunk-heads' [128, 128] scores or weights is among the program's
    arrays (268 MB each where XLA lowered the chunks), and the
    temporaries of the pair stay under 1.5 GB (they were 4).  Causal
    flash at 32 query heads over 2 K/V heads of 128, sixteen a group,
    the widest grouping asked of the kernels: the dispatch answers fused
    and every call is named after the op's own scope."""
    import re
    from paddle_tpu.ops import ssd_ops
    b, t, h, p, g, n = 1, 8192, 64, 64, 8, 128

    def scan(*args):
        with jax.named_scope('ssd_scan'):
            out, pull = jax.vjp(
                lambda *x: ssd_ops.ssd_scan(*x, 128), *args[:6])
            return (out,) + pull(args[6])

    wide, narrow = _spec((b, t, h, p), dtype), _spec((b, t, g, n), dtype)
    compiled = _compiled(scan, one_chip, wide, _spec((b, t, h)),
                         _spec((h,)), narrow, narrow, _spec((h,)), wide)
    _compiled_on_chip('ssd_scan')
    text = compiled.as_text()
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(names) == 2, names
    assert all('ssd_scan' in name for name in names), names
    assert ' while(' not in text
    assert not re.search(r'f32\[(1,)?64,(64|8,8),128,128\]', text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9

    heads, kv, d = 32, 2, 128

    def attend(q, k, v):
        def loss(q, k, v):
            with jax.named_scope('fused_multihead_attention'):
                o = flash_attention.flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compiled(attend, one_chip, _spec((b, t, heads, d), dtype),
                     _spec((b, t, kv, d), dtype),
                     _spec((b, t, kv, d), dtype)).as_text()
    _compiled_on_chip('flash_attention')
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(names) >= 2
    assert all('fused_multihead_attention' in name for name in names), names


def test_every_dispatchable_kernel_is_compiled_here():
    """A kernel registered later must bring its compile with it."""
    assert set(common.kernels()) == {
        'flash_attention', 'grouped_matmul', 'kda_chunk', 'kda_walk',
        'quant_collective', 'sinkhorn', 'ssd_scan', 'ssm_scan'}
