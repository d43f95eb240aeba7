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

from paddle_tpu.ops import registry
from paddle_tpu.ops.pallas import (common, embedding, flash_attention,
                                   fused_optimizer, quant_collective)

# BERT-base (models.bert.BASE) at the chip_smoke.py width
VOCAB, MAX_POS, HIDDEN, FFN, LAYERS = 30522, 512, 768, 3072, 12
N_IDS = 4 * 2048


@pytest.fixture(scope='module')
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """jax.devices() still answers "cpu" beside a described topology;
    steer the one platform probe so dispatch() takes its chip branch
    (compiled, not interpreted)."""
    monkeypatch.setattr(common, 'on_tpu', lambda: True)


def _compile(fn, one_chip, *specs):
    """Compile fn for the described chip; returns the number of Mosaic
    kernels in the executable."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    compiled = jax.jit(fn).lower(
        *jax.tree_util.tree_map(place, specs)).compile()
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compiled_on_chip(kernel):
    assert common._LAST[kernel] == {
        'path': 'fused', 'reason': 'tpu', 'interpret': False}, \
        common._LAST[kernel]


@pytest.mark.parametrize('b,t,h,d,rate', [
    (4, 2048, 12, 64, 0.0),     # chip_smoke.py / bench_bert_long
    (4, 2048, 12, 64, 0.1),     # in-kernel dropout mask
    (16, 512, 12, 64, 0.0),     # the dispatch floor (FLASH_MIN_SEQ)
    (4, 2048, 16, 128, 0.0),    # d128: the two-pass backward
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
    assert n >= 2, n    # forward + fused (or dq, dkv) backward


@pytest.mark.parametrize('rows', [VOCAB, MAX_POS])
def test_embedding_gather_and_scatter_add(one_chip, as_on_tpu, rows):
    def step(w, ids):
        def loss(w):
            return jnp.sum(embedding.embedding_lookup(w, ids) ** 2)
        return jax.value_and_grad(loss)(w)

    n = _compile(step, one_chip, _spec((rows, HIDDEN)),
                 _spec((4, 2048), jnp.int32))
    _compiled_on_chip('embedding_lookup')
    assert n == 2, n    # row gather + sorted scatter-add


def test_embedding_fused_row_update(one_chip, as_on_tpu):
    def step(w, mom, ids, g, lr):
        return embedding.apply_update(
            registry.LowerCtx(0),
            {'Param': [w], 'Moment': [mom], 'Ids': [ids], 'Grad': [g],
             'LearningRate': [lr]}, {'epsilon': 1e-6})

    table = _spec((VOCAB, HIDDEN))
    n = _compile(step, one_chip, table, table,
                 _spec((N_IDS,), jnp.int32), _spec((N_IDS, HIDDEN)),
                 _spec((1,)))
    _compiled_on_chip('embedding_update')
    assert n == 1, n


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


@pytest.mark.parametrize('kind,launches', [('adam', 1), ('lamb', 2)])
def test_fused_optimizer_over_bert_base(one_chip, as_on_tpu, kind,
                                        launches):
    shapes = _bert_base_param_shapes()
    n_t = len(shapes)

    def step(p, g, m1, m2, lr, b1p, b2p):
        return fused_optimizer.apply(
            kind, registry.LowerCtx(0),
            {'Param': p, 'Grad': g, 'Moment1': m1, 'Moment2': m2,
             'LearningRate': [lr] * n_t, 'Beta1Pow': [b1p] * n_t,
             'Beta2Pow': [b2p] * n_t}, {})

    tensors = [_spec(s) for s in shapes]
    scalar = _spec((1,))
    n = _compile(step, one_chip, tensors, tensors, tensors, tensors,
                 scalar, scalar, scalar)
    _compiled_on_chip('fused_optimizer')
    assert n == launches, n


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


def test_every_dispatchable_kernel_is_compiled_here():
    """A kernel registered later must bring its compile with it."""
    assert set(common.kernels()) == {
        'flash_attention', 'embedding_lookup', 'embedding_update',
        'fused_optimizer', 'quant_collective'}
