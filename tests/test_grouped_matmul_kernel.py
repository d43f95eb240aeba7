"""The ``grouped_matmul`` kernels (``paddle_tpu/ops/pallas/
grouped_matmul.py``: rows x w, rows x w^T and rows^T x cot per group)
under the Pallas interpreter against ``jax.lax.ragged_dot`` and its
``jax.vjp`` in bfloat16; the two expert MLPs of ``parallel/moe.py``
through them against their dense selves; and what the dispatch answers,
with its counters, for operands the kernels do not take.  CPU; what the
chip's compiler says of them is ``tests/test_chip_compile.py``'s."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.fluid import monitor
from paddle_tpu.ops.pallas import common, grouped_matmul
from paddle_tpu.parallel import moe

TILE = grouped_matmul.ROW_TILE
BF16 = jnp.bfloat16


def _operands(seed, m, e, k, n, dtype=BF16):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), dtype),
            jnp.asarray(rng.randn(e, k, n) / 8, dtype),
            jnp.asarray(rng.randn(m, n), dtype))


def _f64(x):
    return np.asarray(x.astype(jnp.float32), np.float64)


def _close(got, want, units=2.0):
    """Within ``units`` of the last bfloat16 place of the reference's
    largest entry (both sides round a float32 sum of the same
    products, added in another order)."""
    got, want = _f64(got), _f64(want)
    assert np.isfinite(got).all()
    if want.size and np.abs(want).max():
        unit = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
        assert np.abs(got - want).max() <= units * unit


def _dense(rows, w, cot, sizes):
    out, pull = jax.vjp(
        lambda r, w: jax.lax.ragged_dot(r, w, sizes), rows, w)
    return (out,) + pull(cot)


def _three_forms(rows, w, cot, sizes):
    walk = grouped_matmul.visits(sizes, rows.shape[0])
    return (grouped_matmul.forward(rows, w, walk, True),
            grouped_matmul.transposed(cot, w, walk, True),
            grouped_matmul.weight_gradient(rows, cot, walk, True))


GROUPS = {
    'equal': [TILE, TILE, TILE, TILE],
    'ragged': [100, 37, 200, 175],
    'empty_first': [0, 150, 130, 232],
    'empty_middle': [150, 0, 0, 362],
    'empty_last': [300, 112, 100, 0],
    'smaller_than_a_tile': [3, 1, 120, 17],
    'one_group_holds_all': [0, 4 * TILE, 0, 0],
    'all_empty': [0, 0, 0, 0],
    'rows_left_over': [90, 140, 33, 70],
}


@pytest.mark.parametrize('k,n', [(128, 128), (256, 128), (128, 1408),
                                 (1408, 256)])
@pytest.mark.parametrize('groups', sorted(GROUPS))
def test_three_forms_against_ragged_dot(groups, k, n):
    """Forward, transposed and weight-gradient products against
    ``ragged_dot`` and its vjp.  The rows past the last group are NaN
    in BOTH inputs: no row inside a group and no weight gradient may
    show one (the held contract)."""
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    live, m = int(sizes.sum()), 4 * TILE
    rows, w, cot = _operands(len(groups) + k + n, m, 4, k, n)
    want = _dense(rows.at[live:].set(0), w, cot.at[live:].set(0), sizes)
    got = _three_forms(rows.at[live:].set(jnp.nan), w,
                       cot.at[live:].set(jnp.nan), sizes)
    _close(got[0][:live], want[0][:live])
    _close(got[1][:live], want[1][:live])
    _close(got[2], want[2])
    if groups == 'empty_middle':
        assert not np.asarray(got[2][1].astype(jnp.float32)).any()


def test_many_groups_and_a_weight_block_that_is_split(monkeypatch):
    """64 groups over 12 tiles, and a VMEM cap so small that the
    output columns are cut into 128-lane blocks: every block of every
    form still lands where it belongs."""
    monkeypatch.setattr(common, 'VMEM_LIMIT_CAP_BYTES', 17 << 20)
    monkeypatch.setattr(common, 'SCOPED_VMEM_BYTES', 1 << 19)
    jax.clear_caches()
    rng = np.random.RandomState(5)
    sizes = rng.multinomial(11 * TILE + 17, np.ones(64) / 64)
    sizes[7] = 0
    sizes = jnp.asarray(sizes, jnp.int32)
    live, m = int(sizes.sum()), 12 * TILE
    rows, w, cot = _operands(6, m, 64, 256, 384)
    want = _dense(rows.at[live:].set(0), w, cot.at[live:].set(0), sizes)
    got = _three_forms(rows, w, cot, sizes)
    jax.clear_caches()
    _close(got[0][:live], want[0][:live])
    _close(got[1][:live], want[1][:live])
    _close(got[2], want[2])


def test_the_column_block_is_a_whole_expert_where_the_count_fits():
    """The cells' weight matrices stay whole (one DMA a group); a
    matrix no call may ask the VMEM for is cut into the widest
    128-lane multiple that divides its output columns."""
    def rows_block(k, n):
        return grouped_matmul._column_block(n, lambda b: (
            grouped_matmul._rows_vmem(k, b, 2)))

    def asked(limit):       # nothing, or more than Mosaic's default
        return limit is None or common.SCOPED_VMEM_BYTES < limit <= \
            common.VMEM_LIMIT_CAP_BYTES

    for k, n in [(2048, 1408), (2048, 1792), (3072, 1024),
                 (2048, 1024), (4096, 1280)]:
        block, limit = rows_block(k, n)
        assert block == n and asked(limit)
        block, limit = grouped_matmul._column_block(n, lambda b: (
            grouped_matmul._weights_vmem(k, b, 2)))
        assert block == n and asked(limit)
    block, limit = rows_block(16384, 1408)       # 46 MB an expert
    assert block == 128 * 1 and 1408 % block == 0
    block, limit = rows_block(16384, 2048)
    assert block == 1024 and limit <= common.VMEM_LIMIT_CAP_BYTES


def _mlp_operands(seed, m, e, d, h, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, d), dtype),
            jnp.asarray(rng.randn(e, d, h) / np.sqrt(d), dtype),
            jnp.asarray(rng.randn(e, d, h) / np.sqrt(d), dtype),
            jnp.asarray(rng.randn(e, h, d) / np.sqrt(h), dtype),
            jnp.asarray(rng.randn(m, d), dtype))


def _mlp_grads(mlp, sizes, rows, w_gate, w_up, w_down, probe):
    def loss(rows, w_gate, w_up, w_down):
        out = mlp(rows, sizes, (w_gate, w_up), w_down, 'gated', True)
        live = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.sum(jnp.where(live, out.astype(jnp.float32) * probe,
                                 0)), out
    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2, 3),
                                         has_aux=True)(
        rows, w_gate, w_up, w_down)
    return (out,) + grads


def _fused():
    return monitor.counter_value(
        'pallas/grouped_matmul/dispatch_fused') or 0


def _dense_count():
    return monitor.counter_value(
        'pallas/grouped_matmul/dispatch_dense') or 0


@pytest.mark.parametrize('mlp,products,dense_products,sizes', [
    ('grouped_expert_mlp', 9, 3, [130, 0, 254, 128]),
    ('held_expert_mlp', 11, 11, [130, 0, 70, 100]),
], ids=['all_held', 'a_range_held'])
def test_expert_mlp_forced_fused_against_dense(
        mlp, products, dense_products, sizes, pallas_interpret):
    """The two expert MLPs under AMP's casts, through the kernels and
    through ``ragged_dot``: output and all four gradients, and one
    fused dispatch counted a product (3 forward; 6 backward, or 8 where
    the held MLP computes gate and up again; on the dense side JAX's
    own transposes of the all-held MLP's three are not ours to
    count)."""
    from paddle_tpu.fluid.flags import set_flags
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    operands = _mlp_operands(3, 4 * TILE, 4, 256, 128)
    before = _fused()
    fused = _mlp_grads(getattr(moe, mlp), sizes, *operands)
    assert _fused() - before == products
    set_flags({'FLAGS_pallas_force': False})
    before = _dense_count()
    dense = _mlp_grads(getattr(moe, mlp), sizes, *operands)
    assert _dense_count() - before == dense_products
    assert fused[0].dtype == dense[0].dtype == jnp.bfloat16
    for got, want in zip(fused[:2], dense[:2]):     # out, drows
        assert got.dtype == want.dtype
        _close(got[:live], want[:live], units=4.0)
    for got, want in zip(fused[2:], dense[2:]):
        assert got.dtype == want.dtype == jnp.float32
        _close(got, want, units=4.0)


@pytest.mark.parametrize('mlp', ['grouped_expert_mlp', 'held_expert_mlp'])
def test_float32_programs_hold_the_ragged_dots_they_held(
        mlp, pallas_interpret):
    """What decides ``correct`` is untouched: with float32 operands the
    MLPs' jaxpr, forward and backward, holds ``ragged_dot``s (3 + 6, or
    3 + 8 with the held MLP's recompute) and no ``pallas_call``, forced
    or not."""
    sizes = jnp.asarray([130, 0, 70, 100], jnp.int32)
    operands = _mlp_operands(4, 4 * TILE, 4, 256, 128)

    def step(*operands):
        def loss(rows, w_gate, w_up, w_down):
            return jnp.sum(getattr(moe, mlp)(
                rows, sizes, (w_gate, w_up), w_down, 'gated', False))
        return jax.grad(loss, (0, 1, 2, 3))(*operands)

    text = str(jax.make_jaxpr(step)(*operands[:4]))
    assert 'pallas_call' not in text
    assert text.count('= ragged_dot') == (
        9 if mlp == 'grouped_expert_mlp' else 11)
    assert 'precision=HIGHEST' in text or 'Precision.HIGHEST' in text


def test_low_precision_jaxpr_holds_kernels_and_no_ragged_dot(
        pallas_interpret):
    sizes = jnp.asarray([130, 0, 70, 100], jnp.int32)
    operands = _mlp_operands(4, 4 * TILE, 4, 256, 128)

    def step(*operands):
        def loss(rows, w_gate, w_up, w_down):
            return jnp.sum(moe.held_expert_mlp(
                rows, sizes, (w_gate, w_up), w_down, 'gated',
                True).astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2, 3))(*operands)

    text = str(jax.make_jaxpr(step)(*operands[:4]))
    assert text.count('= pallas_call') == 11 and 'ragged_dot' not in text


@pytest.mark.parametrize('what,m,d,h,low,partitioned,forced', [
    ('dtype', 4 * TILE, 256, 128, False, False, True),
    ('layout', 4 * TILE, 192, 128, True, False, True),      # K off
    ('layout', 4 * TILE - 8, 256, 128, True, False, True),  # M off
    ('auto_partitioned', 4 * TILE, 256, 128, True, True, True),
    ('off_tpu', 4 * TILE, 256, 128, True, False, False),
], ids=['float32', 'k_off_the_lanes', 'm_off_the_row_tile',
        'auto_partitioned', 'off_tpu'])
def test_the_dispatch_answers_dense_with_its_reason_counted(
        what, m, d, h, low, partitioned, forced):
    from paddle_tpu.fluid.flags import get_flag, set_flags
    was = get_flag('FLAGS_pallas_force', False)
    set_flags({'FLAGS_pallas_force': forced})
    try:
        rows, w_gate, w_up, w_down, _ = _mlp_operands(7, m, 4, d, h)
        sizes = jnp.asarray([100, 50, 0, 60], jnp.int32)
        name = 'pallas/grouped_matmul/fallback/' + what
        before = monitor.counter_value(name) or 0
        dense, fused = _dense_count(), _fused()
        out = moe.grouped_expert_mlp(rows, sizes, (w_gate, w_up), w_down,
                                     'gated', low, partitioned)
    finally:
        set_flags({'FLAGS_pallas_force': was})
    assert out.shape == rows.shape
    assert monitor.counter_value(name) == before + 3
    assert _dense_count() == dense + 3 and _fused() == fused
    assert common._LAST['grouped_matmul'] == {
        'path': 'dense', 'reason': what, 'interpret': False}


@pytest.mark.parametrize('mlp,form,products', [
    ('grouped_expert_mlp', 'gated', 9), ('held_expert_mlp', 'gated', 11),
    ('held_expert_mlp', 'relu2', 7),
], ids=['all_held', 'a_range_held', 'a_range_held_relu2'])
def test_an_expert_width_off_the_lanes_is_padded_not_refused(
        mlp, form, products, pallas_interpret):
    """An expert width off the lanes (Nemotron-H's 1856; 192 here):
    ``_operands`` pads the width with zeros to the next 128-lane tile
    and the kernels run, forward and backward; the stream's width (K of
    the input products) off the lanes still answers dense.  Output, drows and every weight's
    gradient, CUT BACK to the weight's own width (``_unpadded``),
    against ``ragged_dot`` on the unpadded operands."""
    from paddle_tpu.fluid.flags import set_flags
    rows, w_gate, w_up, w_down, probe = _mlp_operands(7, 4 * TILE, 4, 256,
                                                      192)
    sizes = jnp.asarray([100, 50, 0, 60], jnp.int32)
    live = int(sizes.sum())
    w_in = (w_gate, w_up) if form == 'gated' else (w_up,)

    def grads():
        def loss(rows, w_in, w_down):
            out = getattr(moe, mlp)(rows, sizes, w_in, w_down, form, True)
            kept = jnp.arange(rows.shape[0])[:, None] < live
            return jnp.sum(jnp.where(
                kept, out.astype(jnp.float32) * probe, 0)), out
        (_, out), (drows, din, ddown) = jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True)(rows, w_in, w_down)
        return (out, drows) + tuple(din) + (ddown,)

    dense, fused = _dense_count(), _fused()
    padded = grads()
    assert (_dense_count(), _fused()) == (dense, fused + products)
    assert common._LAST['grouped_matmul']['path'] == 'fused'
    set_flags({'FLAGS_pallas_force': False})
    plain = grads()
    assert padded[0].dtype == jnp.bfloat16
    for got, want in zip(padded[:2], plain[:2]):    # out, drows
        assert got.dtype == want.dtype
        _close(got[:live], want[:live], units=4.0)
    for got, want, w in zip(padded[2:], plain[2:], w_in + (w_down,)):
        assert got.shape == want.shape == w.shape
        assert got.dtype == want.dtype == jnp.float32
        _close(got, want, units=4.0)


def test_the_kernel_is_registered_with_its_dense_fallback():
    entry = common.kernels()['grouped_matmul']
    assert entry['dense_fallback'] == 'jax.lax.ragged_dot'
    assert entry['op_types'] == ('moe_experts',) and entry['has_vjp']
    assert callable(jax.lax.ragged_dot)
