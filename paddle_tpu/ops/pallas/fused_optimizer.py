"""Fused multi-tensor optimizer updates as one Pallas launch.

The optimizer phase of ``step_report()`` attributes real wall time to
the ~100s of per-parameter elementwise chains ``ops/optimizer_ops.py``
lowers (one adam/adamw/lamb op per tensor — each a handful of tiny
HBM-bound VPU ops).  Here the executor's run-grouping
(``fluid/executor.py:_fused_opt_run``) hands the whole run to ONE
kernel: every tensor is flattened, padded to a (32, 128) f32 block
multiple, and concatenated into parameter/grad/moment slabs; a
per-block scalar table carries each tensor's learning rate and beta
powers, so tensors with different lr schedules still fuse.  The grid
walks blocks; hyperparameters shared by the run (beta1/beta2/epsilon/
weight-decay — the grouping key) are compile-time constants.  The
scalar table and the block->tensor map ride in SMEM as scalar-prefetch
operands: Mosaic refuses a (1, 8) VMEM block of a [nblk, 8] table, and
per-tensor scalars are what SMEM is for.

lamb needs a per-TENSOR trust ratio ``||p|| / ||r||``, a reduction the
elementwise pass can't see whole: pass 1 updates moments and emits
per-block partial sums of ``p**2`` and ``r**2``, a segment-sum over the block->tensor map builds the trust
ratios, and pass 2 applies them (the partial rows are one (1, 128)
lane row per block of a [nblk, 1, 128] output — the unit middle axis
makes the block's last two dims equal the array's) — the [T, nblk] one-hot matmul a dense
multi-tensor lamb would need never materializes.

Dense fallback: the per-tensor registered lowerings looped in run
order — bit-for-bit the ungrouped program.  The fused path evaluates
the same elementwise expressions in the same order, but the compiled
kernel body is free to contract mul+add into FMAs the op-by-op dense
chain rounds individually, so adam/adamw parity is 1-2 ulp (not
bitwise); lamb additionally sums its trust-ratio norms from per-block
partials.  The parity suite pins both bounds.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common

BLOCK_ROWS = 32
BLOCK_LANES = 128
BLOCK = BLOCK_ROWS * BLOCK_LANES

# per-tensor scalar row: [lr, beta1_pow, beta2_pow, trust]
SCAL_COLS = 4

common.register_kernel(
    'fused_optimizer',
    dense_fallback='ops.optimizer_ops.{adam,adamw,lamb} per-tensor loop',
    has_vjp=False,
    doc='one launch updating a whole run of same-hyper optimizer ops '
        'over flattened parameter slabs (lamb trust ratio in-kernel)',
    op_types=('adam', 'adamw', 'lamb', 'fused_adam', 'fused_adamw',
              'fused_lamb'))


def _pack(tensors):
    """Flatten+pad each tensor to a BLOCK multiple and concatenate ->
    (slab [nblk, BLOCK_ROWS, BLOCK_LANES] f32,
     tid  [nblk] numpy int32 block->tensor map,
     spans [(first_block, nblocks, numel, shape)]).

    Per-tensor padding (not one tail pad) keeps every block owned by
    exactly one tensor — the lamb partial-norm rows need that.

    The ``pack`` scope (and ``unpack`` below) names this work in the
    compiled program, under the fluid op's own scope, so a device
    trace tells the copies around the kernel from the kernel
    (fluid.profiler.hlo_scopes)."""
    flats, tids, spans = [], [], []
    off = 0
    with jax.named_scope('pack'):
        for i, t in enumerate(tensors):
            n = int(np.prod(t.shape)) if t.shape else 1
            nb = -(-n // BLOCK)
            f = t.reshape(-1).astype(jnp.float32)
            if nb * BLOCK - n:
                f = jnp.concatenate(
                    [f, jnp.zeros((nb * BLOCK - n,), jnp.float32)])
            flats.append(f)
            tids.append(np.full((nb,), i, np.int32))
            spans.append((off, nb, n, t.shape))
            off += nb
        slab = jnp.concatenate(flats).reshape(-1, BLOCK_ROWS,
                                              BLOCK_LANES)
    return slab, np.concatenate(tids), spans


def _unpack(slab, spans):
    # slice each tensor's BLOCKS out first: a reshape of a slice of the
    # whole flat slab is hoisted above the slice by XLA's simplifier,
    # and a [numel/2, 2] view of the full slab (an fc with 2 outputs)
    # pads 64x under the (8, 128) tiling — 28 GB at BERT-base
    with jax.named_scope('unpack'):
        return [slab[off:off + nb].reshape(-1)[:n].reshape(shape)
                for off, nb, n, shape in spans]


def _slab_spec():
    return pl.BlockSpec((1, BLOCK_ROWS, BLOCK_LANES),
                        lambda i, tid_ref, scal_ref: (i, 0, 0))


def _part_spec():
    return pl.BlockSpec((1, 1, BLOCK_LANES),
                        lambda i, tid_ref, scal_ref: (i, 0, 0))


def _scalars(tid_ref, scal_ref):
    """This block's tensor's scalar row, read from the flat SMEM
    table [n * SCAL_COLS]."""
    base = tid_ref[pl.program_id(0)] * SCAL_COLS
    return [scal_ref[base + c] for c in range(SCAL_COLS)]


def _launch(kernel, nblk, n_in, out_specs, out_shape, interpret):
    """pallas_call over the block grid with (tid, scal) prefetched
    into SMEM and ``n_in`` slab operands."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblk,),
            in_specs=[_slab_spec()] * n_in,
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret)


def _adam_kernel(tid_ref, scal_ref, p_ref, g_ref, m1_ref, m2_ref,
                 po_ref, m1o_ref, m2o_ref, *, beta1, beta2, epsilon,
                 coeff):
    # same expression order as ops.optimizer_ops.adam/adamw — the
    # interpret-mode fused path is bitwise the dense reference
    lr, b1p, b2p, _ = _scalars(tid_ref, scal_ref)
    p = p_ref[...]
    g = g_ref[...]
    m1n = beta1 * m1_ref[...] + (1 - beta1) * g
    m2n = beta2 * m2_ref[...] + (1 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p * beta2) / (1 - b1p * beta1)
    pn = p - lr_t * m1n / (jnp.sqrt(m2n) + epsilon)
    if coeff:
        pn = pn - lr * coeff * p
    po_ref[...] = pn
    m1o_ref[...] = m1n
    m2o_ref[...] = m2n


def _lamb1_kernel(tid_ref, scal_ref, p_ref, g_ref, m1_ref, m2_ref,
                  m1o_ref, m2o_ref, part_ref, *, beta1, beta2,
                  epsilon, wd):
    _, b1p, b2p, _ = _scalars(tid_ref, scal_ref)
    p = p_ref[...]
    g = g_ref[...]
    m1n = beta1 * m1_ref[...] + (1 - beta1) * g
    m2n = beta2 * m2_ref[...] + (1 - beta2) * g * g
    mhat = m1n / (1 - b1p * beta1)
    vhat = m2n / (1 - b2p * beta2)
    r = mhat / (jnp.sqrt(vhat) + epsilon) + wd * p
    m1o_ref[...] = m1n
    m2o_ref[...] = m2n
    # per-block partial norms in lanes 0 and 1; padded blocks
    # contribute exact zeros (p and every moment term are zero there)
    lane = jax.lax.broadcasted_iota(jnp.int32, part_ref.shape, 2)
    part_ref[...] = jnp.where(
        lane == 0, jnp.sum(p * p),
        jnp.where(lane == 1, jnp.sum(r * r), 0.0))


def _lamb2_kernel(tid_ref, scal_ref, p_ref, m1o_ref, m2o_ref, po_ref,
                  *, beta1, beta2, epsilon, wd):
    lr, b1p, b2p, trust = _scalars(tid_ref, scal_ref)
    p = p_ref[...]
    mhat = m1o_ref[...] / (1 - b1p * beta1)
    vhat = m2o_ref[...] / (1 - b2p * beta2)
    r = mhat / (jnp.sqrt(vhat) + epsilon) + wd * p
    po_ref[...] = p - lr * trust * r


def _dense(kind, ctx, ins, attrs):
    """The fallback: per-tensor registered lowerings in run order —
    exactly what the ungrouped program would have executed."""
    from .. import optimizer_ops
    fn = {'adam': optimizer_ops.adam, 'adamw': optimizer_ops.adamw,
          'lamb': optimizer_ops.lamb}[kind]
    outs = {}
    for i in range(len(ins['Param'])):
        one = {slot: [vals[i]] for slot, vals in ins.items() if vals}
        for slot, vals in fn(ctx, one, attrs).items():
            outs.setdefault(slot, []).append(vals[0])
    return outs


def apply(kind, ctx, ins, attrs):
    """Multi-tensor ``kind`` in {'adam', 'adamw', 'lamb'}: every slot
    of ``ins`` holds N aligned entries (the executor's run grouping);
    returns the standard per-op output slots, each with N entries."""
    from ...fluid.flags import get_flag
    params = ins['Param']
    n = len(params)
    dtype_ok = all(
        t.dtype == jnp.float32
        for t in list(params) + list(ins['Moment1']) +
        list(ins['Moment2'])) and all(
        jnp.issubdtype(g.dtype, jnp.floating) for g in ins['Grad'])
    min_n = int(get_flag('FLAGS_pallas_opt_min_tensors', 2))
    fused, interpret = common.dispatch(
        'fused_optimizer',
        bool(get_flag('FLAGS_pallas_opt_fuse', True)),
        checks=(('below_floor', n >= min_n), ('dtype', dtype_ok)),
        auto_partitioned=ctx.auto_partitioned)
    if not fused:
        return _dense(kind, ctx, ins, attrs)

    beta1 = attrs.get('beta1', 0.9)
    beta2 = attrs.get('beta2', 0.999)
    epsilon = attrs.get('epsilon', 1e-6 if kind == 'lamb' else 1e-8)
    slab_p, tid, spans = _pack(params)
    slab_g = _pack(ins['Grad'])[0]
    slab_m1 = _pack(ins['Moment1'])[0]
    slab_m2 = _pack(ins['Moment2'])[0]
    nblk = slab_p.shape[0]
    b1ps = [ins['Beta1Pow'][i].reshape(()) for i in range(n)]
    b2ps = [ins['Beta2Pow'][i].reshape(()) for i in range(n)]
    scal_t = jnp.stack(
        [jnp.stack([ins['LearningRate'][i].reshape(())
                    for i in range(n)]).astype(jnp.float32),
         jnp.stack(b1ps).astype(jnp.float32),
         jnp.stack(b2ps).astype(jnp.float32)] +
        [jnp.zeros((n,), jnp.float32)] * (SCAL_COLS - 3),
        axis=1)                                  # [n, SCAL_COLS]
    tid_j = jnp.asarray(tid)
    slab_shape = jax.ShapeDtypeStruct(slab_p.shape, jnp.float32)

    if kind in ('adam', 'adamw'):
        coeff = attrs.get('coeff', 0.01) if kind == 'adamw' else 0.0
        po, m1o, m2o = _launch(
            functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                              epsilon=epsilon, coeff=coeff),
            nblk, 4, [_slab_spec()] * 3, [slab_shape] * 3, interpret,
        )(tid_j, scal_t.reshape(-1), slab_p, slab_g, slab_m1, slab_m2)
    else:
        wd = attrs.get('weight_decay', 0.01)
        m1o, m2o, part = _launch(
            functools.partial(_lamb1_kernel, beta1=beta1, beta2=beta2,
                              epsilon=epsilon, wd=wd),
            nblk, 4, [_slab_spec()] * 2 + [_part_spec()],
            [slab_shape] * 2 +
            [jax.ShapeDtypeStruct((nblk, 1, BLOCK_LANES), jnp.float32)],
            interpret,
        )(tid_j, scal_t.reshape(-1), slab_p, slab_g, slab_m1, slab_m2)
        pn = jnp.sqrt(jnp.zeros((n,), jnp.float32)
                      .at[tid_j].add(part[:, 0, 0]))
        rn = jnp.sqrt(jnp.zeros((n,), jnp.float32)
                      .at[tid_j].add(part[:, 0, 1]))
        trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
        po = _launch(
            functools.partial(_lamb2_kernel, beta1=beta1, beta2=beta2,
                              epsilon=epsilon, wd=wd),
            nblk, 3, _slab_spec(), slab_shape, interpret,
        )(tid_j, scal_t.at[:, 3].set(trust).reshape(-1), slab_p, m1o,
          m2o)

    return {
        'ParamOut': _unpack(po, spans),
        'Moment1Out': _unpack(m1o, spans),
        'Moment2Out': _unpack(m2o, spans),
        'Beta1PowOut': [
            (b1ps[i] * beta1).reshape(ins['Beta1Pow'][i].shape)
            for i in range(n)],
        'Beta2PowOut': [
            (b2ps[i] * beta2).reshape(ins['Beta2Pow'][i].shape)
            for i in range(n)],
    }
