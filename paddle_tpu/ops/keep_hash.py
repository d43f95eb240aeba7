"""The counter hash every dropout of the tree draws its keep mask from.

One definition for the flash kernels' in-kernel draw
(``ops/pallas/flash_attention.py``: forward, both backward kernels, the
dense arm, the einsum ring) and for the ``dropout`` op (``keep_nd``):
an element's bit is a pure function of (seed, its position), so a
replay (per-op grad, whole-program vjp, a fresh scope at the same
step) draws the mask again instead of storing it, and GSPMD shards the
draw at GLOBAL positions because the positions are iotas.  What the
hash is and what it costs an element: ``_dropout_keep``.
"""

import jax
import jax.numpy as jnp


def _keep_rows(seed, g, qpos):
    """The part of the keep hash's pre-mix that depends on the head
    and the query position only (uint32, shaped like ``g`` x
    ``qpos``)."""
    return (qpos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ \
        (jnp.asarray(g, jnp.uint32) * jnp.uint32(0xC2B2AE3D)) ^ \
        jnp.asarray(seed, jnp.uint32)


def _keep_cols(kpos):
    """The part that depends on the key position only."""
    return kpos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)


def _dropout_keep(rows, cols, keep_threshold):
    """Deterministic per-(head, q, k) keep mask from a counter hash
    (murmur3-finalizer mix): the same element draws the same bit in the
    forward kernel, both backward kernels, the dense path, and any
    replay (per-op grad or whole-program vjp) — the (op_seed, step)
    keying discipline the dropout op uses, in-kernel.  Integer ops
    only, so Mosaic and interpret mode agree bit-for-bit.

    The pre-mix is (qpos * A) ^ (kpos * B) ^ (g * C) ^ seed: a xor of
    a term of the row (_keep_rows) and a term of the column
    (_keep_cols), so callers build a [rows, 1] and a [1, cols] vector
    and ONE broadcast xor makes the tile; only the finalizer below is
    per-element work."""
    h = rows ^ cols
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> jnp.uint32(16))
    # 24 bits against the threshold, compared as signed: the same bit
    return (h >> jnp.uint32(8)).astype(jnp.int32) < \
        jnp.int32(keep_threshold)


def _keep_threshold(rate):
    """24-bit integer threshold for keep-probability (1 - rate)."""
    return int(round((1.0 - float(rate)) * (1 << 24)))


def keep_nd(seed, shape, rate):
    """Keep mask (bool, ``shape``) of a tensor of any rank: the row is
    the element's flattened index over all leading axes, the column
    its index along the last one (rank 1: one row; rank 0: one
    element).  The row term is built from one ``broadcasted_iota`` an
    axis over ``shape[:-1] + (1,)``, so it costs a row's worth of
    work and a sharded leading axis draws at its global positions."""
    shape = tuple(int(d) for d in shape)
    lead, last = shape[:-1], shape[-1:]
    ones = (1,) * len(last)
    row = jnp.zeros(lead + ones, jnp.int32)
    stride = 1
    for axis in reversed(range(len(lead))):
        row = row + stride * jax.lax.broadcasted_iota(
            jnp.int32, (1,) * axis + (lead[axis],) +
            (1,) * (len(lead) - axis - 1) + ones, axis)
        stride *= lead[axis]
    col = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * len(lead) + last, len(lead)) \
        if last else jnp.zeros((), jnp.int32)
    return _dropout_keep(_keep_rows(seed, 0, row), _keep_cols(col),
                         _keep_threshold(rate))
