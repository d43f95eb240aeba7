"""Device-mesh construction and the global mesh registry.

Replaces the reference's NCCL ring/communicator bookkeeping
(platform/nccl_helper.h:90 NCCLContextMap, :179 multi-ring,
platform/collective_helper.h:50 NCCLCommContext keyed by ring_id):
on TPU a single jax.sharding.Mesh with named axes subsumes every ring —
XLA routes each collective over ICI (mesh-adjacent axes) or DCN.
"""

import numpy as np
import jax
from jax.sharding import Mesh

_GLOBAL_MESH = None

# canonical axis order: data, fully-sharded-data (parameter scatter —
# the auto-sharding planner's ZeRO/weight-update axis), model(tensor),
# pipeline, sequence, expert
AXES = ('dp', 'fsdp', 'mp', 'pp', 'sp', 'ep')


def create_mesh(dp=None, mp=1, pp=1, sp=1, ep=1, fsdp=1, devices=None):
    """Build a mesh over the available devices.  dp defaults to
    'whatever remains'.  Axis sizes must multiply to the device count."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    rest = mp * pp * sp * ep * fsdp
    if dp is None:
        if n % rest:
            raise ValueError('device count %d not divisible by %d'
                             % (n, rest))
        dp = n // rest
    sizes = dict(dp=dp, fsdp=fsdp, mp=mp, pp=pp, sp=sp, ep=ep)
    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise ValueError('mesh %s needs %d devices, have %d'
                         % (sizes, total, n))
    axes = [a for a in AXES if sizes[a] > 1] or ['dp']
    shape = tuple(sizes[a] for a in axes)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, tuple(axes))


# --- trace-time mesh context ------------------------------------------
# The executor's GSPMD path (parallel_executor._run_segment_parallel)
# publishes the active mesh here while a segment traces, and beside it
# the axes it split the batch over, so MESH-AWARE op lowerings
# (ring_attention, moe_ffn in ops/parallel_ops.py; the flash kernels'
# wrap, ops/pallas/flash_attention.py) can open a shard_map over named
# axes.  Thread-local: parallel test runners trace independent
# programs concurrently.

import contextlib
import threading

_TRACE = threading.local()


@contextlib.contextmanager
def use_trace_mesh(mesh, batch_axes=()):
    """``batch_axes``: the mesh axes the runner sharded dimension 0 of
    the batch feeds over, in the order of its PartitionSpec; () where
    it replicated the batch (a tp-only plan) or the publisher is not a
    runner that shards one."""
    prev = trace_mesh(), trace_batch_axes()
    _TRACE.mesh, _TRACE.batch_axes = mesh, tuple(batch_axes)
    try:
        yield mesh
    finally:
        _TRACE.mesh, _TRACE.batch_axes = prev


def trace_mesh():
    """The mesh the current segment is being traced under, or None
    (single-device executor path / inside an outer shard_map)."""
    return getattr(_TRACE, 'mesh', None)


def trace_batch_axes():
    """The axes of trace_mesh() the batch is split over: what
    use_trace_mesh() was handed."""
    return getattr(_TRACE, 'batch_axes', ())


def axis_size(mesh, name):
    """Size of a named mesh axis, 1 when absent."""
    return int(mesh.shape[name]) if (mesh is not None and
                                     name in mesh.axis_names) else 1


def set_global_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    # ring 0 keeps mapping to the dp axis; extra rings map to the other
    # axes in order, mirroring the reference's ring_id convention
    from ..ops import collective_ops
    collective_ops.RING_AXES = {i: a for i, a in
                                enumerate(mesh.axis_names)}
    return mesh


def get_global_mesh():
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = create_mesh()
    return _GLOBAL_MESH
