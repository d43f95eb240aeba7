"""Data parallel over all the cell's chips, the reference's
ParallelExecutor path: ``CompiledProgram.with_data_parallel`` on a mesh
with one axis ``dp``, parameters replicated, the global batch split
along its first dimension, gradients all-reduced by GSPMD."""


def shardings(devices):
    """-> (mesh or None, sharding of the state, sharding of the batch)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ('dp',))
    return mesh, NamedSharding(mesh, P()), NamedSharding(mesh, P('dp'))


def place(main, loss, devices, host_batch):
    """-> (what Executor.run is given, the feed)."""
    import jax
    import paddle_tpu.fluid as fluid
    mesh, _, split = shardings(devices)
    target = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name).with_mesh(mesh)
    return target, {k: jax.device_put(v, split)
                    for k, v in host_batch.items()}
