"""One chip: the program as built, the batch device-resident and
uncommitted (a committed feed makes jit compile the train segment a
second time; every on-chip number of the repo was taken this way)."""


def shardings(devices):
    """-> (mesh or None, sharding of the state, sharding of the batch)."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(devices[0])
    return None, one, one


def place(main, loss, devices, host_batch):
    """-> (what Executor.run is given, the feed)."""
    import jax
    return main, {k: jax.device_put(v) for k, v in host_batch.items()}
