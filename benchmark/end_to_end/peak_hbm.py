"""Peak device memory on the fullest chip of the cell, after the
window: ``memory_stats()``'s ``peak_bytes_in_use`` (the allocator's
buffers) plus ``peak_bytes_reserved`` (what the running program
reserves for its temporaries, which the first does not count)."""

UNIT = 'GB'


def read(run):
    return run['memory_peak_bytes'] / 1e9
