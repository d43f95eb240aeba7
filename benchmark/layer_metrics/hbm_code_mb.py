"""The executables' own size on the device: the compiler's
``generated_code_size_in_bytes`` summed over EVERY executable the
process holds (``fluid.memviz``'s rows: the start-up program, the
reference check's ``for_test`` clone, both step programs; they all stay
loaded).  It moves with the kernels' bodies, not with the batch.  Its
note is each executable's size."""

LAYER = 'compile plane'
UNIT = 'MB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None:
        return None
    memory_split.note(run, 'hbm_code_mb', ', '.join(
        '%s %s' % (memory_split.name_of(r),
                   memory_split.mb(r['generated_code_bytes']))
        for r in got['rows']))
    return got['code_bytes'] / 1e6
