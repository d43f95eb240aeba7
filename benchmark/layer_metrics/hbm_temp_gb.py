"""What the train step's executable holds beside its arguments while
it runs: the compiler's own ``temp_size_in_bytes`` of the step program
that holds most (``benchmark/lib/memory_split.py``; one chip's under a
mesh), the figure ``tools/step_hlo_hash.py --memory`` gives for a
described chip.  ``hbm_residual_gb`` and ``hbm_unwalked_gb`` say what
is in it."""

LAYER = 'op lowerings'
UNIT = 'GB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None:
        return None
    step = got['step']
    memory_split.note(run, 'hbm_temp_gb', (
        '%s: temp %s MB, the compiler\'s peak %s MB (arguments %s MB)'
        % (memory_split.name_of(step),
           memory_split.mb(step['temp_bytes']),
           memory_split.mb(step['peak_bytes']),
           memory_split.mb(step['argument_bytes']))))
    return step['temp_bytes'] / 1e9
