"""What the train step's executable is handed: the compiler's
``argument_size_in_bytes`` of the step program that holds most
(``benchmark/lib/memory_split.py``; one chip's under a mesh).  Its note
splits it by ``fluid.memviz``'s row: parameters, other state (the
optimizer's moments, counters), the batch, and what the compiler's
layout adds over the raw shapes."""

LAYER = 'executor'
UNIT = 'GB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None:
        return None
    step = got['step']
    classes = step['classes']
    memory_split.note(run, 'hbm_args_gb', (
        '%s: param %s MB, state %s MB, feed %s MB, alignment %s MB; '
        'outputs that are no donated argument %s MB'
        % (memory_split.name_of(step), memory_split.mb(classes['param']),
           memory_split.mb(classes['state']),
           memory_split.mb(classes['feed']),
           memory_split.mb(step['arg_overhead_bytes']),
           memory_split.mb(got['outputs_bytes']))))
    return step['argument_bytes'] / 1e9
