"""Of the train step's temporaries where their sum is largest
(``fluid.profiler.live_tables()``: the walk of the executable's
scheduled HLO text, ``temp_peak`` of ``fluid.memviz``'s row), the bytes
kept for the backward pass: buffers defined under a forward fluid op
and last read under a ``_grad`` one.  Its note is the whole table at
that point: the instruction and its fluid op, the bytes by class and by
fluid op, and the ten largest buffers with shape, defining instruction
and op."""

LAYER = 'op lowerings'
UNIT = 'GB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None or 'temp_peak' not in got['step']:
        return None
    peak = got['step']['temp_peak']
    total = peak['bytes'] or 1.0
    named = sum(b for op, b in peak['by_op'].items() if op != 'None')
    memory_split.note(run, 'hbm_residual_gb', (
        '%s MB alive in %d buffers at %s (%s), a fluid op named for '
        '%.1f%%; by class: %s; by fluid op: %s; largest: %s'
        % (memory_split.mb(peak['bytes']), peak['buffers'], peak['point'],
           peak['op'], 100.0 * named / total,
           ', '.join('%s %s' % (c, memory_split.mb(b)) for c, b in sorted(
               peak['by_class'].items(), key=lambda kv: -kv[1])),
           ', '.join('%s %s' % (op, memory_split.mb(b))
                     for op, b in list(peak['by_op'].items())[:16]),
           '; '.join('%s %s %s (%s, %s)' % (
               memory_split.mb(b['bytes']), b['shape'], b['instruction'],
               b['op'], b['class']) for b in peak['top_buffers']))))
    return peak['by_class'].get('residual', 0.0) / 1e9
