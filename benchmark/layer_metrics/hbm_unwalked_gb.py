"""How far the table of the step's temporaries can be trusted,
``unscoped_ms``'s twin: the compiler's ``temp_size_in_bytes`` less the
sum of the buffers the walk of the scheduled HLO text finds alive at
their peak (``fluid.profiler.live_tables()``).  What is left is the
compiler's packing and alignment, what a loop or a custom call
allocates for itself, and any buffer the walk mis-aliases; negative
where the walk counts memory the compiler shares.  In GB, not a share:
against ``hbm_temp_gb``.  Best NEAR ZERO, on either side: the
manifest's ``better: lower`` says nothing of it (a ``benchmark`` PR's
to change)."""

LAYER = 'compile plane'
UNIT = 'GB'
MOVES = 'peak_hbm'


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None or 'temp_peak' not in got['step']:
        return None
    step = got['step']
    walked = step['temp_peak']['bytes']
    peak = step['temp_peak']
    memory_split.note(run, 'hbm_unwalked_gb', (
        'temp %s MB, walked %s MB (%.2f%% of it); not in the walk\'s sum: '
        '%s MB alive at that point in another memory space (the '
        'compiler\'s figure counts every space), %s MB the loops\' '
        'bodies compute anew for their next trip; tables built in %.2f s'
        % (memory_split.mb(step['temp_bytes']), memory_split.mb(walked),
           100.0 * walked / (step['temp_bytes'] or 1.0),
           memory_split.mb(peak.get('elsewhere_bytes', 0.0)),
           memory_split.mb(peak.get('carried_anew_bytes', 0.0)),
           got['table_seconds'])))
    return (step['temp_bytes'] - walked) / 1e9
