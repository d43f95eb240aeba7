"""The REMAINDER of the run's ``peak_hbm``: the run's own
``memory_peak_bytes`` less the train step's arguments, its outputs that
are no donated argument, its temporaries as the COMPILER counts them
and every executable's code (``benchmark/lib/memory_split.py``), so
that ``hbm_args_gb + hbm_temp_gb + hbm_code_mb / 1000 +
hbm_outside_step_gb`` + the noted outputs is that run's ``peak_hbm`` by
construction: the identity verifies nothing.

The value is the sum of two parts of opposite sign, and the note gives
both: ``in use beside the step`` (``peak_bytes_in_use`` less the step's
arguments, outputs and all code: what is truly outside the step, a
caller's arrays, another executable's arguments, rounding) and
``reserved less temp`` (``peak_bytes_reserved`` less the step's
``temp_size_in_bytes``: negative by what the compiler's figure
overstates of what the runtime reserves).  On the chip the second
outweighs the first in every cell (my chip runs, PR 52), so the value
is chiefly the compiler's overstatement, reads negative, and
``better: lower`` says nothing of it: a harness program that adds 0.2
GB shows in the note's first part and not in the value.  Name and
direction are a ``benchmark`` PR's to change.

The note also says who set the marks ``peak_hbm`` adds: every new
executable's first run in order with the allocator's
``peak_bytes_in_use`` and ``peak_bytes_reserved`` after it, the
executable whose first run last raised each, and every executable's
(arguments, temporaries, code)."""

LAYER = 'device'
UNIT = 'GB'
MOVES = 'peak_hbm'


def _marks(entry):
    after = entry.get('after')
    if not after:
        return 'no marks'
    before = entry.get('before') or {}
    return 'in use %s (+%s) reserved %s (+%s) MB' % tuple(
        '%.1f' % (v / 1e6) for v in (
            after['peak_bytes_in_use'],
            after['peak_bytes_in_use'] -
            before.get('peak_bytes_in_use', 0.0),
            after['peak_bytes_reserved'],
            after['peak_bytes_reserved'] -
            before.get('peak_bytes_reserved', 0.0)))


def _parts(got):
    """The remainder's two parts, by the two marks the peak adds."""
    beside, reserved = (got['outside_in_use_bytes'],
                        got['reserved_less_temp_bytes'])
    if beside is None or reserved is None:
        return 'no marks to split it by'
    return ('in use beside the step (the in-use mark less args, outputs '
            'and all code: truly outside) %s + reserved less temp (the '
            'reserved mark less the compiler\'s figure: its '
            'overstatement where negative) %s'
            % ('%.1f' % (beside / 1e6), '%.1f' % (reserved / 1e6)))


def read(trace, run):
    from benchmark.lib import memory_split
    got = memory_split.split(run)
    if got is None or got['outside_bytes'] is None:
        return None
    water = got['high_water']
    memory_split.note(run, 'hbm_outside_step_gb', (
        'peak %s MB = step %s: args %s + outputs %s + temp %s, code of '
        'all %s, outside %s MB = %s; first runs in order: %s; raised '
        'last by: %s; executables (args, temp, code MB): %s'
        % (memory_split.mb(run['memory_peak_bytes']),
           memory_split.name_of(got['step']),
           memory_split.mb(got['step']['argument_bytes']),
           memory_split.mb(got['outputs_bytes']),
           memory_split.mb(got['step']['temp_bytes']),
           memory_split.mb(got['code_bytes']),
           memory_split.mb(got['outside_bytes']),
           _parts(got),
           '; '.join('%d %s: %s' % (
               e['order'], memory_split.name_of(e), _marks(e))
               for e in water['first_runs']),
           ', '.join('%s %s at %s MB' % (
               mark, memory_split.name_of(e), memory_split.mb(e['bytes']))
               for mark, e in sorted(water['raised_by'].items())) or
           'none',
           '; '.join('%s (%s, %s, %s)' % (
               memory_split.name_of(r),
               memory_split.mb(r['argument_bytes']),
               memory_split.mb(r['temp_bytes']),
               memory_split.mb(r['generated_code_bytes']))
               for r in got['rows']))))
    return got['outside_bytes'] / 1e9
