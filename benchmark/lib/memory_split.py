"""Whose bytes a run's ``peak_hbm`` is made of, from the program's own
memory plane (``paddle_tpu.fluid.memviz``): what the compiler says each
executable the process holds keeps (arguments, outputs, temporaries,
its own code), the train step's temporaries where their sum is largest
(``fluid.profiler.live_tables()``: by class, fluid op and buffer), and
the allocator's two high-water marks around every new executable's
first run.  Asked once a run and kept in ``run``, as
``setup_totals.py`` does; the six ``hbm_*`` readers read this.

The train step is the program of the LAST executable the process ran
for the first time (the quiet step: a compile after warm-up fails the
run), and of its executables (the step that fetches the loss, the quiet
one) the one that holds most: arguments + temporaries.  Under a mesh
the compiler's figures are one chip's.

By construction, with ``outputs`` the step's outputs that are no
donated argument's memory::

    args + outputs + temp + code of every executable + outside
        == run['memory_peak_bytes']

so ``outside`` is a REMAINDER and the identity verifies nothing.  It is
two things of opposite sign, which the split keeps apart by the two
marks the run's peak adds (those of the chip whose sum it is)::

    outside == (peak_bytes_in_use - args - outputs - code)
             + (peak_bytes_reserved - temp)

The first is what the allocator held at its peak BESIDE the step's
arguments and all code, truly outside the step: a caller's arrays (the
harness's reference check), another executable's arguments, rounding.
The second is the runtime's reservation against the compiler's
``temp_size_in_bytes``: negative by what that figure overstates (the
TPU runtime reserves less than it wherever the step holds loops, 2.3
GB less in Ouro), positive only where another executable reserved
more than the step.  On the chip the second outweighs the first in
every cell, so the sum reads negative and alone cannot show a harness
program that adds a fifth of a GB: read the two parts in the note.

A program without the tables (a parent of the PR that added them)
gives ``None``: the readers then leave their metric out.
"""

import time

from benchmark.lib import setup_totals

_KEY = 'memory_split'


def split(run):
    """{'step', 'rows', 'code_bytes', 'outputs_bytes', 'outside_bytes',
    'outside_in_use_bytes', 'reserved_less_temp_bytes', 'high_water',
    'table_seconds'} of this run, computed once; None where the
    program has no such tables or filed no executable."""
    if _KEY not in run:
        run[_KEY] = _split(run)
    return run[_KEY]


def _split(run):
    from paddle_tpu.fluid import memviz
    if not hasattr(memviz, 'build_tables'):
        return None
    setup_totals.totals(run)    # before the tables compile anything anew
    t0 = time.perf_counter()
    memviz.build_tables()
    seconds = time.perf_counter() - t0
    rows = [r for r in memviz.report(limit=1 << 20)
            if not r.get('estimated')]
    water = memviz.high_water()
    if not rows or not water['first_runs']:
        return None
    program = water['first_runs'][-1]['program']
    of_step = [r for r in rows if r['program'] == program]
    if not of_step:
        return None
    step = max(of_step,
               key=lambda r: r['argument_bytes'] + r['temp_bytes'])
    code = sum(r['generated_code_bytes'] for r in rows)
    outputs = step['output_bytes'] - step.get('alias_bytes', 0.0)
    peak = run.get('memory_peak_bytes')
    outside = None if peak is None else \
        peak - step['argument_bytes'] - outputs - step['temp_bytes'] - code
    in_use, reserved = _marks_at(peak)
    return {'step': step, 'rows': rows, 'code_bytes': code,
            'outputs_bytes': outputs, 'outside_bytes': outside,
            'outside_in_use_bytes': None if in_use is None else
            in_use - step['argument_bytes'] - outputs - code,
            'reserved_less_temp_bytes': None if reserved is None else
            reserved - step['temp_bytes'],
            'high_water': water, 'table_seconds': seconds}


def _marks_at(peak):
    """(``peak_bytes_in_use``, ``peak_bytes_reserved``) of the chip
    whose sum is the run's ``memory_peak_bytes`` (``benchmark/run.py``
    adds each chip's two and keeps the largest sum); (None, None) where
    the backend reports no ``memory_stats()``, as the CPU's does not."""
    import jax
    pairs = [(s['peak_bytes_in_use'], s.get('peak_bytes_reserved', 0))
             for s in (d.memory_stats() or {} for d in jax.local_devices())
             if 'peak_bytes_in_use' in s]
    if not pairs or peak is None:
        return None, None
    return min(pairs, key=lambda p: abs(p[0] + p[1] - peak))


def note(run, name, text):
    run.setdefault('notes', {})[name] = text


def mb(nbytes):
    return '%.1f' % (nbytes / 1e6)


def name_of(row):
    if not row['program']:      # what ran outside the program's own
        return row['segment']
    return '%s/%s' % (row['program'], row['segment'])
