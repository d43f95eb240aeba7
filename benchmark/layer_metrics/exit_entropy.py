"""Mean entropy of the looped model's exit distribution over the
batch's positions, nats, on the last run that fetched: the program's
gauge ``ouro/exit_entropy`` (``paddle_tpu/models/ouro.py``
``record_exit``, read through ``Program.watch``).  ln(total_ut_steps)
= 1.386 at most; 0 means the gate has collapsed onto one exit and the
other passes' head products train nothing, whatever the step costs.
Beside it, as a note, ``ouro/exit_mass_last``: the mean probability of
running all the passes.  Nothing where the program has no such
gauge."""

LAYER = 'op lowerings'
UNIT = 'nats'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('ouro/exit_entropy', None)
    if value is None:
        return None
    run.setdefault('notes', {})['exit_entropy'] = (
        'ouro/exit_mass_last %s on the last run that fetched'
        % monitor.gauge_value('ouro/exit_mass_last', None))
    return float(value)
