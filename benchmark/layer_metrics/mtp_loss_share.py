"""What the multi-token-prediction module weighs in the training
loss, lambda x L_mtp / (L_main + lambda x L_mtp), on the last run that
fetched: the program's gauge ``mtp/loss_share``
(``paddle_tpu/models/xing4.py`` ``record``, read through
``Program.watch``).  With random weights both cross-entropies start at
ln(vocabulary rows) and the share at lambda / (1 + lambda) = 0.23; the
module costs a sixth of the layers, W_eh and half of the head products
whatever it weighs.  Beside it, as a note, the module's own loss
(gauge ``mtp/loss``).  Declared "higher is better" only because a
metric must say; it is a reading, not a goal.  Nothing where the
program has no such gauge."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('mtp/loss_share', None)
    if value is None:
        return None
    run.setdefault('notes', {})['mtp_loss_share'] = (
        'mtp/loss %s on the last run that fetched'
        % monitor.gauge_value('mtp/loss', None))
    return float(value)
