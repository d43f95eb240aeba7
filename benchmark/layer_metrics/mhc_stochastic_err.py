"""How far the residual streams' mixing matrices are from the doubly
stochastic ones after their Sinkhorn normalisations: the largest
|row sum - 1| or |column sum - 1| of H_res over the tokens and the
hyper-connected operators of the step, on the last run that fetched:
the program's gauge ``mhc/stochastic_err``
(``paddle_tpu/models/xing4.py`` ``record``, read through
``Program.watch`` from the ``hyper_connection_pre`` ops' ``Err``
outputs).  It says whether ``hc_sinkhorn_iters`` normalisations
converged at these weights: 1e-3 would be a mix that creates or loses
a thousandth of the stream an operator.  Beside it, as a note, the
static gauges ``mhc/streams`` and ``mhc/sinkhorn_iters`` and the
counter ``mhc/calls``.  Nothing where the program has no such
gauge."""

LAYER = 'op lowerings'
UNIT = 'ratio'
MOVES = 'throughput'


def read(trace, run):
    from paddle_tpu.fluid import monitor
    value = monitor.gauge_value('mhc/stochastic_err', None)
    if value is None:
        return None
    run.setdefault('notes', {})['mhc_stochastic_err'] = (
        'mhc/streams %s, mhc/sinkhorn_iters %s, mhc/calls %d lowerings'
        % (monitor.gauge_value('mhc/streams', None),
           monitor.gauge_value('mhc/sinkhorn_iters', None),
           monitor.flat().get('mhc/calls', 0)))
    return float(value)
