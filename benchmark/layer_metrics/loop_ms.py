"""Device time per step, chip 0, of every instruction inside the bodies
of the program's differentiable loops (a fluid ``while`` lowered as a
scan), forward and backward together (``benchmark/lib/loop_time.py``):
in a looped model all of the step but the embedding, the loss's last
mean and the optimizer.  What the loop itself adds around its bodies
(stacking the residuals, the carries' selects) is inside; the ops read
under their own metrics too (``matmul_ms``, ``causal_attention_ms``:
those go by fluid op, this by place).  Nothing where the program has
no such table or its steps hold no such loop."""

LAYER = 'executor'
UNIT = 'ms/step'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.lib import loop_time
    got = loop_time.measured(trace, run)
    if got is None:
        return None
    run.setdefault('notes', {})['loop_ms'] = (
        'loop bodies, chip 0: forward %.3f ms/step, backward %.3f '
        'ms/step' % (trace.per_step_ms(got['forward']),
                     trace.per_step_ms(got['backward'])))
    return trace.per_step_ms(got['forward'] + got['backward'])
