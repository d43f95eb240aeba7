"""Device time per step, chip 0, in the ``hyper_connection_pre`` and
``hyper_connection_post`` ops and their gradients
(``benchmark/lib/scope_time.py``): the residual streams' mix around
every operator of the step (two a layer, the prediction module's layer
too), forward, the forward every block's gradient runs again, and
backward.  As a note, the parts the ops' own named scopes tell apart:
the maps (r, the projection r phi, the sigmoids AND their Sinkhorn
loop: the table folds a scope under a scope into the outer one) against
the read-out against the write-back.  Forward and backward read
together: under a recompute group jax names the transposed instructions
by their forward ops; so the note also says how many recompute
groups the program lowered (counter ``executor/recompute_groups``; a
program from before it reads 0) and what ONE more forward weighs by
the hand count (``xing_flops.mhc_forward_share``): work that neither
``mhc_roofline`` nor ``mfu`` counts as necessary.  Nothing where the
program holds no such op."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

OPS = ('hyper_connection_pre', 'hyper_connection_post')
PARTS = ('maps', 'read_out', 'write_back')


def belongs(op_type):
    return op_type in OPS


def read(trace, run):
    from benchmark.lib import scope_time
    ms = scope_time.per_step_ms(trace, run, belongs)
    if not ms:
        return None
    by_scope = scope_time.measured(trace, run)['by_scope']
    parts = {part: trace.per_step_ms(sum(
        ns for scope, ns in by_scope.items()
        if scope is not None and belongs(scope_time.op_type(scope)) and
        scope.split('/')[-1] == part)) for part in PARTS}
    from benchmark.lib import xing_flops
    from paddle_tpu.fluid import monitor
    cell = run['cell']
    n = cell.family.sizes(cell.config, cell.traffic).get('hc_mult', 0)
    run.setdefault('notes', {})['mhc_ms'] = (
        'the hyper-connections take %.3f ms a step; by part: %s; the '
        'rest under the ops themselves.  The program lowered %d '
        'recompute groups: inside one the ops\' forward runs again for '
        'the gradient, by the hand count %.0f%% on top of the forward '
        'and backward that mhc_roofline counts'
        % (ms, ', '.join('%s %.3f' % (part, parts[part])
                         for part in PARTS),
           monitor.flat().get('executor/recompute_groups', 0),
           100 * xing_flops.mhc_forward_share(n) / (
               1 - xing_flops.mhc_forward_share(n)) if n else 0))
    return ms
