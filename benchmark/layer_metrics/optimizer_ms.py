"""Device time per step, chip 0, in what the optimizer ops were lowered
to (``benchmark/lib/scope_time.py``): ``adam``, ``momentum``, ``lamb`` and
their fused runs, the Pallas kernel with its pack and unpack together
(the note of ``unscoped_ms`` lists the three apart).  Loss scaling's
``check_finite_and_unscale`` and ``update_loss_scaling`` are AMP's,
not counted here.

Not all of the fused run's packing is here.  The ``concatenate``s
that feed the kernel's padded operands are built by XLA while it
merges the pad and concat chains and come out with no ``op_name``, so
they land in ``unscoped_ms`` (four of them, 11.4 of its ms a step in
the one-chip BERT cells; my chip run, PR 23).  A change to the packing
has to report ``optimizer_ms + unscoped_ms``."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['adam', 'adamw', 'momentum', 'lamb', 'fused_adam',
                   'fused_adamw', 'fused_lamb'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    return scope_time.per_step_ms(trace, run, belongs)
