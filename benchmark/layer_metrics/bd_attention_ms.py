"""Device time per step, chip 0, in everything
``layers.block_diffusion_attention`` lowers to and its gradient
(``benchmark/lib/scope_time.py``): the three parts (the
``fused_multihead_attention`` op: clean over clean and corrupted over
clean under their own ``block<n>_<relation>`` scopes, the corrupted
copy's own blocks folded into the batch under the op's own scope) and
their merge (``attention_merge``), a recompute group's second forward
with them.  The model has no other attention, so the op types are the
layer's.  The splits, reshapes and concatenations around them are not
counted.  Nothing where the program has no scope table or no
block-mask call."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['fused_multihead_attention', 'attention_merge'])


def read(trace, run):
    import re
    from benchmark.lib import scope_time
    got = scope_time.measured(trace, run)
    if got is None:
        return None
    parts = {}
    for scope, ns in got['by_scope'].items():
        if scope is None or scope_time.op_type(scope) not in TYPES:
            continue
        named = re.search(r'/(block\d+_\w+)', scope)
        kind = named.group(1) if named else scope_time.op_type(scope)
        parts[kind] = parts.get(kind, 0) + ns
    if not any(kind.startswith('block') for kind in parts):
        return None
    run.setdefault('notes', {})['bd_attention_ms'] = (
        'ms a step by part (forward, recomputed forward and gradient; '
        'fused_multihead_attention = the corrupted copy\'s own blocks): '
        + ', '.join('%s %.3f' % (kind, trace.per_step_ms(ns))
                    for kind, ns in sorted(parts.items())))
    return trace.per_step_ms(sum(parts.values()))
