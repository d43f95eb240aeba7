"""Device time per step, chip 0, in everything ``layers.eva_attention``
lowers to and its gradient (``benchmark/lib/scope_time.py``): the
chunk pooling (``eva_chunk_summary``), both attention streams (the
``fused_multihead_attention`` op: the local calls under its own scope,
the remote ones under ``remote``) and the merge (``attention_merge``).
The model has no other attention, so the op type is the layer's.  The
reshapes that fold windows into the batch move nothing and are not
counted."""

LAYER = 'op lowerings'
UNIT = 'ms/step'
MOVES = 'throughput'

TYPES = frozenset(['eva_chunk_summary', 'fused_multihead_attention',
                   'attention_merge'])


def belongs(op_type):
    return op_type in TYPES


def read(trace, run):
    from benchmark.lib import scope_time
    got = scope_time.measured(trace, run)
    if got is None:
        return None
    parts = {}
    for scope, ns in got['by_scope'].items():
        if scope is not None and belongs(scope_time.op_type(scope)):
            kind = 'remote' if '/remote' in scope else \
                scope_time.op_type(scope)
            parts[kind] = parts.get(kind, 0) + ns
    if not parts:
        return None
    run.setdefault('notes', {})['eva_attention_ms'] = (
        'ms a step by part (forward and gradient; '
        'fused_multihead_attention = the local stream): '
        + ', '.join('%s %.3f' % (kind, trace.per_step_ms(ns))
                    for kind, ns in sorted(parts.items())))
    return trace.per_step_ms(sum(parts.values()))
