"""Profiler: per-op time summary + XLA trace capture.

Reference: python/paddle/fluid/profiler.py:129 (profiler context
manager) over platform/profiler.h:166-175 EnableProfiler/
DisableProfiler, which print a per-op time table sorted by
`sorted_key` in {'calls','total','max','min','ave'}.

TPU-native split, mirroring the reference's two profilers:

- tracer_option='Serial': while profiling is enabled the executor
  compiles each device op as its OWN one-op segment and host-times it
  to completion (block_until_ready).  That is the reference's
  host-side RecordEvent semantics — per-op serialization is the
  documented price of op-granular timing there too (the CUDA profiler
  also serializes streams per event).  NOTE the measured program is a
  different (unfused) compilation of the same ops.
- tracer_option='Default' (round 5): the PRODUCTION program runs
  untouched under a jax.profiler device-trace capture; on exit the
  trace's per-kernel events are attributed back to fluid op types
  through the named_scope metadata every lowering runs under
  (executor._lower_ops -> XLA op_metadata of the compiled HLO) and
  summed into the same sorted table.  This is the reference's
  DeviceTracer leg (platform/device_tracer.h: CUPTI kernels correlated
  back to op RecordEvents) — per-op attribution of the REAL fused run.
  The trace is the ``.xplane.pb`` jax.profiler writes; its op events
  name HLO instructions only, so the fluid op of each comes from the
  executables this process holds (``hlo_scopes`` / ``scope_tables``),
  and so do the FLOPs and bytes of each (``hlo_costs`` /
  ``cost_tables``; the rules of both are written out further down).

stop_profiler prints the sorted table; summary_records() /
summary_string() expose it programmatically.  start_trace()/
stop_trace() capture without printing: the same table, plus the
events for tools/timeline.py's merged Perfetto file.
"""

import bisect
import collections
import contextlib
import functools
import math
import os
import re

import jax

_SORT_KEYS = ('calls', 'total', 'max', 'min', 'ave')
# a trace-derived row's cost: key in summary_records(), table heading
_COST_COLUMNS = (('gflop', 'GFLOP'), ('mb', 'MB'), ('tflops', 'TFLOP/s'),
                 ('gbps', 'GB/s'))

_enabled = False
_mode = 'Serial'         # 'Serial' | 'Default' (trace-derived)
_records = {}  # op type -> [calls, total, max, min]
_costs = {}    # op type -> [GFLOP or None (unknown), MB], trace-derived
_passes = {}   # pass -> seconds, trace-derived
_folded = False          # records already added to fluid.monitor
_trace_path = None
_prof_trace_dir = None   # capture dir while a 'Default' profile runs


def is_enabled():
    """True when the executor must split per-op ('Serial' mode only:
    the trace-derived mode measures the production program)."""
    return _enabled and _mode == 'Serial'


def record_op(op_type, seconds):
    """Executor hook: account one timed execution of `op_type`."""
    rec = _records.get(op_type)
    if rec is None:
        _records[op_type] = [1, seconds, seconds, seconds]
    else:
        rec[0] += 1
        rec[1] += seconds
        rec[2] = max(rec[2], seconds)
        rec[3] = min(rec[3], seconds)


def reset_profiler():
    """Drop all accumulated per-op records (reference
    platform::ResetProfiler)."""
    global _folded
    _records.clear()
    _costs.clear()
    _passes.clear()
    _folded = False


def summary_records():
    """{op_type: {'calls', 'total', 'max', 'min', 'ave'}} (seconds).
    After a device trace a row whose instructions the cost table
    (``cost_tables()``) knows also holds 'mb' (bytes moved) and, unless
    one of its instructions is a custom call, 'gflop' and the rates
    over 'total', 'tflops' and 'gbps': what a custom call computes, and
    how much of its operands it reads, no HLO text says."""
    out = {}
    for t, (c, tot, mx, mn) in _records.items():
        row = out[t] = {'calls': c, 'total': tot, 'max': mx, 'min': mn,
                        'ave': tot / c}
        if t in _costs:
            gflop, mb = _costs[t]
            row['mb'] = mb
            if gflop is not None:
                row['gflop'] = gflop
                if tot > 0:
                    row['gbps'] = mb / 1e3 / tot
                    row['tflops'] = gflop / 1e3 / tot
    return out


def summary_string(sorted_key='total'):
    """The reference's profiler table (profiler.h:166 prints Event
    rows sorted by sorted_key); after a device trace with four columns
    more, a row's cost and what it achieved ('-': not known), and,
    where the capture held a recompute group, a last line with the
    device time of each pass (``fluid_pass``)."""
    if sorted_key not in (None,) + _SORT_KEYS:
        raise ValueError('sorted_key must be one of %s, got %r'
                         % (_SORT_KEYS, sorted_key))
    key = sorted_key or 'total'
    rows = sorted(summary_records().items(),
                  key=lambda kv: kv[1][key], reverse=True)
    extra = _COST_COLUMNS if _costs else ()
    lines = ['%-28s %8s %12s %12s %12s %12s'
             % ('Event', 'Calls', 'Total(ms)', 'Min(ms)', 'Max(ms)',
                'Ave(ms)') + ''.join(' %10s' % head for _, head in extra)]
    for t, r in rows:
        lines.append('%-28s %8d %12.4f %12.4f %12.4f %12.4f'
                     % (t, r['calls'], r['total'] * 1e3,
                        r['min'] * 1e3, r['max'] * 1e3,
                        r['ave'] * 1e3) + ''.join(
                            ' %10.2f' % r[k] if k in r else ' %10s' % '-'
                            for k, _ in extra))
    if _passes.get('recomputed'):
        lines.append('by pass (ms): ' + ', '.join(
            '%s %.4f' % (p, _passes.get(p, 0.0) * 1e3) for p in PASSES))
    return '\n'.join(lines)


def _registered_op_types():
    from ..ops import registry
    return set(registry._REGISTRY)


def attributed_type(tf_op, op_types=None):
    """An event's ``tf_op`` -> the op type its time is filed under, or
    None: a scope of the scope table as it stands (``'mul_grad'``,
    ``'fused_adam/pack'``), else a raw ``op_name`` path, read by the
    one rule there is (``fluid_scope``)."""
    op_types = op_types or _registered_op_types()
    head = tf_op.split('/', 1)[0]
    if _is_op_type(head, op_types):
        return head
    scope = fluid_scope(tf_op, op_types)
    return scope.split('/', 1)[0] if scope else None


def attribute_trace_events(events, op_types=None, with_stats=False,
                           costs=None, passes=None):
    """Map device-trace kernel events back to fluid op types.

    `events` are chrome-trace events (``load_trace_events``, or a
    trace.json's 'traceEvents').  Each kernel event carries
    args['tf_op'] — a fluid scope from ``scope_tables()``, or a raw
    XLA op_metadata op_name, i.e. the jax.named_scope path the
    executor wrapped the lowering in ('jit_segment_x/relu/max' or,
    under whole-program autodiff,
    'jit_.../transpose(jvp(relu))/...').  Attribution: the op type of
    the scope, the backward's as ``<type>_grad``; a raw path is read by
    the scope table's own rule (``fluid_scope``), so this table and
    that one cannot disagree; kernels with no fluid op (copies, infeed,
    glue) land under 'unattributed/<hlo name>'.  Returns {name: [calls,
    total_s, max_s, min_s]}.

    ``passes``, a dict, receives {pass: seconds} summed from the events
    that carry one (``load_trace_events`` gives args['pass']).

    Tolerant by contract: real captures contain malformed rows (counter
    events without dur, instant events, non-string tf_op metadata,
    null fields) — those are skipped or zero-timed, never raised on,
    so one odd event cannot lose a whole profile.  `with_stats=True`
    returns (recs, {'events', 'attributed', 'dropped'}) so skipped
    rows are COUNTED, not silently eaten.

    `costs`, a dict, receives {name: [GFLOP or None, MB]} summed from
    the events that carry a cost (``load_trace_events`` gives
    args['mb'] and, where known, args['gflop']);
    None once one event of the name has bytes and no FLOPs.

    Both positive and negative lookups are cached per tf_op string
    (a capture repeats each unattributed scope on every step; without
    the negative cache every repeat re-splits the path)."""
    op_types = op_types or _registered_op_types()
    recs = {}
    cache = {}   # tf_op -> resolved name | False for negative
    n_events = n_attr = dropped = 0

    def _fold(name, sec, cost=None):
        rec = recs.get(name)
        if rec is None:
            recs[name] = [1, sec, sec, sec]
        else:
            rec[0] += 1
            rec[1] += sec
            rec[2] = max(rec[2], sec)
            rec[3] = min(rec[3], sec)
        if cost is not None and costs is not None:
            gflop, mb = cost
            held = costs.setdefault(name, [0.0, 0.0])
            held[0] = None if gflop is None or held[0] is None \
                else held[0] + gflop
            held[1] += mb

    for e in events:
        if not isinstance(e, dict):
            dropped += 1
            continue
        if e.get('ph') != 'X':
            continue   # counter/instant/metadata rows are filtered by
        n_events += 1  # design, not malformed
        args = e.get('args') or {}
        tf_op = args.get('tf_op') if isinstance(args, dict) else None
        if not tf_op or not isinstance(tf_op, str):
            dropped += 1
            continue
        try:
            # an event other events nest in counts its own part only
            sec = float(e.get('self_dur', e.get('dur')) or 0) * 1e-6
        except (TypeError, ValueError):
            sec = 0.0
        name = cache.get(tf_op)
        if name is None:
            name = cache[tf_op] = attributed_type(tf_op, op_types) \
                or False
        if passes is not None and isinstance(args.get('pass'), str):
            passes[args['pass']] = passes.get(args['pass'], 0.0) + sec
        cost = None
        if isinstance(args.get('mb'), (int, float)):
            gflop = args.get('gflop')
            if not isinstance(gflop, (int, float)):
                gflop = None
            cost = (gflop, args['mb'])
        if not name:
            # per-HLO-name bucket: distinct kernels share a scope
            # path, so the bucket keys on the event name instead
            _fold('unattributed/' +
                  str(e.get('name', '?')).split('.')[0], sec, cost)
            continue
        n_attr += 1
        _fold(name, sec, cost)
    if with_stats:
        return recs, {'events': n_events, 'attributed': n_attr,
                      'dropped': dropped}
    return recs


UNSCOPED = '(unscoped)'


def instructions_under(events, scopes, op_types=None):
    """The instructions a capture ran under the given fluid scopes
    (``'moe_dispatch'`` holds ``'moe_dispatch/<scope>'`` too;
    ``UNSCOPED``: those the scope table gives no fluid op, whatever
    their module), from the events ``load_trace_events`` gives or a
    ``device.trace.json`` holds, of the first process that ran any:
    [{'name', 'tf_op', 'pass', 'kind', 'shapes', 'calls', 'ms', 'mb',
    'gbps'}], longest first; ``mb`` is one call's, ``gbps`` over all
    calls' own time.  Two instructions of one name, kind and shapes (a
    quiet step's and a fetching step's) are one row."""
    op_types = op_types or _registered_op_types()
    wanted = set(scopes)
    rows, pid = {}, None
    for e in events:
        if not isinstance(e, dict) or e.get('ph') != 'X':
            continue
        args = e.get('args')
        if not isinstance(args, dict) or \
                not isinstance(args.get('tf_op'), str):
            continue
        if pid is None:
            pid = e.get('pid')
        if e.get('pid') != pid:
            continue
        tf_op = args['tf_op']
        if attributed_type(tf_op, op_types) is None:
            if UNSCOPED not in wanted:
                continue
        elif not (tf_op in wanted or tf_op.split('/', 1)[0] in wanted):
            continue
        key = (e.get('name'), tf_op, args.get('kind'), args.get('shapes'))
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                'name': key[0], 'tf_op': tf_op, 'pass': args.get('pass'),
                'kind': key[2], 'shapes': key[3], 'calls': 0, 'ms': 0.0,
                'mb': args.get('mb')}
        row['calls'] += 1
        row['ms'] += float(e.get('self_dur', e.get('dur')) or 0) / 1e3
    for row in rows.values():
        row['gbps'] = row['mb'] * row['calls'] / row['ms'] \
            if row['mb'] is not None and row['ms'] > 0 else None
    return sorted(rows.values(), key=lambda r: -r['ms'])


# ------------------------------------------------ HLO instruction scopes
# A device trace names HLO instructions (``fusion.933``), and XLA renames
# them whenever a lowering changes.  The executor lowers every fluid op
# inside ``jax.named_scope(op.type)``, so each instruction of the
# OPTIMISED HLO still says where it came from, in the ``op_name`` of its
# metadata: ``jit(segment_x)/mul/dot_general``, or
# ``jit(segment_wpg_x)/transpose(jvp(mul))/dot_general`` for backward
# code jax derived inside the scope.  The rule, written once:
#
# - an instruction counts to the first component of its ``op_name``,
#   the primitive's own name at the end left out, that is a registered
#   fluid op type; jax's transform wrappers are looked through, and a
#   ``transpose`` among them makes it that type's backward
#   (``mul_grad``, the name the explicit grad op lowers under);
# - the PASS an instruction runs in (``fluid_pass``, ``pass_tables()``)
#   is read off the same components, and the scope's ``_grad`` follows
#   it, so the two cannot disagree.  A recompute group
#   (``executor._lower_recomputed``: ``jax.checkpoint``) puts the
#   ``transpose`` on an EARLIER, nameless component and runs the
#   group's forward a second time inside the backward pass:
#   ``transpose(jvp(jvp()))/checkpoint/mul/dot_general`` is the group's
#   backward, ``.../checkpoint/rematted_computation/mul/dot_general``
#   its second forward.  So:
#     ``recomputed``: a component ``rematted_computation`` anywhere
#       before the op's, whatever else wraps it; the scope stays the
#       forward's (``mul``);
#     ``backward``: else a ``transpose`` among the wrappers of the op's
#       component or of ANY component before it, an explicit ``_grad``
#       op type, or a transposed control-flow op around it (below);
#       the scope is ``mul_grad``;
#     ``forward``: else.
#   An optimizer's op belongs to no pass, nor does an instruction with
#   no fluid scope or one the compiler named itself.  What the pass is
#   NOT: an op whose own ``custom_vjp`` backward runs a chunk's forward
#   again (``ssd_scan``, ``selective_scan``, ``kda_attention``, the
#   ``sinkhorn`` backward) does so under its ``_grad`` scope and stays
#   ``backward``; only ``jax.checkpoint``'s second forward is
#   ``recomputed``.  What the program runs between the gradient and the
#   optimizer under plain scopes (loss-scale checks, clipping) reads
#   ``forward``.
# - a control-flow op (``while``, ``conditional_block``) is looked
#   INTO: an instruction of its sub-block counts to the first fluid op
#   type further down the path
#   (``jvp(while)/while/body/loop_body/mul/dot_general`` is ``mul``),
#   backward where the control-flow component is (jax names the
#   transposed body's instructions by their forward ops), and only
#   what the loop itself adds (the carries' selects, the residuals'
#   stacking) counts to ``while`` / ``while_grad``;
# - a plain named scope the lowering itself opened right under the op's
#   is kept as ``<type>/<scope>``;
# - a fusion counts to the ``dot`` / ``convolution`` / custom call it
#   holds, else to its root; a root that carries no scope (the tuple of
#   a multi-output fusion, a bitcast or copy XLA put there) stands for
#   the nearest of its operands inside the fusion that does, and only
#   if none does, the fusion's own ``op_name`` decides; scope AND pass
#   are those of that one instruction (``_fusion_decider``);
# - an instruction the TPU compiler expands and renames itself keeps no
#   ``op_name`` of the program's (a grouped matmul comes out as Mosaic
#   calls named ``ragged-dot-none``): it counts to the op whose
#   registration declares that name (``ops.registry.COMPILER_NAMED``);
#   forward and backward cannot be told apart there;
# - an instruction with no fluid scope counts to none.
#
# The same parse says what each instruction COSTS, so that the time a
# trace gives it reads against the chip's peaks: a
# ``Cost(kind, flops, bytes, dtype, group, shapes)``, by one rule:
#
# - ``dot``: 2 x (elements of the result) x (product of the lhs
#   contracting sizes).  ``convolution``: 2 x the multiply-adds whose
#   two factors are both elements of the operands: batch x output
#   features x input features / ``feature_group_count`` x, for each
#   spatial dimension by ``dim_labels``, the (output position, window
#   tap) pairs that land on an element.  A tap on padding, or on a
#   hole ``lhs_dilate`` opens between elements, is no multiply-add.
#   That is what makes the rule hold for what the TPU compiler prints:
#   it writes EVERY dot as a convolution, a batched one with its batch
#   dimensions as spatial ones dilated so that one tap in ``size``
#   lands (``window={size=192x12 stride=191x11 lhs_dilate=192x12}``),
#   and an input-gradient at stride 2 with three holes in four.  The
#   window-times-result count would be 2,304 and 4 times too high
#   there.  The price: a model count that takes a padded window whole
#   (``benchmark/lib/flops.py``, every published one) is higher, by
#   3.45% for ResNet-50 at 224 x 224 (its 3 x 3 and 7 x 7 windows'
#   taps on padding; 18% of the 3 x 3 at 7 x 7, none of a matmul).
# - a ``fusion``: the FLOPs of the dots and convolutions of the
#   computation it calls; the bytes of its operands and its result AT
#   THE FUSION BOUNDARY (tuples summed), an operand the body reads only
#   through ``slice`` / ``dynamic-slice`` / ``gather`` at the size
#   read, one it only updates in place (operand 0 of a
#   ``dynamic-update-slice``) at nothing, and a result written through
#   ``dynamic-update-slice`` at the size written.  Elementwise FLOPs
#   are not counted (under 2% at these widths, as ``flops.py``).
# - a Mosaic or other ``custom-call``: ``flops=None`` (unknown, never
#   0), bytes of operands and result; so has a fusion that holds one.
#   No rate is made of either: how much of its operands a call reads
#   is not in the text (the chip's grouped matmul skips the rows past
#   its last group).
# - a collective (``all-reduce``, ``all-gather``, ``reduce-scatter``,
#   ``all-to-all``, ``collective-permute``, their ``-start`` forms and
#   an ``async-start`` that wraps one): ``kind='collective'``, the
#   bytes of its operands (one device's, in whatever memory space),
#   ``group`` the devices of one of its ``replica_groups``.  The
#   ``-done`` costs nothing more.
# - ``while`` / ``conditional`` / ``call`` and what moves no data
#   (``parameter``, ``tuple``, ``bitcast``, ...) have no cost: the
#   trace names a loop's body's instructions one by one.  Nor has an
#   asynchronous copy or slice (``copy-start`` / ``-done``): it moves
#   its bytes beside the op line, and its time there is the wait.
# - any other instruction: bytes of operands and result, by the same
#   slice rules.
# - bytes are those of the device's main memory, the one the bandwidth
#   peak is of: an array whose layout names another space (``S(1)``,
#   where such a copy put an operand ahead of its use) counts nothing,
#   and the copy's own read is given to no instruction, so a scope's
#   GB/s is a floor.
# - ``dtype`` is the type of the dot's or convolution's lhs operand
#   (the largest's, where a fusion holds several), else the result's:
#   an f32 product takes the MXU several bf16 passes, and every share
#   of a peak is against the bf16 peak.  ``kind`` is the opcode held
#   (``dot``, ``convolution``, ``custom-call``, ``collective``) or the
#   instruction's own; ``shapes`` the operands and result of what is
#   held, for a reader (``fusion.933`` says nothing).
_TRANSFORMS = re.compile(
    r'^(jvp|transpose|vmap|checkpoint|remat|custom_jvp|custom_vjp)'
    r'\((.*)\)$')
_HLO_MODULE = re.compile(r'^HloModule\s+([^\s,]+)')
_HLO_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$')
_HLO_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?(\S+)\s+=\s+')
_HLO_OPERAND = re.compile(r'%([^\s,()]+)')
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r'\bcalls=%?([^\s,}]+)')
_HELD_BY_FUSION = ('dot', 'convolution', 'custom-call')

# ``shape`` and ``attrs`` stay text too (the result's shape, and the
# line after the operands): costing reads them for the few opcodes it
# counts
_Instruction = collections.namedtuple(
    '_Instruction', 'name opcode operands op_name calls root shape attrs')

Cost = collections.namedtuple('Cost', 'kind flops bytes dtype group shapes')


def _is_op_type(name, op_types):
    """Registered, or the generic gradient of a registered type (grad
    ops have no registry entry of their own)."""
    return name in op_types or (name.endswith('_grad') and
                                name[:-5] in op_types)


# ops the executor lowers itself and whose sub-block's ops lower
# inside their named scope
_CONTROL_FLOW = frozenset(['while', 'conditional_block', 'while_grad',
                           'conditional_block_grad'])


def _unwrapped(comp):
    """One ``op_name`` component without jax's transform wrappers ->
    (name, whether a ``transpose`` was among them)."""
    backward = False
    m = _TRANSFORMS.match(comp)
    while m:
        backward = backward or m.group(1) == 'transpose'
        comp = m.group(2)
        m = _TRANSFORMS.match(comp)
    return comp, backward


PASSES = ('forward', 'recomputed', 'backward')
_REMATTED = 'rematted_computation'  # jax.checkpoint's second forward


def _scope_and_pass(op_name, op_types, optimizers):
    """(fluid scope, pass) of an ``op_name`` by the rule above, each
    None for none."""
    if not op_name:
        return None, None
    parts = op_name.split('/')
    around = None           # the control-flow op the walk is inside
    # a ``transpose`` among the wrappers of a component so far, or an
    # explicit ``while_grad`` / ``conditional_block_grad`` around
    behind = False
    rematted = False
    for i, comp in enumerate(parts[:-1]):
        if comp == _REMATTED:
            rematted = True
            continue
        comp, transposed = _unwrapped(comp)
        behind = behind or transposed
        if not _is_op_type(comp, op_types):
            continue
        backward = not rematted and (behind or comp.endswith('_grad'))
        if backward and not comp.endswith('_grad'):
            comp += '_grad'
        inner = parts[i + 1] if i + 2 < len(parts) else ''
        found = comp + '/' + inner if inner and '(' not in inner else comp
        phase = None if comp in optimizers else \
            'recomputed' if rematted else \
            'backward' if backward else 'forward'
        if comp in _CONTROL_FLOW:
            around = around or (found, phase)
            behind = behind or comp.endswith('_grad')
            continue
        return found, phase
    if around is not None:
        return around
    from ..ops import registry
    for prefix, op_type in registry.COMPILER_NAMED.items():
        if op_name.startswith(prefix):
            return op_type, None
    return None, None


def fluid_scope(op_name, op_types=None):
    """The fluid op an HLO instruction was lowered from, by the rule
    above, from the ``op_name`` of its metadata: ``'mul'``,
    ``'mul_grad'``, ``'<type>/<scope>'``, or None."""
    return _scope_and_pass(op_name, op_types or _registered_op_types(),
                           ())[0]


def fluid_pass(op_name, op_types=None):
    """The pass an HLO instruction runs in, by the same rule:
    ``'forward'``, ``'recomputed'``, ``'backward'``, or None."""
    return _scope_and_pass(op_name, op_types or _registered_op_types(),
                           _optimizer_types())[1]


def loop_side(op_name):
    """Which side of a differentiable fluid loop an instruction belongs
    to, from its ``op_name``: 'forward' for the scan's body as the
    forward pass runs it, 'backward' for the body of its transpose (or
    anything of an explicit ``while_grad`` op), None outside such a
    body."""
    from ..ops import registry
    backward = False
    for comp in (op_name or '').split('/'):
        comp, transposed = _unwrapped(comp)
        backward = backward or transposed or comp == 'while_grad'
        if comp == registry.LOOP_BODY_SCOPE:
            return 'backward' if backward else 'forward'
    return None


def _fusion_loop_side(fusion, body):
    """A fusion's side is that of the dot or call it holds, else its
    own, else the first of its instructions that has one."""
    held = [ins for ins in body if ins.opcode in _HELD_BY_FUSION]
    for ins in held + [fusion] + body:
        side = loop_side(ins.op_name)
        if side:
            return side
    return None


def _closing(text, start):
    """Index just past the parenthesis that closes the one at
    ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == '(') - (text[i] == ')')
        if depth == 0:
            return i + 1
    return len(text)


def _parse_hlo(text):
    """An HLO module's text -> (module name, {computation name:
    [_Instruction]}).  ``operands`` stays the text between the
    opcode's parentheses: only a fusion's root and costing need it
    split."""
    module, computations, body = '', {}, None
    for line in text.splitlines():
        if body is not None:
            m = _HLO_INSTRUCTION.match(line)
            if m:
                i = m.end()
                # the shape: one word, or a tuple that holds spaces
                i = _closing(line, i) if line[i] == '(' else \
                    line.find(' ', i)
                paren = line.find('(', i)
                if paren < 0:
                    continue
                end = _closing(line, paren)
                op_name = _HLO_OP_NAME.search(line, end)
                calls = _HLO_CALLS.search(line, end)
                body.append(_Instruction(
                    m.group(2), line[i:paren].strip(),
                    line[paren + 1:end - 1],
                    op_name.group(1) if op_name else '',
                    calls.group(1) if calls else None,
                    bool(m.group(1)), line[m.end():i], line[end:]))
            elif line.startswith('}'):
                body = None
            continue
        m = _HLO_COMPUTATION.match(line)
        if m:
            body = computations[m.group(1)] = []
        elif not module:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
    return module, computations


def _fusion_decider(fusion, body, op_types):
    """The one instruction whose ``op_name`` decides a fusion's scope
    and pass, by the rule above: of the computation it calls, or the
    fusion itself."""
    scoped = {ins.name for ins in body
              if fluid_scope(ins.op_name, op_types)}
    for ins in body:
        if ins.opcode in _HELD_BY_FUSION and ins.name in scoped:
            return ins
    by_name = {ins.name: ins for ins in body}
    roots = [ins for ins in body if ins.root] or body[-1:]
    queue, seen = collections.deque(roots), set()
    while queue:                # breadth first: the nearest operand
        ins = queue.popleft()
        if ins.name in scoped:
            return ins
        for name in _HLO_OPERAND.findall(ins.operands):
            if name in by_name and name not in seen:
                seen.add(name)
                queue.append(by_name[name])
    return fusion


# ------------------------------------------------- HLO instruction costs
_HLO_ARRAY = re.compile(
    r'\b([a-z]+\d*[a-z0-9]*)\[([^\]]*)\](?:\{[^}]*?S\(([1-9]\d*)\)[^}]*\})?')
_HLO_WINDOW = re.compile(r'\bwindow=\{([^}]*)\}')
_HLO_DIM_LABELS = re.compile(r'\bdim_labels=(\w+)_(\w+)->(\w+)')
_HLO_FEATURE_GROUPS = re.compile(r'\bfeature_group_count=(\d+)')
_HLO_CONTRACTING = re.compile(r'\blhs_contracting_dims=\{([\d,]*)\}')
_HLO_REPLICA_GROUPS = re.compile(
    r'\breplica_groups=(?:\{\{([\d,]*)\}|\[\d+,(\d+)\])')
_ITEMSIZE = {'pred': 1, 's4': 0.5, 'u4': 0.5, 's8': 1, 'u8': 1,
             's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2, 's32': 4, 'u32': 4,
             'f32': 4, 's64': 8, 'u64': 8, 'f64': 8, 'c64': 8, 'c128': 16}
_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
                'collective-permute', 'collective-broadcast',
                'ragged-all-to-all')
_SLICED = ('slice', 'dynamic-slice', 'gather')
_UPDATED = 'dynamic-update-slice'
# control flow (its bodies' instructions are costed one by one) and
# what moves no data
_NO_COST = frozenset([
    'while', 'conditional', 'call', 'parameter', 'tuple',
    'get-tuple-element', 'bitcast', 'constant', 'after-all',
    'partition-id', 'replica-id', 'opt-barrier', 'async-update'])


@functools.lru_cache(maxsize=8192)
def _arrays(shape):
    """((dtype, dims, memory space), ...) of a shape's text, a tuple's
    in order; the space is '' for the device's main memory, else the
    ``S(n)`` of the layout."""
    return tuple(
        (dtype, tuple(int(d.lstrip('<=')) for d in dims.split(',') if d),
         space)
        for dtype, dims, space in _HLO_ARRAY.findall(shape))


@functools.lru_cache(maxsize=8192)
def _nbytes(shape, every_space=False):
    """Bytes of a shape in the device's main memory: an array the
    layout puts in another space (``S(1)``: a prefetched operand) is
    not read from there.  ``every_space``: wherever they lie."""
    total = 0
    for dtype, dims, space in _arrays(shape):
        if space and not every_space:
            continue
        size = 1 if dtype.startswith('f8') else _ITEMSIZE.get(dtype, 0)
        total += size * math.prod(dims)
    return int(total)


def _dtype(shape):
    """The type of a shape's first array, or None."""
    typed = _arrays(shape)
    return typed[0][0] if typed else None


def _plain(shape):
    """A shape's text without its layout."""
    return ', '.join('%s[%s]' % (t, ','.join(map(str, dims)))
                     for t, dims, _space in _arrays(shape))


@functools.lru_cache(maxsize=4096)
def _landing_taps(n, o, k, stride, lo, dilate, rhs_dilate):
    """The (output position, window tap) pairs of one spatial dimension
    of a convolution whose tap lands on an element of the lhs: ``n``
    elements ``dilate`` apart behind ``lo`` of padding, ``o`` output
    positions ``stride`` apart, ``k`` taps ``rhs_dilate`` apart.  One
    congruence an output position, not one test a pair: a batch
    dimension the TPU compiler writes as a spatial one has ``o == k ==``
    its size."""
    last = (n - 1) * dilate
    g = math.gcd(rhs_dilate, dilate)
    period = dilate // g
    inverse = pow(rhs_dilate // g, -1, period) if period > 1 else 0
    total = 0
    for p in range(o):
        base = p * stride - lo          # tap t lands at base + t * rhs_dilate
        first = max(0, -(base // rhs_dilate))
        end = min(k - 1, (last - base) // rhs_dilate)
        if end < first or base % g:
            continue
        t0 = (-base // g * inverse) % period
        total += (end - t0) // period - (first - 1 - t0) // period
    return total


def _window(attrs, rank):
    """{field: [value a spatial dimension]} of ``window={...}``; ``pad``
    is the low side's."""
    m = _HLO_WINDOW.search(attrs)
    given = dict(item.split('=', 1) for item in m.group(1).split()) \
        if m else {}

    def field(key, default):
        if key not in given:
            return [default] * rank
        return [int(v.split('_')[0]) for v in given[key].split('x')]

    return {'size': field('size', 1), 'stride': field('stride', 1),
            'pad': field('pad', 0), 'lhs_dilate': field('lhs_dilate', 1),
            'rhs_dilate': field('rhs_dilate', 1)}


def _operand_names(ins):
    return _HLO_OPERAND.findall(ins.operands)


def _matmul_flops(ins, shapes):
    """FLOPs of a ``dot`` or ``convolution`` by the rule above; None
    where its text does not say (unknown, never 0)."""
    try:
        lhs = _arrays(shapes[_operand_names(ins)[0]])[0][1]
        out = _arrays(ins.shape)[0][1]
        if ins.opcode == 'dot':
            m = _HLO_CONTRACTING.search(ins.attrs)
            return 2 * math.prod(out) * math.prod(
                lhs[int(i)] for i in m.group(1).split(',') if i)
        lhs_l, _rhs_l, out_l = _HLO_DIM_LABELS.search(ins.attrs).groups()
        spatial = sorted(c for c in out_l if c.isdigit())
        window = _window(ins.attrs, len(spatial))
        groups = _HLO_FEATURE_GROUPS.search(ins.attrs)
        macs = out[out_l.index('b')] * out[out_l.index('f')] * (
            lhs[lhs_l.index('f')] // (int(groups.group(1)) if groups else 1))
        for j, c in enumerate(spatial):
            macs *= _landing_taps(
                lhs[lhs_l.index(c)], out[out_l.index(c)],
                window['size'][j], window['stride'][j], window['pad'][j],
                window['lhs_dilate'][j], window['rhs_dilate'][j])
        return 2 * macs
    except (AttributeError, IndexError, KeyError, ValueError):
        return None


def _held_cost(ins, shapes):
    """(kind, flops, dtype, shapes' text) of a ``dot``, ``convolution``
    or custom call."""
    operands = [shapes.get(n, '') for n in _operand_names(ins)]
    flops = None if ins.opcode == 'custom-call' else \
        _matmul_flops(ins, shapes)
    return (ins.opcode, flops,
            _dtype(operands[0] if operands else '') or _dtype(ins.shape),
            '%s -> %s' % (' x '.join(_plain(o) for o in operands),
                          _plain(ins.shape)))


def _moved_bytes(ins, shapes):
    """Bytes of an instruction's operands and result, by the slice
    rules."""
    names = _operand_names(ins)
    if ins.opcode in _SLICED:           # reads what it returns
        return 2 * _nbytes(ins.shape) + sum(
            _nbytes(shapes.get(n, '')) for n in names[1:])
    if ins.opcode == _UPDATED:          # writes the update, in place
        return 2 * _nbytes(shapes.get(names[1], '')) + sum(
            _nbytes(shapes.get(n, '')) for n in names[2:])
    return _nbytes(ins.shape) + sum(_nbytes(shapes.get(n, ''))
                                    for n in names)


def _boundary_bytes(body):
    """Bytes a fusion moves at its boundary, from the computation it
    calls: its parameters as far as the body reads them, its root as
    far as the body writes it."""
    by_name, users = {}, collections.defaultdict(list)
    for ins in body:
        by_name[ins.name] = ins
        for position, name in enumerate(_operand_names(ins)):
            users[name].append((ins, position))

    def read(name, full):
        total = 0
        for user, position in users.get(name, ()):
            if user.opcode == 'bitcast':
                part = read(user.name, full)
            elif user.opcode in _SLICED and position == 0:
                part = _nbytes(user.shape)
            elif user.opcode == _UPDATED and position == 0:
                part = 0
            else:
                return full
            total += part
            if total >= full:
                return full
        return total

    def written(name):
        inner = by_name[name]
        while inner.opcode == 'bitcast':
            source = by_name.get((_operand_names(inner) or [None])[0])
            if source is None:
                break
            inner = source
        if inner.opcode == _UPDATED:
            update = by_name.get((_operand_names(inner) + [None, None])[1])
            if update is not None:
                return _nbytes(update.shape)
        return _nbytes(by_name[name].shape)

    total = sum(read(ins.name, _nbytes(ins.shape)) for ins in body
                if ins.opcode == 'parameter')
    for root in [ins for ins in body if ins.root] or body[-1:]:
        outputs = _operand_names(root) if root.opcode == 'tuple' \
            else [root.name]
        total += sum(written(n) for n in outputs if n in by_name)
    return total


def _group_size(attrs):
    m = _HLO_REPLICA_GROUPS.search(attrs)
    if not m:
        return None
    if m.group(2):
        return int(m.group(2))
    return len([d for d in m.group(1).split(',') if d]) or None


def _collective_cost(ins, shapes, attrs):
    names = _operand_names(ins)
    nbytes = sum(_nbytes(shapes.get(n, ''), True) for n in names)
    return Cost('collective', 0, nbytes,
                _dtype(shapes.get(names[0], '')) if names else None,
                _group_size(attrs),
                ', '.join(_plain(shapes.get(n, '')) for n in names))


def _base_opcode(opcode):
    """An opcode without the ``-start`` / ``-done`` of its
    asynchronous forms."""
    for suffix in ('-start', '-done'):
        if opcode.endswith(suffix):
            return opcode[:-len(suffix)]
    return opcode


def _instruction_cost(ins, shapes, called):
    """The Cost of one instruction a trace can name, by the rule above,
    or None; ``shapes`` are those of its computation's instructions,
    ``called`` the body of the computation it calls, if any."""
    opcode = ins.opcode
    base = _base_opcode(opcode)
    if base in _COLLECTIVES:
        return None if opcode.endswith('-done') else \
            _collective_cost(ins, shapes, ins.attrs)
    if base == 'async':
        wrapped = [i for i in called or () if i.opcode in _COLLECTIVES]
        return _collective_cost(ins, shapes, wrapped[0].attrs) \
            if wrapped and opcode == 'async-start' else None
    if opcode in _NO_COST or base != opcode:
        return None                     # an asynchronous copy or slice
    if opcode == 'fusion' and called is not None:
        inner = {i.name: i.shape for i in called}
        held = [_held_cost(i, inner) for i in called
                if i.opcode in _HELD_BY_FUSION]
        nbytes = _boundary_bytes(called)
    else:
        held = [_held_cost(ins, shapes)] \
            if opcode in _HELD_BY_FUSION else []
        nbytes = _moved_bytes(ins, shapes)
    if not held:
        return Cost(opcode, 0, nbytes, _dtype(ins.shape), None,
                    _plain(ins.shape))
    unknown = any(h[1] is None for h in held)
    kind, _, dtype, text = max(
        held, key=lambda h: (h[0] != 'custom-call', h[1] or 0))
    return Cost(kind, None if unknown else sum(h[1] for h in held),
                nbytes, dtype, None, text)


# ------------------------------------------------- HLO live temporaries
# The compiler says how much an executable holds beside its arguments
# (``memory_analysis().temp_size_in_bytes``) and not what: the TPU
# executable keeps no buffer assignment.  Its text is SCHEDULED
# (``is_scheduled=true``: the order of a computation's instructions is
# the order they run in) and its header carries ``input_output_alias``,
# so the same parse walks the entry computation once more and says
# which buffers are alive where their sum is largest.  The rule:
#
# - a buffer is born at the instruction that defines it, at
#   ``_nbytes(shape)``: main memory only, an ``S(1)`` result counts
#   nothing, as in the cost table; it dies after the last instruction
#   that reads it or an alias of it.  A tuple's leaves are buffers of
#   their own.
# - these define nothing and ALIAS an operand: ``bitcast``,
#   ``get-tuple-element``, ``tuple``, ``opt-barrier``, ``copy-done`` and
#   the other ``-done``s (the part of their ``-start``'s tuple that is
#   the result), the operand part of a ``copy-start`` / ``slice-start`` /
#   ``all-gather-start`` / ``async-start`` tuple, a
#   ``dynamic-update-slice``, a fusion whose root (or a leaf of its root
#   tuple) is a ``dynamic-update-slice`` of one of its parameters, a
#   fusion or custom call that says so (``output_to_operand_aliasing``),
#   and a ``while`` (its carried tuple).  An alias reads nothing: only
#   what reads IT keeps the buffer alive.
# - entry parameters are arguments and constants are the executable's:
#   neither is a temporary.  An operand of the root is live-out: an
#   output (a donated parameter's memory by the header's
#   ``input_output_alias``, else counted in ``output_size_in_bytes``),
#   no temporary either way; so the header need not be read.  Nothing
#   but an output is written in place into an argument: any other
#   in-place update of one comes out as a buffer of its own.
# - ``while`` / ``conditional`` / ``call`` are walked INTO: the peak of
#   the body (the larger of body and condition, the largest branch)
#   stands at the call, over what is alive around it.  Inside, the
#   parameter is the caller's memory, and so is an operand of the root:
#   a ``while`` writes its carried tuple in place, and a branch writes
#   the call's result.
# - a buffer's fluid op is its defining instruction's
#   (``instruction_scopes``' rule); a copy the compiler put in
#   (``copy``, ``copy-start``, ``slice-start`` with no ``op_name``)
#   takes that of what it copies, and a buffer it allocates for a loop
#   to fill (``AllocateBuffer``: a scan's stacked residuals) that of
#   its nearest reader; a reader that is such a copy is looked
#   through the same way.  Its pass goes with its op (that of the
#   same instruction).  Its class:
#   ``recomputed``: defined by an instruction of a recompute group's
#   second forward (pass ``recomputed``: what the group did NOT keep);
#   ``residual``: defined under a forward op of the first forward, last
#   read under a ``_grad`` one or by a ``recomputed`` instruction (kept
#   for the backward pass: of a group, its inputs); ``gradient``:
#   defined under a ``_grad`` op, last read by an optimizer op or a
#   collective; ``optimizer``: defined under an optimizer op;
#   ``working``: born and dead on one side; ``unscoped``: no fluid op.
#
# What the walk cannot see: the compiler's packing (alignment, a buffer
# reused by an elementwise result of the instruction that frees it),
# what a custom call allocates for itself, and how the chip's compiler
# holds a loop's carried state.  ``temp_bytes`` less the walk's sum
# says how far off it is: 0.4 to 1.5% in the BERT, ResNet and OLMoE
# steps compiled for a described v5e (two thirds of it the ``S(1)``
# buffers alive at the peak, which the compiler's figure counts and
# the table gives apart as ``elsewhere_bytes``), 3 to 25% where the
# step holds loops (PR 52; a scan whose carry a fusion rewrites read
# 23% short, one whose trips only stack read 0.1%:
# ``carried_anew_bytes`` is what the bodies compute anew for their
# next trip).
_HLO_ENTRY = re.compile(r'^ENTRY\s+%?([^\s(]+)', re.M)
_HLO_CALLED = re.compile(
    r'\b(condition|body|to_apply|true_computation|false_computation)'
    r'=%?([^\s,}]+)')
_HLO_BRANCHES = re.compile(r'\bbranch_computations=\{([^}]*)\}')
_HLO_OUTPUT_ALIASING = re.compile(r'\boutput_to_operand_aliasing=\{(.*?)\)\}')
_HLO_ALIAS_PAIR = re.compile(r'\{([\d, ]*)\}:\s*\((\d+),\s*\{([\d, ]*)\}')
_HLO_TUPLE_INDEX = re.compile(r'\bindex=(\d+)')
_HLO_COMMENT = re.compile(r'/\*.*?\*/')
_PASS_THROUGH = frozenset(['bitcast', 'opt-barrier', 'add-dependency',
                           'while', 'async-update', _UPDATED])
# the compiler's own copies: they carry no op_name of the program's
_COPIES = frozenset(['copy', 'copy-start', 'slice-start'])
# the compiler's allocation of what a loop fills trip by trip (the
# stacked residuals of a scan): it takes the op of its nearest reader
_ALLOCATE = 'custom_call_target="AllocateBuffer"'
_CALLER = object()      # a value that is the caller's (or an argument)
_ARGUMENT = object()    # an entry parameter: never written in place


@functools.lru_cache(maxsize=8192)
def _shape_tree(shape):
    """A shape's text -> a leaf's text, or a list of trees for a
    tuple."""
    shape = _HLO_COMMENT.sub('', shape).strip()
    if not shape.startswith('('):
        return shape
    parts, depth, layout, start = [], 0, 0, 1
    for i, c in enumerate(shape):
        if c in '{[':
            layout += 1
        elif c in '}]':
            layout -= 1
        elif layout:
            continue
        elif c == '(':
            depth += 1
        elif c == ')':
            depth -= 1
            if depth == 0:
                parts.append(shape[start:i])
        elif c == ',' and depth == 1:
            parts.append(shape[start:i])
            start = i + 1
    return [_shape_tree(p.strip()) for p in parts if p.strip()]


def _leaves(value):
    """The buffers of a value (a buffer, None, ``_CALLER`` or a nest of
    lists of them)."""
    if isinstance(value, list):
        for v in value:
            for leaf in _leaves(v):
                yield leaf
    elif isinstance(value, _Buffer):
        yield value


def _element(value, index):
    if isinstance(value, list):
        return value[index] if index < len(value) else None
    return value            # the caller's, or nothing: so are its parts


class _Buffer(object):
    __slots__ = ('bytes', 'elsewhere', 'shape', 'ins', 'scope', 'phase',
                 'born', 'last', 'reader', 'out')

    def __init__(self, nbytes, elsewhere, shape, ins, scoped, born):
        self.bytes, self.elsewhere = nbytes, elsewhere
        self.shape, self.ins, self.born = shape, ins, born
        self.scope, self.phase = scoped     # the fluid op and its pass
        self.last, self.reader, self.out = born, None, False


def _in_place_outputs(called):
    """{leaf index of a fusion's result (None: the whole of it):
    parameter number} for the leaves its computation writes in place: a
    ``dynamic-update-slice`` of a parameter, looked at through
    bitcasts."""
    by_name = {ins.name: ins for ins in called}
    roots = [ins for ins in called if ins.root] or called[-1:]
    if not roots:
        return {}

    def source(name, want):
        ins = by_name.get(name)
        while ins is not None and ins.opcode == 'bitcast':
            ins = by_name.get((_operand_names(ins) or [None])[0])
        return ins if ins is not None and ins.opcode == want else None

    def parameter(name):
        updated = source(name, _UPDATED)
        if updated is None:
            return None
        param = source((_operand_names(updated) or [None])[0], 'parameter')
        return int(param.operands) if param is not None and \
            param.operands.strip().isdigit() else None

    root = roots[0]
    outputs = dict(enumerate(_operand_names(root))) \
        if root.opcode == 'tuple' else {None: root.name}
    found = {leaf: parameter(name) for leaf, name in outputs.items()}
    return {leaf: n for leaf, n in found.items() if n is not None}


class _LiveWalk(object):
    """The walk of one module by the rule above."""

    def __init__(self, computations, scopes, passes, entry):
        self.computations = computations
        self.entry = entry
        self.scopes = scopes
        self.passes = passes
        self.optimizers = _optimizer_types()
        self.walking = None     # the computation value_of is asked in
        self.walked = {}        # computation -> (peak, point, [buffers])
        self.every = []         # every buffer the walk defined
        self.carried_anew = 0   # bytes the loops' bodies compute anew
        self.users = collections.defaultdict(list)  # name -> [readers]

    def define(self, tree, ins, index):
        if isinstance(tree, list):
            return [self.define(t, ins, index) for t in tree]
        everywhere = _nbytes(tree, True)
        if not everywhere:
            return None
        nbytes = _nbytes(tree)
        buf = _Buffer(nbytes, everywhere - nbytes, _plain(tree), ins.name,
                      self.scoped(ins.name), index)
        self.every.append(buf)
        return buf

    def scoped(self, name):
        """(fluid op, pass) of an instruction."""
        return self.scopes.get(name), self.passes.get(name)

    def called(self, ins):
        """[(the computation a control-flow instruction runs, whether
        as a loop's body)]."""
        names = [(m.group(2), m.group(1) == 'body')
                 for m in _HLO_CALLED.finditer(ins.attrs)]
        branches = _HLO_BRANCHES.search(ins.attrs)
        if branches:
            names += [(n.strip().lstrip('%'), False)
                      for n in branches.group(1).split(',') if n.strip()]
        return [(n, loop) for n, loop in names if n in self.computations]

    def value_of(self, ins, index, values):
        """What instruction ``ins`` gives: buffers it defines, or the
        operands' it passes on."""
        opcode = ins.opcode
        names = _operand_names(ins)
        operands = [values.get(n) for n in names]
        first = operands[0] if operands else None
        tree = _shape_tree(ins.shape)
        if opcode == 'parameter':
            return _ARGUMENT if self.walking == self.entry else _CALLER
        if opcode == 'constant':
            return None
        if opcode == 'tuple':
            return operands
        if opcode == 'get-tuple-element':
            m = _HLO_TUPLE_INDEX.search(ins.attrs)
            return _element(first, int(m.group(1))) if m else first
        if opcode in _PASS_THROUGH and not (
                opcode == _UPDATED and first is _ARGUMENT):
            return first
        if opcode.endswith('-done'):
            if opcode == 'copy-done':
                return _element(first, 0)
            if opcode == 'all-reduce-done':
                return first
            return _element(first, 1)
        if opcode.endswith('-start') and isinstance(tree, list) and \
                opcode != 'all-reduce-start':
            if opcode == 'copy-start':          # (copy, operand, context)
                return [self.define(tree[0], ins, index), first] + \
                    [None] * (len(tree) - 2)
            return [operands if isinstance(tree[0], list) else first] + \
                self.define(tree[1:], ins, index)   # (operands, result, ..)
        made = self.define(tree, ins, index)
        aliased = {}
        if opcode == 'fusion':
            aliased = _in_place_outputs(
                self.computations.get(ins.calls) or [])
        m = _HLO_OUTPUT_ALIASING.search(ins.attrs)
        if m:
            for out, operand, _inner in _HLO_ALIAS_PAIR.findall(m.group(0)):
                path = [int(i) for i in out.replace(' ', '').split(',') if i]
                aliased[path[0] if path else None] = int(operand)
        for leaf, n in aliased.items():
            if n >= len(operands) or operands[n] is _ARGUMENT:
                continue
            if leaf is None:
                self.forget(made)
                made = operands[n]
            elif isinstance(made, list) and leaf < len(made):
                self.forget(made[leaf])
                made[leaf] = operands[n]
        return made

    def copied_scope(self, ins, by_name):
        """The (fluid op, pass) of what a compiler's copy copies: that
        of the nearest instruction up its first operands that has an
        op."""
        for _ in range(16):
            ins = by_name.get((_operand_names(ins) or [None])[0])
            if ins is None:
                break
            if self.scopes.get(ins.name):
                return self.scoped(ins.name)
        return None, None

    def forget(self, value):
        for buf in _leaves(value):
            buf.bytes = buf.elsewhere = 0

    def walk(self, name, loop=False):
        """(peak bytes, the instruction it stands at, the buffers alive
        there, the bytes alive there in another memory space) of one
        computation, what it calls included; ``loop``: it is a
        ``while``'s body."""
        if name in self.walked:
            return self.walked[name]
        self.walked[name] = (0, None, [], 0)    # a cycle is not walked
        body = self.computations[name]
        self.walking = name
        by_name = {ins.name: ins for ins in body}
        values, local, inner = {}, [], {}
        for index, ins in enumerate(body):
            start = len(self.every)
            values[ins.name] = self.value_of(ins, index, values)
            for operand in _operand_names(ins):
                self.users[operand].append(ins)
            for buf in self.every[start:]:
                local.append(buf)
                if buf.scope is None and ins.opcode in _COPIES:
                    buf.scope, buf.phase = self.copied_scope(ins, by_name)
            if ins.opcode in ('tuple', 'get-tuple-element', 'bitcast',
                              'parameter', 'constant'):
                if not ins.root:
                    continue                    # an alias reads nothing
            live_out = ins.root and ins.opcode == 'tuple'
            for operand in _operand_names(ins):
                for buf in _leaves(values.get(operand)):
                    if live_out:
                        buf.out = True
                    else:
                        buf.last, buf.reader = index, ins
            if ins.root:
                for buf in _leaves(values[ins.name]):
                    buf.out = True
            callees = self.called(ins) if ins.opcode in (
                'while', 'conditional', 'call') else ()
            if callees:
                inner[index] = max((self.walk(c, is_body) for c, is_body
                                    in callees), key=lambda got: got[0])
                self.walking = name
        for buf in local:
            made_by = by_name[buf.ins]
            if buf.scope is None and made_by.opcode == 'custom-call' and \
                    _ALLOCATE in made_by.attrs:
                buf.scope, buf.phase = self.read_scope(made_by)
        if loop:
            # what the body computes anew for the next trip: by the
            # rule the carried buffer's memory, so no temporary; kept
            # apart because it is the first suspect where a program
            # with loops holds more than the walk finds
            self.carried_anew += sum(
                b.bytes for b in local if b.out and
                not by_name[b.ins].opcode.endswith('-start'))
        born, dying = {}, {}
        for buf in local:
            if buf.out or not (buf.bytes or buf.elsewhere):
                continue
            born.setdefault(buf.born, []).append(buf)
            dying.setdefault(buf.last, []).append(buf)
        live = peak = 0
        at = None
        for index in range(len(body)):
            live += sum(b.bytes for b in born.get(index, ()))
            here = live + (inner[index][0] if index in inner else 0)
            if here > peak:
                peak, at = here, index
            live -= sum(b.bytes for b in dying.get(index, ()))
        if at is None:
            return self.walked[name]
        there = [b for b in local if not b.out and b.born <= at <= b.last]
        alive = [b for b in there if b.bytes]
        elsewhere = sum(b.elsewhere for b in there)
        point = body[at].name
        if at in inner and inner[at][1]:
            alive += inner[at][2]
            elsewhere += inner[at][3]
            point = '%s > %s' % (point, inner[at][1])
        self.walked[name] = (peak, point, alive, elsewhere)
        return self.walked[name]

    def read_scope(self, reader):
        """The (fluid op, pass) that reads through ``reader``: its own,
        or where it has no op (a copy the compiler put in, an alias)
        that of the nearest reader of its result."""
        queue, seen = collections.deque([reader]), {reader.name}
        while queue and len(seen) < 64:
            ins = queue.popleft()
            if self.scopes.get(ins.name):
                return self.scoped(ins.name)
            for user in self.users.get(ins.name, ()):
                if user.name not in seen:
                    seen.add(user.name)
                    queue.append(user)
        return None, None

    def kind(self, buf):
        """A buffer's class, by the rule above."""
        if not buf.scope:
            return 'unscoped'
        op = buf.scope.split('/')[0]
        if op in self.optimizers:
            return 'optimizer'
        if buf.phase == 'recomputed':
            return 'recomputed'
        backward = op.endswith('_grad')
        reader = buf.reader
        read_by, read_in = self.read_scope(reader) if reader \
            else (None, None)
        read_op = read_by.split('/')[0] if read_by else ''
        if not backward and (read_op.endswith('_grad') or
                             read_in == 'recomputed'):
            return 'residual'
        if backward and reader is not None:
            if read_op in self.optimizers or read_op.startswith('c_') or \
                    _base_opcode(reader.opcode) in _COLLECTIVES:
                return 'gradient'
        return 'working'


def _optimizer_types():
    """The registered op types that update a parameter: those of
    ``ops/optimizer_ops.py``."""
    from ..ops import registry
    return frozenset(
        t for t, d in registry._REGISTRY.items()
        if getattr(getattr(d, 'fn', None), '__module__', '').endswith(
            'optimizer_ops'))


def _live_table(hlo_text, computations, scopes, passes, every=False):
    """The live table of one parsed module: where the sum of its live
    temporaries is largest and what is alive there.  ``every``: also
    every buffer the walk defined, under 'every' (tests)."""
    m = _HLO_ENTRY.search(hlo_text)
    if m is None or m.group(1) not in computations or \
            'is_scheduled=true' not in hlo_text[:4096]:
        return None     # no entry, or its order is not the schedule
    walk = _LiveWalk(computations, scopes, passes, m.group(1))
    peak, point, alive, elsewhere = walk.walk(m.group(1))
    where = (point or '').split(' > ')[-1]

    def row(buf):
        return {'bytes': buf.bytes, 'shape': buf.shape,
                'instruction': buf.ins, 'op': buf.scope,
                'class': walk.kind(buf)}

    buffers = sorted((row(b) for b in alive),
                     key=lambda r: (-r['bytes'], r['instruction']))
    by_class, by_op = collections.Counter(), collections.Counter()
    for r in buffers:
        by_class[r['class']] += r['bytes']
        by_op[r['op']] += r['bytes']
    table = {'bytes': peak, 'point': point, 'op': scopes.get(where),
             'by_class': dict(by_class), 'by_op': dict(by_op),
             'buffers': buffers,
             # alive at that point in another memory space (``S(1)``),
             # and what the loops' bodies compute anew for their next
             # trip: neither is in ``bytes``, both are where to look
             # when the compiler's figure is larger
             'elsewhere_bytes': elsewhere,
             'carried_anew_bytes': walk.carried_anew}
    if every:
        names = {name: [i.name for i in body]
                 for name, body in computations.items()}
        owner = {ins.name: comp for comp, body in computations.items()
                 for ins in body}
        table['every'] = [
            dict(row(b), born=b.ins, out=b.out,
                 dies=names[owner[b.ins]][b.last]) for b in walk.every
            if b.bytes]
    return table


# what one parse of a module gives
_Built = collections.namedtuple(
    '_Built', 'module scopes costs loops live passes')


def _tables(hlo_text, op_types=None, every_buffer=False):
    """One compiled module's optimised HLO text -> a ``_Built`` (module
    name, scope table, cost table, loop table, live table, pass table)
    from ONE parse; the scope, cost and pass tables hold every
    instruction a trace can name (those of fused computations are left
    out, their fusion stands for them), so ``pick_table`` picks the
    same program in each; the loop table holds those inside a
    differentiable loop's body (``loop_side``); the live table
    (``_live_table``) is the module's temporaries at their peak."""
    op_types = op_types or _registered_op_types()
    optimizers = _optimizer_types()
    module, computations = _parse_hlo(hlo_text)
    fused = {ins.calls for body in computations.values() for ins in body
             if ins.opcode == 'fusion'}
    scopes, costs, loops, passes = {}, {}, {}, {}
    for name, body in computations.items():
        if name in fused:
            continue
        shapes = {ins.name: ins.shape for ins in body}
        for ins in body:
            called = computations.get(ins.calls)
            if ins.opcode == 'fusion' and called is not None:
                decider = _fusion_decider(ins, called, op_types)
                side = _fusion_loop_side(ins, called)
            else:
                decider = ins
                side = loop_side(ins.op_name)
            scopes[ins.name], passes[ins.name] = _scope_and_pass(
                decider.op_name, op_types, optimizers)
            if side:
                loops[ins.name] = side
            costs[ins.name] = _instruction_cost(ins, shapes, called)
    return _Built(module, scopes, costs, loops, _live_table(
        hlo_text, computations, scopes, passes, every_buffer), passes)


def hlo_scopes(hlo_text, op_types=None):
    """One compiled module's optimised HLO text (``Compiled.as_text()``)
    -> (module name, {instruction name: fluid scope or None}) for every
    instruction a trace can name: those of fused computations are left
    out, their fusion stands for them."""
    return _tables(hlo_text, op_types)[:2]


def hlo_costs(hlo_text):
    """The same text -> (module name, {instruction name: Cost or None})
    for the same instructions, by the cost rule above."""
    module, _scopes, costs = _tables(hlo_text)[:3]
    return module, costs


def hlo_live(hlo_text, every=False):
    """The same text -> (module name, the live table: the module's
    temporaries where their sum is largest, by the rule above;
    ``every``: with every buffer the walk defined, where it is born
    and where it dies)."""
    built = _tables(hlo_text, every_buffer=every)
    return built.module, built.live


def _held_tables():
    """The ``_Built`` of every executable this process holds.  The
    compile plane keeps what ``_tables`` made of an executable while it
    holds it: each is printed and parsed once, whichever table is asked
    for first and however often."""
    return [built for _key, built in _keyed_tables()]


def _keyed_tables():
    from . import compile_cache
    return compile_cache.plane().held_tables(_tables)


def _by_module(field):
    tables = {}
    for built in _held_tables():
        tables.setdefault(built.module, []).append(getattr(built, field))
    return tables


def scope_tables():
    """{HLO module name: [table, ...]} (tables as ``hlo_scopes`` gives
    them) of every executable this process holds
    (``CompilePlane.held_tables``).  Built when asked for and at no
    other time: it prints and parses whole modules, seconds at
    BERT-base.  Two programs of one name (a segment planned for two
    fetch lists) keep a table each; ``pick_table`` tells them apart."""
    return _by_module('scopes')


def cost_tables():
    """{HLO module name: [table, ...]} (tables as ``hlo_costs`` gives
    them), beside ``scope_tables()`` and from the same parse."""
    return _by_module('costs')


def loop_tables():
    """{HLO module name: [table, ...]} beside ``scope_tables()`` and
    from the same parse: {instruction name: 'forward' | 'backward'} for
    the instructions inside the bodies of the program's differentiable
    loops (``loop_side``); a module without one has an empty
    table."""
    return _by_module('loops')


def pass_tables():
    """{HLO module name: [table, ...]} beside ``scope_tables()`` and
    from the same parse: {instruction name: 'forward' | 'recomputed' |
    'backward' | None} for the same instructions, by the pass rule
    above (``fluid_pass``).  Built when asked for and at no other time,
    as the others."""
    return _by_module('passes')


def live_tables():
    """{the compile plane's key of the executable: (HLO module name,
    table)} beside ``scope_tables()`` and from the same parse: each
    program's temporaries where their sum is largest (``hlo_live``:
    the point, the sum, and every buffer alive there with its bytes,
    shape, defining instruction, fluid op and class); the table is
    None for a module whose text names no entry computation or is not
    scheduled.  By executable and not by module name, because
    fluid.memviz's rows, which carry it, are filed by executable."""
    return {key: (built.module, built.live)
            for key, built in _keyed_tables()}


def pick_table(candidates, instruction_names):
    """Of the tables of same-named modules, the one that knows most of
    the instructions one run of the module executed and, among equals,
    holds the fewest others ({} for none)."""
    if not candidates:
        return {}
    if len(candidates) == 1:
        return candidates[0]
    names = set(instruction_names)
    return max(candidates,
               key=lambda t: (len(names.intersection(t)), -len(t)))


# a module run is named "<module name>(<program id>)"
_PROGRAM_ID = re.compile(r'\(\d+\)$')
_MODULE_LINE = 'XLA Modules'


def module_runs(plane):
    """Sorted [(start ns, end ns, name)] of the module runs of one
    device plane of a trace (its 'XLA Modules' line; the name is the
    module's with the program id, ``jit_segment_x(12)``); [] where the
    plane has no such line."""
    return sorted(
        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
        for line in plane.lines if line.name == _MODULE_LINE
        for ev in line.events)


def program_at(runs, t):
    """The name of the module run (``module_runs``) that holds the
    instant ``t``; '' for none."""
    i = bisect.bisect_right(runs, (t, float('inf'), '')) - 1
    return runs[i][2] if i >= 0 and t <= runs[i][1] else ''


def instruction_scopes(ops, tables):
    """[(program, instruction name)] of executed instructions -> their
    fluid scopes (None for none), in order.  ``program`` is the name of
    the module run the instruction ran in (``program_at``) or a
    module's bare name: two programs of one module name, the quiet step
    and the one that fetches, hold a ``fusion.933`` each, so the
    instructions of each program are looked up in the one table
    (``pick_table``) of that module name that knows most of them.
    Where the program is not known ('': a trace without module runs)
    every table of ``tables`` is a candidate."""
    by_program = {}
    for i, (program, _name) in enumerate(ops):
        by_program.setdefault(program, []).append(i)
    found = [None] * len(ops)
    for program, indices in by_program.items():
        candidates = tables.get(_PROGRAM_ID.sub('', program))
        if candidates is None and not program:
            candidates = [t for ts in tables.values() for t in ts]
        table = pick_table(candidates, {ops[i][1] for i in indices})
        for i in indices:
            found[i] = table.get(ops[i][1])
    return found


def instruction_costs(ops, tables):
    """The same walk over ``cost_tables()``: -> the instructions' Costs
    (None for none).  Both tables of a program hold the same
    instructions, so both walks pick the same program."""
    return instruction_scopes(ops, tables)


# ---------------------------------------------------- reading the trace
_DEVICE_PLANE = re.compile(r'^/device:[A-Za-z]+:\d+$')
_TRACED_INSTRUCTION = re.compile(r'^%?(\S+) = ')


def _newest_xplane(logdir):
    import glob
    paths = glob.glob(os.path.join(logdir, '**', '*.xplane.pb'),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _self_durations(events):
    """[(start, duration)] of one trace line -> the part of each
    duration no later-starting event of the line covers (a ``while``
    covers its body's ops; durations must not be summed)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [d for _s, d in events]
    stack = []                      # indices of the open nest
    for i in order:
        start, dur = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] \
                <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(0.0, d) for d in own]


def load_trace_events(logdir):
    """The newest ``.xplane.pb`` under ``logdir`` (what jax.profiler
    writes on this runtime) as chrome-trace 'X' events, times in us:
    one pid per plane, one tid per line.  An event that is an executed
    HLO instruction (a TPU plane's 'XLA Ops' line, or a CPU thunk with
    an ``hlo_op`` stat) carries ``args['tf_op']``: the fluid scope
    ``scope_tables()`` gives it, else the module's name,
    ``self_dur`` where other events of its line nest inside it, and
    ``args['pass']`` where ``pass_tables()`` gives it one, and what
    ``cost_tables()`` knows of it: ``args['kind']`` (the opcode held),
    ``args['shapes']``, ``args['mb']`` and, unless it is a custom call,
    ``args['gflop']`` and the rates over its ``dur``,
    ``args['tflops']`` and ``args['gbps']``."""
    path = _newest_xplane(logdir)
    if path is None:
        return []
    from jax.profiler import ProfileData
    tables = scope_tables(), cost_tables(), pass_tables()
    out = []
    for pid, plane in enumerate(ProfileData.from_file(path).planes):
        out.append({'ph': 'M', 'pid': pid, 'name': 'process_name',
                    'args': {'name': plane.name}})
        device = bool(_DEVICE_PLANE.match(plane.name))
        lines = list(plane.lines)
        runs = module_runs(plane) if device else []
        for tid, line in enumerate(lines):
            op_line = device and line.name == 'XLA Ops'
            rows = []
            for ev in line.events:
                row = {'ph': 'X', 'pid': pid, 'tid': tid,
                       'name': ev.name, 'ts': ev.start_ns / 1e3,
                       'dur': ev.duration_ns / 1e3}
                if op_line:
                    m = _TRACED_INSTRUCTION.match(ev.name)
                    row['name'] = m.group(1) if m else ev.name
                    row['module'] = program_at(runs, ev.start_ns)
                elif not device:
                    stats = dict(ev.stats)
                    if 'hlo_op' in stats:
                        row['name'] = str(stats['hlo_op'])
                        row['module'] = str(stats.get('hlo_module', ''))
                rows.append(row)
            _attach_scopes([r for r in rows if 'module' in r], tables)
            out.extend(rows)
    return out


def _attach_scopes(rows, tables):
    """Give the instruction events of one line their ``tf_op``, their
    pass, their cost and their ``self_dur``; ``tables`` are (scope
    tables, cost tables, pass tables)."""
    if not rows:
        return
    programs = [row.pop('module') for row in rows]
    ops = [(p, row['name']) for p, row in zip(programs, rows)]
    found = zip(rows, programs, *(instruction_scopes(ops, t)
                                  for t in tables))
    for row, program, scope, cost, phase in found:
        args = row['args'] = {
            'tf_op': scope or _PROGRAM_ID.sub('', program) or
            'unknown_module'}
        if phase:
            args['pass'] = phase
        if cost is None:
            continue
        args['kind'] = cost.kind
        args['shapes'] = cost.shapes
        args['mb'] = cost.bytes / 1e6
        if cost.flops is not None:
            args['gflop'] = cost.flops / 1e9
            if row['dur'] > 0:          # us: MB / us is TB/s
                args['gbps'] = args['mb'] / row['dur'] * 1e3
                args['tflops'] = args['gflop'] / row['dur'] * 1e3
    for row, own in zip(rows, _self_durations(
            [(r['ts'], r['dur']) for r in rows])):
        if own != row['dur']:
            row['self_dur'] = own


def _attach_span_tracer():
    """Auto-attach the fluid.trace span tracer to a starting device
    capture, and emit the paired clock-sync annotation (the device
    trace records 'pt_clock_sync' on ITS clock while the tracer notes
    the host epoch-us — tools/timeline.py merges on that offset)."""
    from . import trace as trace_mod
    trace_mod.attach_capture()
    try:
        with jax.profiler.TraceAnnotation('pt_clock_sync'):
            trace_mod.mark_clock_sync()
    except Exception:
        pass


def start_profiler(state='All', tracer_option='Serial'):
    """Enable profiling (reference EnableProfiler).  `state` kept for
    API parity; on TPU there is no CPU/GPU split to select.
    tracer_option='Serial' re-segments per op and host-times each;
    'Default' captures a device trace of the PRODUCTION program and
    attributes kernels back to ops on stop (reference DeviceTracer)."""
    global _enabled, _mode, _prof_trace_dir
    if state not in ('CPU', 'GPU', 'All'):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    if tracer_option not in ('Serial', 'Default', 'OpDetail',
                             'AllOpDetail'):
        raise ValueError('unknown tracer_option %r' % (tracer_option,))
    reset_profiler()
    if _prof_trace_dir is not None:
        # a 'Default' capture is still active (start called twice /
        # mode switch without stop): close it or the device trace runs
        # forever and the next start_trace raises
        import shutil
        from . import trace as trace_mod
        try:
            jax.profiler.stop_trace()
        finally:
            # drop the rider, restore its state — even when the jax
            # stop raises, or the tracer stays force-enabled forever
            trace_mod.detach_capture()
        shutil.rmtree(_prof_trace_dir, ignore_errors=True)
        _prof_trace_dir = None
    _mode = 'Serial' if tracer_option == 'Serial' else 'Default'
    if _mode == 'Default':
        import tempfile
        _prof_trace_dir = tempfile.mkdtemp(prefix='pt_prof_')
        jax.profiler.start_trace(_prof_trace_dir)
        # one capture yields host AND device events: the span tracer
        # rides along so stop_profiler can write the merged timeline
        _attach_span_tracer()
    _enabled = True


def _fold_into_monitor():
    """Fold the per-op table into the always-on stats registry under
    'profiler/<op>/…' keys, so one monitor.snapshot()/dump_jsonl()
    carries BOTH the cheap counters and the last profile's per-op
    accounting (the reference keeps StatRegistry and the profiler
    side by side; here they meet at stop time)."""
    global _folded
    if _folded:
        # a second stop_profiler (defensive stop, re-reading the
        # returned table) must not re-add the same cumulative records
        return
    _folded = True
    from . import monitor
    for t, (c, tot, mx, mn) in _records.items():
        # 'unattributed/<hlo>' buckets carry '/' — keep them one level
        safe = t.replace('/', ':')
        monitor.add('profiler/%s/calls' % safe, float(c))
        monitor.add('profiler/%s/total_seconds' % safe, tot)


def stop_profiler(sorted_key='total', profile_path=None):
    """Disable profiling and print the sorted per-op table (reference
    DisableProfiler).  profile_path, when given, receives the table as
    a text file — and, after a 'Default' (device-trace) profile, the
    MERGED host+device chrome-trace timeline lands next to it as
    '<table path>.timeline.json' (a directory profile_path gets
    'profile_summary.txt' + 'profile_summary.txt.timeline.json'
    inside), so one profile yields both the table and the step
    timeline.  Returns the table string, folds the per-op records
    into fluid.monitor under 'profiler/…' keys, and resets the tracer
    mode to 'Serial' so a later bare start_profiler()/is_enabled()
    sequence never inherits a stale 'Default' trace mode."""
    global _enabled, _mode, _prof_trace_dir
    _enabled = False
    device_events = []
    host_cap = None
    if _mode == 'Default' and _prof_trace_dir is not None:
        import shutil
        from . import trace as trace_mod
        try:
            jax.profiler.stop_trace()
        finally:
            # detach even when the jax stop raises, or the attached
            # capture keeps recording (and buffering) forever
            host_cap = trace_mod.detach_capture()
        device_events = load_trace_events(_prof_trace_dir)
        recs, stats = attribute_trace_events(
            [e for e in device_events if 'args' in e], with_stats=True,
            costs=_costs, passes=_passes)
        _records.update(recs)
        if stats['dropped']:
            # malformed capture rows are counted, not silently eaten
            from . import monitor as _monitor
            _monitor.add('profiler/dropped_events',
                         float(stats['dropped']))
        shutil.rmtree(_prof_trace_dir, ignore_errors=True)
        _prof_trace_dir = None
    _mode = 'Serial'
    _fold_into_monitor()
    table = summary_string(sorted_key)
    print(table)
    if profile_path:
        if os.path.isdir(profile_path) or profile_path.endswith(os.sep):
            # pre-round-4 callers passed a trace DIRECTORY here; keep
            # them working by dropping the table inside it
            os.makedirs(profile_path, exist_ok=True)
            profile_path = os.path.join(profile_path,
                                        'profile_summary.txt')
        d = os.path.dirname(profile_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(profile_path, 'w') as f:
            f.write(table + '\n')
        if host_cap is not None:
            from . import trace as trace_mod
            merged = trace_mod.merge_device_trace(
                trace_mod.chrome_events(host_cap['events']),
                device_events, sync_host_us=host_cap['sync_us'],
                capture_t0_us=host_cap['t0_us'])
            trace_mod.write_chrome(profile_path + '.timeline.json',
                                   merged)
    return table


@contextlib.contextmanager
def profiler(state='All', sorted_key='total',
             profile_path='/tmp/profile.txt', tracer_option='Serial'):
    """Profiling scope.  tracer_option='Serial': ops run
    one-per-segment and host-timed (op-granular, but an unfused
    program).  'Default': the production program runs untouched under
    a device-trace capture, kernels attributed back to ops.  On exit
    the sorted table prints (and lands in profile_path)."""
    start_profiler(state, tracer_option=tracer_option or 'Serial')
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    yield


def start_trace(logdir='/tmp/profile'):
    """Device-trace capture (Perfetto/XPlane) — the DeviceTracer leg.
    The fluid.trace span tracer auto-attaches, so ONE capture yields
    host phase spans AND device kernels; stop_trace writes the host
    side as 'host_trace.json' next to the device dump and
    tools/timeline.py merges the two into one Perfetto file.

    Like start_profiler, double-starts fail with a clear error instead
    of jax's raw 'profiler already started' (only one device trace can
    run per process, and a 'Default' profile capture owns it too)."""
    global _trace_path
    if _trace_path is not None:
        raise RuntimeError(
            'a trace capture is already active (logdir %r): call '
            'stop_trace() before starting another' % (_trace_path,))
    if _prof_trace_dir is not None:
        raise RuntimeError(
            "a profiler capture (tracer_option='Default') owns the "
            'device tracer: call stop_profiler() before start_trace()')
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _trace_path = logdir
    _attach_span_tracer()


def stop_trace():
    """Stop the device capture; returns the logdir.  The capture's
    ``.xplane.pb`` is read back (``load_trace_events``): its per-op
    device time becomes the profiler's table (``summary_records()`` /
    ``summary_string()``, with each row's FLOPs, bytes and achieved
    rates where ``cost_tables()`` knows them), and its events persist as
    '<logdir>/device.trace.json' beside the attached span tracer's
    host events, '<logdir>/host_trace.json', for the timeline merger
    (tools/timeline.py)."""
    global _trace_path
    from . import trace as trace_mod
    try:
        jax.profiler.stop_trace()
    finally:
        # detach even when the jax stop raises (trace already stopped
        # by code driving jax.profiler directly), or the rider stays
        # force-enabled and its capture buffer grows unboundedly
        host_cap = trace_mod.detach_capture()
    path, _trace_path = _trace_path, None
    if path is None:
        return path
    device_events = load_trace_events(path)
    reset_profiler()
    _records.update(attribute_trace_events(
        [e for e in device_events if 'args' in e], costs=_costs,
        passes=_passes))
    try:
        trace_mod.write_chrome(os.path.join(path, 'device.trace.json'),
                               device_events)
        if host_cap is not None:
            trace_mod.write_host_trace(
                os.path.join(path, 'host_trace.json'), host_cap)
    except OSError:
        pass  # read-only logdir: the .xplane.pb is still usable
    return path


record_event = jax.profiler.TraceAnnotation
