"""append_backward: symbolic reverse-mode autodiff over the Program IR.

Reference: python/paddle/fluid/backward.py:1023 (append_backward) which
asks C++ per-op GradOpDescMakers (core.get_grad_op_desc, backward.py:876)
for hand-written grad ops and inserts sum ops for gradient aggregation.

TPU-native re-design: grad ops are synthesized — for forward op `foo`, op
`foo_grad` takes the same primal inputs plus 'GRAD::<out_slot>' cotangent
slots and its lowering calls jax.vjp over foo's lowering
(ops/registry.py grad_op_def).  No per-op gradient code exists anywhere.
Aggregation (a var consumed by N ops) still inserts an explicit `sum` op,
matching the reference's semantics; XLA fuses it away.
"""

from collections import defaultdict

import numpy as np

from . import framework
from .framework import Parameter, grad_var_name


def _is_float_dtype(dtype):
    return str(dtype) in ('float16', 'bfloat16', 'float32', 'float64')


def _creates_grad(var):
    return _is_float_dtype(var.dtype) and not var.stop_gradient


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """Returns [(param, grad_var), ...]. Single-block programs for now
    (control-flow sub-blocks are lowered inside their parent op)."""
    program = loss.block.program
    block = program.global_block()
    no_grad_set = set(no_grad_set or [])
    # recorded for the whole-program-grad executor mode: jax.vjp over
    # the forward region must treat these names as constants exactly
    # like this pruning pass does (executor._wpg_partition)
    program._backward_no_grad_names = set(getattr(
        program, '_backward_no_grad_names', ())) | no_grad_set
    with program._role_guard('backward'):
        return _append_backward_impl(loss, program, block, parameter_list,
                                     no_grad_set, callbacks, checkpoints)


def _append_backward_impl(loss, program, block, parameter_list,
                          no_grad_set, callbacks, checkpoints):

    loss_idx = None
    for i in range(len(block.ops) - 1, -1, -1):
        if loss.name in block.ops[i].output_arg_names:
            loss_idx = i
            break
    if loss_idx is None:
        raise ValueError('loss %s is not produced in this program'
                         % loss.name)

    # contributions: var name -> list of grad var names
    contribs = defaultdict(list)

    # seed d(loss) = 1
    loss_grad = block.create_var(
        name=grad_var_name(loss.name), shape=loss.shape, dtype=loss.dtype,
        persistable=False)
    block.append_op(
        'fill_constant', outputs={'Out': loss_grad},
        attrs={'shape': list(loss.shape), 'dtype': loss.dtype,
               'value': 1.0})
    contribs[loss.name].append(loss_grad.name)

    def resolve_grad(name):
        """Collapse accumulated contributions into <name>@GRAD."""
        lst = contribs.get(name)
        if not lst:
            return None
        target = grad_var_name(name)
        if len(lst) == 1:
            return lst[0]
        if not block.has_var(target):
            src = block._find_var_recursive(name)
            tv = block.create_var(name=target,
                                  shape=src.shape if src else (),
                                  dtype=src.dtype if src else 'float32')
            tv.stop_gradient = True
        block.append_op('sum', inputs={'X': list(lst)},
                        outputs={'Out': target}, infer_shape=False)
        contribs[name] = [target]
        return target

    checkpoint_names = set(v.name if isinstance(v, framework.Variable)
                           else v for v in (checkpoints or []))

    recompute = None
    if checkpoint_names:
        recompute = _RecomputePlan(block, block.ops[:loss_idx + 1],
                                   checkpoint_names, loss.name)

    for op in reversed(block.ops[:loss_idx + 1]):
        rename = {}
        if recompute is not None:
            rename = recompute.activations_for(op)
        if not _op_backward(block, op, contribs, resolve_grad, no_grad_set,
                            rename):
            continue

    # resolve every accumulated grad and publish the name map so callers
    # (OpTest, calc_gradient, AMP) can find grads of arbitrary vars
    grad_map = {}
    for name in list(contribs.keys()):
        g = resolve_grad(name)
        if g is not None:
            grad_map[name] = g
    if not hasattr(program, '_grad_name_map'):
        program._grad_name_map = {}
    program._grad_name_map.update(grad_map)

    params_grads = []
    wanted = None
    if parameter_list is not None:
        wanted = set(p.name if isinstance(p, framework.Variable) else p
                     for p in parameter_list)
    for p in block.all_parameters():
        if not p.trainable or p.name in no_grad_set:
            continue
        if wanted is not None and p.name not in wanted:
            continue
        g = resolve_grad(p.name)
        if g is None:
            continue
        gv = block._find_var_recursive(g)
        params_grads.append((p, gv))
    return params_grads


class _RecomputePlan(object):
    """Activation checkpointing by program rewrite — the TPU-native
    version of the reference's recompute backward
    (python/paddle/fluid/backward.py:618
    _append_backward_ops_with_checkpoints_):

    Forward ops are split into spans at checkpoint-producing ops.  When
    the backward walk enters a span, the span's forward ops are
    re-emitted reading the span's external inputs through a
    `recompute_barrier` (jax.lax.optimization_barrier — stops XLA from
    CSE-ing the recomputation against the original forward, which is
    what actually frees the activation memory), writing renamed
    `<name>@RC` outputs; grad ops of that span then read the recomputed
    activations instead of the originals.
    """

    def __init__(self, block, fwd_ops, checkpoint_names, loss_name):
        from ..ops import registry
        self.block = block
        produced = set()
        for op in fwd_ops:
            produced.update(op.output_arg_names)
        # stable names are free to read anywhere: params/persistables
        # and anything not produced by the forward ops (feeds, startup)
        self.stable = set()
        for op in fwd_ops:
            for n in op.input_arg_names:
                if n not in produced:
                    self.stable.add(n)
                else:
                    v = block._find_var_recursive(n)
                    if v is not None and getattr(v, 'persistable', False):
                        self.stable.add(n)
        keep = set(checkpoint_names) | {loss_name}

        # span assignment: a new span starts after an op that produces
        # a checkpoint
        self.span_of = {}
        self.spans = []
        cur = []
        for op in fwd_ops:
            if op.type in registry.HOST_OPS:
                continue
            cur.append(op)
            self.span_of[id(op)] = len(self.spans)
            if any(n in keep for n in op.output_arg_names):
                self.spans.append(cur)
                cur = []
        if cur:
            self.spans.append(cur)
        self.keep = keep
        self._emitted = {}  # span idx -> rename map

    def activations_for(self, op):
        """Rename map for the span containing `op`, emitting the span's
        recompute ops on first use (the backward walk reaches the span's
        last op first, so recomputation lands just before its grads)."""
        s = self.span_of.get(id(op))
        if s is None:
            return {}
        span_ops = self.spans[s]
        if len(span_ops) <= 1:
            return {}  # nothing to recompute: grads re-derive one op
        if s in self._emitted:
            return self._emitted[s]
        rename = {}
        span_produced = set()
        for f in span_ops:
            span_produced.update(f.output_arg_names)
        # barrier the span's non-stable external activation inputs
        for f in span_ops:
            for n in f.input_arg_names:
                if n in rename or n in span_produced or n in self.stable:
                    continue
                self._mk_var(n, n + '@RCIN')
                self.block.append_op(
                    'recompute_barrier', inputs={'X': [n]},
                    outputs={'Out': [n + '@RCIN']}, infer_shape=False)
                rename[n] = n + '@RCIN'
        # re-emit the span's forward ops with renamed outputs (keep
        # outputs stay materialized: their @RC twin is dead code)
        for f in span_ops:
            ins = {slot: [rename.get(n, n) for n in names]
                   for slot, names in f.inputs.items()}
            outs = {}
            for slot, names in f.outputs.items():
                row = []
                for n in names:
                    rc = n + '@RC'
                    self._mk_var(n, rc)
                    if n not in self.keep:
                        rename[n] = rc
                    row.append(rc)
                outs[slot] = row
            attrs = dict(f.attrs)
            attrs['__op_role__'] = 'backward'
            self.block.append_op(f.type, inputs=ins, outputs=outs,
                                 attrs=attrs, infer_shape=False)
        self._emitted[s] = rename
        return rename

    def _mk_var(self, src_name, new_name):
        if self.block.has_var(new_name):
            return
        v = self.block._find_var_recursive(src_name)
        nv = self.block.create_var(
            name=new_name, shape=v.shape if v is not None else (),
            dtype=v.dtype if v is not None else 'float32')
        nv.stop_gradient = True


def _op_backward(block, op, contribs, resolve_grad, no_grad_set,
                 rename=None):
    rename = rename or {}
    if op.type in ('while', 'conditional_block'):
        # would the loop/branch need a gradient?  The op's declared
        # outputs can be empty (conditional_block discovers its writes
        # at lowering time), so inspect the sub-block's writes too.
        out_names = set(op.output_arg_names)

        def _collect(sub_idx, seen):
            if sub_idx is None or sub_idx in seen:
                return
            seen.add(sub_idx)
            for sop in block.program.blocks[sub_idx].ops:
                out_names.update(sop.output_arg_names)
                _collect(sop.attrs.get('sub_block'), seen)

        _collect(op.attrs.get('sub_block'), set())
        needs = any(contribs.get(n) for n in out_names)
        if needs:
            return _control_flow_backward(block, op, contribs,
                                          resolve_grad, no_grad_set)
        return False
    from ..ops import registry
    if op.type in registry.HOST_OPS:
        return False
    # gather available output grads
    grad_in = {}
    any_grad = False
    for slot, names in op.outputs.items():
        row = []
        need = False
        for n in names:
            if contribs.get(n):
                need = True
        if not need:
            continue
        for n in names:
            g = resolve_grad(n)
            if g is None:
                # sibling output without grad: zeros placeholder keeps
                # positional alignment within the slot
                v = block._find_var_recursive(n)
                z = block.create_var(
                    name=framework.unique_name.generate(n + '@ZERO'),
                    shape=v.shape, dtype=v.dtype)
                block.append_op('fill_zeros_like',
                                inputs={'X': rename.get(n, n)},
                                outputs={'Out': z})
                g = z.name
            row.append(g)
        grad_in['GRAD::' + slot] = row
        any_grad = True
    if not any_grad:
        return False

    # does any input need a gradient?
    in_vars = []
    for slot, names in op.inputs.items():
        for n in names:
            v = block._find_var_recursive(n)
            in_vars.append((slot, n, v))
    if not any(v is not None and _creates_grad(v) and n not in no_grad_set
               for (_, n, v) in in_vars):
        return False

    grad_inputs = {slot: [rename.get(n, n) for n in names]
                   for slot, names in op.inputs.items()}
    grad_inputs.update(grad_in)
    grad_outputs = {}
    for slot, names in op.inputs.items():
        row = []
        for n in names:
            v = block._find_var_recursive(n)
            gname = framework.unique_name.generate(grad_var_name(n))
            gv = block.create_var(name=gname,
                                  shape=v.shape if v else (),
                                  dtype=v.dtype if v else 'float32')
            gv.stop_gradient = True
            row.append(gname)
            if v is not None and _creates_grad(v) and n not in no_grad_set:
                contribs[n].append(gname)
        grad_outputs['GRAD::' + slot] = row
    attrs = dict(op.attrs)
    # the grad op inherits the forward op's attrs (incl. __op_seed__, so
    # e.g. dropout regenerates the same mask) but NOT its role
    attrs['__op_role__'] = 'backward'
    block.append_op(op.type + '_grad', inputs=grad_inputs,
                    outputs=grad_outputs, attrs=attrs,
                    infer_shape=False)
    return True


def _control_flow_backward(block, op, contribs, resolve_grad, no_grad_set):
    """Differentiate a while / conditional_block op.

    TPU-native analog of the reference's WhileGradOp
    (/root/reference/paddle/fluid/operators/controlflow/while_op.cc) and
    ConditionalBlockGradOp (conditional_block_op.cc).  Instead of
    replaying saved step scopes, a loop runs as a bounded, masked
    lax.scan (reverse-differentiable, hence the max_trip_count
    requirement) whose residuals are the scan's own, and its forward
    runs ONCE a step: the whole-program vjp differentiates the scan in
    place and lowers no grad op; on the per-op path the forward op
    takes its scan under jax.vjp and keeps the vjp for the grad op of
    the same trace.  Only a grad op cut into another segment than its
    forward op re-runs the sub-block from the ENTRY values of the loop
    state, which the forward op saves; a branch's grad op always does
    (lax.cond, one pass of the body at most).  See
    executor._lower_while / _lower_while_grad /
    _lower_conditional_block_grad.
    """
    is_while = op.type == 'while'
    if is_while and int(op.attrs.get('max_trip_count') or 0) <= 0:
        # unbounded trip count: AUTO-BUCKET.  The executor cuts the
        # program before this op, runs a cheap counting pass
        # (non-differentiable lax.while_loop) on the concrete carries,
        # rounds the count to the next power of two, and compiles the
        # masked-scan rendering at that bucket — one executable per
        # bucket, O(log trips) recompiles, the bucketing-loader recipe
        # applied to control flow.  The reference's WhileGradOp gets
        # dynamic trips by replaying saved step scopes
        # (operators/controlflow/while_op.cc); a shape-static compiler
        # buys the same with buckets.
        op.attrs['__auto_bucket__'] = True
        op.attrs['__bucket_group__'] = framework.unique_name.generate(
            'while_bucket')
    carry_names = list(op.output('Out'))
    cond_slot = 'Condition' if is_while else 'Cond'
    cond_name = op.input(cond_slot)[0]
    if is_while and cond_name not in carry_names:
        carry_names.append(cond_name)

    float_carries = []
    for n in carry_names:
        v = block._find_var_recursive(n)
        if v is not None and _is_float_dtype(v.dtype):
            float_carries.append(n)

    # cotangents for the post-op values of the float carries; consuming
    # them resets the var's contribution list — producers BEFORE the op
    # get the entry-grad appended below instead
    cot_row = []
    for n in float_carries:
        g = resolve_grad(n)
        if g is None:
            v = block._find_var_recursive(n)
            z = block.create_var(
                name=framework.unique_name.generate(n + '@ZERO'),
                shape=v.shape, dtype=v.dtype)
            z.stop_gradient = True
            block.append_op('fill_zeros_like', inputs={'X': n},
                            outputs={'Out': z}, infer_shape=False)
            g = z.name
        cot_row.append(g)
        contribs[n] = []

    # entry vars: the forward op re-declares them as outputs and its
    # lowering stashes the pre-loop carry values there (__needs_grad__)
    entry_row = []
    for n in carry_names:
        v = block._find_var_recursive(n)
        en = framework.unique_name.generate(n + '@CF_ENTRY')
        ev = block.create_var(name=en, shape=v.shape if v else (),
                              dtype=v.dtype if v else 'float32')
        ev.stop_gradient = True
        entry_row.append(en)
    op.attrs['__needs_grad__'] = True
    op.attrs['__carry_names__'] = list(carry_names)
    op.attrs['__entry_names__'] = list(entry_row)
    op.outputs['Entry'] = list(entry_row)

    # closure reads: declared X values the sub-block only reads
    # (parameters etc.) — unchanged after the op, so read by name
    closure = []
    for n in op.input('X'):
        if n in carry_names or n in closure:
            continue
        v = block._find_var_recursive(n)
        if v is not None and _creates_grad(v) and n not in no_grad_set:
            closure.append(n)

    entry_grad_row = []
    for n in float_carries:
        gname = framework.unique_name.generate(grad_var_name(n))
        v = block._find_var_recursive(n)
        gv = block.create_var(name=gname, shape=v.shape, dtype=v.dtype)
        gv.stop_gradient = True
        entry_grad_row.append(gname)
        if _creates_grad(v) and n not in no_grad_set:
            contribs[n].append(gname)
    closure_grad_row = []
    for n in closure:
        gname = framework.unique_name.generate(grad_var_name(n))
        v = block._find_var_recursive(n)
        gv = block.create_var(name=gname, shape=v.shape, dtype=v.dtype)
        gv.stop_gradient = True
        closure_grad_row.append(gname)
        contribs[n].append(gname)

    # the forward op can then take its scan under jax.vjp itself
    # (executor._lower_while keep_vjp)
    op.attrs['__float_carries__'] = list(float_carries)
    op.attrs['__closure_names__'] = list(closure)
    grad_inputs = {'X': list(op.input('X')), cond_slot: [cond_name],
                   'Entry': list(entry_row), 'GRAD::Out': cot_row}
    attrs = {'sub_block': op.attrs['sub_block'],
             '__carry_names__': list(carry_names),
             '__float_carries__': list(float_carries),
             '__closure_names__': list(closure),
             '__op_role__': 'backward'}
    if is_while:
        if op.attrs.get('__auto_bucket__'):
            # the executor's counting pass sets max_trip_count on every
            # op of the group (forward while + this grad) per step
            attrs['__bucket_group__'] = op.attrs['__bucket_group__']
        else:
            attrs['max_trip_count'] = int(op.attrs['max_trip_count'])
    block.append_op(op.type + '_grad', inputs=grad_inputs,
                    outputs={'GRAD::Entry': entry_grad_row,
                             'GRAD::X': closure_grad_row},
                    attrs=attrs, infer_shape=False)
    return True


def recompute_guard(program=None):
    """Context manager: the ops appended inside are ONE group whose
    intermediate values the backward pass computes again from the
    group's inputs instead of keeping them (``jax.checkpoint`` around
    the group's lowering).  It takes effect where jax differentiates
    the lowering itself: the whole-program vjp of a train step and the
    scan of a differentiable ``While``, whose residuals are otherwise
    fixed per trip as the body is traced, out of the compiler's reach.
    A recomputed product the gradient does not read (a matmul's output
    is no input of its own gradient) is dead code and costs nothing.
    ``RecomputeOptimizer`` is the other mechanism: it re-emits forward
    spans as ops of the backward block for the per-op gradient path and
    cannot reach into a sub-block."""
    return (program or framework.default_main_program())._recompute_guard()


def calc_gradient(targets, inputs, target_gradients=None, no_grad_set=None):
    """Reference: backward.py:1407.  Multiple targets differentiate the
    weighted sum sum_i <target_gradients_i, targets_i> (implicit ones
    when target_gradients is None) — the reverse-mode contract the
    reference implements by seeding each target's grad var."""
    targets = targets if isinstance(targets, (list, tuple)) \
        else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if target_gradients is not None and not isinstance(
            target_gradients, (list, tuple)):
        target_gradients = [target_gradients]
    block = targets[0].block
    program = block.program
    if len(targets) == 1 and target_gradients is None and \
            int(np.prod(targets[0].shape or (1,))) in (1,):
        loss = targets[0]
    else:
        parts = []
        for i, t in enumerate(targets):
            tg = target_gradients[i] if target_gradients else None
            weighted = t
            if tg is not None:
                weighted = block.create_var(
                    name=framework.unique_name.generate(
                        t.name + '@WEIGHTED'),
                    shape=t.shape, dtype=t.dtype)
                block.append_op('elementwise_mul',
                                inputs={'X': t, 'Y': tg},
                                outputs={'Out': weighted},
                                attrs={'axis': -1})
            s = block.create_var(
                name=framework.unique_name.generate(t.name + '@TSUM'),
                shape=(), dtype=t.dtype)
            block.append_op('reduce_sum', inputs={'X': weighted},
                            outputs={'Out': s},
                            attrs={'dim': None, 'reduce_all': True,
                                   'keep_dim': False},
                            infer_shape=False)
            parts.append(s.name)
        if len(parts) == 1:
            loss = block.vars[parts[0]]
        else:
            total = block.create_var(
                name=framework.unique_name.generate('calc_grad_total'),
                shape=(), dtype=targets[0].dtype)
            block.append_op('sum', inputs={'X': parts},
                            outputs={'Out': total}, infer_shape=False)
            loss = total
    pg = append_backward(loss, no_grad_set=no_grad_set)
    del pg
    outs = []
    for v in inputs:
        gname = program._grad_name_map.get(v.name)
        outs.append(block._find_var_recursive(gname) if gname else None)
    return outs


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    return calc_gradient(targets, inputs, target_gradients, no_grad_set)
