"""Operator registry: op type -> JAX lowering rule.

Reference design: REGISTER_OPERATOR / REGISTER_OP_*_KERNEL macros
(framework/op_registry.h:223,265,268) + OpInfoMap (framework/op_info.h:124)
+ per-op GradOpDescMaker (framework/grad_op_desc_maker.h:39).

TPU-native re-design: an op is ONE pure function
    fn(ctx, ins: {slot: [jnp.Array,...]}, attrs: dict) -> {slot: [jnp.Array,...]}
that is traceable by JAX.  This single definition replaces the reference's
four artifacts per op (proto maker, shape inference, CPU kernel, CUDA
kernel): shape/dtype inference is `jax.eval_shape` over the lowering, and
the gradient op is synthesized automatically with `jax.vjp` over the same
lowering (see `grad_op_def`), so no hand-written grad kernels exist at all.
When a whole program segment is jitted, XLA CSE merges the vjp's forward
re-computation with the original forward ops, and fusion does the rest —
the per-op granularity costs nothing at runtime.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..parallel import mesh as pmesh


class LowerCtx(object):
    """Per-op lowering context: deterministic per-(op, step) RNG.

    `step` is a traced scalar fed by the executor each run, so stochastic
    ops (dropout, random init) are pure functions of (seed, step) — the
    XLA-friendly replacement for the reference's stateful curand
    generators (platform/device_context.h).
    """

    def __init__(self, step, op_seed=0, prefer_test=False):
        self.step = step
        self.op_seed = int(op_seed)
        self.prefer_test = prefer_test
        # the GSPMD runner (with_data_parallel / with_mesh) publishes
        # its mesh while a segment traces: the op is then lowered ONCE
        # for all devices and XLA partitions it, which it cannot do to
        # a Mosaic kernel.  A lowering hands this to the kernel's
        # dispatch(), which then answers dense, or wraps the call in a
        # shard_map (the flash op: mesh_flash_attention); code inside
        # one is per-device and passes nothing.
        mesh = pmesh.trace_mesh()
        self.auto_partitioned = mesh is not None and mesh.devices.size > 1

    def rng(self, salt=0):
        key = jax.random.PRNGKey(self.op_seed + 7919 * salt)
        return jax.random.fold_in(key, self.step)

    def draw_seed(self):
        """uint32 counter-hash seed of this (op, step): what every
        dropout of the tree keys ops/keep_hash.py with, so the
        (op_seed, step) keying never diverges between them."""
        return (jnp.uint32(self.op_seed * 2654435761 % (1 << 32)) ^
                jnp.asarray(self.step, jnp.uint32) *
                jnp.uint32(0x9E3779B9))

    def dropout_seed(self, attrs):
        """draw_seed() for in-kernel dropout, or None in eval mode
        (prefer_test lowering or a clone-stamped is_test attr): the
        stochastic attention lowerings then run at rate 0.  The
        dropout op tests its own is_test and calls draw_seed()."""
        if self.prefer_test or attrs.get("is_test"):
            return None
        return self.draw_seed()


# gauges that are SUMS over one traced program: lowerings add to them
# (trace_sum) and a trace's beginning takes them back to zero
_TRACE_SUMS = {'dropout/elements'}


def trace_sum(name, amount):
    """Add ``amount`` to the gauge ``name``, a sum over ONE traced
    program (``dropout/elements``, ``kda/chunks``)."""
    from ..fluid import monitor
    _TRACE_SUMS.add(name)
    monitor.set_gauge(name, monitor.gauge_value(name) + amount)


def begin_trace():
    """The executor calls this as a trace of a segment begins: a gauge
    the lowerings sum into over one traced program starts at zero, so
    what shape inference lowered at build time, or an earlier program,
    is not in the reading."""
    from ..fluid import monitor
    for name in _TRACE_SUMS:
        monitor.set_gauge(name, 0.0)


class OpDef(object):
    __slots__ = ("type", "fn", "in_slots", "out_slots", "no_grad_out_slots",
                 "host_only", "stochastic")

    def __init__(self, type, fn, in_slots=None, out_slots=None,
                 no_grad_out_slots=(), host_only=False,
                 stochastic=False):
        self.type = type
        self.fn = fn
        self.in_slots = in_slots
        self.out_slots = out_slots
        self.no_grad_out_slots = tuple(no_grad_out_slots)
        self.host_only = host_only
        # draws randomness without a declared is_test attr: clone
        # (for_test=True) stamps is_test on these so eval is
        # deterministic (framework.Program.clone)
        self.stochastic = stochastic

    def run(self, ctx, ins, attrs):
        """Invoke the lowering with AMP gray/black dtype harmonization
        (reference fp16_utils._insert_cast_op: gray ops FOLLOW a
        low-precision input by casting the f32 side DOWN — without this,
        jnp type promotion silently casts a bf16 activation UP at every
        f32 master-param bias add, and everything downstream — residual
        stream, flash-attention operands — runs f32 at double HBM
        traffic; black ops cast up to f32).  Grad ops skip the top-level
        pass: their synthesized fn replays the forward through run(), so
        the casts sit INSIDE the vjp and master-param gradients come
        back f32, the reference's backward cast op."""
        if not self.type.endswith("_grad"):
            ins = _amp_harmonize(ins, attrs)
        return self.fn(ctx, ins, attrs)


def _amp_harmonize(ins, attrs):
    if attrs.get("__amp_black__"):
        def up(v):
            dt = getattr(v, "dtype", None)
            if dt is not None and (dt == jnp.bfloat16 or dt == jnp.float16):
                return jnp.asarray(v, jnp.float32)
            return v
        return {s: [up(v) for v in vs] for s, vs in ins.items()}
    if attrs.get("__amp_gray__"):
        low = None
        for vs in ins.values():
            for v in vs:
                dt = getattr(v, "dtype", None)
                if dt is not None and (dt == jnp.bfloat16
                                       or dt == jnp.float16):
                    low = dt
                    break
            if low is not None:
                break
        if low is None:
            return ins
        def down(v):
            if getattr(v, "dtype", None) == jnp.float32:
                return jnp.asarray(v, low)
            return v
        return {s: [down(v) for v in vs] for s, vs in ins.items()}
    return ins


_REGISTRY = {}
# Op types executed by the host runtime, never traced into XLA.
HOST_OPS = set()


# op_name prefix -> op type, for instructions the TPU compiler expands
# and renames itself, which keep no named scope of the program's: the
# lowering that emits them says so (register(compiler_named=...)) and
# fluid.profiler's scope table reads it here
COMPILER_NAMED = {}
# the named scope the executor opens around the body of a
# differentiable loop (a `while` lowered as a masked scan):
# fluid.profiler's loop table tells the body's instructions by it
LOOP_BODY_SCOPE = 'loop_body'


def register(type, in_slots=None, out_slots=None, no_grad_out_slots=(),
             stochastic=False, compiler_named=()):
    """Decorator: register `fn(ctx, ins, attrs) -> outs` as op `type`.
    ``compiler_named``: op_name prefixes of the instructions the chip's
    compiler emits for this lowering under a name of its own."""

    def deco(fn):
        _REGISTRY[type] = OpDef(type, fn, in_slots, out_slots,
                                no_grad_out_slots,
                                stochastic=stochastic)
        for prefix in compiler_named:
            COMPILER_NAMED[prefix] = type
        return fn

    return deco


def register_host(type):
    """Register a host-level op (feed/fetch/save/load/print...)."""

    def deco(fn):
        _REGISTRY[type] = OpDef(type, fn, host_only=True)
        HOST_OPS.add(type)
        return fn

    return deco


def is_registered(type):
    return type in _REGISTRY or (
        type.endswith("_grad") and type[:-5] in _REGISTRY)


def get(type):
    if type in _REGISTRY:
        return _REGISTRY[type]
    if type.endswith("_grad") and type[:-5] in _REGISTRY:
        d = grad_op_def(_REGISTRY[type[:-5]])
        _REGISTRY[type] = d
        return d
    raise KeyError("Operator '%s' is not registered" % type)


def registered_ops():
    return sorted(_REGISTRY.keys())


# ---------------------------------------------------------------------------
# Generic gradient synthesis
# ---------------------------------------------------------------------------


def _is_float(x):
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def grad_op_def(fwd):
    """Build the grad OpDef for a forward OpDef via jax.vjp.

    Grad-op calling convention (mirrors the reference's GradOpDescMaker
    outputs, framework/grad_op_desc_maker.h:39):
      inputs : every forward input slot (primal values) +
               'GRAD::<out_slot>' for each available output gradient
      outputs: 'GRAD::<in_slot>' for each requested input gradient
    """

    def fn(ctx, ins, attrs):
        primal_slots = sorted(
            s for s in ins.keys() if not s.startswith("GRAD::"))
        primals = {s: ins[s] for s in primal_slots}

        def f(p):
            outs = fwd.run(ctx, p, attrs)
            # Only float outputs participate in differentiation.
            return {
                s: [v for v in vs]
                for s, vs in outs.items()
                if s not in fwd.no_grad_out_slots
            }

        outs, vjp_fn = jax.vjp(f, primals)
        # Build cotangents matching `outs` structure.
        cts = {}
        for s, vs in outs.items():
            g_in = ins.get("GRAD::" + s)
            row = []
            for i, v in enumerate(vs):
                if g_in is not None and i < len(g_in) and g_in[i] is not None:
                    row.append(jnp.asarray(g_in[i], v.dtype))
                elif _is_float(v):
                    row.append(jnp.zeros_like(v))
                else:
                    row.append(np.zeros(v.shape, jax.dtypes.float0))
            cts[s] = row
        (d_primals,) = vjp_fn(cts)
        result = {}
        for s, vs in d_primals.items():
            row = []
            for v, p in zip(vs, primals[s]):
                if v is None or (hasattr(v, "dtype")
                                 and v.dtype == jax.dtypes.float0):
                    row.append(jnp.zeros_like(p))
                else:
                    row.append(v)
            result["GRAD::" + s] = row
        return result

    return OpDef(fwd.type + "_grad", fn)


# ---------------------------------------------------------------------------
# Shape inference (jax.eval_shape over the lowering)
# ---------------------------------------------------------------------------

# Sentinel concrete size substituted for -1 (dynamic batch) dims during
# graph-build-time shape inference; output dims equal to it map back to -1.
# A large prime so it never collides with a real layer width.
_DYN_SENTINEL = 86243


def infer_shapes(op_type, in_specs, attrs, prefer_test=True):
    """in_specs: {slot: [(shape, dtype), ...]} with -1 allowed in shapes.
    Returns {slot: [(shape, dtype), ...]} for outputs, -1 restored."""
    opdef = get(op_type)
    has_dyn = False
    abstract = {}
    for slot, specs in in_specs.items():
        row = []
        for shape, dtype in specs:
            shape = tuple(shape)
            if -1 in shape:
                has_dyn = True
                shape = tuple(_DYN_SENTINEL if d == -1 else d for d in shape)
            row.append(jax.ShapeDtypeStruct(shape, dtype))
        abstract[slot] = row

    ctx = LowerCtx(step=0, op_seed=int(attrs.get("__op_seed__", 0)),
                   prefer_test=True)

    def f(ins):
        return opdef.run(ctx, ins, attrs)

    out = jax.eval_shape(f, abstract)
    result = {}
    for slot, vs in out.items():
        row = []
        for v in vs:
            shape = tuple(v.shape)
            if has_dyn:
                # only dims EQUAL to the sentinel map back to -1.
                # Products of it (layer_norm's Mean row count, a
                # beam-expanded batch) deliberately stay literal: they
                # re-enter later infer_shapes calls as input specs, and
                # keeping the concrete product is what lets downstream
                # size arithmetic (reshape -1 inference across a
                # beam-width fold, etc.) stay consistent — mapping them
                # to -1 would re-substitute the bare sentinel and lose
                # the multiplier.  The cost is cosmetic: declared
                # shapes can show sentinel-scaled dims where the true
                # value is batch-dependent.
                shape = tuple(-1 if d == _DYN_SENTINEL else d for d in shape)
            row.append((shape, v.dtype))
        result[slot] = row
    return result
