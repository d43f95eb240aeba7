"""fluid.comms_plan — cost-model-driven collective planner.

ROADMAP item 3: the v1.6 collective transpiler inserted one flat dense
``c_allreduce`` per gradient.  This module chooses the reduction
implementation per gradient tensor (and per mesh) instead, from the
calibrated cost model PR 7 built (``tools/comms_calibrate.py`` ->
``comms_model.json``: per-collective latency alpha + inverse bandwidth
beta, fitted within 2x of measured).  Three arms:

- **dense / flat** — the v1.6 ``psum``; always the fallback.
- **dense / rs_ag** — reduce-scatter + allgather synthesis
  (arXiv:2110.10548): the same 2(n-1)/n ring bytes, but two pipelined
  phases whose cost the model prices separately; chosen when
  ``T_rs + T_ag < T_allreduce`` under the model (or, with no model, for
  payloads past ``FLAGS_comms_rs_ag_min_bytes``).  Elementwise
  bit-identical to flat (the reduction per element is the same sum).
- **quant** — EQuARX-style block-scaled int8 quantized allreduce
  (arXiv:2506.17615): quantize -> int8 reduce-scatter (all_to_all) with
  per-block fp32 scales -> fp32 reduce -> requantize -> int8 allgather.
  ~4x fewer bytes on the wire for fp32 grads (* (1 + 4/block) scale
  overhead), ~1e-2 relative error on the reduced values; gated
  per-tensor by ``FLAGS_comms_quantize`` AND a payload floor so
  latency-bound small tensors keep the dense path bit for bit.

**Grad-bucket fusion** (``bucket_grads``) coalesces consecutive
same-dtype grads into fused buckets up to ``FLAGS_comms_bucket_bytes``
so the latency term alpha is paid once per bucket, not once per grad;
the chosen arm then applies to the whole bucket
(``c_allreduce_fused``).

**HBM budget.**  With ``FLAGS_comms_hbm_budget_bytes`` set, the
planner respects the per-segment footprint the
``executor/segment_peak_bytes`` gauge reports (fluid.comms
``record_memory``): bucket fusion caps the fused buffer to the
remaining headroom, and the quantized arm (which holds quantized +
dequantized temporaries, ~2.25x the payload) degrades to dense when
the headroom is tighter than that.

**Fingerprint honesty.**  Decisions are pure functions of (payload,
dtype, participants, flags, model file, HBM headroom); ``digest()``
folds the flag values, the model file's identity and the
power-of-two-bucketed headroom into a string the parallel /
collective runners add to their segment fingerprints, so an
executable can never be REUSED (shared-jit / disk-cache / rebuilt
program) under a plan other than the one it was traced with, and
unchanged decisions never retrace.  Like every lowering flag
(FLAGS_conv_precision, FLAGS_whole_program_grad, ...), changes apply
to segments (re)built after the change: a live segment's own
executable memo keeps the plan it was traced with until the program
is rebuilt or the process restarts.

Every planned dispatch is observable: lowerings file their arm +
predicted seconds + dense-equivalent wire bytes into the fluid.comms
records, and ``comms.account_dispatch`` turns those into
``comms/plan_arm/<arm>`` counters, ``comms/plan_wire_bytes`` vs
``comms/plan_dense_equiv_bytes`` (the named saving), and
``comms/plan_predicted_seconds`` vs ``comms/plan_measured_seconds``
(the model's honesty).  ``/statusz`` renders the active plan per
transpiled program via ``program_plans()``.

No jax imports at module level (hot-path discipline, like monitor /
comms); everything here runs at transpile or trace time, never per
step.
"""

import hashlib
import json
import os
import threading

from . import monitor
from .flags import get_flag

__all__ = [
    'decide', 'bucket_grads', 'fuse_cutoff_bytes', 'quant_wire_bytes',
    'predict_seconds', 'load_model', 'model_entry', 'digest',
    'order_axes',
    'hbm_headroom_bytes', 'bucket_cap_bytes', 'quant_block',
    'current_model',
    'record_program_plan', 'program_plans', 'reset',
]

_lock = threading.Lock()
# (path, mtime, size) -> parsed model (or None for an unusable file —
# negatives cache too); one entry (models are small and a process
# consults one file)
_model_cache = {}
_MODEL_MISS = object()
# label -> plan summary, insertion-ordered and bounded (/statusz view)
_PLANS = {}
_PLANS_CAP = 64
_plan_seq = [0]

# quantized-arm temporaries: int8 copy + fp32 dequant buffers alongside
# the payload — the factor the HBM-headroom gate prices
_QUANT_MEM_FACTOR = 2.25
# with the Pallas fused quantize / dequant-reduce-requant kernels
# (ops/pallas/quant_collective.py) the fp32 temporaries stay in VMEM
# tiles; only the int8 shards + scales transit HBM (~payload/4 each
# side of the wire, plus scale rows)
_QUANT_MEM_FACTOR_FUSED = 0.75
_MIN_BUCKET_FLOOR = 64 << 10


def _fused_quant_available():
    """Whether the quantized arm would run the fused Pallas element
    phases — the same predicate collective_ops dispatches on, so the
    priced HBM term always matches the path that executes."""
    try:
        from ..ops.pallas import quant_collective
        return bool(quant_collective.fused_available())
    except Exception:
        return False


def quant_hbm_temp(payload_bytes, fused=None):
    """HBM bytes of quantized-arm temporaries the headroom gate must
    cover for one payload: ~2.25x with the dense element phases, ~0.75x
    when the fused kernels keep the fp32 dequant buffers in VMEM."""
    if fused is None:
        fused = _fused_quant_available()
    factor = _QUANT_MEM_FACTOR_FUSED if fused else _QUANT_MEM_FACTOR
    return factor * float(payload_bytes)


def reset():
    """Drop the model cache + plan registry (tests)."""
    with _lock:
        _model_cache.clear()
        _PLANS.clear()
        _plan_seq[0] = 0


# ------------------------------------------------------------- cost model
def _model_path():
    p = get_flag('FLAGS_comms_model_path', '') or ''
    if p:
        return p
    return 'comms_model.json' if os.path.exists('comms_model.json') \
        else ''


def load_model(path=None):
    """The parsed comms_model.json, or None.  Cached by (path, mtime,
    size) so an overwritten model (re-calibration) is picked up by
    plans made after the change (segments already compiled keep the
    plan they were traced with, like any lowering flag) — and the
    cache key doubles as the fingerprint component ``digest()`` folds
    into segment fingerprints."""
    p = path if path is not None else _model_path()
    if not p:
        return None
    try:
        st = os.stat(p)
    except OSError:
        return None
    key = (os.path.abspath(p), st.st_mtime_ns, st.st_size)
    with _lock:
        cached = _model_cache.get(key, _MODEL_MISS)
    if cached is not _MODEL_MISS:
        return cached
    try:
        with open(p) as f:
            model = json.load(f)
        if not isinstance(model.get('collectives'), dict):
            model = None
    except Exception:
        model = None
    # cache negatives too (same (path, mtime, size) key): an
    # unparsable/schema-less file would otherwise be re-read and
    # re-parsed on EVERY predict_seconds call
    with _lock:
        _model_cache.clear()
        _model_cache[key] = model
    return model


def current_model(model=None):
    """The model PLANNING prices from: an explicit argument wins, then
    the cached on-disk comms_model.json."""
    if model is not None:
        return model
    return load_model()


def model_entry(kind, model=None):
    model = current_model(model)
    if not model:
        return None
    return model.get('collectives', {}).get(kind)


def predict_seconds(kind, wire_bytes, model=None):
    """Model-predicted seconds for `wire_bytes` over collective `kind`,
    or None when the model has no entry — or a PARTIAL/malformed one
    (a hand-edited or truncated comms_model.json must degrade every
    consumer to its heuristic, never crash the planner)."""
    entry = model_entry(kind, model)
    if not entry:
        return None
    from . import comms
    try:
        return comms.model_predict(entry, wire_bytes)
    except (KeyError, TypeError, ValueError):
        # entry exists but lacks latency_s/inv_bw_s_per_byte (or they
        # are non-numeric): same contract as a missing entry
        return None


def digest():
    """One string capturing every input a planning decision depends on
    besides the tensor itself: the planner flags and the model file's
    identity.  The parallel/collective runners fold this into their
    segment fingerprints, so planner decisions are part of the
    fingerprint — flag or model changes retrace exactly once, and an
    unchanged plan never retraces."""
    p = _model_path()
    try:
        st = os.stat(p) if p else None
        mid = '%s:%d:%d' % (os.path.abspath(p), st.st_mtime_ns,
                            st.st_size) if st else 'none'
    except OSError:
        mid = 'none'
    # the HBM-headroom gate reads a runtime gauge; bucket it to powers
    # of two here so a materially-changed headroom (budget refilled or
    # exhausted) changes the digest — and retraces the plan — while
    # steady drift does not thrash the compile caches
    headroom = hbm_headroom_bytes()
    if headroom is None:
        hr = 'off'
    else:
        hr = str(int(headroom).bit_length())
    parts = ('plan=%d' % bool(get_flag('FLAGS_comms_plan', True)),
             'hr=%s' % hr,
             'q=%d' % bool(get_flag('FLAGS_comms_quantize', False)),
             'qmin=%d' % int(get_flag('FLAGS_comms_quantize_min_bytes',
                                      65536)),
             'qblk=%d' % int(get_flag('FLAGS_comms_quant_block', 256)),
             # fused-kernel availability moves the quant arm's HBM
             # gate factor (and the executed path), so it must retrace
             'qfuse=%d' % int(_fused_quant_available()),
             'bkt=%d' % int(get_flag('FLAGS_comms_bucket_bytes',
                                     4 << 20)),
             'fuse=%d' % int(get_flag('FLAGS_comms_fuse_grad_max_bytes',
                                      64 << 10)),
             'rsag=%d' % int(get_flag('FLAGS_comms_rs_ag_min_bytes',
                                      8 << 20)),
             'hbm=%d' % int(get_flag('FLAGS_comms_hbm_budget_bytes',
                                     0)),
             'model=%s' % hashlib.sha256(
                 mid.encode()).hexdigest()[:12])
    return 'comms_plan(%s)' % ','.join(parts)


# ---------------------------------------------------------- wire formulas
def quant_wire_bytes(payload_bytes, itemsize, participants, block=None):
    """Bytes each participant moves over the wire for the quantized
    arm: int8 payload + per-block fp32 scales through BOTH phases —
    the ring (n-1)/n factor for the reduce-scatter (all_to_all) phase
    plus (n-1) * the reduced chunk for the allgather phase.  For fp32
    this is ~dense/4 * (1 + 4/block)."""
    n = max(1, int(participants))
    if n == 1:
        return 0.0
    block = int(block or quant_block())
    itemsize = max(1, int(itemsize))
    elems = float(payload_bytes) / itemsize
    q_bytes = elems * (1.0 + 4.0 / block)     # int8 + fp32 scale share
    rs = (n - 1.0) / n * q_bytes              # all_to_all phase
    ag = (n - 1.0) * (q_bytes / n)            # chunk allgather phase
    return rs + ag


def quant_block():
    return max(8, int(get_flag('FLAGS_comms_quant_block', 256)))


# ------------------------------------------------------------- HBM budget
def hbm_headroom_bytes():
    """Remaining per-segment HBM under FLAGS_comms_hbm_budget_bytes;
    None when no budget is configured.

    The footprint is PER PROGRAM where the memory plane can attribute
    it: inside an executor/runner/transpiler ``memviz.program_scope``
    the ambient program's own peak (fluid.memviz ``record_segment``)
    is the reference — one big resident program no longer suppresses
    quantization/fusion for every other program.  Outside a program
    scope, or before any attribution row lands for the program, the
    job-wide ``executor/segment_peak_bytes`` gauge keeps the old
    conservative behavior."""
    budget = float(get_flag('FLAGS_comms_hbm_budget_bytes', 0) or 0)
    if budget <= 0:
        return None
    used = None
    try:
        from . import memviz
        label = memviz.current_program()
        if label is not None:
            used = memviz.peak_bytes(label)
    except Exception:
        used = None
    if used is None:
        used = monitor.gauge_value('executor/segment_peak_bytes') or 0.0
    return max(0.0, budget - used)


def bucket_cap_bytes():
    """Effective fused-bucket byte target: the configured target,
    shrunk to a quarter of the HBM headroom when a budget is set (the
    fused buffer plus its reduced copy must fit), floored so fusion
    never degenerates below 64KiB buckets."""
    cap = float(get_flag('FLAGS_comms_bucket_bytes', 4 << 20) or 0)
    if cap <= 0:
        return 0.0
    headroom = hbm_headroom_bytes()
    if headroom is not None:
        cap = min(cap, max(_MIN_BUCKET_FLOOR, headroom / 4.0))
    return cap


# --------------------------------------------------------------- decision
def decide(payload_bytes, itemsize, participants, forced_arm=None,
           model=None):
    """Choose the reduction implementation for one tensor (or fused
    bucket): {'arm': 'dense'|'quant', 'strategy': 'flat'|'rs_ag',
    'block', 'wire_bytes', 'dense_wire_bytes', 'predicted_s'}.

    Pure in (args, flags, model file, HBM headroom) — every input
    besides the args is folded into digest(), the property the
    fingerprints bank on.  `forced_arm` bypasses the gates (calibrator
    sweeps): 'quant' forces the quantized arm, 'dense' forces the flat
    dense baseline (no strategy synthesis either)."""
    from . import comms
    n = max(1, int(participants))
    payload = float(payload_bytes)
    itemsize = max(1, int(itemsize))
    dense_wire = comms.wire_bytes('allreduce', payload, n)
    block = quant_block()
    out = {'arm': 'dense', 'strategy': 'flat', 'block': block,
           'wire_bytes': dense_wire, 'dense_wire_bytes': dense_wire,
           'predicted_s': predict_seconds('allreduce', dense_wire,
                                          model)}
    if n == 1 or payload <= 0:
        return out

    # --- quantized arm gate: flag + per-tensor size floor + a
    # quantizable float dtype + HBM headroom for the temporaries
    want_quant = forced_arm == 'quant' or (
        forced_arm is None and
        bool(get_flag('FLAGS_comms_quantize', False)) and
        payload >= float(get_flag('FLAGS_comms_quantize_min_bytes',
                                  65536)))
    if want_quant and itemsize > 1:
        headroom = hbm_headroom_bytes()
        if forced_arm == 'quant' or headroom is None or \
                headroom >= quant_hbm_temp(payload):
            q_wire = quant_wire_bytes(payload, itemsize, n, block)
            pred = predict_seconds('allreduce_quant', q_wire, model)
            if pred is None:
                # no calibrated quant entry: price it as dense traffic
                # at the quantized byte count (the latency term rides
                # along) — honest enough for reporting, and the gate
                # itself is the flag + floor, not the model
                dense_pred = out['predicted_s']
                if dense_pred is not None and dense_wire > 0:
                    pred = dense_pred * (q_wire / dense_wire) \
                        if q_wire < dense_wire else dense_pred
            out.update(arm='quant', wire_bytes=q_wire,
                       predicted_s=pred)
            return out

    if forced_arm == 'dense':
        # forced baseline: flat psum, no strategy synthesis
        return out

    # --- dense strategy synthesis: flat allreduce vs reduce-scatter +
    # allgather, priced from the model when one is loaded
    rs_wire = comms.wire_bytes('reducescatter', payload, n)
    ag_wire = comms.wire_bytes('allgather', payload / n, n)
    t_flat = out['predicted_s']
    t_rs = predict_seconds('reducescatter', rs_wire, model)
    t_ag = predict_seconds('allgather', ag_wire, model)
    t_rs_ag = t_rs + t_ag if (t_rs is not None and t_ag is not None) \
        else None
    if t_flat is not None and t_rs_ag is not None:
        if t_rs_ag < t_flat:
            out.update(strategy='rs_ag', predicted_s=t_rs_ag)
    elif payload >= float(get_flag('FLAGS_comms_rs_ag_min_bytes',
                                   8 << 20)):
        # heuristic pick (model absent or partial): predicted_s must
        # price the arm that RUNS — rs+ag when priceable, else unknown
        # (keeping the flat prediction here would poison the
        # predicted-vs-measured honesty metrics)
        out.update(strategy='rs_ag', predicted_s=t_rs_ag)
    return out


def fuse_cutoff_bytes(cap=None, model=None):
    """Per-grad fusion eligibility: grads at/above this PAYLOAD size
    are bandwidth-bound — fusing them amortizes no latency but pays
    real concat/split copies — so they reduce alone.  With a cost
    model the cutoff comes from its latency/bandwidth crossover
    alpha/beta; that crossover is in WIRE bytes (the fit's x axis),
    and an allreduce ring moves 2(n-1)/n ~ 2x the payload, so the
    payload-domain cutoff is half of it (~20KB on the CPU CI mesh,
    ~500KB on a real ICI; the factor is 1 at n=2, so halving only
    errs toward fusing less — the safe side).  Without a model,
    FLAGS_comms_fuse_grad_max_bytes."""
    cap = bucket_cap_bytes() if cap is None else float(cap)
    entry = model_entry('allreduce', model)
    if entry:
        try:
            alpha = float(entry['latency_s'])
            beta = float(entry['inv_bw_s_per_byte'])
            if beta > 0:
                return max(4 << 10, min(alpha / beta / 2.0, cap))
        except (KeyError, TypeError, ValueError):
            pass
    return min(float(get_flag('FLAGS_comms_fuse_grad_max_bytes',
                              64 << 10)), cap)


def bucket_grads(grads, cap_bytes=None, fuse_cutoff=None):
    """Coalesce gradient tensors into fused reduction buckets:
    `grads` is an ordered [(name, nbytes, dtype_str)]; LATENCY-BOUND
    grads (below fuse_cutoff_bytes()) join the most recent still-open
    bucket of their dtype — a dtype switch opens a new bucket but an
    earlier dtype's bucket stays open for its later grads — until the
    bucket would pass the byte cap (bucket_cap_bytes() by default,
    HBM-budget-aware).  Grads with unknown size (nbytes <= 0) and
    bandwidth-bound grads stand alone — the planner still picks their
    arm, they just skip the concat.  Returns
    [{'names': [...], 'bytes': total, 'dtype': dt}] preserving
    first-appearance order — the reduction is elementwise, so grouping
    never changes the math."""
    cap = bucket_cap_bytes() if cap_bytes is None else float(cap_bytes)
    cutoff = fuse_cutoff_bytes(cap) if fuse_cutoff is None \
        else float(fuse_cutoff)
    buckets = []
    open_by_dtype = {}
    for name, nbytes, dtype in grads:
        nbytes = float(nbytes or 0)
        if cap <= 0 or nbytes <= 0 or nbytes >= min(cap, cutoff):
            buckets.append({'names': [name], 'bytes': max(nbytes, 0.0),
                            'dtype': dtype})
            continue
        cur = open_by_dtype.get(dtype)
        if cur is not None and cur['bytes'] + nbytes <= cap:
            cur['names'].append(name)
            cur['bytes'] += nbytes
        else:
            cur = {'names': [name], 'bytes': nbytes, 'dtype': dtype}
            buckets.append(cur)
            open_by_dtype[dtype] = cur
    return buckets


def verify_buckets(block, buckets):
    """Static legality of a bucket rewrite BEFORE the collective ops
    land (fluid.progcheck discipline — legality first, pricing
    second): every bucketed grad must be a declared block var, carry
    the bucket's dtype, and appear in exactly one bucket.  A planner
    rewrite that tears one of these produces an elementwise-wrong (or
    untraceable) fused reduction; raise with the defect named instead.
    Returns the verified bucket list unchanged."""
    import time as _time
    from . import progcheck
    t0 = _time.perf_counter()
    rep = progcheck.Report('comms_plan', 'transpile:bucket')
    seen = {}
    for bi, b in enumerate(buckets):
        for name in b['names']:
            if name in seen:
                rep.add(progcheck.Diagnostic(
                    'shard_conflict',
                    'grad %r appears in buckets %d and %d — it would '
                    'reduce twice' % (name, seen[name], bi), var=name))
            seen[name] = bi
            v = block._find_var_recursive(name)
            if v is None:
                rep.add(progcheck.Diagnostic(
                    'undefined_read',
                    'bucket %d names grad %r which no block declares'
                    % (bi, name), var=name))
                continue
            if len(b['names']) > 1 and v.dtype != b['dtype']:
                rep.add(progcheck.Diagnostic(
                    'dtype_mismatch',
                    'grad %r is %s but joined a %s fused bucket — the '
                    'concat would silently cast'
                    % (name, v.dtype, b['dtype']), var=name))
    rep.ops_checked = len(buckets)
    rep.seconds = _time.perf_counter() - t0
    # the shared recording path: counters, /statusz report trail,
    # stat_summary --verify all see bucket verifications too
    progcheck._record(rep)
    if not rep.ok():
        raise progcheck.ProgramVerifyError(rep)
    return buckets


def order_axes(axes):
    """Deterministic mesh-axis order for a multi-axis reduce
    synthesized as per-axis phases: largest axis first
    (arXiv:2110.10548's axis-order convention), with a stable name
    tie-break so the phase sequence — and hence the traced graph and
    its fingerprint — never depends on dict/attr ordering.  Today each
    phase reduces the full payload (no phase hands a scattered chunk
    to the next), so the order is cost-neutral; the largest-first
    convention is the one that pays off if/when the phases move to
    per-axis reduce-scatter chunking.  `axes` is [(name, size)];
    returns the names ordered."""
    return [name for name, _ in
            sorted(axes, key=lambda a: (-int(a[1]), a[0]))]


# ----------------------------------------------------- /statusz registry
def record_program_plan(summary, label=None):
    """File one transpiled program's plan for /statusz: bucket count,
    fused grads, per-bucket decisions, the flags that produced them.
    Bounded, insertion-ordered; returns the label."""
    with _lock:
        if label is None:
            _plan_seq[0] += 1
            label = 'program_%d' % _plan_seq[0]
        if label not in _PLANS and len(_PLANS) >= _PLANS_CAP:
            _PLANS.pop(next(iter(_PLANS)))
        _PLANS[label] = summary
    return label


def program_plans():
    """{label: plan summary} for every planned program, /statusz's
    'comms_plan' section."""
    with _lock:
        plans = {k: v for k, v in _PLANS.items()}
    return {
        'digest': digest(),
        'model_path': _model_path() or None,
        'model_loaded': load_model() is not None,
        'programs': plans,
        'arm_counters': {
            k.rsplit('/', 1)[1]: monitor.counter_value(k)
            for k in ('comms/plan_arm/dense', 'comms/plan_arm/rs_ag',
                      'comms/plan_arm/quant')},
        'plan_wire_bytes': monitor.counter_value(
            'comms/plan_wire_bytes'),
        'plan_dense_equiv_bytes': monitor.counter_value(
            'comms/plan_dense_equiv_bytes'),
        'predicted_seconds': monitor.counter_value(
            'comms/plan_predicted_seconds'),
        'measured_seconds': monitor.counter_value(
            'comms/plan_measured_seconds'),
    }
