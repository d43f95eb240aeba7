"""fluid.comms — collective communication telemetry + cost model.

ROADMAP item 3 (topology-aware and quantized collectives) needs a
per-topology, per-size latency/bandwidth model before it can choose
reduce-scatter+allgather vs allreduce or gate a quantized arm — and
the trace plane "was built exactly so this tuning can be data-driven".
This module closes the loop between the two ends that already exist
(the collective op lowerings; the per-step trace spans):

**Trace-time records.**  Every collective lowering (c_allreduce_* /
c_allgather / c_reducescatter / c_broadcast in ops/collective_ops.py,
the ppermute ring and MoE all_to_all in ops/parallel_ops.py) calls
``record_trace(kind, payload_bytes, ...)`` while the segment traces.
The parallel/collective runners open a ``collecting(fingerprint)``
context around the first (tracing) call, so each compiled segment owns
an immutable tuple of collective records — kind, per-participant
payload bytes, dtype, mesh axis, participant count, and the
ring-algorithm bytes-on-wire.  Shared jits (compile_cache.shared_jit)
key records by the same fingerprint, so a re-built program that reuses
an executable also reuses its comms profile.

**Dispatch-time accounting.**  ``account_dispatch(records, wall_s)``
runs after every segment execution whose fingerprint has records:
``comms/bytes_on_wire`` / ``comms/payload_bytes`` counters accumulate
per step, and each record observes its achieved ALGORITHMIC bandwidth
(segment wire bytes / wall seconds) into a per-(collective,
size-bucket) histogram ``comms/bw_gbps/<kind>/<bucket>``.  For a
single-collective segment (the calibrator's sweeps) this is the
collective's real achieved bandwidth; for fused training segments the
compute overlapped into the same wall time makes it a LOWER bound —
still the right ordering signal for a placement planner.

**Memory accounting.**  ``record_memory(label, compiled)`` reads an
XLA executable's ``memory_analysis()`` (argument/output/temp/peak
bytes) into ``executor/segment_*_bytes`` gauges and a bounded
per-segment registry that ``/statusz`` renders — the HBM-budget side
of the same planner.

**Cost model.**  ``fit_linear(points)`` / ``model_predict(entry, b)``
fit measured (wire_bytes, seconds) sweeps to the classic
latency + inverse-bandwidth line T(b) = alpha + beta*b — the
``comms_model.json`` artifact tools/comms_calibrate.py emits and the
hierarchical-collective synthesis (arXiv:2110.10548) / EQuARX gating
(arXiv:2506.17615) planners will consume.

Hot-path discipline mirrors monitor/trace: NO jax imports at module
level; record_trace runs at trace time only (never per step);
account_dispatch is a dict lookup away from free for segments without
collectives.
"""

import threading

from . import monitor

__all__ = [
    'collecting', 'record_trace', 'records_for', 'wire_bytes',
    'size_bucket', 'account_dispatch', 'bw_samples',
    'record_memory', 'memory_report', 'fit_linear',
    'model_predict', 'reset', 'BW_BUCKETS', 'MEM_BUCKETS',
    'RATIO_BUCKETS',
]

# achieved algorithmic bandwidth, GB/s: CPU-mesh psums sit well under
# 1 GB/s, ICI links reach hundreds
BW_BUCKETS = (0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0,
              25.0, 50.0, 100.0, 200.0, 500.0)
# per-segment memory footprints, bytes (KB..tens of GB of HBM)
MEM_BUCKETS = (1e4, 1e5, 1e6, 1e7, 1e8, 1e9, 4e9, 16e9, 64e9)
# predicted/measured wall ratio for the planner's honesty histogram:
# 1.0 = the cost model nailed it; < 1 when compute shares the wall
RATIO_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0, 1.5, 2.0,
                 4.0, 10.0)

# size-bucket edges for the per-(collective, size) bandwidth
# histograms: powers of 16 from 4KiB keep the label set small while
# separating the latency-bound from the bandwidth-bound regimes
_SIZE_EDGES = ((4 << 10, 'le4KiB'), (64 << 10, 'le64KiB'),
               (1 << 20, 'le1MiB'), (16 << 20, 'le16MiB'),
               (256 << 20, 'le256MiB'))
_SIZE_TOP = 'gt256MiB'

_tls = threading.local()
_lock = threading.Lock()
# fingerprint -> tuple of records; bounded (segments are bounded by the
# executable caches, but a pathological retrace loop must not leak)
_BY_KEY = {}
_BY_KEY_CAP = 512
# rolling raw bandwidth samples per (kind, bucket) — the report-side
# complement of the fixed-bucket histograms (bench/calibrate read
# medians from here); bounded per series
_BW_SAMPLES = {}
_BW_SAMPLES_CAP = 256
# label -> memory row; bounded like _BY_KEY
_MEMORY = {}
_MEMORY_CAP = 256
# key -> cached summarize() of the frozen records (span annotation on
# the steady dispatch path must be a dict lookup, not an O(records)
# rebuild per step); invalidated whenever _BY_KEY[key] changes
_SUMMARY = {}


def reset():
    """Drop registries (tests, per-entry bench subprocess isolation)."""
    with _lock:
        _BY_KEY.clear()
        _BW_SAMPLES.clear()
        _MEMORY.clear()
        _SUMMARY.clear()


def wire_bytes(kind, payload_bytes, participants):
    """Ring-algorithm bytes each participant moves over the wire for a
    collective with `payload_bytes` per participant: allreduce rings
    send 2(n-1)/n of the payload, reduce-scatter / all-to-all /
    broadcast (n-1)/n, allgather receives the other n-1 shards.  n=1
    moves nothing (the reference's nranks==1 identity)."""
    n = max(1, int(participants))
    p = float(payload_bytes)
    if n == 1:
        return 0.0
    if kind == 'allreduce':
        return 2.0 * (n - 1) / n * p
    if kind == 'allgather':
        return (n - 1) * p
    # reducescatter / all_to_all / broadcast / ppermute rotations are
    # recorded with payload = the bytes actually forwarded per hop
    return (n - 1) / n * p


def size_bucket(payload_bytes):
    """Histogram label for a collective's per-participant payload."""
    for edge, label in _SIZE_EDGES:
        if payload_bytes <= edge:
            return label
    return _SIZE_TOP


class _Collecting(object):
    """Ambient trace-time record sink: the runner opens one around a
    segment's first (tracing) call; lowerings append through
    record_trace.  On exit the records are frozen under `key` so
    shared/reused jits keep their comms profile."""

    __slots__ = ('key', '_prev', '_records')

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self._prev = getattr(_tls, 'sink', None)
        self._records = []
        _tls.sink = self._records
        return self._records

    def __exit__(self, *exc):
        _tls.sink = self._prev
        with _lock:
            # keep an existing non-empty profile: a re-entered context
            # whose call skipped tracing (executable reused) must not
            # blank the registered records — and a pure replacement
            # must not evict some OTHER live segment (nor, at the cap,
            # pop this very key and then overwrite it with nothing)
            if self._records or self.key not in _BY_KEY:
                if self.key not in _BY_KEY and \
                        len(_BY_KEY) >= _BY_KEY_CAP:
                    evicted = next(iter(_BY_KEY))
                    _BY_KEY.pop(evicted)
                    _SUMMARY.pop(evicted, None)
                _BY_KEY[self.key] = tuple(self._records)
                _SUMMARY.pop(self.key, None)
        return False


def collecting(key):
    return _Collecting(key)


def record_trace(kind, payload_bytes, dtype=None, axis=None,
                 participants=1, wire=None, arm=None, predicted_s=None,
                 dense_wire=None, fused=0):
    """Called from a collective lowering AT TRACE TIME: append one
    record to the ambient collecting() context (no-op without one —
    e.g. eager/test execution outside the runners).  `wire` overrides
    the ring-formula estimate for lowerings that know their exact
    traffic (ppermute rotations, the quantized arm's int8+scales).
    Planner-chosen collectives (fluid.comms_plan) additionally carry
    their `arm` ('dense'|'rs_ag'|'quant'), the planner's
    `predicted_s`, the `dense_wire` bytes a flat dense allreduce would
    have moved (so the saving is a counter, not a claim), and `fused`
    = how many grads the record's bucket coalesced."""
    sink = getattr(_tls, 'sink', None)
    if sink is None:
        return None
    rec = {
        'kind': str(kind),
        'payload_bytes': float(payload_bytes),
        'wire_bytes': float(wire if wire is not None
                            else wire_bytes(kind, payload_bytes,
                                            participants)),
        'dtype': str(dtype) if dtype is not None else None,
        'axis': str(axis) if axis is not None else None,
        'participants': int(participants),
        'bucket': size_bucket(float(payload_bytes)),
    }
    if arm is not None:
        rec['arm'] = str(arm)
        rec['dense_wire_bytes'] = float(
            dense_wire if dense_wire is not None else rec['wire_bytes'])
        if predicted_s is not None:
            rec['predicted_s'] = float(predicted_s)
        if fused:
            rec['fused'] = int(fused)
    sink.append(rec)
    return rec


def records_for(key):
    """The frozen records registered for a segment fingerprint, or ()."""
    if key is None:
        return ()
    return _BY_KEY.get(key, ())


def summarize(records):
    """Compact span-annotation form of a record list: total bytes, the
    per-kind call counts, the axes involved."""
    kinds = {}
    axes = set()
    payload = wire = 0.0
    participants = 1
    for r in records:
        kinds[r['kind']] = kinds.get(r['kind'], 0) + 1
        if r['axis']:
            axes.add(r['axis'])
        payload += r['payload_bytes']
        wire += r['wire_bytes']
        participants = max(participants, r['participants'])
    return {
        'collectives': ' '.join('%s:%d' % (k, kinds[k])
                                for k in sorted(kinds)),
        'payload_bytes': payload,
        'wire_bytes': wire,
        'axes': ','.join(sorted(axes)) or None,
        'participants': participants,
    }


def summary_for(key):
    """summarize() of the records registered under `key`, memoized —
    the per-step span-annotation path pays one dict lookup."""
    cached = _SUMMARY.get(key)
    if cached is None:
        recs = records_for(key)
        if not recs:
            return None
        cached = summarize(recs)
        with _lock:
            _SUMMARY[key] = cached
    return cached


def account_dispatch(records, wall_s, compile_run=False):
    """Account one executed segment's collective traffic: bytes-on-wire
    counters every run; achieved-bandwidth histograms only on steady
    (non-compile) runs with a sane wall time.  Each (kind,
    size-bucket) series observes ITS OWN wire bytes over the segment
    wall — exact for single-collective segments (the calibrator's
    sweeps), and a true lower bound per collective when other
    collectives or compute share the wall (attributing the segment
    TOTAL to every series would overstate the small buckets by the
    large transfers' bytes).  The per-record aggregation runs in one
    local pass so a many-grad segment pays O(distinct series) monitor
    traffic per step, not O(records)."""
    if not records:
        return
    total_wire = payload = 0.0
    kinds = {}
    series_wire = {}
    plan_arms = {}
    plan_wire = plan_dense = plan_pred = 0.0
    plan_fused = plan_unpriced = 0
    for r in records:
        total_wire += r['wire_bytes']
        payload += r['payload_bytes']
        kinds[r['kind']] = kinds.get(r['kind'], 0) + 1
        key = (r['kind'], r['bucket'])
        series_wire[key] = series_wire.get(key, 0.0) + r['wire_bytes']
        arm = r.get('arm')
        if arm is not None:
            plan_arms[arm] = plan_arms.get(arm, 0) + 1
            plan_wire += r['wire_bytes']
            plan_dense += r.get('dense_wire_bytes', r['wire_bytes'])
            pred = r.get('predicted_s')
            if pred is None:
                plan_unpriced += 1
            else:
                plan_pred += pred
            plan_fused += r.get('fused', 0)
    monitor.add('comms/payload_bytes', payload)
    monitor.add('comms/collective_calls', float(len(records)))
    for kind, n in kinds.items():
        monitor.add('comms/%s_calls' % kind, float(n))
    monitor.add('comms/bytes_on_wire', total_wire)
    if plan_arms:
        # planner observability: which arm ran, the wire bytes it moved
        # vs what flat dense would have moved, and predicted-vs-measured
        # wall so the cost model's honesty is a scrape away.  Measured
        # is the SEGMENT wall — exact for the calibrator's one-
        # collective programs, an upper bound when compute shares the
        # segment (the ratio then under-reports the model, never
        # over-reports it).
        for arm, n in plan_arms.items():
            monitor.add('comms/plan_arm/%s' % arm, float(n))
        monitor.add('comms/plan_wire_bytes', plan_wire)
        monitor.add('comms/plan_dense_equiv_bytes', plan_dense)
        if plan_fused:
            monitor.add('comms/plan_fused_grads', float(plan_fused))
        if plan_unpriced:
            # partial model: some arms in this segment had no entry —
            # comparing a partial prediction against the FULL wall
            # would bias the honesty ratio low, so count instead
            monitor.add('comms/plan_unpriced', float(plan_unpriced))
        elif plan_pred > 0 and not compile_run and wall_s > 0:
            monitor.add('comms/plan_predicted_seconds', plan_pred)
            monitor.add('comms/plan_measured_seconds', wall_s)
            monitor.observe('comms/plan_pred_over_measured',
                            plan_pred / wall_s, RATIO_BUCKETS)
    if compile_run or wall_s <= 0 or total_wire <= 0:
        return
    for (kind, bucket), wire in series_wire.items():
        if wire <= 0:
            continue
        bw_gbps = wire / wall_s / 1e9
        monitor.observe('comms/bw_gbps/%s/%s' % (kind, bucket),
                        bw_gbps, BW_BUCKETS)
        with _lock:
            samples = _BW_SAMPLES.setdefault((kind, bucket), [])
            if len(samples) >= _BW_SAMPLES_CAP:
                del samples[:_BW_SAMPLES_CAP // 2]
            samples.append(bw_gbps)


def bw_samples():
    """{(kind, bucket): [raw GB/s samples]} — report-side medians for
    bench/calibrate (the monitor histograms keep the scrape form)."""
    with _lock:
        return {k: list(v) for k, v in _BW_SAMPLES.items()}


# ------------------------------------------------------ memory accounting
def record_memory(label, compiled):
    """Read an XLA executable's memory_analysis() into the per-segment
    registry + executor/segment_*_bytes gauges.  Never raises:
    backends where the analysis raises, returns None or reports only
    partial fields are tolerated and counted
    (``memviz/analysis_unavailable``, via fluid.memviz — the shared
    extraction) instead of silently skipped; returns the row or
    None."""
    from . import memviz
    fields = memviz.analysis_fields(compiled)
    if fields is None:
        return None
    row = {'argument_bytes': fields['argument_bytes'],
           'output_bytes': fields['output_bytes'],
           'temp_bytes': fields['temp_bytes'],
           'peak_bytes': fields['peak_bytes'],
           'generated_code_bytes': fields['generated_code_bytes']}
    peak = row['peak_bytes']
    with _lock:
        if label not in _MEMORY and len(_MEMORY) >= _MEMORY_CAP:
            _MEMORY.pop(next(iter(_MEMORY)))
        _MEMORY[label] = row
        rows = list(_MEMORY.values())
    # job-level gauges the HBM-budget planner (and /statusz) read:
    # sums over distinct segments, peak as the largest single segment
    monitor.set_gauge('executor/segment_argument_bytes',
                      sum(r['argument_bytes'] for r in rows))
    monitor.set_gauge('executor/segment_output_bytes',
                      sum(r['output_bytes'] for r in rows))
    monitor.set_gauge('executor/segment_temp_bytes',
                      sum(r['temp_bytes'] for r in rows))
    monitor.set_gauge('executor/segment_peak_bytes',
                      max(r['peak_bytes'] for r in rows))
    monitor.observe('comms/segment_peak_bytes_hist', peak, MEM_BUCKETS)
    return row


def memory_report():
    """Per-segment memory rows for /statusz, largest peak first."""
    with _lock:
        rows = [dict(r, segment=k) for k, r in _MEMORY.items()]
    rows.sort(key=lambda r: -r['peak_bytes'])
    return rows


# ------------------------------------------------------------ cost model
def fit_linear(points):
    """Weighted least-squares fit of T(b) = alpha + beta*b over
    (bytes, seconds) points — the latency + inverse-bandwidth
    collective cost model.  Weights are 1/t^2, i.e. the fit minimizes
    RELATIVE error: an unweighted fit is dominated by the largest
    transfer and can mispredict the latency-bound small sizes by far
    more than the 2x envelope the planner needs.  alpha is clamped
    non-negative (a negative launch latency is noise), beta to a tiny
    positive floor so predicted bandwidth stays finite.  Returns
    (alpha_s, beta_s_per_byte)."""
    pts = [(float(b), float(t)) for b, t in points if t > 0]
    if not pts:
        return 0.0, 1e-12
    if len(pts) == 1:
        b, t = pts[0]
        return 0.0, max(t / max(b, 1.0), 1e-15)
    sw = swb = swbb = swt = swbt = 0.0
    for b, t in pts:
        w = 1.0 / (t * t)
        sw += w
        swb += w * b
        swbb += w * b * b
        swt += w * t
        swbt += w * b * t
    denom = sw * swbb - swb * swb
    if denom <= 0:
        return 0.0, max(swt / max(swb, 1e-30), 1e-15)
    beta = (sw * swbt - swb * swt) / denom
    alpha = (swt - beta * swb) / sw
    if alpha < 0.0:
        # re-solve through the origin rather than keep a negative
        # launch latency
        alpha = 0.0
        beta = swbt / max(swbb, 1e-30)
    beta = max(beta, 1e-15)
    return alpha, beta


def model_predict(entry, wire):
    """Predicted seconds for `wire` bytes under one comms_model.json
    collective entry ({'latency_s', 'inv_bw_s_per_byte'})."""
    return float(entry['latency_s']) + \
        float(entry['inv_bw_s_per_byte']) * float(wire)
