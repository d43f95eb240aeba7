"""fluid.serving — multi-tenant serving plane: continuous batching
over the compiled-step substrate.

The reference serves inference as one process running one program
through ``inference/predictor.py`` — no batching, no queueing, no
multi-program residency, so an accelerator idles between single
requests.  This module turns the already-landed substrate into
throughput:

- **Residency.**  A ``ServingExecutor`` keeps many programs resident
  at once: each registered *tenant* is (program, per-tenant
  ``core.Scope`` holding its parameters, feed/fetch contract).  The
  LRU-capped plan/segment/compile caches already support many
  programs; the per-tenant scope guarantees resident programs cannot
  see each other's state, and the per-(keyset, scope) binder tables in
  the executor keep the steady-state bind fast across tenant switches.

- **Continuous batching.**  Requests enter a thread-safe admission
  queue and a single dispatcher thread coalesces same-tenant requests
  into dynamic batches, padded to the next power-of-two ROW bucket
  (``reader.pow2_bucket_ladder`` / ``bucket_for`` — the
  BucketedGeneratorLoader recipe applied to the batch dim, masks under
  the ``'@MASK'`` convention) so the executor sees O(log max_batch)
  shapes per program and one AOT executable per (program, bucket).
  Results are sliced back per request, bitwise-identical to unbatched
  execution padded to the same bucket (co-batched rows and row
  position cannot change a per-row result's bytes; ACROSS buckets XLA
  may accumulate a row's reductions in a different order, so
  cross-bucket equality is float-noise, not bitwise).

- **Zero serving-path retraces.**  ``warmup()`` pre-compiles the whole
  bucket ladder through ``Executor.warmup`` + the persistent compile
  cache, so a fresh replica answers its first request — any admissible
  shape — without tracing; a bucket that somehow misses is counted
  (``serving/retraces``), never hidden.

- **Admission overlaps compute.**  The dispatcher pads and H2D-stages
  batch k+1 (one async ``jax.device_put``) and resolves batch k-1's
  async fetch handles (``return_numpy='async'``) while batch k
  executes — the PR-2 overlap discipline at batch granularity.

- **SLO observability.**  Per-tenant queue-depth gauges, batch
  occupancy and admission-to-completion latency histograms, pad-waste
  bytes — all through ``fluid.monitor`` (scraped at ``/metrics``), and
  every coalesced batch's step record is tagged tenant/bucket via
  ``trace.step_tags`` so ``step_report()`` and the flight recorder
  attribute serving steps.  ``/statusz`` lists resident programs;
  ``/healthz`` readiness waits for serving warmup.

Hot-path discipline: nothing here imports jax at module level; the
dispatcher thread owns all device interaction; admission is a lock,
an append and a notify.
"""

import collections
import threading
import time as _time
import weakref

import numpy as np

from . import compile_cache
from . import core
from . import monitor
from . import trace as _trace
from .executor import Executor
from .reader import bucket_for, mask_name, pow2_bucket_ladder

__all__ = [
    'ServingExecutor', 'pad_rows_to_bucket', 'slice_rows',
    'readiness', 'resident_report', 'OCCUPANCY_BUCKETS',
    'DeadlineExpired', 'ServingDegraded', 'enter_degraded',
    'exit_degraded', 'degraded_reason',
]


class DeadlineExpired(RuntimeError):
    """A request's submit-time deadline passed while it was still
    queued: it was SHED (completed exceptionally,
    ``serving/shed_expired``) instead of padded into a batch — a
    stalled dispatcher must not burn compute on answers nobody is
    waiting for."""


class ServingDegraded(RuntimeError):
    """The replica is shedding load (``enter_degraded`` — e.g. the
    self-healing supervisor is mid-recovery): the request failed fast
    instead of queueing into a backend that cannot serve it."""


# recovery-degradation latch (the supervisor's serving leg): while a
# reason is set, /healthz reports not-ready and submit() sheds
_deg_lock = threading.Lock()
_degraded_reason = None


def enter_degraded(reason):
    """Flip this replica to degraded: readiness() goes False and every
    submit() completes exceptionally (``serving/shed_degraded``) until
    ``exit_degraded``.  Idempotent; the latest reason wins."""
    global _degraded_reason
    with _deg_lock:
        _degraded_reason = str(reason)
    monitor.set_gauge('serving/degraded', 1.0)


def exit_degraded():
    global _degraded_reason
    with _deg_lock:
        _degraded_reason = None
    monitor.set_gauge('serving/degraded', 0.0)


def degraded_reason():
    return _degraded_reason

# batch-occupancy histogram edges (fraction of the bucket that carried
# real rows: 1.0 = perfectly full batches)
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

# live ServingExecutors, for the health plane's readiness/statusz view
_live = weakref.WeakSet()


# ------------------------------------------------------- pad/mask/slice
def pad_rows_to_bucket(feed, rows, bucket, mask_specs=()):
    """Pad every batch-aligned feed's leading dim from `rows` to
    `bucket` with zero rows, and synthesize the row masks in
    `mask_specs` (ones for live rows, zeros for padding) under their
    '@MASK' names.  Feeds whose leading dim is not `rows` (scalars,
    per-model side inputs) pass through untouched.  An all-zero mask
    row is exactly the bucketed loader's "no tokens here" convention,
    so sequence ops ignore padding the same way they ignore short
    sequences.  Returns (padded_feed, pad_waste_bytes)."""
    if rows == bucket and not mask_specs:
        return feed, 0.0
    out = {}
    waste = 0.0
    for name, v in feed.items():
        a = np.asarray(v)
        if a.ndim and a.shape[0] == rows and rows != bucket:
            padded = np.zeros((bucket,) + a.shape[1:], a.dtype)
            padded[:rows] = a
            out[name] = padded
            waste += float(padded.nbytes - a.nbytes)
        else:
            out[name] = a
    for mname, tail in mask_specs:
        if mname in out:
            continue  # caller supplied its own mask: padded above
        m = np.zeros((bucket,) + tuple(tail), 'float32')
        m[:rows] = 1.0
        out[mname] = m
    return out, waste


def slice_rows(val, off, n, bucket):
    """One request's rows of a batched fetch.  Outputs that do not
    carry the bucket's batch dim (scalars, whole-batch aggregates) are
    returned verbatim to every request — slicing them would fabricate
    per-request meaning they don't have."""
    a = np.asarray(val)
    if a.ndim and a.shape[0] == bucket:
        return a[off:off + n]
    return a


def _deliver(future, result=None, exc=None):
    """Resolve a request future, tolerating races with cancellation:
    a future that can no longer accept a result must never kill the
    dispatcher thread."""
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
    except Exception:
        monitor.add('serving/undeliverable_results')


# ------------------------------------------------------------- requests
class _Request(object):
    __slots__ = ('tenant', 'feed', 'rows', 'future', 't_admit',
                 'deadline')

    def __init__(self, tenant, feed, rows, future, deadline_s=None):
        self.tenant = tenant
        self.feed = feed
        self.rows = rows
        self.future = future
        self.t_admit = _time.perf_counter()
        # absolute expiry on the monotonic clock; None = no deadline
        self.deadline = (self.t_admit + float(deadline_s)
                         if deadline_s is not None else None)


class _Batch(object):
    __slots__ = ('tenant', 'requests', 'rows', 'bucket', 'handles',
                 'error', 't_dispatch')

    def __init__(self, tenant, requests, rows):
        self.tenant = tenant
        self.requests = requests
        self.rows = rows
        self.bucket = None
        self.handles = None
        self.error = None
        self.t_dispatch = None


class _Tenant(object):
    """One resident program: its scope, feed/fetch contract, bucket
    ladder and serving counters."""

    __slots__ = ('name', 'program', 'scope', 'feed_names', 'fetch_names',
                 'feed_specs', 'mask_specs', 'ladder', 'fingerprint',
                 'pending', 'warmed', 'requests', 'batches', 'rows',
                 'retraces', 'cache_hit_batches', 'pad_rows', 'errors')

    def __init__(self, name, program, scope, feed_names, fetch_names,
                 feed_specs, mask_specs, ladder, fingerprint):
        self.name = name
        self.program = program
        self.scope = scope
        self.feed_names = tuple(feed_names)
        self.fetch_names = list(fetch_names)
        self.feed_specs = dict(feed_specs)
        self.mask_specs = tuple(mask_specs)
        self.ladder = tuple(ladder)
        self.fingerprint = fingerprint
        self.pending = collections.deque()
        self.warmed = False
        self.requests = 0
        self.batches = 0
        self.rows = 0
        self.retraces = 0
        self.cache_hit_batches = 0
        self.pad_rows = 0
        self.errors = 0

    def report(self):
        return {
            'tenant': self.name,
            'fingerprint': self.fingerprint,
            'bucket_ladder': list(self.ladder),
            'warmed': self.warmed,
            'requests_served': self.requests,
            'batches': self.batches,
            'rows': self.rows,
            'cache_hit_batches': self.cache_hit_batches,
            'retraces': self.retraces,
            'pad_rows': self.pad_rows,
            'errors': self.errors,
            'queue_depth': len(self.pending),
        }


class ServingExecutor(object):
    """Multi-tenant continuous-batching server over one Executor.

    Usage::

        srv = serving.ServingExecutor(max_batch=32)
        srv.add_program('ranker', infer_prog, ['x'], [score],
                        scope=ranker_scope)
        srv.warmup()                      # whole ladder, zero-retrace
        fut = srv.submit('ranker', {'x': batch})   # thread-safe
        score, = fut.result()

    ``submit`` never touches the device; the dispatcher thread owns
    batching, padding, H2D staging and async fetch resolution.
    """

    def __init__(self, place=None, max_batch=32, admit_wait_s=0.05,
                 executor=None):
        self._exe = executor or Executor(place)
        self.max_batch = max(1, int(max_batch))
        # idle-dispatcher poll bound only: submit() notifies the
        # condition, so admissions wake the dispatcher immediately —
        # while a batch is in flight it polls with zero wait (the
        # in-flight batch IS the latency floor)
        self._admit_wait_s = float(admit_wait_s)
        self._tenants = {}
        self._rr = []        # tenant round-robin order
        self._rr_next = 0
        self._cond = threading.Condition()
        self._thread = None
        self._stopping = False
        self._closed = False
        _live.add(self)

    # -- registration --------------------------------------------------
    def add_program(self, name, program, feed_names, fetch_list,
                    scope=None, feed_specs=None, bucket_ladder=None):
        """Make `program` resident as tenant `name`.

        `scope` must already hold the program's parameters (run the
        startup program / load_inference_model into it); default: a
        fresh ``core.Scope()``.  `feed_specs` maps feed name ->
        (per-row shape, dtype) for feeds whose declared var shape has
        dynamic non-batch dims; everything else is derived from the
        program's var declarations.  `bucket_ladder` overrides the
        power-of-two row ladder (default: up to ``max_batch``)."""
        from . import framework as _fw
        if name in self._tenants:
            raise ValueError('tenant %r already registered' % name)
        fetch_names = [v.name if isinstance(v, _fw.Variable) else v
                       for v in fetch_list]
        block = program.global_block()
        feed_specs = dict(feed_specs or {})
        specs = {}
        for n in feed_names:
            if n in feed_specs:
                tail, dt = feed_specs[n]
                specs[n] = (tuple(int(s) for s in tail), str(dt))
                continue
            var = block._find_var_recursive(n)
            if var is None:
                raise ValueError('feed %r is not declared by the '
                                 'program' % n)
            tail = tuple(int(s) for s in var.shape[1:])
            if any(s < 0 for s in tail):
                raise ValueError(
                    'feed %r has dynamic non-batch dims %s: pass '
                    'feed_specs={%r: (shape, dtype)} with the padded '
                    'shape the serving path should compile for'
                    % (n, tail, n))
            specs[n] = (tail, core.convert_dtype(var.dtype))
        # '@MASK' companions the program declares but the request
        # contract does not feed: the serving plane synthesizes row
        # masks for them (1=live row, 0=padding)
        mask_specs = []
        for n in feed_names:
            mn = mask_name(n)
            if mn in feed_names:
                continue
            mvar = block._find_var_recursive(mn)
            if mvar is not None:
                if mn in feed_specs:
                    mtail = tuple(int(s) for s in feed_specs[mn][0])
                else:
                    mtail = tuple(int(s) for s in mvar.shape[1:])
                if any(s < 0 for s in mtail):
                    # same contract as the feed path: dynamic non-batch
                    # dims need an explicit padded spec, not a guess
                    raise ValueError(
                        'mask %r has dynamic non-batch dims %s: pass '
                        'feed_specs={%r: (shape, dtype)} with the '
                        'padded shape' % (mn, mtail, mn))
                mask_specs.append((mn, mtail))
        # batch-aggregating fetches (declared leading dim != -1) do not
        # slice back per request and WOULD see the zero pad rows: fail
        # at registration, not with a silently shared wrong aggregate
        for fn in fetch_names:
            fvar = block._find_var_recursive(fn)
            fshape = getattr(fvar, 'shape', None) if fvar is not None \
                else None
            if fshape is not None and (
                    len(fshape) == 0 or int(fshape[0]) >= 0):
                raise ValueError(
                    'fetch %r declares shape %s (a whole-batch '
                    'aggregate, not batch-leading): batch padding '
                    'would change it and it cannot be sliced back per '
                    'request — fetch per-row outputs and aggregate '
                    'client-side' % (fn, tuple(fshape)))
        ladder = tuple(bucket_ladder) if bucket_ladder else \
            tuple(pow2_bucket_ladder(self.max_batch))
        fp = compile_cache.fingerprint(
            block.ops, (), (), donate=False, purpose='serving-id')[:16]
        tenant = _Tenant(name, program, scope or core.Scope(),
                         feed_names, fetch_names, specs, mask_specs,
                         ladder, fp)
        with self._cond:
            self._tenants[name] = tenant
            self._rr.append(name)
        monitor.set_gauge('serving/resident_programs',
                          len(self._tenants))
        return tenant

    # -- warmup --------------------------------------------------------
    def _bucket_feed_shapes(self, tenant, bucket):
        shapes = {}
        for n in tenant.feed_names:
            tail, dt = tenant.feed_specs[n]
            shapes[n] = ((bucket,) + tail, dt)
        for mn, mtail in tenant.mask_specs:
            shapes[mn] = ((bucket,) + tuple(mtail), 'float32')
        return shapes

    def warmup(self, wait=True, timeout=None):
        """Pre-compile every (tenant, bucket) executable through
        ``Executor.warmup`` — disk entries deserialize, the rest
        compile concurrently in the background pool.  `wait=True`
        blocks until the whole ladder resolved and marks tenants
        warmed (``/healthz`` readiness gates on this); `wait=False`
        returns immediately and a background thread flips warmed when
        the compiles land."""
        t0 = _time.perf_counter()
        work = []
        for tenant in self._tenant_list():
            results = []
            for bucket in tenant.ladder:
                res = self._exe.warmup(
                    tenant.program,
                    feed_shapes=self._bucket_feed_shapes(tenant, bucket),
                    fetch_list=tenant.fetch_names,
                    scope=tenant.scope)
                monitor.add('serving/warmup_buckets')
                results.append(res)
            work.append((tenant, results))

        def finish():
            for tenant, results in work:
                for res in results:
                    res.wait(timeout)
                tenant.warmed = True
            monitor.observe('serving/warmup_seconds',
                            _time.perf_counter() - t0)

        if wait:
            finish()
        else:
            threading.Thread(target=finish, daemon=True,
                             name='pt_serving_warmup').start()
        return self

    @property
    def ready(self):
        """True when every registered tenant finished warmup."""
        return all(t.warmed for t in self._tenant_list())

    # -- admission -----------------------------------------------------
    def submit(self, tenant, feed, deadline_s=None):
        """Enqueue one request (a dict of batch-aligned arrays, any
        row count up to the largest bucket) and return a
        ``concurrent.futures.Future`` resolving to the fetch list,
        sliced back to the request's rows.

        `deadline_s` bounds the request's useful life from SUBMIT
        time: a request still queued when its deadline passes is shed
        — completed exceptionally with ``DeadlineExpired``
        (``serving/shed_expired``) instead of padded into a batch and
        dispatched; an ALREADY-expired deadline (``deadline_s <= 0``)
        is shed at admission, before it can queue.  While the replica
        is degraded (supervisor recovery), every submit completes
        exceptionally with ``ServingDegraded`` immediately."""
        from concurrent.futures import Future
        if _degraded_reason is not None:
            # shed, don't queue: a mid-recovery backend answering
            # "try another replica" NOW beats a request parked behind
            # a dead dispatcher
            monitor.add('serving/shed_degraded')
            fut = Future()
            fut.set_exception(ServingDegraded(
                'replica degraded: %s' % _degraded_reason))
            return fut
        t = self._tenants.get(tenant)
        if t is None:
            raise KeyError('unknown tenant %r (resident: %r)'
                           % (tenant, sorted(self._tenants)))
        if deadline_s is not None and float(deadline_s) <= 0:
            # admission-time expiry: a deadline that has already
            # passed must fail fast HERE, not queue behind live work
            # only to be shed at batch close
            monitor.add('serving/shed_expired')
            fut = Future()
            fut.set_exception(DeadlineExpired(
                'request for %r submitted with non-positive deadline '
                '%.3fs: already expired at admission'
                % (tenant, float(deadline_s))))
            return fut
        missing = [n for n in t.feed_names if n not in feed]
        if missing:
            raise ValueError('request for %r missing feeds %r'
                             % (tenant, missing))
        # every feed must agree on the leading (batch) dim: one
        # malformed request must fail HERE, not poison the shapes of
        # the whole coalesced batch it would have joined
        dims = {}
        for n in t.feed_names:
            shape = np.shape(feed[n])
            dims[n] = int(shape[0]) if shape else -1
        if len(set(dims.values())) != 1:
            raise ValueError(
                'request for %r has mismatched leading dims %r: all '
                'feeds must share the batch dim' % (tenant, dims))
        rows = dims[t.feed_names[0]]
        if rows <= 0 or rows > t.ladder[-1]:
            raise ValueError(
                'request rows %d outside (0, %d]: split it or register '
                'the tenant with a larger bucket ladder'
                % (rows, t.ladder[-1]))
        fut = Future()
        req = _Request(tenant, feed, rows, fut, deadline_s=deadline_s)
        with self._cond:
            if self._closed or self._stopping:
                raise RuntimeError('ServingExecutor is stopped')
            t.pending.append(req)
            depth = len(t.pending)
            self._ensure_thread()
            self._cond.notify()
        monitor.add('serving/requests')
        monitor.set_gauge('serving/queue_depth/%s' % tenant, depth)
        return fut

    def infer(self, tenant, feed, timeout=None):
        """Blocking convenience: submit + result."""
        return self.submit(tenant, feed).result(timeout)

    # -- dispatcher ----------------------------------------------------
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name='pt_serving')
            self._thread.start()

    def _take_batch(self, wait_s):
        """Coalesce the next batch: pick the next tenant (round-robin)
        with pending work and drain its queue up to the largest
        bucket.  Returns None when nothing arrived within `wait_s`."""
        with self._cond:
            if not any(t.pending for t in self._tenants.values()):
                if wait_s:
                    self._cond.wait(wait_s)
            n = len(self._rr)
            for i in range(n):
                name = self._rr[(self._rr_next + i) % n]
                t = self._tenants[name]
                if not t.pending:
                    continue
                self._rr_next = (self._rr_next + i + 1) % n
                reqs = []
                rows = 0
                cap = t.ladder[-1]
                now = _time.perf_counter()
                while t.pending and \
                        rows + t.pending[0].rows <= cap:
                    req = t.pending.popleft()
                    if req.deadline is not None and \
                            now > req.deadline:
                        # expired while queued: shed it — padding it
                        # into a batch would spend device time on an
                        # answer whose caller already gave up
                        monitor.add('serving/shed_expired')
                        _deliver(req.future, exc=DeadlineExpired(
                            'request for %r expired %.3fs before '
                            'dispatch (deadline %.3fs after submit)'
                            % (name, now - req.deadline,
                               req.deadline - req.t_admit)))
                        continue
                    # claim the future: a request cancelled while
                    # queued is dropped here, and a claimed future can
                    # no longer be cancelled mid-flight (delivery in
                    # _complete cannot hit InvalidStateError)
                    if not req.future.set_running_or_notify_cancel():
                        continue
                    reqs.append(req)
                    rows += req.rows
                monitor.set_gauge('serving/queue_depth/%s' % name,
                                  len(t.pending))
                if not reqs:
                    continue   # whole window was cancelled
                return _Batch(t, reqs, rows)
        return None

    def _dispatch(self, batch):
        """Pad, stage and dispatch one coalesced batch; returns with
        async fetch handles while the device computes."""
        t = batch.tenant
        batch.t_dispatch = _time.perf_counter()
        try:
            with _trace.span('serving_pad', tenant=t.name,
                             rows=batch.rows):
                if len(batch.requests) == 1:
                    feed = {n: np.asarray(batch.requests[0].feed[n])
                            for n in t.feed_names}
                else:
                    feed = {n: np.concatenate(
                        [np.asarray(r.feed[n]) for r in batch.requests],
                        axis=0) for n in t.feed_names}
                bucket = bucket_for(batch.rows, t.ladder)
                feed, waste = pad_rows_to_bucket(
                    feed, batch.rows, bucket, t.mask_specs)
            batch.bucket = bucket
            monitor.observe('serving/batch_occupancy',
                            batch.rows / float(bucket),
                            OCCUPANCY_BUCKETS)
            if waste:
                monitor.add('serving/bucket_pad_waste_bytes', waste)
            t.pad_rows += bucket - batch.rows
            # server-wide pad-waste ratio, derived from the same
            # per-tenant pad/row tallies the occupancy counters feed
            # (t.rows lands below, so this batch's live rows count in)
            pad_total = rows_total = 0
            for tt in list(self._tenants.values()):
                pad_total += tt.pad_rows
                rows_total += tt.rows
            denom = pad_total + rows_total + batch.rows
            if denom > 0:
                monitor.set_gauge('serving/pad_waste_ratio',
                                  pad_total / float(denom))
            # ONE async H2D for the whole padded batch: the DMA (and
            # everything above: concat, pad) overlaps the in-flight
            # batch's compute
            import jax
            feed = jax.device_put(feed, self._exe.place.jax_device())
            lowered0 = monitor.counter_value('executor/segments_lowered')
            with _trace.step_tags(tenant=t.name, bucket=bucket,
                                  batch_rows=batch.rows):
                batch.handles = self._exe.run(
                    t.program, feed=feed, fetch_list=t.fetch_names,
                    scope=t.scope, return_numpy='async')
            lowered = monitor.counter_value(
                'executor/segments_lowered') - lowered0
            if lowered:
                # a serving-path retrace: warmup missed this
                # (program, bucket) — loud in metrics, never silent
                t.retraces += int(lowered)
                monitor.add('serving/retraces', lowered)
            else:
                t.cache_hit_batches += 1
            t.batches += 1
            t.rows += batch.rows
            monitor.add('serving/batches')
        except Exception as e:  # noqa: BLE001 — delivered per request
            batch.error = e

    def _complete(self, batch):
        """Resolve a dispatched batch's async fetches and deliver each
        request its slice."""
        t = batch.tenant
        if batch.error is None:
            try:
                with _trace.span('serving_fetch', tenant=t.name):
                    outs = [np.asarray(h) for h in batch.handles]
            except Exception as e:  # noqa: BLE001
                batch.error = e
        done = _time.perf_counter()
        if batch.error is not None:
            t.errors += len(batch.requests)
            monitor.add('serving/request_errors',
                        float(len(batch.requests)))
            for req in batch.requests:
                _deliver(req.future, exc=batch.error)
            return
        off = 0
        for req in batch.requests:
            res = [slice_rows(o, off, req.rows, batch.bucket)
                   for o in outs]
            off += req.rows
            t.requests += 1
            monitor.observe('serving/admit_to_done_seconds',
                            done - req.t_admit)
            _deliver(req.future, result=res)

    def _loop(self):
        inflight = None
        while True:
            with self._cond:
                if self._stopping and inflight is None and \
                        not any(t.pending
                                for t in self._tenants.values()):
                    return
            batch = None
            try:
                # dispatch batch k+1 BEFORE resolving batch k's
                # fetches: admission/padding/H2D overlap the
                # in-flight compute
                batch = self._take_batch(
                    0.0 if (inflight or self._stopping)
                    else self._admit_wait_s)
                if batch is not None:
                    self._dispatch(batch)
                if inflight is not None:
                    self._complete(inflight)
                inflight = batch
            except Exception as e:  # noqa: BLE001 — the dispatcher
                # must survive anything: fail what it was holding and
                # keep serving (a dead dispatcher strands every queued
                # future forever)
                monitor.add('serving/dispatcher_errors')
                for b in (inflight, batch):
                    if b is not None:
                        for req in b.requests:
                            _deliver(req.future, exc=e)
                inflight = None

    # -- lifecycle / status --------------------------------------------
    def stop(self, drain=True):
        """Stop the dispatcher.  `drain=True` serves queued requests
        first; otherwise they fail with RuntimeError."""
        with self._cond:
            self._stopping = True
            if not drain:
                for t in self._tenants.values():
                    while t.pending:
                        t.pending.popleft().future.set_exception(
                            RuntimeError('ServingExecutor stopped'))
            self._cond.notify_all()
        th = self._thread
        if th is not None and th.is_alive():
            th.join(timeout=30)

    def close(self):
        """Stop and deregister from the health plane's live set."""
        self.stop(drain=False)
        self._closed = True
        _live.discard(self)

    def _tenant_list(self):
        """Snapshot of the tenant table under the admission lock: the
        health HTTP thread reads this while add_program may be
        inserting."""
        with self._cond:
            return [t for _, t in sorted(self._tenants.items())]

    def resident_report(self):
        """The /statusz 'serving' section: resident programs with
        fingerprint, bucket ladder, requests served and cache
        behavior."""
        tenants = self._tenant_list()
        return {
            'ready': all(t.warmed for t in tenants),
            'max_batch': self.max_batch,
            'tenants': [t.report() for t in tenants],
            'compile_plane': compile_cache.plane().stats(),
        }


# --------------------------------------------------- health integration
def readiness():
    """(ready, reasons) over every live ServingExecutor — (None, [])
    when no serving plane exists, so plain trainers keep the original
    /healthz semantics.  A registered-but-unwarmed tenant makes the
    process unready: a load balancer must not route to a replica that
    would trace on its first request."""
    execs = [s for s in list(_live) if not s._closed]
    if not execs:
        return None, []
    reasons = []
    if _degraded_reason is not None:
        # the supervisor's recovery leg: /healthz flips so routers
        # stop sending traffic while submit() sheds what still arrives
        reasons.append('degraded: %s' % _degraded_reason)
    for s in execs:
        for t in s._tenant_list():
            if not t.warmed:
                reasons.append('serving tenant %r warmup pending'
                               % t.name)
    return (not reasons), reasons


def resident_report():
    """Every live ServingExecutor's resident-program report (the
    /statusz section body)."""
    return [s.resident_report() for s in list(_live)
            if not s._closed]


def tenant_scopes():
    """[(tenant label, scope)] over every live ServingExecutor — the
    memviz census walks these so per-tenant device residency shows up
    in the live-HBM classes and in OOM snapshots."""
    out = []
    for s in list(_live):
        if s._closed:
            continue
        for t in s._tenant_list():
            out.append((t.name, t.scope))
    return out


# census integration: registering the provider at import keeps plain
# trainers unaware of the serving plane (memviz only walks it when this
# module was imported, i.e. when a serving plane can exist)
from . import memviz as _memviz  # noqa: E402

_memviz.register_scope_provider(tenant_scopes)
