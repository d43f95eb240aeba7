"""fluid.supervisor — the self-healing controller: automated failure
recovery, the hung-step watchdog, and the signal->decision plane.

PR 11 built every recovery PRIMITIVE — crash-consistent checkpoint
generations, priced cross-topology reshard, ``rejoin_trainer``,
deterministic fault injection — but a human still had to notice a dead
worker and drive the recovery by hand.  This module is the CONTROLLER
(the ROADMAP item-4 follow-on): the first plane where the telemetry
*acts* instead of being read.

**Periodic async checkpoints with backpressure.**  An attached
supervisor snapshots the training program's persistables at a step
boundary every ``FLAGS_supervisor_checkpoint_steps`` steps (host
copies, taken on the training thread so a checkpoint can never mix two
steps' params) and writes the elastic generation on a background
thread — the slow half (hashing + file IO) overlaps training.  Never
two saves in flight: a cadence point reached while a write is still
running defers (``supervisor/checkpoint_deferred``) and retries next
step.  Every save's wall is recorded (``supervisor/save_seconds``);
when the write time approaches the wall-clock distance between cadence
points the cadence doubles (``supervisor/cadence_stretched``) — a
checkpoint plane that cannot keep up must slow down, not pile up.
Each published generation is digest-VERIFIED; a torn write (bitrot,
injected ``elastic.shard_write:torn``) is detected immediately and
re-saved (``supervisor/checkpoint_torn``) so the newest generation is
always trustworthy and lost work stays bounded by ONE cadence.

**Automated failure recovery.**  The controller thread watches the
rank-0 health aggregator's per-worker consecutive-miss state (the
``FLAGS_heartbeat_misses`` signal PR 11 already computes).  On a
CONFIRMED death it prices the degrade path — the reshard schedule from
the last-good manifest through ``elastic.plan_reshard`` /
``comms_plan.predict_seconds`` — against the
``FLAGS_supervisor_rejoin_wait_s`` budget and decides:

- ``degrade_to_survivors`` when resharding is cheaper than the
  worst-case wait: resume from last-good on the surviving topology
  (``elastic.resume`` — the auto-shard planner replans the layout for
  the reduced device count when ``FLAGS_auto_shard`` is on);
- ``wait_for_rejoin`` when resharding costs more than the budget:
  watch for the dead worker's return.  A worker that re-registers
  inside the budget is RE-ADMITTED (its own process resumes via
  ``elastic.rejoin_trainer``; rank 0 just clears the incident); budget
  expiry degrades.  The state machine guarantees exactly ONE recovery
  action per incident — a death + rejoin race can never reshard twice.

Recovery executes on the TRAINING thread at the next step boundary
(``on_step_begin``): the in-flight save is drained, the last-good
generation loads (torn generations refused by name fall back), the
executor's step counter rewinds to the checkpoint step, and control
returns to the train loop by raising ``supervisor.Recovered`` — the
loop catches it and continues, re-reading ``executor._step`` to pick
the right batch.  Lost work is bounded by the checkpoint cadence.

**Hung-step watchdog.**  ``FLAGS_step_timeout_s`` (default off) arms
``guard_dispatch`` around segment dispatch in the executor and both
parallel runners: the dispatch runs on a guard thread, and a
collective blocked past the deadline (dead peer, wedged fabric) dumps
the flight recorder WITH THE IN-FLIGHT SEGMENT NAMED, counts
``executor/step_timeouts``, and raises ``StepTimeoutError`` in the
training thread instead of hanging the process forever.  An active
supervisor converts the timeout into a recovery (the step's donated
state is no longer trustworthy once an abandoned dispatch may have
consumed it).  Disabled cost: one flag read per segment.

**On a serving replica** the supervisor flips ``/healthz`` to degraded
and sheds load during recovery (``serving.enter_degraded``): requests
fail fast instead of queueing into a dead backend.

Every decision is OBSERVABLE — ``supervisor/*`` counters, a bounded
decision log rendered in the ``/statusz`` ``supervisor`` section, a
flight-recorder dump on every state transition — and REVERTIBLE:
``FLAGS_supervisor=0`` freezes the controller (intents are logged with
``acted=False``, nothing executes, ``supervisor/frozen_intents``) and
every primitive stays hand-drivable.  The proof is the chaos soak:
``tools/check_chaos.py`` (``make check``) drives a real multi-process
job through scripted worker kills, torn shard writes, RPC faults,
heartbeat flaps and collective stalls and asserts zero-intervention
completion with every injected fault matched to a logged decision.

Hot-path discipline: no jax imports at module level; an unattached
process pays one module-global read per step (``active()``), a
disarmed watchdog one flag read per segment.
"""

import os
import threading
import time

import numpy as np

from . import monitor
from . import trace
from .flags import get_flag

__all__ = [
    'Supervisor', 'Recovered', 'StepTimeoutError', 'guard_dispatch',
    'attach', 'detach', 'current', 'active', 'report', 'reset',
]

# decision log: module-level (like elastic._refusals) so /statusz keeps
# the trail across supervisor replacement; bounded.
_lock = threading.Lock()
_decisions = []
_DECISIONS_CAP = 64
_seq = [0]

_active = None          # the process's attached Supervisor (or None)

# supervisor states (gauge supervisor/state renders the index)
STATES = ('idle', 'waiting_rejoin', 'recovering', 'degraded')

# runtime counters whose movement the controller logs as 'tolerate'
# decisions (faults the runtime already absorbed)
WATCHED_COUNTERS = ('elastic/heartbeat_flaps', 'rpc/retries',
                    'rpc/dropped_pushes')


class Recovered(RuntimeError):
    """Raised by ``on_step_begin`` after an automated recovery executed:
    the scope was reloaded from generation ``.generation`` and
    ``executor._step`` rewound to ``.step`` — the train loop catches
    this, re-reads the step counter and continues.  `.lost_steps` is
    the work rolled back (bounded by the checkpoint cadence)."""

    def __init__(self, msg, generation=None, step=None, lost_steps=None):
        super(Recovered, self).__init__(msg)
        self.generation = generation
        self.step = step
        self.lost_steps = lost_steps


class StepTimeoutError(RuntimeError):
    """A guarded segment dispatch blocked past FLAGS_step_timeout_s:
    `.segment` names the in-flight segment, `.timeout_s` the armed
    deadline, `.dump_path` the flight-recorder dump."""

    def __init__(self, msg, segment=None, timeout_s=None,
                 dump_path=None):
        super(StepTimeoutError, self).__init__(msg)
        self.segment = segment
        self.timeout_s = timeout_s
        self.dump_path = dump_path


# ------------------------------------------------------------ watchdog
class _GuardWorker(object):
    """One long-lived guard thread per DISPATCHING thread: armed
    watchdog dispatches reuse it call after call (no per-segment
    thread spawn on the hot path).  A timeout ABANDONS the worker —
    it is parked inside the runtime and its eventual result is
    meaningless — and the next dispatch gets a fresh one; the
    abandoned thread exits on its own once the stuck call returns."""

    def __init__(self):
        import queue
        self._q = queue.SimpleQueue()
        self.abandoned = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name='pt_step_guard')
        self._thread.start()

    def _loop(self):
        while True:
            fn, box, done = self._q.get()
            if fn is None:
                return       # poison pill: the owner thread exited
            try:
                box['out'] = fn()
            except BaseException as e:   # delivered to the caller
                box['exc'] = e
            finally:
                done.set()
            if self.abandoned:
                return

    def poison(self):
        """Reap the worker once its owning dispatch thread is gone —
        without this, every exited dispatcher would leave one daemon
        thread parked in SimpleQueue.get() forever."""
        self.abandoned = True
        self._q.put((None, None, None))

    def submit(self, fn):
        box = {}
        done = threading.Event()
        self._q.put((fn, box, done))
        return box, done

    def alive(self):
        return not self.abandoned and self._thread.is_alive()


_guard_tls = threading.local()


class _GuardAnchor(object):
    """Weak-referenceable TLS marker: dies with its dispatch thread,
    and its finalizer reaps that thread's guard worker."""


def guard_dispatch(fn, segment, timeout_s, step=None):
    """Run `fn` (one segment dispatch) under the hung-step watchdog:
    the call executes on this thread's guard worker and this thread
    waits at most `timeout_s`.  On expiry the flight recorder is
    dumped with the in-flight segment named,
    ``executor/step_timeouts`` counts, an active supervisor schedules
    recovery from last-good (the abandoned dispatch may consume
    donated state, so the step is not retryable in place), and
    StepTimeoutError raises — the process is unblocked even though
    the guard worker stays parked in the runtime until the stuck call
    returns (it is daemonic and its result is discarded)."""
    worker = getattr(_guard_tls, 'worker', None)
    if worker is None or not worker.alive():
        import weakref
        worker = _guard_tls.worker = _GuardWorker()
        # the anchor dies with the dispatching thread's TLS: its
        # finalizer reaps the (non-abandoned) worker thread
        anchor = _guard_tls.anchor = _GuardAnchor()
        weakref.finalize(anchor, worker.poison)
    box, done = worker.submit(fn)
    if not done.wait(timeout_s):
        worker.abandoned = True
        monitor.add('executor/step_timeouts')
        path = trace.dump_on_error(
            'step_timeout_step%s' % ('' if step is None else step),
            extra={'incident': 'step_timeout', 'segment': str(segment),
                   'timeout_s': float(timeout_s), 'step': step})
        sup = _active
        if sup is not None:
            sup._on_hung_step(segment, timeout_s, step=step)
        raise StepTimeoutError(
            'segment dispatch [%s] blocked longer than '
            'FLAGS_step_timeout_s=%.3fs (step %s) — a collective '
            'waiting on a dead peer hangs exactly like this; flight '
            'recorder dumped to %s' % (segment, timeout_s, step, path),
            segment=str(segment), timeout_s=float(timeout_s),
            dump_path=path)
    if 'exc' in box:
        raise box['exc']
    return box['out']


# -------------------------------------------------------- peer signals
def _aggregator_peers():
    """Default peer view: the rank-0 health aggregator's per-worker
    consecutive-miss state ({} when this process aggregates nothing)."""
    from . import health
    s = health.server()
    if s is None or s.aggregator is None:
        return {}
    try:
        return s.aggregator.peer_health()
    except Exception:
        return {}


def _price_degrade_default(store_dir):
    """Predicted seconds of the degrade path: the reshard schedule
    from the last-good manifest, priced through the elastic plane's
    ``comms_plan.predict_seconds`` path.  None when nothing loadable
    exists (the controller then degrades — there is nothing to
    reshard, only a restart-from-scratch to avoid blocking on)."""
    from . import elastic
    try:
        gen = elastic.latest_generation(store_dir)
        if gen is None:
            return None
        manifest = elastic.read_manifest(store_dir, gen)
        sched = elastic.plan_reshard(manifest, {})
        return float(sched['predicted_s'])
    except Exception:
        return None


def _serving_module():
    import sys as _sys
    return _sys.modules.get(__package__ + '.serving')


class Supervisor(object):
    """Rank-0 self-healing controller over one training process.

    Usage (the chaos-soak child is the canonical example)::

        sup = supervisor.attach(store_dir, program=main, executor=exe,
                                feed_shapes={'x': x0, 'y': y0},
                                fetch_list=[loss])
        while exe._step < target:
            x, y = batch_for(exe._step)       # key batches on _step
            try:
                exe.run(main, feed=..., fetch_list=[loss])
            except (supervisor.Recovered,
                    supervisor.StepTimeoutError):
                continue                      # loop re-reads _step

    The controller thread watches the health aggregator + runtime
    counters; checkpointing and recovery execute on the TRAINING
    thread at step boundaries (the Executor.run hooks call
    ``on_step_begin``/``on_step_end``).
    """

    def __init__(self, store_dir, program=None, executor=None,
                 scope=None, feed_shapes=None, fetch_list=None,
                 checkpoint_steps=None, rejoin_wait_s=None,
                 interval=0.25, peers=None, price=None, save_fn=None,
                 clock=None):
        from . import core
        self.store_dir = os.path.abspath(store_dir)
        self._program = program
        self._executor = executor
        self._scope = scope or core.global_scope()
        self._feed_shapes = feed_shapes
        self._fetch_list = fetch_list
        if checkpoint_steps is None:
            checkpoint_steps = int(get_flag(
                'FLAGS_supervisor_checkpoint_steps', 0) or 0)
        self._cadence = int(checkpoint_steps)
        self._base_cadence = max(1, self._cadence) if self._cadence \
            else 0
        self._rejoin_wait_s = float(
            rejoin_wait_s if rejoin_wait_s is not None else
            (get_flag('FLAGS_supervisor_rejoin_wait_s', 10.0) or 10.0))
        self.interval = float(interval)
        self._peers = peers or _aggregator_peers
        self._price = price or (
            lambda: _price_degrade_default(self.store_dir))
        self._save_fn = save_fn           # tests inject a slow writer
        self._clock = clock or time.monotonic

        self.state = 'idle'
        self._last_ckpt_step = 0
        self._last_trigger_wall = None
        self._save_thread = None
        self._save_inflight = False
        self._deferred_logged = False
        self._pending_recovery = None     # dict when a recovery waits
        self._down_handled = set()        # ranks with an open incident
        self._wait_rank = None
        self._wait_deadline = None
        # counter-delta watch state, seeded NOW: activity predating
        # the attach (startup RPC retries, old flaps) is not a fault
        # under supervision and must not fabricate tolerate decisions
        self._watched = {k: monitor.counter_value(k)
                         for k in WATCHED_COUNTERS}
        self._stop = threading.Event()
        self._thread = None
        monitor.set_gauge('supervisor/checkpoint_cadence_steps',
                          float(self._cadence))

    # -- lifecycle -----------------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name='pt_supervisor')
            self._thread.start()
        monitor.set_gauge('supervisor/active', 1.0)
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        t = self._save_thread
        if t is not None:
            t.join(timeout=30)
        monitor.set_gauge('supervisor/active', 0.0)

    def _loop(self):
        while not self._stop.is_set():
            try:
                self._tick()
            except Exception:
                monitor.add('supervisor/tick_errors')
            self._stop.wait(self.interval)

    def enabled(self):
        """False = FLAGS_supervisor=0: the controller is FROZEN — it
        keeps watching and logs every intent (acted=False), but
        executes nothing.  The revert switch."""
        return bool(get_flag('FLAGS_supervisor', True))

    # -- decision log --------------------------------------------------
    def _decide(self, kind, choice, acted=True, fault=None, **info):
        frozen = not self.enabled()
        if frozen:
            acted = False
            monitor.add('supervisor/frozen_intents')
        rec = {
            'seq': None, 'wall_unix': time.time(),
            'step': int(getattr(self._executor, '_step', 0) or 0),
            'kind': kind, 'choice': choice, 'acted': bool(acted),
            'frozen': frozen, 'fault': fault, 'state': self.state,
        }
        if info:
            rec['info'] = info
        with _lock:
            _seq[0] += 1
            rec['seq'] = _seq[0]
            _decisions.append(rec)
            del _decisions[:-_DECISIONS_CAP]
        monitor.add('supervisor/decisions')
        monitor.add('supervisor/decision/%s' % kind)
        return rec

    def _set_state(self, new, why=None):
        old = self.state
        if new == old:
            return
        self.state = new
        monitor.set_gauge('supervisor/state',
                          float(STATES.index(new)))
        # every state transition leaves a flight-recorder dump: the
        # steps that led INTO a recovery are exactly what a post-mortem
        # needs, and they evict within FLAGS_trace_buffer_steps.
        # FLAGS_supervisor_dump_interval_s > 0 bounds a transition
        # storm to one dump per interval (shared limiter)
        trace.rate_limited_dump(
            'supervisor/state',
            float(get_flag('FLAGS_supervisor_dump_interval_s', 0.0)
                  or 0.0),
            tag='supervisor_%s' % new, extra={
                'incident': 'supervisor_state', 'from': old, 'to': new,
                'why': why})
        monitor.add('supervisor/state_transitions')

    # -- step hooks (training thread) ----------------------------------
    def _supervises(self, exe):
        """Supervision is pinned to the ATTACHED executor: a second
        executor in the same process (a serving replica's dispatcher,
        a bench/warmup executor) must neither drive the checkpoint
        cadence off its own step counter nor execute a pending
        recovery against the wrong scope."""
        return self._executor is None or exe is self._executor

    def on_step_begin(self, exe):
        if not self._supervises(exe):
            return
        pend = self._pending_recovery
        if pend is not None:
            self._pending_recovery = None
            self._recover(exe, pend)

    def on_step_end(self, exe):
        if self._cadence > 0 and self._supervises(exe):
            self.maybe_checkpoint(exe)

    # -- checkpoint plane ----------------------------------------------
    def maybe_checkpoint(self, exe):
        step = int(getattr(exe, '_step', 0) or 0)
        if step - self._last_ckpt_step < self._cadence:
            return
        if self._save_inflight:
            # backpressure: never two saves in flight — defer to the
            # next step boundary (logged once per episode)
            monitor.add('supervisor/checkpoint_deferred')
            if not self._deferred_logged:
                self._deferred_logged = True
                self._decide('checkpoint', 'deferred_backpressure',
                             step_due=step)
            return
        self._deferred_logged = False
        if not self.enabled():
            self._decide('checkpoint', 'take', acted=False, step=step)
            self._last_ckpt_step = step
            return
        now = self._clock()
        trigger_gap = (now - self._last_trigger_wall) \
            if self._last_trigger_wall is not None else None
        self._last_trigger_wall = now
        t0 = time.perf_counter()
        snap = self._snapshot()
        monitor.observe('supervisor/snapshot_seconds',
                        time.perf_counter() - t0)
        self._last_ckpt_step = step
        self._save_inflight = True
        self._save_thread = threading.Thread(
            target=self._write_generation,
            args=(snap, step, trigger_gap), daemon=True,
            name='pt_supervisor_save')
        self._save_thread.start()

    def _snapshot(self):
        """Host copies of the program's persistables at THIS step
        boundary: the background write then cannot mix two steps'
        params no matter how long it takes."""
        from . import core
        from .io import _persistable_vars
        snap = core.Scope()
        for v in _persistable_vars(self._program):
            val = self._scope.find_var(v.name)
            if val is None:
                raise RuntimeError(
                    'supervisor checkpoint: persistable %r not in '
                    'scope' % v.name)
            snap.set_var(v.name, np.asarray(core.as_array(val)))
        return snap

    def _write_generation(self, snap, step, trigger_gap, retry=False):
        from . import elastic
        import types
        t0 = time.perf_counter()
        shim = types.SimpleNamespace(_step=step)
        try:
            if self._save_fn is not None:
                gen = self._save_fn(self.store_dir, self._program,
                                    snap, shim)
            else:
                gen = elastic.save_checkpoint(
                    self.store_dir, self._program, scope=snap,
                    executor=shim)
            wall = time.perf_counter() - t0
            monitor.observe('supervisor/save_seconds', wall)
            monitor.add('supervisor/checkpoints_taken')
            # post-save verification applies to the real elastic
            # writer only (an injected save_fn publishes nothing the
            # digest pass could read)
            torn = self._verify_generation(gen) \
                if self._save_fn is None else None
            if torn is not None:
                # self-healing of the checkpoint plane itself: a torn
                # write detected NOW costs one resave; detected at
                # recovery time it costs a whole extra cadence of work
                monitor.add('supervisor/checkpoint_torn')
                if not retry:
                    self._decide('checkpoint_torn', 'resave',
                                 fault='torn', generation=gen,
                                 shard=torn.shard, reason=torn.reason)
                    self._write_generation(snap, step, None,
                                           retry=True)
                else:
                    # the RESAVE tore too (persistent bitrot, an
                    # open-ended torn clause): say so — claiming a
                    # good checkpoint here would silently cost an
                    # extra cadence of lost work at recovery time
                    self._decide('checkpoint_torn', 'gave_up',
                                 fault='torn', generation=gen,
                                 shard=torn.shard, reason=torn.reason)
                return
            self._decide('checkpoint', 'take', generation=gen,
                         step=step, save_seconds=round(wall, 4))
            if trigger_gap is not None and wall > 0.5 * trigger_gap:
                # the write ate over half the distance between cadence
                # points: stretch before saves pile into backpressure
                self._cadence *= 2
                monitor.add('supervisor/cadence_stretched')
                monitor.set_gauge(
                    'supervisor/checkpoint_cadence_steps',
                    float(self._cadence))
                self._decide('cadence_stretched', 'double',
                             cadence_steps=self._cadence,
                             save_seconds=round(wall, 4),
                             trigger_gap_s=round(trigger_gap, 4))
        except Exception as e:
            monitor.add('supervisor/checkpoint_errors')
            self._decide('checkpoint', 'failed', error=str(e))
            # rewind the cadence marker so the NEXT step boundary
            # retries: a transient write failure (ENOSPC blip) that
            # silently waited a whole further cadence could double
            # the lost-work bound
            self._last_ckpt_step = min(self._last_ckpt_step,
                                       step - self._cadence)
        finally:
            self._save_inflight = False

    def _verify_generation(self, gen):
        """Digest-verify a just-published generation; returns the
        ElasticCheckpointError on a torn shard, None when intact."""
        from . import elastic
        try:
            elastic.verify_generation(self.store_dir, gen)
            return None
        except elastic.ElasticCheckpointError as e:
            return e

    # -- failure watching (controller thread) --------------------------
    def _tick(self):
        self._watch_counters()
        now = self._clock()
        try:
            peers = self._peers() or {}
        except Exception:
            peers = {}
        for rank in sorted(peers):
            p = peers[rank]
            if p.get('confirmed_down') and rank not in \
                    self._down_handled:
                self._down_handled.add(rank)
                monitor.add('supervisor/deaths_confirmed')
                self._on_confirmed_death(rank, now)
            elif p.get('up') and rank in self._down_handled:
                # the dead worker answered again
                self._down_handled.discard(rank)
                if self._wait_rank == rank:
                    # inside the rejoin budget: re-admission wins; the
                    # returning trainer resumes itself (rejoin_trainer
                    # from last-good) — rank 0 closes the incident
                    # WITHOUT a reshard.  Exactly one recovery action
                    # per incident.
                    self._wait_rank = None
                    self._wait_deadline = None
                    monitor.add('supervisor/rejoins_admitted')
                    self._decide('rejoin', 'readmit', fault='worker_death',
                                 rank=rank)
                    self._set_state('idle', why='rejoined %s' % rank)
                else:
                    self._decide('rejoin', 'late_readmit',
                                 fault='worker_death', rank=rank)
        if self._wait_deadline is not None and \
                now >= self._wait_deadline:
            rank = self._wait_rank
            self._wait_rank = None
            self._wait_deadline = None
            self._decide('death', 'degrade_after_wait',
                         fault='worker_death', rank=rank,
                         budget_s=self._rejoin_wait_s)
            self._schedule_recovery('worker %s never rejoined inside '
                                    'the %.1fs budget'
                                    % (rank, self._rejoin_wait_s),
                                    fault='worker_death', rank=rank)

    def _on_confirmed_death(self, rank, now):
        predicted = None
        try:
            predicted = self._price()
        except Exception:
            predicted = None
        budget = self._rejoin_wait_s
        if self._wait_deadline is not None:
            # a SECOND death while already waiting on another rank:
            # overwriting the wait slot would silently drop the first
            # incident.  Two dead workers is past waiting games —
            # degrade now, closing both incidents with one recovery.
            self._wait_rank = None
            self._wait_deadline = None
            self._decide('death', 'degrade_to_survivors',
                         fault='worker_death', rank=rank,
                         predicted_reshard_s=predicted,
                         budget_s=budget, concurrent_incident=True)
            self._schedule_recovery(
                'worker %s confirmed dead while already waiting on '
                'another rank' % rank, fault='worker_death', rank=rank)
            return
        # decision rule: resharding cheaper than the worst-case wait ->
        # degrade NOW (capacity back in predicted_s); resharding more
        # expensive than the whole budget -> waiting for the worker to
        # rejoin is the cheaper bet, degrade only on budget expiry
        if predicted is not None and predicted >= budget:
            self._decide('death', 'wait_for_rejoin',
                         fault='worker_death', rank=rank,
                         predicted_reshard_s=predicted,
                         budget_s=budget)
            if self.enabled():
                self._wait_rank = rank
                self._wait_deadline = now + budget
                self._set_state('waiting_rejoin',
                                why='worker %s down' % rank)
        else:
            self._decide('death', 'degrade_to_survivors',
                         fault='worker_death', rank=rank,
                         predicted_reshard_s=predicted,
                         budget_s=budget)
            self._schedule_recovery(
                'worker %s confirmed dead; reshard predicted %.4fs '
                'under the %.1fs rejoin budget'
                % (rank, predicted or 0.0, budget),
                fault='worker_death', rank=rank)

    def _watch_counters(self):
        """Signal->decision for faults the runtime already absorbs
        (the controller's 'tolerate' legs): RPC retry/backoff
        engagement and heartbeat flaps get a logged decision so a
        chaos run can match EVERY injected fault to one."""
        kinds = {'elastic/heartbeat_flaps': 'heartbeat_flap',
                 'rpc/retries': 'rpc_backoff',
                 'rpc/dropped_pushes': 'rpc_drop'}
        for key in WATCHED_COUNTERS:
            kind = kinds[key]
            cur = monitor.counter_value(key)
            prev = self._watched.get(key, 0.0)
            if cur > prev:
                self._watched[key] = cur
                self._decide(kind, 'tolerate', fault=kind,
                             count=cur - prev, counter=key)

    def _on_hung_step(self, segment, timeout_s, step=None):
        """Called by guard_dispatch on the training thread when a
        dispatch blew the deadline: the abandoned dispatch may consume
        donated state, so the only safe continuation is recovery from
        last-good."""
        monitor.add('supervisor/hung_steps')
        self._decide('hung_step', 'recover_from_last_good',
                     fault='hung_step', segment=str(segment),
                     timeout_s=float(timeout_s), at_step=step)
        self._schedule_recovery(
            'segment %s blocked > %.3fs' % (segment, timeout_s),
            fault='hung_step')

    # -- recovery ------------------------------------------------------
    def _schedule_recovery(self, why, **info):
        if not self.enabled():
            self._decide('recovery', 'scheduled', acted=False,
                         why=why, **info)
            return
        if self._pending_recovery is None and \
                self.state != 'recovering':
            self._pending_recovery = dict(info, why=why)

    def _recover(self, exe, pend):
        from . import elastic
        self._set_state('recovering', why=pend.get('why'))
        srv = _serving_module()
        if srv is not None:
            # serving replica: shed load instead of queueing requests
            # into a backend that is mid-recovery
            srv.enter_degraded('supervisor recovery: %s'
                               % pend.get('why'))
        t0 = time.perf_counter()
        step_before = int(getattr(exe, '_step', 0) or 0)
        t = self._save_thread
        if t is not None and t.is_alive():
            # drain the in-flight save first: it may hold the newest
            # consistent state, and loading mid-publish is pointless
            t.join(timeout=60)
        try:
            info = elastic.resume(
                exe, self.store_dir, program=self._program,
                feed_shapes=self._feed_shapes,
                fetch_list=self._fetch_list, scope=self._scope)
        except Exception as e:
            monitor.add('supervisor/recovery_errors')
            self._decide('recovery', 'failed', why=pend.get('why'),
                         error=str(e))
            self._set_state('degraded', why='recovery failed')
            # serving stays DEGRADED: the replica's state is
            # half-restored at best — un-shedding traffic into it
            # would route requests at a backend that just failed to
            # recover.  Only a successful recovery clears the latch.
            raise
        wall = time.perf_counter() - t0
        resumed = int(info.get('step') or 0)
        lost = max(0, step_before - resumed)
        # re-sync the checkpoint cadence to the REWOUND step counter:
        # keeping the pre-recovery _last_ckpt_step would suppress
        # post-recovery saves for up to a whole cadence and let a
        # second crash lose ~two cadences of work
        self._last_ckpt_step = resumed
        self._last_trigger_wall = None
        monitor.add('supervisor/recoveries')
        monitor.add('supervisor/lost_steps', float(lost))
        monitor.observe('supervisor/recovery_seconds', wall)
        self._decide('recovery', 'recovered', fault=pend.get('fault'),
                     why=pend.get('why'),
                     generation=info['generation'], resumed_step=resumed,
                     step_before=step_before, lost_steps=lost,
                     reshard=info.get('reshard'),
                     seconds=round(wall, 4))
        self._set_state('idle', why='recovered')
        if srv is not None:
            srv.exit_degraded()
        raise Recovered(
            'supervisor recovered from generation %d (step %d, %d '
            'steps of work rolled back): %s'
            % (info['generation'], resumed, lost, pend.get('why')),
            generation=info['generation'], step=resumed,
            lost_steps=lost)

    # -- /statusz ------------------------------------------------------
    def describe(self):
        return {
            'state': self.state,
            'store_dir': self.store_dir,
            'enabled': self.enabled(),
            'checkpoint_cadence_steps': self._cadence,
            'rejoin_wait_s': self._rejoin_wait_s,
            'save_inflight': self._save_inflight,
            'last_checkpoint_step': self._last_ckpt_step,
            'open_incidents': sorted(self._down_handled),
            'waiting_on': self._wait_rank,
        }


# ------------------------------------------------------- module surface
def attach(store_dir, program=None, executor=None, scope=None,
           start=True, **kwargs):
    """Create, register and start the process supervisor.  The
    Executor.run hooks fire only while one is attached; a second
    attach replaces the first (its controller thread is stopped)."""
    global _active
    sup = Supervisor(store_dir, program=program, executor=executor,
                     scope=scope, **kwargs)
    old = _active
    _active = sup
    if old is not None:
        old.stop()
    if start:
        sup.start()
    return sup


def detach():
    """Stop and unregister the process supervisor (tests, teardown)."""
    global _active
    sup = _active
    _active = None
    if sup is not None:
        sup.stop()


def current():
    return _active


def active():
    """One module-global read: the Executor.run hook gate."""
    return _active is not None


def on_step_begin(exe):
    sup = _active
    if sup is not None:
        sup.on_step_begin(exe)


def on_step_end(exe):
    sup = _active
    if sup is not None:
        sup.on_step_end(exe)


def decisions():
    """A copy of the bounded decision log (newest last)."""
    with _lock:
        return [dict(d) for d in _decisions]


def report():
    """The /statusz ``supervisor`` section: controller state, the
    decision trail, and the counter rollup."""
    sup = _active
    return {
        'active': sup is not None,
        'controller': sup.describe() if sup is not None else None,
        'decisions': decisions(),
        'counters': {
            k: monitor.counter_value('supervisor/' + k)
            for k in ('decisions', 'checkpoints_taken',
                      'checkpoint_deferred', 'checkpoint_torn',
                      'cadence_stretched', 'deaths_confirmed',
                      'recoveries', 'lost_steps', 'hung_steps',
                      'rejoins_admitted', 'frozen_intents')},
        'step_timeouts': monitor.counter_value(
            'executor/step_timeouts'),
    }


def reset():
    """Drop the decision log and detach (tests)."""
    detach()
    with _lock:
        del _decisions[:]
        _seq[0] = 0
