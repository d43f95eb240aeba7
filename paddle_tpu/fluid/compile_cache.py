"""AOT compile plane: content-addressed segment executables, persisted.

Reference contract: Executor::Prepare caches an ExecutorPrepareContext
per program IN-PROCESS (framework/executor.h:81).  In the TPU-native
rebuild the dominant cold cost is not op-plan preparation but the XLA
trace+compile of every segment — paid serially inside the first
``Executor.run()`` of EVERY process.  A production service that
restarts, autoscales and re-shards pays it on every replica.  This
module amortizes that cost behind a stable abstraction boundary (the
Tensor-Processing-Primitives argument, arXiv:2104.05755):

- ``fingerprint(...)``: a canonical content hash over everything that
  determines a segment's lowering — op descs (type/inputs/outputs/
  attrs, recursing into control-flow sub-blocks), boundary arg
  shapes/dtypes, the flags that change lowering, donation, backend and
  jax/jaxlib versions.  Two structurally identical segments — in this
  process, another process, or another program object — share one
  fingerprint.

- an always-on in-memory executable map (LRU) keyed by fingerprint, so
  ``Executor.run``, ``Executor.compile``/``CompiledStep`` and re-built
  plans share executables instead of re-tracing.

- a persistent on-disk store (``FLAGS_compile_cache_dir`` /
  ``PADDLE_TPU_COMPILE_CACHE_DIR``): serialized AOT executables
  (jax.experimental.serialize_executable) written atomically
  (tmpfile + os.replace) and read corrupt-tolerantly — a truncated or
  stale entry recompiles, never crashes.  JAX's own persistent
  compilation cache is switched on beside it, so compiles that bypass
  the segment store (CompiledStep jits, parallel/collective runners,
  bucket counters) still dedupe their XLA compile across processes;
  WHERE it lives is ``place_jax_cache()``'s rule, not this flag's.

- a background ``ThreadPoolExecutor`` (``FLAGS_compile_threads``) that
  compiles segments concurrently; results are delivered via futures so
  a running step blocks only on the segment it is about to execute
  (``Executor.warmup``).

Hot-path discipline: nothing here runs per step unless the plane is
active (cache dir set or ``warmup()`` called); the steady-state fast
path of PR 2 is untouched when it is off.
"""

import hashlib
import os
import pickle
import tempfile
import threading

from . import monitor
from . import trace as _trace
from .flags import get_flag

# bump when the entry layout or fingerprint recipe changes: old entries
# simply miss instead of deserializing garbage
FORMAT_VERSION = 1

_PICKLE_MAGIC = b'ptcc1\n'



# What JAX itself reports about obtaining programs, folded into
# fluid.monitor under 'compile/*' as it happens (only ever at compile
# time, never per step):
#
#   compile/trace_seconds, _count          tracing python to jaxprs
#   compile/lower_seconds, _count          lowering jaxprs to MLIR
#   compile/backend_built_seconds, _count  programs the compiler built
#   compile/backend_loaded_seconds, _count programs JAX's persistent
#                                          cache served (load time)
#
# JAX announces a persistent-cache hit with an event on the compiling
# thread just before the backend_compile_duration of that program
# ends, so a per-thread flag splits built from loaded (the warmup pool
# compiles concurrently); compile_lowered() reads the same flag's
# count around ONE compile.
_JAX_CACHE_HIT = '/jax/compilation_cache/cache_hits'
_JAX_DURATIONS = {
    '/jax/core/compile/jaxpr_trace_duration': 'compile/trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'compile/lower',
}
_JAX_BACKEND = '/jax/core/compile/backend_compile_duration'
_hits = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _on_jax_event(event, **_):
    if event == _JAX_CACHE_HIT:
        _hits.n = getattr(_hits, 'n', 0) + 1
        _hits.pending = True


def _on_jax_duration(event, seconds, **_):
    if event == _JAX_BACKEND:
        served = getattr(_hits, 'pending', False)
        _hits.pending = False
        name = 'compile/backend_loaded' if served \
            else 'compile/backend_built'
    else:
        name = _JAX_DURATIONS.get(event)
        if name is None:
            return
    monitor.add(name + '_seconds', seconds)
    monitor.add(name + '_count')


def listen():
    """Register the listeners above with JAX, once per process.  The
    package calls this as it is imported, so the 'compile/*' totals
    cover every program the process obtained."""
    global _listening
    with _listener_lock:
        if not _listening:
            import jax
            jax.monitoring.register_event_listener(_on_jax_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _listening = True


def compile_lowered(lowered):
    """``lowered.compile()`` for an executable bound for the segment
    store -> ``(compiled, from_jax_cache)``.  The bit travels WITH the
    executable (build() -> obtain()/submit() -> disk_store()): one JAX
    re-loaded from its own persistent cache re-serializes to a payload
    that loads cleanly and then fails at its first dispatch
    ("Function ... not found", CPU backend, jaxlib 0.9.0), so the
    store must not publish it."""
    listen()        # the hit counter below is the listener's
    before = getattr(_hits, 'n', 0)
    compiled = lowered.compile()
    return compiled, getattr(_hits, 'n', 0) != before


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_jax_cache():
    """The one rule for where JAX's persistent compilation cache lives
    (chip_smoke.py and the segment store both call this and
    nothing else sets the directory): where JAX_COMPILATION_CACHE_DIR
    is set, JAX has already read it and no code moves it; otherwise
    ``<checkout>/.jax_cache``, computed from this file's location — the
    path is part of every cache key, so it never derives from a
    temporary name, a pid or the clock.  Returns the directory."""
    d = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not d:
        import jax
        d = os.path.join(_CHECKOUT, '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', d)
    return d

class LRUCache(object):
    """Dict-shaped LRU used for the plan cache, per-segment executable
    cache and the plane's process-wide executable map.  ``cap <= 0``
    means unbounded.  Evictions bump ``evict_stat`` so long-running
    services can see cache churn (``executor/segment_cache_evictions``
    etc.)."""

    __slots__ = ('_d', 'cap', 'evict_stat')

    def __init__(self, cap=0, evict_stat=None):
        # cap may be a callable (re-read per insertion) so set_flags
        # on a capacity flag affects ALREADY-built caches — notably
        # the default main program's plan cache, constructed at import
        self._d = {}
        self.cap = cap if callable(cap) else int(cap or 0)
        self.evict_stat = evict_stat

    def _capacity(self):
        c = self.cap
        return int(c() or 0) if callable(c) else c

    def get(self, key, default=None):
        d = self._d
        try:
            v = d.pop(key)
        except KeyError:
            return default
        d[key] = v          # move to MRU position
        return v

    def __getitem__(self, key):
        v = self.get(key, _MISSING)
        if v is _MISSING:
            raise KeyError(key)
        return v

    def __setitem__(self, key, value):
        d = self._d
        d.pop(key, None)
        d[key] = value
        cap = self._capacity()
        if cap > 0:
            while len(d) > cap:
                d.pop(next(iter(d)))
                if self.evict_stat:
                    monitor.add(self.evict_stat)

    def __contains__(self, key):
        return key in self._d

    def __iter__(self):
        return iter(list(self._d))

    def __len__(self):
        return len(self._d)

    def keys(self):
        return list(self._d)

    def values(self):
        return list(self._d.values())

    def items(self):
        return list(self._d.items())

    def pop(self, key, default=None):
        return self._d.pop(key, default)

    def clear(self):
        self._d.clear()


_MISSING = object()

# ---------------------------------------------------------------- hashing

# attrs that never change the lowering: creation-site stacks and the
# cached host-side bucket-count jits
_VOLATILE_ATTRS = ('__op_callstack__', '__count_fn__')


def _hash_obj(h, v):
    """Feed one python value into the hash with type tags, so e.g. the
    string '1' and the int 1 never collide."""
    import numpy as np
    if v is None:
        h.update(b'N')
    elif isinstance(v, bool):
        h.update(b'B1' if v else b'B0')
    elif isinstance(v, (int, np.integer)):
        h.update(b'I' + str(int(v)).encode())
    elif isinstance(v, (float, np.floating)):
        h.update(b'F' + repr(float(v)).encode())
    elif isinstance(v, str):
        h.update(b'S' + v.encode('utf-8', 'replace'))
    elif isinstance(v, bytes):
        h.update(b'Y' + v)
    elif isinstance(v, np.ndarray):
        h.update(b'A' + str(v.dtype).encode() + str(v.shape).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    elif isinstance(v, (list, tuple)):
        h.update(b'L%d(' % len(v))
        for x in v:
            _hash_obj(h, x)
        h.update(b')')
    elif isinstance(v, dict):
        h.update(b'D%d(' % len(v))
        for k in sorted(v, key=str):
            _hash_obj(h, str(k))
            _hash_obj(h, v[k])
        h.update(b')')
    else:
        # rare attr kinds (dtypes, enums): repr is stable enough and a
        # collision only costs a spurious cache miss/hit within one
        # repr class — never silent corruption of a DIFFERENT entry
        h.update(b'R' + repr(v).encode('utf-8', 'replace'))


def _hash_ops(h, ops, seen_blocks):
    """Canonical op-desc walk, recursing into control-flow sub-blocks
    (their ops are part of the parent segment's lowering)."""
    for op in ops:
        h.update(b'OP' + op.type.encode())
        for label, io in ((b'in', op.inputs), (b'out', op.outputs)):
            h.update(label)
            for slot in sorted(io):
                _hash_obj(h, slot)
                _hash_obj(h, io[slot])
        for k in sorted(op.attrs):
            if k in _VOLATILE_ATTRS:
                continue
            _hash_obj(h, k)
            _hash_obj(h, op.attrs[k])
        sub = op.attrs.get('sub_block')
        if isinstance(sub, int) and sub not in seen_blocks:
            seen_blocks.add(sub)
            h.update(b'SUB%d(' % sub)
            _hash_ops(h, op.block.program.blocks[sub].ops, seen_blocks)
            h.update(b')')


_env_key_cache = None


def _env_key():
    """Everything environmental that invalidates an executable: jax and
    jaxlib versions, backend, device kind/count, process count.  Tests
    monkeypatch this to simulate a version bump."""
    global _env_key_cache
    if _env_key_cache is None:
        import jax
        import jaxlib
        dev = jax.devices()[0]
        _env_key_cache = (FORMAT_VERSION, jax.__version__,
                          jaxlib.__version__, jax.default_backend(),
                          getattr(dev, 'device_kind', '?'),
                          jax.device_count(), jax.process_count())
    return _env_key_cache


_canon_memo = {}


def canonical_dtype(dt):
    """The dtype jax will actually trace/compile under (x64-disabled
    canonicalization folds i64->i32, f64->f32): spec keys computed
    from raw host values and from staged device arrays must agree.
    Memoized — this runs per argument per step when the plane is on
    (the memo is tiny: one entry per distinct dtype object seen)."""
    try:
        return _canon_memo[dt]
    except (KeyError, TypeError):
        pass
    import numpy as np
    import jax
    out = np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dt)))
    try:
        _canon_memo[dt] = out
    except TypeError:
        pass  # unhashable dtype carrier: skip the memo
    return out


def arg_specs(*arg_dicts):
    """Canonical (name, shape, dtype) spec tuple over bound argument
    dicts, sorted by name: jax flattens dict pytrees in sorted-key
    order, so two dicts with the same (name -> aval) mapping are the
    same executable interface regardless of insertion order — the key
    must agree (the binder and warmup build their dicts differently)."""
    import numpy as np
    out = []
    for d in arg_dicts:
        row = tuple(sorted(
            (n, tuple(int(s) for s in getattr(v, 'shape', ())),
             canonical_dtype(getattr(v, 'dtype', np.float32)).str)
            for n, v in d.items()))
        out.append(row)
    return tuple(out)


def fingerprint(ops, specs, flag_items, donate=True, purpose='aot'):
    """Hex digest naming one segment executable.  `specs` is the
    arg_specs() tuple (or () for shape-polymorphic jit entries),
    `flag_items` the lowering-changing flag values, `purpose`
    distinguishes executable families ('aot' run path, 'jit'
    CompiledStep, 'parallel'/'collective' runners)."""
    h = hashlib.sha256()
    _hash_obj(h, _env_key())
    _hash_obj(h, purpose)
    _hash_obj(h, bool(donate))
    _hash_obj(h, tuple(flag_items))
    _hash_obj(h, specs)
    _hash_ops(h, ops, set())
    return h.hexdigest()


# ---------------------------------------------------------------- plane
class CompilePlane(object):
    """Process-wide compile plane: fingerprint -> executable (or a
    Future still compiling), plus the on-disk store."""

    def __init__(self):
        self._lock = threading.RLock()
        self._mem = LRUCache(
            int(get_flag('FLAGS_compile_cache_memory_capacity', 256)
                or 256))
        # fp -> {name: (shape, dtype_str)}; LRU like the executable
        # map — a long-running service cycling programs must not leak
        self._outspecs = LRUCache(
            int(get_flag('FLAGS_compile_cache_memory_capacity', 256)
                or 256))
        # lazily jitted callables the runners hold, with what their
        # first call was given: key -> (weak reference to the callable,
        # lowering args, the (program, segment) its memory row is filed
        # under).  Weak, so a dead segment's program is not kept alive
        # from here; read by _held() only
        self._lazy = LRUCache(
            int(get_flag('FLAGS_compile_cache_memory_capacity', 256)
                or 256))
        # key -> (weak reference to the program, what held_tables'
        # builder made of its HLO text)
        self._built = {}
        self._pool = None
        self._warmed = False
        self._wired_dir = None
        self._dir_memo = None   # (raw flag value, normalized path)

    def note_out_specs(self, fp, out_specs):
        """Remember a segment's output specs so warmup() can propagate
        boundary shapes to downstream segments without re-tracing."""
        if out_specs:
            with self._lock:
                self._outspecs[fp] = out_specs

    def out_specs(self, fp):
        with self._lock:
            return self._outspecs.get(fp)

    # -- configuration -------------------------------------------------
    def cache_dir(self):
        """The persistent store directory, or None.  Read per call so
        set_flags({'FLAGS_compile_cache_dir': ...}) takes effect
        immediately; switches jax's own persistent cache on at first
        sight of a directory.  The normalization is memoized on the
        raw flag
        value — this runs on the (plane-active) step path."""
        raw = get_flag('FLAGS_compile_cache_dir') or None
        if not raw:
            return None
        memo = self._dir_memo
        if memo is not None and memo[0] == raw:
            return memo[1]
        d = os.path.abspath(os.path.expanduser(str(raw)))
        if d != self._wired_dir:
            self._wire_jax_cache(d)
        self._dir_memo = (raw, d)
        return d

    def _wire_jax_cache(self, d):
        with self._lock:
            if d == self._wired_dir:
                return
            try:
                os.makedirs(os.path.join(d, 'segments'), exist_ok=True)
            except OSError as e:  # unwritable dir: run uncached
                monitor.add('executor/compile_cache_errors')
                import warnings
                warnings.warn('compile cache dir %r unusable: %s'
                              % (d, e))
                return
            import jax
            place_jax_cache()
            # small programs compile in ms; cache them anyway — the
            # point is process-restart latency, not compile CPU
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', 0.0)
            jax.config.update(
                'jax_persistent_cache_min_entry_size_bytes', -1)
            self._wired_dir = d

    @property
    def active(self):
        """AOT run-path switch: on when a cache dir is configured or a
        warmup() primed this process.  Off (the default) leaves the
        PR-2 steady-state fast path byte-identical."""
        return self._warmed or bool(self.cache_dir())

    def mark_warmed(self):
        self._warmed = True

    def pool(self):
        with self._lock:
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor
                n = int(get_flag('FLAGS_compile_threads', 0) or 0)
                if n <= 0:
                    n = min(4, os.cpu_count() or 1)
                self._pool = ThreadPoolExecutor(
                    max_workers=n,
                    thread_name_prefix='pt_compile')
            return self._pool

    # -- disk store ----------------------------------------------------
    def _entry_path(self, fp):
        d = self.cache_dir()
        return os.path.join(d, 'segments', fp + '.pkl') if d else None

    def disk_store(self, fp, compiled, out_specs=None,
                   from_jax_cache=False):
        """Serialize one AOT executable atomically; failures (backend
        without serialization support, read-only dir) degrade to the
        jax-level cache, never to an error."""
        path = self._entry_path(fp)
        if path is None:
            return False
        if from_jax_cache:
            # compile_lowered()'s word on `compiled`; JAX's own cache
            # holds it, so a restart still skips the compile
            monitor.add('executor/compile_cache_skipped_jax_hit')
            return False
        try:
            from jax.experimental.serialize_executable import (
                serialize, deserialize_and_load)
            with _trace.span('cache_serialize', fp=fp[:12]):
                payload, in_tree, out_tree = serialize(compiled)
            # round-trip proof BEFORE publishing: an executable that
            # .compile() itself re-loaded from the XLA-level persistent
            # cache serializes to a payload whose symbols cannot be
            # re-loaded (observed on the CPU backend) — writing it
            # would poison the store for every future process.  The
            # reload is over the devices the executable was built for:
            # by default deserialize_and_load spreads it over EVERY
            # device of the backend and a one-device executable is
            # refused ("8 shards, got: [1]")
            devices = compiled.runtime_executable().local_devices()
            deserialize_and_load(payload, in_tree, out_tree,
                                 execution_devices=devices)
            blob = _PICKLE_MAGIC + pickle.dumps(
                {'fp': fp, 'payload': payload, 'in_tree': in_tree,
                 'out_tree': out_tree, 'out_specs': out_specs,
                 'device_ids': [d.id for d in devices]},
                protocol=pickle.HIGHEST_PROTOCOL)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       prefix='.tmp_' + fp[:8])
            try:
                with os.fdopen(fd, 'wb') as f:
                    f.write(blob)
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            monitor.add('executor/compile_cache_disk_writes')
            return True
        except Exception:
            monitor.add('executor/compile_cache_errors')
            return False

    def disk_load(self, fp, with_specs=False):
        """Load one executable from disk, tolerating corruption: a
        truncated/garbage/stale entry counts
        ``executor/compile_cache_corrupt``, is unlinked, and the caller
        recompiles.  Returns the loaded executable (optionally with the
        recorded out_specs) or None."""
        path = self._entry_path(fp)
        if path is None or not os.path.exists(path):
            return None
        try:
            with _trace.span('cache_deserialize', fp=fp[:12]):
                with open(path, 'rb') as f:
                    blob = f.read()
                if not blob.startswith(_PICKLE_MAGIC):
                    raise ValueError('bad magic')
                rec = pickle.loads(blob[len(_PICKLE_MAGIC):])
                if rec.get('fp') != fp:
                    raise ValueError('fingerprint mismatch')
                from jax.experimental.serialize_executable import \
                    deserialize_and_load
                import jax
                by_id = {d.id: d for d in jax.devices()}
                compiled = deserialize_and_load(
                    rec['payload'], rec['in_tree'], rec['out_tree'],
                    execution_devices=[by_id[i]
                                       for i in rec['device_ids']])
            if with_specs:
                return compiled, rec.get('out_specs')
            return compiled
        except Exception:
            monitor.add('executor/compile_cache_corrupt')
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    # -- executable map ------------------------------------------------
    def lookup(self, fp):
        """Memory-map probe (no disk, no blocking): the executable, a
        Future, or None."""
        with self._lock:
            return self._mem.get(fp)

    def store(self, fp, value):
        with self._lock:
            self._mem[fp] = value

    def obtain(self, fp, build, disk=True):
        """The run-path resolution order: memory (hit), in-flight
        future (block on THIS segment only), disk (deserialize), else
        `build()` (trace+compile) and publish both layers.  `build`
        returns (compiled, out_specs_or_None, from_jax_cache) — the
        last as compile_lowered() reported it."""
        from concurrent.futures import Future
        v = self.lookup(fp)
        if v is not None and not isinstance(v, Future):
            monitor.add('executor/compile_cache_memory_hit')
            return v
        if isinstance(v, Future):
            try:
                ex = v.result()
                self.store(fp, ex)
                return ex
            except Exception:
                # a background compile died (e.g. a warmup spec that
                # does not match reality): fall through and build live
                with self._lock:
                    if self._mem.get(fp) is v:
                        self._mem.pop(fp)
        disk = disk and self.cache_dir() is not None
        if disk:
            loaded = self.disk_load(fp, with_specs=True)
            if loaded is not None:
                ex, out_specs = loaded
                monitor.add('executor/compile_cache_disk_hit')
                self.store(fp, ex)
                # keep the recorded out specs: a later warmup() then
                # skips the foreground re-trace of this segment
                self.note_out_specs(fp, out_specs)
                # a restarted process builds nothing, so the memory
                # accounting (executor/segment_*_bytes, /statusz)
                # must ride the disk hit or it would go dark exactly
                # in the zero-retrace posture
                from . import comms
                comms.record_memory('fp:%s' % fp[:12], ex)
                return ex
            monitor.add('executor/compile_cache_disk_miss')
        ex, out_specs, from_jax_cache = build()
        self.store(fp, ex)
        self.note_out_specs(fp, out_specs)
        if disk:
            self.disk_store(fp, ex, out_specs, from_jax_cache)
        return ex

    def submit(self, fp, build, disk=True):
        """Background variant of obtain(): publish a Future under `fp`
        and compile in the pool.  Returns the future (or the already-
        resolved value)."""
        from concurrent.futures import Future
        with self._lock:
            v = self._mem.get(fp)
            if v is not None:
                return v
            fut = Future()
            self._mem[fp] = fut

        disk = disk and self.cache_dir() is not None

        def run():
            try:
                if disk:
                    loaded = self.disk_load(fp, with_specs=True)
                    if loaded is not None:
                        ex, out_specs = loaded
                        monitor.add('executor/compile_cache_disk_hit')
                        fut.set_result(ex)
                        self.store(fp, ex)
                        self.note_out_specs(fp, out_specs)
                        from . import comms
                        comms.record_memory('fp:%s' % fp[:12], ex)
                        return
                    monitor.add('executor/compile_cache_disk_miss')
                ex, out_specs, from_jax_cache = build()
                fut.set_result(ex)
                self.store(fp, ex)
                self.note_out_specs(fp, out_specs)
                if disk:
                    self.disk_store(fp, ex, out_specs, from_jax_cache)
            except BaseException as e:
                fut.set_exception(e)

        self.pool().submit(run)
        return fut

    def entry_count(self):
        """Resident executable-map entries (compiled or in flight)."""
        with self._lock:
            return len(self._mem)

    def stats(self):
        """One JSON-able snapshot of the plane for status surfaces
        (fluid.health /statusz, fluid.serving resident report):
        residency plus the hit/miss/compile counters."""
        return {
            'memory_entries': self.entry_count(),
            'cache_dir': self.cache_dir(),
            'warmed': self._warmed,
            'memory_hits': monitor.counter_value(
                'executor/compile_cache_memory_hit'),
            'disk_hits': monitor.counter_value(
                'executor/compile_cache_disk_hit'),
            'disk_misses': monitor.counter_value(
                'executor/compile_cache_disk_miss'),
            'aot_compiles': monitor.counter_value(
                'executor/aot_compiles'),
        }

    def note_lazy(self, key, jitted, lowering_args, label=None):
        """A runner's word, at the first call of a lazily jitted
        segment, on how to find the compiled program again without a
        second trace: ``jitted.lower(*lowering_args)`` repeats the
        call's signature, so jit's own caches serve the lowering and
        the compile.  ``label``: the (program, segment) fluid.memviz
        files the executable's memory row under."""
        import weakref
        with self._lock:
            self._lazy[key] = (weakref.ref(jitted), lowering_args, label)

    def _held(self):
        """[(key, the object that is the program while it lives, () ->
        its executable, what a lazy jit was noted with or None)]: the
        AOT executables of the map and the lazily jitted callables the
        runners noted (``(lowering args, label)``)."""
        from concurrent.futures import Future
        with self._lock:
            held = [(fp, ex, lambda ex=ex: ex, None)
                    for fp, ex in self._mem.items()
                    if not isinstance(ex, Future) and
                    hasattr(ex, 'as_text')]
            lazy = [(key, ref(), args, label)
                    for key, (ref, args, label) in self._lazy.items()]
        for key, jitted, args, label in lazy:
            if jitted is not None:      # else its segment is gone
                held.append((key, jitted, lambda jitted=jitted, args=args:
                             jitted.lower(*args).compile(), (args, label)))
        return held

    def held_executables(self):
        """[(key, executable, noted)] of every executable this process
        holds; ``noted`` is None for an AOT executable of the map and
        ``(lowering args, label)`` for a lazily jitted one, whose
        executable jit's own caches answer (``_held``).  On demand only
        (fluid.memviz's tables), as ``held_hlo``."""
        return [(key, executable(), noted)
                for key, _program, executable, noted in self._held()]

    @staticmethod
    def _hlo_text(key, executable):
        text = executable.as_text()
        if not text:
            raise RuntimeError(
                'the executable %r gives no HLO text, so no scope '
                'table can be built for it' % (key,))
        return text

    def held_hlo(self, skip=()):
        """[(key, optimised HLO text)] of every executable this process
        holds (but those whose key is in ``skip``: printing a module
        costs seconds): the AOT executables of the map and the lazily
        jitted callables the runners noted.  On demand only (fluid.profiler's
        scope table), never on the step path: it prints whole modules,
        and where jit's caches were dropped a lazily jitted callable
        lowers and compiles again.  A program that cannot be lowered
        again, or an executable that keeps no HLO, raises: a table
        that silently lacked a program would move all of its device
        time to 'unattributed'."""
        return [(key, self._hlo_text(key, executable()))
                for key, _program, executable, _noted in self._held()
                if key not in skip]

    def held_tables(self, build):
        """[(key, build(optimised HLO text))] of the same executables,
        each lowered, printed and built ONCE while the process holds it
        (fluid.profiler's scope and cost tables come from one parse,
        and a second trace of the process parses nothing again).  A
        key can come back for another program (a lazy key holds an
        ``id``), so an entry counts only while the object it was built
        for is the one held.  One builder a process: the profiler's.
        Raises what ``held_hlo`` raises."""
        import weakref
        kept = {}
        out = []
        for key, program, executable, _noted in self._held():
            hit = self._built.get(key)
            if hit is None or hit[0]() is not program:
                try:
                    ref = weakref.ref(program)
                except TypeError:   # an executable that takes no weak
                    ref = lambda program=program: program   # reference
                hit = (ref, build(self._hlo_text(key, executable())))
            kept[key] = hit
            out.append((key, hit[1]))
        self._built = kept      # what is gone from the plane goes here too
        return out

    def shared_jit(self, fp, make_fn):
        """One process-wide jit callable per fingerprint, for the
        shape-polymorphic users (CompiledStep, parallel runners): the
        SECOND identical segment reuses the first one's traced jit
        object instead of paying a fresh trace, and with a cache dir
        set the underlying XLA compile dedupes across processes via
        jax's persistent cache."""
        with self._lock:
            v = self._mem.get(fp)
            if v is not None:
                monitor.add('executor/compile_cache_memory_hit')
                return v
        jitted = make_fn()
        self.store(fp, jitted)
        return jitted


_plane = None
_plane_lock = threading.Lock()


def plane():
    global _plane
    if _plane is None:
        with _plane_lock:
            if _plane is None:
                _plane = CompilePlane()
    return _plane


def reset_plane():
    """Drop the process-wide plane (tests): in-memory executables and
    the warmed flag go away; on-disk entries and jax config survive."""
    global _plane
    with _plane_lock:
        old, _plane = _plane, None
    if old is not None and old._pool is not None:
        old._pool.shutdown(wait=False)
    return old
