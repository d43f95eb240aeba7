"""What JAX itself reports while a run goes on: every backend compile
(a jit that quietly re-specialises is invisible to the executor's own
``segment_cache_miss``) and the persistent cache's hits and misses.
Copied from ``chip_smoke.py`` (PR 21), with one correction.

``backend_compile_duration`` fires around JAX's
``compile_or_get_cached``, so also for a program served from the
persistent cache (its duration is then the time to load it; measured on
the chip in PR 22: 4 hits and 5 such events in every warm run).  So
``compiles`` counts every program this process had to obtain, and
``compiles - cache_hits`` those the compiler really built.
"""

_COMPILE = '/jax/core/compile/backend_compile_duration'
_CACHE = '/jax/compilation_cache/'


class CompileListener(object):
    """Counts from the moment it is made; JAX keeps listeners for the
    life of the process, so make one per process."""

    def __init__(self):
        import jax
        self.compile_seconds = []       # one entry per backend compile
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE:
            self.compile_seconds.append(duration)

    def _on_event(self, event, **_):
        if event == _CACHE + 'cache_hits':
            self.cache_hits += 1
        elif event == _CACHE + 'cache_misses':
            self.cache_misses += 1

    @property
    def compiles(self):
        return len(self.compile_seconds)

    @property
    def built(self):
        """Programs the compiler built: not served from the cache."""
        return self.compiles - self.cache_hits

    def snapshot(self):
        return {'compiles': self.compiles, 'built': self.built,
                'compile_seconds': list(self.compile_seconds),
                'cache_hits': self.cache_hits,
                'cache_misses': self.cache_misses}
