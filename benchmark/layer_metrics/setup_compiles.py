"""Programs the compiler built during set-up, that is, not served from
the persistent cache: JAX's own ``backend_compile_duration`` events
less its ``cache_hits`` (``benchmark/lib/listener.py``)."""

LAYER = 'compile plane'
UNIT = 'count'
MOVES = 'setup_s'


def read(trace, run):
    return float(run['built_in_setup'])
