"""Seconds the process spent importing ``paddle_tpu``, as the package
itself records them once (``import/paddle_tpu_seconds``).  JAX is
imported before it here, so its import is not in this number."""

LAYER = 'compile plane'
UNIT = 's'
MOVES = 'setup_s'


def read(trace, run):
    if 'setup_seconds' not in run:      # nothing was set up
        return None
    from benchmark.lib import setup_totals
    return setup_totals.totals(run)[setup_totals.IMPORT]
