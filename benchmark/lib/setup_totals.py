"""The program's own totals of what obtaining its programs cost
(``fluid.monitor``'s ``compile/*`` counters, fed by JAX's duration
events, and the package's import seconds), as they stood when first
asked for in this run.  Nothing compiles inside the measured window
(else the run is not ``correct``), so they are set-up's, as long as
they are taken before the scope table is built: that lowers and
compiles again what the runners jitted lazily and fires the same
events.  ``scope_time`` therefore asks here first, and the three
``setup_*`` readers read this snapshot, not the live counters.
"""

_KEY = 'setup_totals'
COUNTERS = tuple('compile/%s_%s' % (stage, what)
                 for stage in ('trace', 'lower', 'backend_built',
                               'backend_loaded')
                 for what in ('seconds', 'count'))
IMPORT = 'import/paddle_tpu_seconds'


def totals(run):
    """{name: value, or None where the program counts no such thing}."""
    if _KEY not in run:
        from paddle_tpu.fluid import monitor
        got = {name: monitor.counter_value(name, None)
               for name in COUNTERS}
        got[IMPORT] = monitor.gauge_value(IMPORT, None)
        run[_KEY] = got
    return run[_KEY]
