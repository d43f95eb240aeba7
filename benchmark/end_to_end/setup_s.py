"""Process start to the first timed step: imports, program build,
startup program, compile or cache load, reference check, warm-up."""

UNIT = 's'


def read(run):
    return run['setup_seconds']
