"""How many kernel dispatches chose the fused (Pallas) side while the
train step was traced: the sum of ``pallas/*/dispatch_fused`` from
after the program build to the end of warm-up.  Exact; reads 0 where a
kernel or its counter is gone, so it says which side of a dispatch the
cell ran."""

LAYER = 'kernels'
UNIT = 'count'
MOVES = 'throughput'


def read(trace, run):
    return float(run['fused_dispatches'])
