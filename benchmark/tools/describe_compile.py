"""Ask the TPU's compiler, without a TPU, whether a cell's train step
fits: compile it for a DESCRIBED v5e (one chip, or the cell's mesh on
a described 2x2) at one or more batch sizes and print
``memory_analysis()``, the Mosaic calls and the collectives in it.

    JAX_PLATFORMS=cpu python benchmark/tools/describe_compile.py \
        --workload <cell> [--batch-per-chip N [N ...]]

Rehearsal 3 of the on-chip-measurement guide; how the batch of every
cell was sized (PERF.md section 4).  Nothing runs on a chip and no
number printed here is a measurement: it says "the chip's compiler
accepts it and this is what it reserves", never "it is fast".  The
startup program runs on the CPU only to give the state its shapes.
"""

import argparse
import os
import re
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

HBM_BYTES = 16 * 2 ** 30        # one v5e chip


def compile_step(run, cell, topo_devices, seed=0):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import mesh as pmesh
    main, startup, _, loss, _ = run.build_programs(cell, seed)
    host = cell.family.batch(cell.config, cell.traffic, cell.batch, seed)
    mesh, state_sh, data_sh = cell.layout.shardings(
        topo_devices[:cell.chips])
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        step = exe.compile(main, feed_names=sorted(host),
                           fetch_names=[loss.name])
        scope = fluid.global_scope()

        def spec(v, sharding):
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)

        def held(n):
            return spec(fluid.core.as_array(scope.find_var(n)), state_sh)

        state = {n: held(n) for n in step.state_names}
        # pure inputs: the batch, and what the scope holds read-only
        # (the learning rate)
        data = {n: spec(host[n], data_sh) if n in host else held(n)
                for n in step.input_names}

    def fn(count, state, data):
        if mesh is None:
            return step.fn(count, state, data)
        with pmesh.use_trace_mesh(mesh):    # as the parallel runner traces
            return step.fn(count, state, data)

    import numpy as np
    t0 = time.time()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        jax.ShapeDtypeStruct((), np.int32), state, data).compile()
    return compiled, time.time() - t0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--batch-per-chip', type=int, nargs='*')
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from benchmark import run
    from benchmark.lib.trace_reduce import COLLECTIVE_OPCODES
    from paddle_tpu.ops.pallas import common
    # jax.devices() still answers "cpu" beside a described topology:
    # steer the one platform probe so dispatch() takes its chip branch
    common.on_tpu = lambda: True
    # an executable compiled for a described chip cannot be read back
    jax.config.update('jax_enable_compilation_cache', False)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    cell = run.Cell(run.load_json(os.path.join(ROOT, 'BENCHMARK.json')),
                    args.workload)
    for per_chip in args.batch_per_chip or \
            [cell.traffic['batch_per_chip']]:
        cell.traffic['batch_per_chip'] = per_chip
        compiled, seconds = compile_step(run, cell, topo.devices)
        m = compiled.memory_analysis()
        text = compiled.as_text()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes +
                 m.temp_size_in_bytes - m.alias_size_in_bytes)
        print('%s batch/chip %d on %d described chip(s): compiled in '
              '%.0f s; per device: arguments %.2f GB, outputs %.2f GB '
              '(aliased %.2f GB), temp %.2f GB, total %.2f GB = %.0f%% '
              'of %.0f GiB; %d Mosaic calls; collectives %s'
              % (cell.name, per_chip, cell.chips, seconds,
                 m.argument_size_in_bytes / 1e9,
                 m.output_size_in_bytes / 1e9,
                 m.alias_size_in_bytes / 1e9, m.temp_size_in_bytes / 1e9,
                 total / 1e9, 100.0 * total / HBM_BYTES,
                 HBM_BYTES / 2 ** 30,
                 text.count('custom_call_target="tpu_custom_call"'),
                 {c: n for c in COLLECTIVE_OPCODES for n in
                  [len(re.findall(r' %s(-start)?\(' % c, text))] if n},
                 flush=True)


if __name__ == '__main__':
    main()
