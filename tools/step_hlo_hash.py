"""Are two trees' train steps the same program?  For every cell of a
tree's ``BENCHMARK.json``, lower the step (the run that fetches the
loss and the quiet one) for a DESCRIBED v5e, without a chip, and print
a hash of the lowered text:

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
        python tools/step_hlo_hash.py <tree> <out.json> [--memory] [cell ...]

Run it on a copy of the parent (``git archive``) and on the tree and
compare the two files: a PR that says an accepted cell's step is
untouched shows equal hashes.  ``--memory`` also COMPILES each quiet
step for the described chip (minutes a cell) and records what the
compiler says it holds (``fluid.memviz.analysis_fields``: arguments,
outputs, temporaries, peak, and the executable's own size, which moves
with the kernels' bodies and not with the batch): which part of a
``peak_hbm`` that moved is whose, before any chip time.

A Mosaic kernel's serialized module carries the SOURCE LINES of its
body and of every caller in the file, so one comment line above a
kernel changes the payload, the lowered text and JAX's persistent
cache key of every step that holds a call (PR 38: verified; PR 37
suspected it).  The lines are stripped here before a kernel is
serialized, so that two trees compare by what their kernels compute;
the persistent cache does no such thing, and a PR that moves a line of
``ops/pallas/flash_attention.py`` above the kernels compiles every
flash cell's step anew once.
"""

import hashlib
import json
import os
import sys


def main(root, out_path, only=(), memory=False):
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.chdir(root)
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    import numpy as np
    import jax
    from jax._src import tpu_custom_call
    from jax.experimental import topologies
    from jaxlib.mlir.passmanager import PassManager
    import paddle_tpu.fluid as fluid
    from benchmark import run
    from paddle_tpu.fluid import memviz
    from paddle_tpu.ops.pallas import common
    from paddle_tpu.parallel import mesh as pmesh

    # jax.devices() still answers "cpu" beside a described topology
    common.on_tpu = lambda: True
    jax.config.update('jax_enable_compilation_cache', False)
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def without_source_lines(module, **kw):
        with module.context:
            PassManager.parse('builtin.module(strip-debuginfo)').run(
                module.operation)
        return serialize(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = without_source_lines
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    manifest = run.load_json(os.path.join(root, 'BENCHMARK.json'))
    hashes = {}
    for entry in manifest['workloads']:
        if only and entry['name'] not in only:
            continue
        try:
            cell = run.Cell(manifest, entry['name'])
            main_prog, startup, _, loss, _ = run.build_programs(cell, 0)
        except Exception as e:      # a cell this tree cannot build
            print('%s: %s: %s' % (entry['name'], type(e).__name__, e),
                  flush=True)
            continue
        host = cell.family.batch(cell.config, cell.traffic, cell.batch, 0)
        mesh, state_sh, data_sh = cell.layout.shardings(
            topo.devices[:cell.chips])
        for kind, fetch in (('fetch', [loss.name]), ('quiet', [])):
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor(fluid.XLAPlace(0))
                exe.run(startup)
                step = exe.compile(main_prog, feed_names=sorted(host),
                                   fetch_names=fetch)
                scope = fluid.global_scope()

                def spec(v, sharding):
                    return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                sharding=sharding)

                def held(n):
                    return spec(fluid.core.as_array(scope.find_var(n)),
                                state_sh)

                state = {n: held(n) for n in step.state_names}
                data = {n: spec(host[n], data_sh) if n in host
                        else held(n) for n in step.input_names}

            def fn(count, state, data):
                if mesh is None:
                    return step.fn(count, state, data)
                # as the data-parallel runner publishes it
                with pmesh.use_trace_mesh(mesh, mesh.axis_names[:1]):
                    return step.fn(count, state, data)

            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                jax.ShapeDtypeStruct((), np.int32), state, data)
            text = lowered.as_text()
            key = '%s/%s' % (entry['name'], kind)
            hashes[key] = [hashlib.sha256(text.encode()).hexdigest()[:16],
                           len(text), text.count('tpu_custom_call')]
            print(key, *hashes[key], flush=True)
            if memory and kind == 'quiet':
                hashes[key + '/memory'] = memviz.analysis_fields(
                    lowered.compile())
                print(key + '/memory', hashes[key + '/memory'], flush=True)
    with open(out_path, 'w') as f:
        json.dump(hashes, f, indent=1)


if __name__ == '__main__':
    main(sys.argv[1], sys.argv[2],
         [a for a in sys.argv[3:] if a != '--memory'],
         memory='--memory' in sys.argv[3:])
