"""Are two trees' train steps the same program?  For every cell of a
tree's ``BENCHMARK.json``, lower the step (the run that fetches the
loss and the quiet one) for a DESCRIBED v5e, without a chip, and print
a hash of the lowered text:

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
        python tools/step_hlo_hash.py <tree> <out.json> [--memory] [cell ...]

Run it on a copy of the parent (``git archive``) and on the tree and
compare the two files: a PR that says an accepted cell's step is
untouched shows equal hashes.  ``--memory`` also COMPILES both steps
for the described chip (under a minute each; the step that fetches
the loss can hold gigabytes more than the quiet one: BERT's keeps a
float32 copy of its logits) and records what
the compiler says it holds (``fluid.memviz.analysis_fields``:
arguments, outputs, temporaries, peak, and the executable's own size,
which moves with the kernels' bodies and not with the batch) and,
under ``temp_peak``, WHOSE the temporaries are where their sum is
largest (``fluid.profiler.hlo_live``: the point, the bytes by class and
fluid op, the ten largest buffers; a tree from before PR 52 has no such
walk and records the fields alone).  Given two such files,

    python tools/step_hlo_hash.py --compare <parent.json> <tree.json>

prints per cell whether the hashes are equal and what moved: every
field, every class and fluid op, and the buffers that are among the
ten largest of one side only.  That is the check a kernel PR runs
before any chip time: which part of a ``peak_hbm`` that moved is whose.
``--memory`` also names, under ``relaid``, every Mosaic call of the
compiled step that reads an operand the compiler RE-LAID for it: one a
``copy`` or ``transpose`` instruction made, or a fusion that holds one
(``relaid_operands``).  A call fixes its operands' layouts; such a line
is the price (PRs 61, 62), and it shows in no memory field.

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
import re
import sys

_LAYOUT_OPS = {'copy', 'transpose'}


def relaid_operands(text):
    """A compiled module's text -> ['call <- producer (opcode)', ..]:
    the Mosaic calls' operands that a ``copy`` or ``transpose``
    instruction made, or a fusion whose body holds one.  (A
    ``copy-start`` / ``copy-done`` pair is the compiler's prefetch into
    its fast memory and moves no layout.)"""
    from paddle_tpu.fluid import profiler
    computations = profiler._parse_hlo(text)[1]
    made = {ins.name: ins for body in computations.values() for ins in body}
    found = []
    for call in made.values():
        if 'custom_call_target="tpu_custom_call"' not in call.attrs:
            continue
        for operand in re.findall(r'%([\w.\-]+)', call.operands):
            by = made.get(operand)
            if by is None:
                continue
            inside = {ins.opcode for ins in computations.get(by.calls, ())}
            if by.opcode in _LAYOUT_OPS or inside & _LAYOUT_OPS:
                found.append('%s <- %s (%s)' % (call.name, operand,
                                               by.opcode))
    return found


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
    from paddle_tpu.fluid import memviz, profiler
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
            if memory:
                compiled = lowered.compile()
                fields = hashes[key + '/memory'] = \
                    memviz.analysis_fields(compiled)
                live = profiler.hlo_live(compiled.as_text())[1] \
                    if hasattr(profiler, 'hlo_live') else None
                if live is not None:
                    fields['temp_peak'] = memviz.temp_peak(live)
                fields['relaid'] = relaid_operands(compiled.as_text())
                for line in fields['relaid']:
                    print(key + '/memory/relaid', line, flush=True)
                print(key + '/memory', {
                    k: v for k, v in fields.items()
                    if k not in ('temp_peak', 'relaid')}, flush=True)
                if 'temp_peak' in fields:
                    peak = fields['temp_peak']
                    print(key + '/memory/temp_peak', peak['bytes'],
                          peak['point'], peak['op'], peak['by_class'],
                          flush=True)
    with open(out_path, 'w') as f:
        json.dump(hashes, f, indent=1)


def _moved(what, before, after, unit=1e6, out=print):
    """Print the keys of two {name: bytes} whose values differ."""
    for name in sorted(set(before) | set(after), key=str):
        a, b = before.get(name, 0.0), after.get(name, 0.0)
        if a != b:
            out('    %s %s: %.3f -> %.3f MB (%+.3f)'
                % (what, name, a / unit, b / unit, (b - a) / unit))


def compare(parent_path, tree_path, out=print):
    """Per cell of two files this tool wrote: are the programs equal,
    and if ``--memory`` was on, what moved and whose it is.  Returns
    the number of keys that differ."""
    with open(parent_path) as f:
        parent = json.load(f)
    with open(tree_path) as f:
        tree = json.load(f)
    differing = 0
    for key in sorted(set(parent) | set(tree)):
        a, b = parent.get(key), tree.get(key)
        if a is None or b is None:
            out('%s: only in %s' % (key, 'the tree' if a is None
                                     else 'the parent'))
            differing += 1
            continue
        if not key.endswith('/memory'):
            same = a[0] == b[0]
            differing += not same
            out('%s: %s' % (key, 'equal' if same else
                            'DIFFERENT (%s -> %s, %d -> %d characters, '
                            '%d -> %d kernels)' % (a[0], b[0], a[1], b[1],
                                                   a[2], b[2])))
            continue
        fields = [{k: v for k, v in side.items()
                   if k not in ('temp_peak', 'relaid')} for side in (a, b)]
        if a == b:
            out('%s: equal' % key)
            continue
        differing += 1
        out('%s: moved' % key)
        _moved('field', fields[0], fields[1], out=out)
        for line in sorted(set(a.get('relaid', ())) ^
                           set(b.get('relaid', ()))):
            out('    a call\'s operand re-laid on %s only: %s'
                % ('the tree' if line in b.get('relaid', ())
                   else 'the parent', line))
        pa, pb = a.get('temp_peak'), b.get('temp_peak')
        if not (pa and pb):
            out('    no temp_peak on %s: whose bytes cannot be said'
                % ('either side' if not (pa or pb) else
                   'the parent\'s side' if not pa else 'the tree\'s side'))
            continue
        out('    temporaries at their peak: %.3f MB at %s (%s) -> %.3f MB '
            'at %s (%s)' % (pa['bytes'] / 1e6, pa['point'], pa['op'],
                            pb['bytes'] / 1e6, pb['point'], pb['op']))
        _moved('class', pa['by_class'], pb['by_class'], out=out)
        _moved('fluid op', pa['by_op'], pb['by_op'], out=out)

        def bag(peak):
            held = {}
            for buf in peak['top_buffers']:
                k = (buf['shape'], str(buf['op']), buf['class'])
                held[k] = held.get(k, 0) + 1
            return held
        ba, bb = bag(pa), bag(pb)
        for k in sorted(set(ba) | set(bb)):
            if ba.get(k, 0) != bb.get(k, 0):
                out('    among the ten largest: %s under %s (%s): %d -> %d'
                    % (k[0], k[1], k[2], ba.get(k, 0), bb.get(k, 0)))
    return differing


if __name__ == '__main__':
    if sys.argv[1] == '--compare':
        sys.exit(1 if compare(sys.argv[2], sys.argv[3]) else 0)
    main(sys.argv[1], sys.argv[2],
         [a for a in sys.argv[3:] if a != '--memory'],
         memory='--memory' in sys.argv[3:])
