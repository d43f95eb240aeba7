"""Compile-plane regression gate: the persistent segment-executable
store must actually carry executables ACROSS PROCESSES (the runtime
analog of tests/test_compile_cache.py's in-process roundtrip).

Runs a tiny fixed-seed training program in two child processes sharing
one fresh cache directory and checks:

  process 1:  aot_compiles > 0, disk writes > 0 (populates the store)
  process 2:  compile_cache_disk_hit > 0 and segments_lowered == 0
              (every segment loads from disk; ZERO re-traces), same
              loss trajectory as process 1 bit-for-bit

A third child runs against a deliberately corrupted store and must
REPORT compile_cache_corrupt > 0 while still producing the same
losses — a bad entry recompiles, never crashes.

Run from `make check` (CPU: JAX_PLATFORMS=cpu).
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

STEPS = 3


def child():
    """One process: build the fixed program, run, dump counters."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, monitor

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1234
    with fluid.program_guard(main, startup):
        x = layers.data('x', shape=[16], dtype='float32')
        h = layers.fc(x, 8, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.05).minimize(loss)
    xs = np.random.RandomState(3).randn(4, 16).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        losses = []
        for _ in range(STEPS):
            l, = exe.run(main, feed={'x': xs}, fetch_list=[loss])
            losses.append(float(np.asarray(l).ravel()[0]))
    flat = monitor.flat()
    print('CHECK_JSON ' + json.dumps({
        'losses': losses,
        'disk_hit': flat.get('executor/compile_cache_disk_hit', 0.0),
        'disk_writes': flat.get('executor/compile_cache_disk_writes',
                                0.0),
        'aot_compiles': flat.get('executor/aot_compiles', 0.0),
        'segments_lowered': flat.get('executor/segments_lowered', 0.0),
        'corrupt': flat.get('executor/compile_cache_corrupt', 0.0),
    }))


def run_child(cache_dir):
    # JAX's own cache starts as empty as the segment store (an
    # executable it served from a warm one is not re-published there),
    # placed from outside through the variable place_jax_cache() honours
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get('JAX_PLATFORMS', 'cpu'),
               FLAGS_compile_cache_dir=cache_dir,
               JAX_COMPILATION_CACHE_DIR=os.path.join(cache_dir, 'xla'))
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), '--child'],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for line in p.stdout.splitlines():
        if line.startswith('CHECK_JSON '):
            return json.loads(line[len('CHECK_JSON '):])
    raise RuntimeError('child produced no result (rc=%d):\n%s\n%s'
                       % (p.returncode, p.stdout[-2000:],
                          p.stderr[-2000:]))


def main():
    if '--child' in sys.argv:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        child()
        return 0
    d = tempfile.mkdtemp(prefix='ptcc_check_')
    failures = []
    try:
        p1 = run_child(d)
        p2 = run_child(d)
        print('process 1: %d aot compiles, %d disk writes'
              % (p1['aot_compiles'], p1['disk_writes']))
        print('process 2: %d disk hits, %d retraces'
              % (p2['disk_hit'], p2['segments_lowered']))
        if not p1['aot_compiles'] > 0:
            failures.append('process 1 did not AOT-compile')
        if not p1['disk_writes'] > 0:
            failures.append('process 1 wrote no cache entries')
        if not p2['disk_hit'] > 0:
            failures.append('process 2 reported no disk hits')
        if p2['segments_lowered'] != 0:
            failures.append('process 2 re-traced %d segments '
                            '(must be 0)' % p2['segments_lowered'])
        if p1['losses'] != p2['losses']:
            failures.append('trajectories diverge: %r vs %r'
                            % (p1['losses'], p2['losses']))
        # corrupt-store tolerance: truncate every entry, run again
        seg_dir = os.path.join(d, 'segments')
        for e in os.listdir(seg_dir):
            with open(os.path.join(seg_dir, e), 'r+b') as f:
                f.truncate(16)
        p3 = run_child(d)
        print('process 3 (corrupted store): %d corrupt entries '
              'tolerated' % p3['corrupt'])
        if not p3['corrupt'] > 0:
            failures.append('corrupted entries were not detected')
        if p3['losses'] != p1['losses']:
            failures.append('corrupt-store recompile diverged: %r vs '
                            '%r' % (p3['losses'], p1['losses']))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if failures:
        for f in failures:
            print('COMPILE-CACHE REGRESSION  ' + f)
        return 1
    print('compile cache: cross-process reuse OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
