"""Diagnose the framework-vs-ceiling gap on long-context BERT (s2048).

Builds BOTH programs in one process, prints XLA cost analysis
(flops/bytes) for each, times them interleaved (A/B/A/B...) so drift
between runs cannot masquerade as a framework gap, and dumps both optimized
HLOs under /tmp/bert_long_hlo/ for side-by-side inspection.

Usage: python tools/diff_bert_long.py [--steps 6] [--rounds 3]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def build_framework(batch, seq):
    import paddle_tpu.fluid as fluid
    from bert_long_common import build_bert_long_program
    main, startup, loss, batch_data = build_bert_long_program(batch, seq)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(scope):
        exe.run(startup)

    def run_steps(n):
        with fluid.scope_guard(scope):
            for _ in range(n - 1):
                exe.run(main, feed=batch_data, fetch_list=[])
            out = exe.run(main, feed=batch_data, fetch_list=[loss])
            np.asarray(out[0])
    return run_steps


def build_framework_direct(batch, seq):
    """The SAME fluid program, but the compiled train segment driven in
    a bare jitted loop (state threaded by hand, donation on) — isolates
    the executor's per-step host path from the compiled program."""
    import jax
    from bert_long_common import build_train_segment
    parts = build_train_segment(batch, seq)
    fn = jax.jit(parts['fn'], donate_argnums=(1,))
    data = parts['data']
    out_state_names = parts['out_state_names']
    holder = {'state': parts['state'], 'step': 0}

    def run_steps(n):
        st = holder['state']
        for _ in range(n):
            outs = fn(holder['step'], st, data)
            holder['step'] += 1
            st = dict(st)
            st.update({k: outs[k] for k in out_state_names})
        holder['state'] = st
        smallest = min(st.values(),
                       key=lambda a: getattr(a, 'size', 1 << 60))
        np.asarray(smallest)
    return run_steps


def build_ceiling(batch, seq):
    import jax
    import jax_ceilings as jc
    # intercept run_bert's timeit to get the jitted step + state + feed
    # (run_bert only prints; we need the fn to time interleaved)
    holder = {}
    real_timeit = jc.timeit

    def capture(step, state, steps, feed):
        holder['step'] = step
        holder['state'] = jax.tree.map(jax.device_put, state)
        # device-put the feed ONCE, exactly like the real timeit —
        # storing the raw numpy here once cost every timed ceiling
        # step a ~130 KB synchronous host-to-device transfer
        holder['feed'] = tuple(jax.device_put(np.asarray(f))
                               for f in feed)
        return 1.0  # skip run_bert's own timing loop

    jc.timeit = capture
    try:
        jc.run_bert(batch, seq, 1)
    finally:
        jc.timeit = real_timeit
    step, state, feed = holder['step'], holder['state'], holder['feed']
    lowered = step.lower(state, *feed)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    print('ceiling   cost: %.1f GFLOP  %.2f GB/step'
          % (ca.get('flops', 0) / 1e9,
             ca.get('bytes accessed', 0) / 1e9))
    os.makedirs('/tmp/bert_long_hlo', exist_ok=True)
    with open('/tmp/bert_long_hlo/ceiling.txt', 'w') as f:
        f.write(compiled.as_text())

    st = [state]

    def run_steps(n):
        for _ in range(n):
            st[0] = step(st[0], *feed)
        st[0][3].block_until_ready()  # the scalar step counter

    return run_steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=6)
    ap.add_argument('--rounds', type=int, default=3)
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--seq', type=int, default=2048)
    args = ap.parse_args()

    fw = build_framework(args.batch, args.seq)
    fd = build_framework_direct(args.batch, args.seq)
    ce = build_ceiling(args.batch, args.seq)
    # warm all
    fw(2)
    fd(2)
    ce(2)
    for r in range(args.rounds):
        for name, fn in (('framework', fw), ('fw-direct', fd),
                         ('ceiling  ', ce)):
            t0 = time.time()
            fn(args.steps)
            dt = (time.time() - t0) / args.steps * 1e3
            print('round %d %s: %.1f ms/step' % (r, name, dt))


if __name__ == '__main__':
    main()
