"""Anchor the bench's model-derived MFU with a TRACE-derived one.

VERDICT r4 weak #6: `bench.py`'s `tflops`/`mfu_pct`/`hbm_pct` come
from XLA cost analysis (`Executor.program_cost`) — a model, not a
measurement ("bytes accessed" counts fusion-internal reads, so
`hbm_pct` can exceed 100).  This tool runs a bench entry twice in ONE
session: once plain (wall ms + cost model) and once under a device
trace, then reports the triangle

    wall ms/step      (what the user gets, incl. dispatch gaps)
    busy ms/step      (sum of device-kernel event durations / steps)
    model TFLOP/step  (XLA cost analysis)

and two MFUs: model-MFU = model_flops / wall (the bench's number) and
kernel-MFU = model_flops / busy (the achievable-if-no-gaps bound).
busy <= wall always; the gap is host dispatch + scheduling bubbles.
If kernel-MFU comes out near
model-MFU the model numbers are anchored; a big spread means the
metric is dispatch-bound, not compute-bound.

Usage: python tools/mfu_crosscheck.py [bert_long|bert|resnet50] [steps]
Needs the real TPU (device-kernel trace events).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PEAK_TFLOPS = 197.0  # v5e bf16


def busy_ms_per_step(logdir, steps):
    """Device kernel busy time per step: the 'XLA Ops' device lane
    ONLY — the trace nests three device lanes (Steps ⊃ XLA Modules ⊃
    XLA Ops) whose totals each cover the same wall span, so summing
    across lanes triple-counts."""
    from paddle_tpu.fluid.profiler import _load_trace_events
    events = _load_trace_events(logdir)
    pid_names = {}
    tid_names = {}
    for e in events:
        if e.get('ph') != 'M':
            continue
        if e.get('name') == 'process_name':
            pid_names[e.get('pid')] = e.get('args', {}).get('name', '')
        elif e.get('name') == 'thread_name':
            tid_names[(e.get('pid'), e.get('tid'))] = \
                e.get('args', {}).get('name', '')
    device_pids = set(p for p, n in pid_names.items()
                      if 'TPU' in n or '/device' in n.lower())
    op_lanes = set(k for k, n in tid_names.items()
                   if k[0] in device_pids and n == 'XLA Ops')
    total_us = 0.0
    for e in events:
        if e.get('ph') != 'X':
            continue
        if (e.get('pid'), e.get('tid')) not in op_lanes:
            continue
        total_us += float(e.get('dur', 0))
    return total_us / 1e3 / max(steps, 1)


def main():
    entry = sys.argv[1] if len(sys.argv) > 1 else 'bert_long'
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    import tempfile

    import bench

    fn = getattr(bench, 'bench_' + entry)
    plain = fn(steps=steps)
    wall_ms = plain.get('value') if plain.get('unit') == 'ms/step' \
        else plain.get('step_ms')
    model_tflops_rate = plain.get('tflops')
    model_tflop_step = model_tflops_rate * wall_ms / 1e3

    logdir = tempfile.mkdtemp(prefix='mfu_xchk_')
    bench.TRACE_LOGDIR = logdir
    try:
        fn(steps=steps)
    finally:
        bench.TRACE_LOGDIR = None
    busy = busy_ms_per_step(logdir, steps)

    model_mfu = plain.get('mfu_pct')
    kernel_mfu = 100.0 * model_tflop_step / (busy / 1e3) / PEAK_TFLOPS
    print('entry=%s steps=%d' % (entry, steps))
    print('wall  %.2f ms/step   (bench metric)' % wall_ms)
    print('busy  %.2f ms/step   (trace: device kernels)' % busy)
    print('gap   %.2f ms/step   (dispatch + bubbles, %.0f%% of wall)'
          % (wall_ms - busy, 100.0 * (wall_ms - busy) / wall_ms))
    print('model %.2f TFLOP/step' % model_tflop_step)
    print('MFU: model %.2f%% (vs wall)  |  kernel %.2f%% (vs busy)'
          % (model_mfu, kernel_mfu))


if __name__ == '__main__':
    main()
