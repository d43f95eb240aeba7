"""Subprocess worker for the job-wide observability tests/gates
(tools/check_comms.py, tests/test_comms.py):
boots a REAL executor on a GradAllReduce-transpiled program (the
collective runner path — c_allreduce_sum per grad over the 'dp' mesh
of this process's devices), enables the fluid.trace flight recorder,
and serves the status plane on the port given in argv[1] (the parent
sets PADDLE_TRAINER_ID / PADDLE_TPU_STATUS_WORKERS / aggregation env
the way distributed/launch.py would).  Prints READY after the first
step; runs until killed or the argv[2] deadline (seconds).  argv[3]
(optional) is a batch multiplier — a deliberately fatter per-step
workload that makes this worker a REAL straggler (its step wall
grows), for skew-detection runs."""

import os
import sys
import time


def main():
    port = int(sys.argv[1])
    run_for = float(sys.argv[2]) if len(sys.argv) > 2 else 60.0
    batch_mult = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, monitor, trace
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce

    fluid.set_flags({'FLAGS_status_port': port})
    trace.enable()
    rank = os.environ.get('PADDLE_TRAINER_ID', '0')
    monitor.add('comms/test_marker_rank%s' % rank)

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 3
    with fluid.program_guard(main_p, startup):
        x = layers.data('x', shape=[32], dtype='float32')
        h = layers.fc(x, 32, act='relu')
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    GradAllReduce().transpile(startup, main_p, 0, ['127.0.0.1:0'],
                              '127.0.0.1:0')

    exe = fluid.Executor(fluid.XLAPlace(0))  # starts the status server
    exe.run(startup)
    feed = {'x': np.ones((8 * batch_mult, 32), 'float32')}
    exe.run(main_p, feed=feed, fetch_list=[loss])
    print('READY', flush=True)
    deadline = time.time() + run_for
    while time.time() < deadline:
        exe.run(main_p, feed=feed, fetch_list=[loss])
        time.sleep(0.02)


if __name__ == '__main__':
    main()
