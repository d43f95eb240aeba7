"""The benchmark's own tests run on the CPU, on four virtual devices
(the dp rehearsal), whatever the machine holds.  Run them with
``python -m pytest benchmark/tests -q`` from the root of the repo."""

import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault('XLA_FLAGS',
                      '--xla_force_host_platform_device_count=4')
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
