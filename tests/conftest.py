"""Test config: run on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): single-host
"cluster-in-a-box" — an 8-device XLA host platform so sharding /
collective paths compile and execute without TPU hardware.

JAX_PLATFORMS is set before jax is imported, which is all it takes.
Set PADDLE_TPU_TEST_PLATFORM to run the suite on another platform.
"""

import os

# PADDLE_TPU_VERIFY=1 arms the static Program verifier
# (fluid.progcheck, FLAGS_program_verify) for the WHOLE suite: every
# Program any test plans gets the full invariant + shape/dtype +
# donation pass before anything traces — the sweep that keeps the
# transpiler/planner rewrite paths verifier-clean.  Must be set
# before paddle_tpu imports (flags read the env at import).
if os.environ.get('PADDLE_TPU_VERIFY'):
    os.environ.setdefault('FLAGS_program_verify', '1')

_platform = os.environ.get('PADDLE_TPU_TEST_PLATFORM', 'cpu')
os.environ['JAX_PLATFORMS'] = _platform
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()


import pytest  # noqa: E402


@pytest.fixture
def pallas_interpret():
    """Run the Pallas kernel bodies under the interpreter for one
    test: off a TPU, common.dispatch() answers "dense" unless
    FLAGS_pallas_force asks otherwise."""
    from paddle_tpu.fluid.flags import get_flag, set_flags
    was = get_flag('FLAGS_pallas_force', False)
    set_flags({'FLAGS_pallas_force': True})
    yield
    set_flags({'FLAGS_pallas_force': was})
