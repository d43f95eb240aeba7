"""paddle_tpu: a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid v1.6 (reference: /root/reference).

Architecture: Program-as-data IR (fluid/framework.py) -> segment lowering
to jitted XLA computations (fluid/executor.py) -> JAX/Pallas kernels
(ops/) -> GSPMD mesh parallelism (parallel/).  See SURVEY.md at the repo
root for the reference layer map this mirrors.
"""

__version__ = '0.1.0'

import time as _time

_import_t0 = _time.perf_counter()

from . import ops  # noqa: E402  registers all operators
from . import fluid  # noqa: E402,F401

# paddle.* compatibility aliases
from .fluid import layers  # noqa: E402,F401

# what the process paid to have the package, once ('compile/*' beside
# it says what it paid to have its programs: from here on JAX's own
# compile events are folded into fluid.monitor)
fluid.monitor.set_gauge('import/paddle_tpu_seconds',
                        _time.perf_counter() - _import_t0)
fluid.compile_cache.listen()


def enable_static():
    from .fluid.dygraph.base import disable_dygraph
    disable_dygraph()


def disable_static():
    from .fluid.dygraph.base import enable_dygraph
    enable_dygraph()
