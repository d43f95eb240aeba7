"""Operator registry + JAX lowerings (the kernel library).

Importing this package registers all ops.  Reference scale:
paddle/fluid/operators/ has 364 REGISTER_OPERATOR ops across ~96k LoC of
C++/CUDA; here each op is a traceable JAX lowering and gradients are
synthesized with jax.vjp, so the whole library is a few files.
"""

from . import registry  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import activation_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import host_ops  # noqa: F401
from . import amp_ops  # noqa: F401
from . import collective_ops  # noqa: F401

from .registry import register, register_host, get, is_registered  # noqa
from . import sequence_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import lang_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import detection_host_ops  # noqa: F401
from . import parallel_ops  # noqa: F401
from . import kda_ops  # noqa: F401
from . import hyper_connection_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import ssd_ops  # noqa: F401

# host-sharded embedding (PS analog) host ops: registration lives with
# the table implementation; import so distributed_lookup_table /
# pull_box_sparse etc. resolve without requiring a manual import
from ..parallel import sparse_embedding as _sparse_embedding  # noqa: F401,E402
