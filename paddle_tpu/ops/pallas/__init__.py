"""Pallas TPU kernels for the hot ops.

Reference counterparts: operators/fused/multihead_matmul_op.* /
fused_attention, layer_norm_op.cu, fusion_group NVRTC JIT codegen
(framework/ir/fusion_group/) — here hand-written MXU/VPU kernels where
XLA's automatic fusion isn't enough.
"""

from . import common
from . import flash_attention
from . import grouped_matmul
from . import kda_chunk
from . import kda_walk
from . import quant_collective
from . import sinkhorn
from . import ssd_scan
from . import ssm_scan
from .flash_attention import flash_attention as flash_attention_fn
