"""Pallas TPU kernels — the fused-op layer.

Capability analog of the reference's fused kernels
(paddle/phi/kernels/fusion/: flash_attn wrappers gpu/flash_attn_kernel.cu,
fused_rms_norm): hand-written TPU kernels for the ops where
XLA's automatic fusion is not enough. Every kernel has an interpret-mode
path so the same code runs (slowly) on CPU for tests, mirroring the
reference's CPU-kernel parity strategy.
"""

from paddle_tpu.ops.pallas import flash_attention  # noqa: F401
from paddle_tpu.ops.pallas import rms_norm  # noqa: F401
from paddle_tpu.ops.pallas import int8_matmul  # noqa: F401
