"""Pallas TPU kernels (the in-tree native layer).

Reference counterpart: ``veomni/ops/kernels/`` Triton/TileLang kernels.
Importing this package registers the Pallas impls into KERNEL_REGISTRY with
priority over the XLA-eager fallbacks on TPU.
"""

from veomni_tpu.ops.pallas import flash_attention as _flash_attention  # noqa: F401
from veomni_tpu.ops.pallas import grouped_gemm as _grouped_gemm  # noqa: F401
from veomni_tpu.ops.pallas import qk_norm_rope as _qk_norm_rope  # noqa: F401
from veomni_tpu.ops.pallas import mla_qkv_rope as _mla_qkv_rope  # noqa: F401
