"""Ops layer: kernel registry + dispatch + XLA/Pallas implementations.

Reference: ``veomni/ops/`` — KERNEL_REGISTRY + OpSlot dispatch with per-op
implementation selection (eager vs Triton vs external CUDA). Here the impl
axes are {"xla", "pallas"}; Pallas is for the ops the chip showed to be hot
(flash attention, the grouped GEMM, and the two chains between the attention
block's projections and the attention op, which XLA does not fuse the way one
would hope: the q/k norm + rope of GQA/MHA, ``ops/pallas/qk_norm_rope.py``,
and MLA's split, rope, head broadcast and relayout, ``ops.mla_qkv_rotary``,
``ops/pallas/mla_qkv_rope.py``).
"""

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, KernelSpec, resolve_op
from veomni_tpu.ops import rms_norm as _rms_norm  # noqa: F401 register
from veomni_tpu.ops import rotary as _rotary  # noqa: F401
from veomni_tpu.ops import qk_norm_rotary as _qk_norm_rotary  # noqa: F401
from veomni_tpu.ops import mla_qkv_rotary as _mla_qkv_rotary  # noqa: F401
from veomni_tpu.ops import swiglu as _swiglu  # noqa: F401
from veomni_tpu.ops import ssd_scan as _ssd_scan  # noqa: F401
from veomni_tpu.ops import kda as _kda  # noqa: F401
from veomni_tpu.ops import attention as _attention  # noqa: F401
from veomni_tpu.ops import cross_entropy as _cross_entropy  # noqa: F401
from veomni_tpu.ops import load_balancing as _load_balancing  # noqa: F401
from veomni_tpu.ops import group_gemm as _group_gemm  # noqa: F401
from veomni_tpu.ops import paged_attention as _paged_attention  # noqa: F401
from veomni_tpu.ops import quantization as _quantization  # noqa: F401
from veomni_tpu.ops import pallas as _pallas  # noqa: F401  (registers TPU kernels)

rms_norm = _rms_norm.rms_norm
apply_rotary = _rotary.apply_rotary
rotary_tables = _rotary.rotary_tables
qk_norm_rotary = _qk_norm_rotary.qk_norm_rotary
mla_qkv_rotary = _mla_qkv_rotary.mla_qkv_rotary
swiglu = _swiglu.swiglu
ssd_scan = _ssd_scan.ssd_scan
kda_scan = _kda.kda_scan
attention = _attention.attention
fused_linear_cross_entropy = _cross_entropy.fused_linear_cross_entropy
fused_linear_topk_distill = _cross_entropy.fused_linear_topk_distill
load_balancing_loss = _load_balancing.load_balancing_loss
group_gemm = _group_gemm.group_gemm
cache_attend = _paged_attention.cache_attend
gather_block_kv = _paged_attention.gather_block_kv
gather_block_kv_q8 = _paged_attention.gather_block_kv_q8
paged_attend = _paged_attention.paged_attend
paged_prefill_attend = _paged_attention.paged_prefill_attend
QuantizedKV = _quantization.QuantizedKV
QuantizedWeight = _quantization.QuantizedWeight
quantize_rows = _quantization.quantize_rows
dequantize_rows = _quantization.dequantize_rows
quantize_weight = _quantization.quantize_weight
quantize_decode_params = _quantization.quantize_decode_params
make_kv_pool = _quantization.make_kv_pool
kv_block_nbytes = _quantization.kv_block_nbytes
decode_dot = _quantization.decode_dot

__all__ = [
    "KERNEL_REGISTRY",
    "KernelSpec",
    "resolve_op",
    "rms_norm",
    "apply_rotary",
    "rotary_tables",
    "qk_norm_rotary",
    "mla_qkv_rotary",
    "swiglu",
    "ssd_scan",
    "kda_scan",
    "attention",
    "fused_linear_cross_entropy",
    "fused_linear_topk_distill",
    "load_balancing_loss",
    "group_gemm",
    "cache_attend",
    "gather_block_kv",
    "gather_block_kv_q8",
    "paged_attend",
    "paged_prefill_attend",
    "QuantizedKV",
    "QuantizedWeight",
    "quantize_rows",
    "dequantize_rows",
    "quantize_weight",
    "quantize_decode_params",
    "make_kv_pool",
    "kv_block_nbytes",
    "decode_dot",
]
