"""RMSNorm. Reference: ``veomni/ops/kernels/rms_norm/`` (Liger/Triton impls).

"xla" is the only impl of the stand-alone norm (the reference's
batch-invariant Triton variant is moot: XLA is batch-invariant by design).
That XLA fuses the reduction + rsqrt + scale chain into its neighbours was
written before the chip. What the chip read (PERF.md, PR 32): the q/k norm
in front of rope wrote an f32 copy of the q projection's output and read it
back between fusions, and the norm + rope chain of the qwen cell moved about
six times the bytes it needs. That chain is now one op with a Pallas kernel each
way (``ops/qk_norm_rotary.py``); the layer norms and MLA's latent norms,
which feed a matmul, stay here and have not been read apart from it.
"""

from __future__ import annotations

import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op


@KERNEL_REGISTRY.register("rms_norm", "xla")
def _rms_norm_xla(x, weight, eps: float = 1e-6, zero_centered: bool = False):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jnp.reciprocal(jnp.sqrt(var + eps))
    w = weight.astype(jnp.float32)
    if zero_centered:  # gemma family stores (w - 1)
        w = 1.0 + w
    return (x * w).astype(dtype)


def rms_norm(x, weight, eps: float = 1e-6, zero_centered: bool = False):
    return resolve_op("rms_norm")(x, weight, eps, zero_centered)
