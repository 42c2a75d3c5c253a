"""What lies between the q/k projections and the attention op: the per-head
RMS norm of q and k (where the model has one) and rope, as ONE op.

On the projections' own outputs: q ``[B, S, Hq*D]``, k ``[B, S, Hk*D]``,
cos/sin ``[B, S, rot]`` (``rot == D``, or less for partial rotary), the norm
weights ``[D]`` or None. Returns q ``[B, S, Hq, D]`` and k ``[B, S, Hk, D]``,
what :func:`ops.attention` takes.

Impl ``xla`` is the composition (:func:`ops.rms_norm`, then
:func:`ops.apply_rotary`): the oracle, the CPU path, and what every call the
kernel does not take is handed to. Impl ``pallas``
(``ops/pallas/qk_norm_rope.py``) is one kernel each way on TPU. The roundings
are the composition's in both: f32 inside the norm and the rotation, the
input dtype between them and out.
"""

from __future__ import annotations

import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op
from veomni_tpu.ops.rms_norm import rms_norm
from veomni_tpu.ops.rotary import apply_rotary


def head_dim_of(cos, q_weight, head_dim):
    """A head's width where the caller did not say: the norm weight's, else
    the tables' (rope over the whole head)."""
    if head_dim:
        return head_dim
    return q_weight.shape[-1] if q_weight is not None else cos.shape[-1]


@KERNEL_REGISTRY.register("qk_norm_rotary", "xla")
def _qk_norm_rotary_xla(q, k, cos, sin, q_weight=None, k_weight=None, eps: float = 1e-6,
                        zero_centered: bool = False, interleaved: bool = False,
                        head_dim=None):
    b, s, _ = q.shape
    d = head_dim_of(cos, q_weight, head_dim)
    q4, k4 = q.reshape(b, s, -1, d), k.reshape(b, s, -1, d)
    if q_weight is not None:
        q4 = rms_norm(q4, q_weight, eps, zero_centered)
        k4 = rms_norm(k4, k_weight, eps, zero_centered)
    rot = cos.shape[-1]
    if rot < d:
        # partial rotary (glm4_moe): rope covers the leading dims only
        q_rot, k_rot = apply_rotary(q4[..., :rot], k4[..., :rot], cos, sin, interleaved)
        q4 = jnp.concatenate([q_rot, q4[..., rot:]], axis=-1)
        k4 = jnp.concatenate([k_rot, k4[..., rot:]], axis=-1)
    else:
        q4, k4 = apply_rotary(q4, k4, cos, sin, interleaved)
    return q4, k4


def qk_norm_rotary(q, k, cos, sin, q_weight=None, k_weight=None, eps: float = 1e-6,
                   zero_centered: bool = False, interleaved: bool = False, head_dim=None):
    return resolve_op("qk_norm_rotary")(
        q, k, cos, sin, q_weight, k_weight, eps, zero_centered, interleaved, head_dim)
