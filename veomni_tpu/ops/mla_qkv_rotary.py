"""What lies between MLA's projections and the attention op: the split of q
into nope and rope lanes a head, the split of ``kv_b_proj``'s output into
``k_nope`` and ``v``, rope on the rope lanes of q and on the one shared
``k_rope``, its broadcast to every head and the two concatenations, as ONE op.

On the projections' own outputs: q ``[B, S, H*(dn+dr)]``, kv
``[B, S, H*(dn+dv)]``, ``k_rope`` ``[B, S, dr]`` (the rope slice of
``kv_a_proj_with_mqa``), cos/sin ``[B, S, dr]``. Returns q, k
``[B, S, H, dn+dr]`` and v ``[B, S, H, dv]``, what :func:`ops.attention` takes.

Impl ``xla`` is the composition: the oracle, the CPU path, and what every call
the kernel does not take is handed to. Impl ``pallas``
(``ops/pallas/mla_qkv_rope.py``) is one kernel each way on TPU. The nope lanes
and v are copies in both; the rotation is f32 with one rounding to the input
dtype in both.
"""

from __future__ import annotations

import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op
from veomni_tpu.ops.rotary import apply_rotary


@KERNEL_REGISTRY.register("mla_qkv_rotary", "xla")
def _mla_qkv_rotary_xla(q, kv, k_rope, cos, sin, dn: int, dr: int, dv: int,
                        interleaved: bool = False):
    b, s, _ = q.shape
    q = q.reshape(b, s, -1, dn + dr)
    nh = q.shape[2]
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = kv.reshape(b, s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    q_rope, k_rope = apply_rotary(
        q_rope, k_rope.reshape(b, s, 1, dr), cos, sin, interleaved=interleaved,
    )
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, nh, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return q, k, v


def mla_qkv_rotary(q, kv, k_rope, cos, sin, dn: int, dr: int, dv: int,
                   interleaved: bool = False):
    return resolve_op("mla_qkv_rotary")(q, kv, k_rope, cos, sin, dn, dr, dv, interleaved)
