"""Rotary position embeddings (llama-style half-rotation, position-id driven).

Reference: ``veomni/ops/kernels/rotary/`` — Liger / deterministic-Triton
impls. Plain XLA here. It does not fuse into the attention projections on a
v5e, as this docstring once said: at 16 + 8 heads of 128 the compiled chain
holds ``_rotate_half``'s two halves as 64-lane arrays, each padded to a
whole 128-lane tile, f32 copies of q and k between fusions and a relayout of
the whole tensor (PERF.md, PR 32). The GQA/MHA attention block therefore
calls ``ops.qk_norm_rotary``, whose Pallas impl rolls whole heads and folds
the sign into the sine table, and MLA's block ``ops.mla_qkv_rotary``, whose
Pallas impl rotates the 64 rope lanes of a head in place (PERF.md, PR 35);
``apply_rotary`` serves the ``xla`` impls of both, the indexer, partial
rotary and the VL / DiT models' own calls.
"""

from __future__ import annotations

import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op


import math


def yarn_get_mscale(factor: float, mscale: float = 1.0) -> float:
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _scale_inv_freq(inv_freq, rope_scaling, head_dim: int, theta: float):
    """Apply HF-style rope_scaling (llama3 / linear / yarn) to base freqs.
    Returns (inv_freq, attention_scale_multiplier)."""
    if not rope_scaling:
        return inv_freq, 1.0
    rtype = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
    factor = float(rope_scaling.get("factor", 1.0))
    if rtype in ("linear",):
        return inv_freq / factor, 1.0
    if rtype == "llama3":
        low = float(rope_scaling.get("low_freq_factor", 1.0))
        high = float(rope_scaling.get("high_freq_factor", 4.0))
        orig = float(rope_scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2 * jnp.pi / inv_freq
        # low-freq (long wavelength) fully scaled; high-freq untouched; smooth ramp between
        smooth = (orig / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        return (1 - smooth) * scaled + smooth * inv_freq, 1.0
    if rtype == "yarn":
        orig = float(rope_scaling.get("original_max_position_embeddings", 4096))
        beta_fast = float(rope_scaling.get("beta_fast", 32))
        beta_slow = float(rope_scaling.get("beta_slow", 1))

        def correction_dim(num_rot):
            return (head_dim / 2) * math.log(orig / (num_rot * 2 * math.pi)) / math.log(theta)

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), head_dim // 2 - 1)
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
            0.0, 1.0,
        )
        extrap_mask = 1.0 - ramp  # 1 where high-freq (keep base)
        inv = inv_freq / factor * (1 - extrap_mask) + inv_freq * extrap_mask
        mscale_all_dim = float(rope_scaling.get("mscale_all_dim", 0.0))
        # deepseek attention-scale correction (applied by the caller)
        att = yarn_get_mscale(factor, mscale_all_dim) ** 2 if mscale_all_dim else 1.0
        # HF also scales cos/sin by yarn_get_mscale(factor, mscale)/yarn_get_mscale(factor, mscale_all_dim)
        return inv, att
    if rtype in ("default", "dynamic", "mrope"):
        # mrope keeps base frequencies; the section mixing happens in
        # rotary_tables (positions [B,3,S])
        return inv_freq, 1.0
    raise ValueError(f"unsupported rope_scaling type {rtype!r}")


def yarn_attention_factor(rope_scaling, head_dim: int) -> float:
    """Softmax-scale multiplier for yarn (deepseek mscale^2 correction)."""
    if not rope_scaling:
        return 1.0
    rtype = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
    if rtype != "yarn":
        return 1.0
    factor = float(rope_scaling.get("factor", 1.0))
    mscale_all_dim = float(rope_scaling.get("mscale_all_dim", 0.0))
    if not mscale_all_dim:
        return 1.0
    return yarn_get_mscale(factor, mscale_all_dim) ** 2


def rotary_tables(
    positions, head_dim: int, theta: float = 10000.0, rope_scaling=None,
    interleaved: bool = False,
):
    """positions [B,S] int -> (cos, sin) each [B,S,head_dim].

    mrope (qwen-vl): positions [B,3,S] (temporal/height/width streams) with
    ``rope_scaling["mrope_section"]`` — the frequency dim is split into
    sections and section *i* reads stream ``i % 3`` (HF
    ``apply_multimodal_rotary_pos_emb`` semantics).

    ``interleaved``: pairwise (deepseek) layout — each half-frequency entry
    is repeated twice adjacently instead of concatenated halves. Also scales
    cos/sin by the yarn mscale ratio when rope_scaling requests it (HF
    deepseek _compute_yarn_parameters attention_factor)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    inv_freq, _ = _scale_inv_freq(inv_freq, rope_scaling, head_dim, theta)
    msec = (rope_scaling or {}).get("mrope_section")
    if msec and positions.ndim == 3:
        import numpy as np

        # [B,3,S] -> [3,B,S,D/2] per-stream angles, then pick each frequency
        # chunk from its stream (static section map, no gather needed)
        ang3 = positions.astype(jnp.float32).transpose(1, 0, 2)[..., None] * inv_freq
        if (rope_scaling or {}).get("mrope_interleaved"):
            # qwen3-vl layout (HF apply_interleaved_mrope): frequency j reads
            # stream 1 when j%3==1 and j<3*sec[1], stream 2 when j%3==2 and
            # j<3*sec[2], else the temporal stream — [THW THW ... TT] keeps
            # frequency continuity across the three streams.
            if sum(msec) != head_dim // 2:
                raise ValueError(
                    f"mrope_section {msec} must sum to head_dim/2 = {head_dim // 2}"
                )
            sec = np.zeros(head_dim // 2, np.int32)
            js = np.arange(head_dim // 2)
            sec[(js % 3 == 1) & (js < 3 * msec[1])] = 1
            sec[(js % 3 == 2) & (js < 3 * msec[2])] = 2
        else:
            sec = np.concatenate(
                [np.full(n, i % 3, np.int32) for i, n in enumerate(msec)]
            )
        if sec.shape[0] != head_dim // 2:
            raise ValueError(
                f"mrope_section {msec} must sum to head_dim/2 = {head_dim // 2}"
            )
        pick = jnp.asarray(sec[None, :] == jnp.arange(3)[:, None], jnp.float32)
        ang = jnp.einsum("tbsd,td->bsd", ang3, pick)
        ang = jnp.concatenate([ang, ang], axis=-1)
        return jnp.cos(ang), jnp.sin(ang)
    if msec and positions.ndim == 2:
        pass  # text-only rows: all three streams equal -> plain 1D rope
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,S,D/2]
    if interleaved:
        ang = jnp.repeat(ang, 2, axis=-1)  # [B,S,D] pairwise
    else:
        ang = jnp.concatenate([ang, ang], axis=-1)  # [B,S,D]
    scale = 1.0
    if rope_scaling and rope_scaling.get("rope_type", rope_scaling.get("type")) == "yarn":
        factor = float(rope_scaling.get("factor", 1.0))
        mscale = float(rope_scaling.get("mscale", 1.0))
        mscale_all = float(rope_scaling.get("mscale_all_dim", 0.0))
        if mscale_all:
            scale = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor, mscale_all)
        else:
            scale = yarn_get_mscale(factor, 1.0)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotate_interleave(x):
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


@KERNEL_REGISTRY.register("rotary", "xla")
def _apply_rotary_xla(q, k, cos, sin, interleaved: bool = False):
    """q [B,S,Hq,D], k [B,S,Hk,D], cos/sin [B,S,D]."""
    dtype = q.dtype
    rot = _rotate_interleave if interleaved else _rotate_half
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    q_out = qf * cos + rot(qf) * sin
    k_out = kf * cos + rot(kf) * sin
    return q_out.astype(dtype), k_out.astype(dtype)


def apply_rotary(q, k, cos, sin, interleaved: bool = False):
    return resolve_op("rotary")(q, k, cos, sin, interleaved)
