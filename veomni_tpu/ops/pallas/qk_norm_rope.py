"""q/k RMS norm and rope as one pass each way (Pallas TPU).

What XLA made of the composition on a v5e (PERF.md, PR 32): f32 copies of q
and k written and read back between fusions, a relayout of the whole tensor,
and ``_rotate_half``'s two 64-lane halves each padded to a whole 128-lane
tile: by its own count 4.1 GB a layer at the qwen cell's shape, where these
kernels move 0.73. Here:

* the kernels read the projections' own ``[B, S, H*D]`` outputs: a block
  ``(1, ts, H*D)`` is dense in the (8, 128) tiling and a head is a static
  ``D``-lane column group, so nothing is reshaped or relaid in front of them.
  They write ``[B, H, S, D]``, the layout the flash kernels read (a head's
  ``(ts, D)`` slab is dense there too), and the backward reads its cotangent
  in that layout: left to XLA, the way from one layout to the other was two
  relayout copies of q and k each way, which it used to fuse into the rope;
* ``qk_norm_rope_fwd``, grid (batch, S / ts), both parallel: per head, in
  f32, mean of squares over the head's lanes, ``rsqrt``, the weight, the
  rounding to the input dtype the composition has between norm and rope, then
  ``y * cos + roll(y, D/2) * sin_signed``. The sign of ``_rotate_half`` is
  folded into the sine table once a block, so no half-width array exists.
  cos/sin ``[B, S, D]`` are read once a block and serve every head of q and k;
* ``qk_norm_rope_bwd`` (a ``jax.custom_vjp``): the residuals are the pre-norm
  q and k, the tables and the weights. No f32 tensor and no ``rstd`` is saved:
  one lane reduction gives it again. The weights' gradients leave as
  per-block partial sums ``[B, S/ts, 8, D]`` f32 that XLA adds, so no grid
  axis carries an accumulator. The tables get no gradient (they come from
  integer positions).

Without norm weights the same kernels run rope alone. What they do not take
(partial or interleaved rotary, a head dim that is no multiple of 128, a
ragged S) goes to the ``xla`` impl with one log line naming the reason.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.ops.pallas import flash_attention as _fa
from veomni_tpu.ops.qk_norm_rotary import _qk_norm_rotary_xla, head_dim_of
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_SUBLANES = 8  # rows of the weight gradients' per-block partial sums


class _Call(NamedTuple):
    """The static half of a call."""

    d: int               # head dim
    ts_fwd: int          # row tiles
    ts_bwd: int
    eps: float
    zero_centered: bool


def _vmem_bytes(kernel: str, ts: int, width: int, d: int, dtype, table_dtype) -> int:
    """VMEM a kernel needs at row tile ``ts``: its blocks twice (the pipeline
    double-buffers them) and its per-head f32 temporaries. ``width`` is q's
    and k's together."""
    rows = ts * width * jnp.dtype(dtype).itemsize
    tables = 2 * ts * d * jnp.dtype(table_dtype).itemsize
    slab = ts * d * 4
    if kernel == "fwd":   # x in, y out; cos, signed sine and six live slabs
        return 2 * (2 * rows + tables) + 8 * slab
    # x and dy in, dx out, the partial sums; the tables, the weight
    # gradient's accumulator and a dozen live slabs
    return 2 * (3 * rows + tables + 2 * _SUBLANES * d * 4) + 14 * slab


def _row_tile(kernel: str, s: int, *widths_and_dtypes) -> Optional[int]:
    """The largest row tile of flash's sizes that divides ``s`` and fits its
    VMEM budget; None where none does."""
    return next((t for t in _fa._TILE_SIZES if s % t == 0
                 and _vmem_bytes(kernel, t, *widths_and_dtypes) <= _fa._VMEM_BUDGET), None)


def _compiler_params(kernel: str, ts: int, *widths_and_dtypes):
    need = _vmem_bytes(kernel, ts, *widths_and_dtypes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=int(min(max(2 * need, 16 * 2 ** 20), 100 * 2 ** 20)),
    )


def _tables(cos_ref, sin_ref, d: int):
    """(cos, signed sine) of a block, f32 ``[ts, d]``: ``_rotate_half(y) * sin``
    is ``roll(y, d/2) * sin_signed`` with the first half's sine negated."""
    cos = cos_ref[0].astype(jnp.float32)
    sin = sin_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    return cos, jnp.where(lane < d // 2, -sin, sin)


def _weight(w_ref, zero_centered: bool):
    w = w_ref[...].astype(jnp.float32)  # [1, d]
    return 1.0 + w if zero_centered else w


def _rstd(xf, eps: float):
    return jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _fwd_kernel(*refs, d, eps, zero_centered, normed):
    if normed:
        q_ref, k_ref, cos_ref, sin_ref, wq_ref, wk_ref, oq_ref, ok_ref = refs
    else:
        q_ref, k_ref, cos_ref, sin_ref, oq_ref, ok_ref = refs
        wq_ref = wk_ref = None
    cos, sin_signed = _tables(cos_ref, sin_ref, d)
    for x_ref, w_ref, o_ref in ((q_ref, wq_ref, oq_ref), (k_ref, wk_ref, ok_ref)):
        w = _weight(w_ref, zero_centered) if normed else None
        for h in range(x_ref.shape[-1] // d):
            cols = slice(h * d, (h + 1) * d)
            x = x_ref[0, :, cols]
            if normed:
                xf = x.astype(jnp.float32)
                x = (xf * _rstd(xf, eps) * w).astype(x.dtype)
            y = x.astype(jnp.float32)
            out = y * cos + pltpu.roll(y, d // 2, axis=1) * sin_signed
            o_ref[0, h] = out.astype(o_ref.dtype)


def _bwd_kernel(*refs, d, eps, zero_centered, normed):
    if normed:
        (q_ref, k_ref, gq_ref, gk_ref, cos_ref, sin_ref, wq_ref, wk_ref,
         dq_ref, dk_ref, dwq_ref, dwk_ref) = refs
    else:
        q_ref, k_ref, gq_ref, gk_ref, cos_ref, sin_ref, dq_ref, dk_ref = refs
        wq_ref = wk_ref = dwq_ref = dwk_ref = None
    cos, sin_signed = _tables(cos_ref, sin_ref, d)
    ts = cos.shape[0]
    for x_ref, g_ref, w_ref, dx_ref, dw_ref in (
            (q_ref, gq_ref, wq_ref, dq_ref, dwq_ref), (k_ref, gk_ref, wk_ref, dk_ref, dwk_ref)):
        if normed:
            w = _weight(w_ref, zero_centered)
            dw = jnp.zeros((ts, d), jnp.float32)
        for h in range(x_ref.shape[-1] // d):
            cols = slice(h * d, (h + 1) * d)
            g = g_ref[0, h].astype(jnp.float32)
            # the rotation's transpose, rounded as the composition rounds the
            # gradient between rope's backward and the norm's
            dy = (g * cos + pltpu.roll(g * sin_signed, d // 2, axis=1)).astype(dx_ref.dtype)
            if not normed:
                dx_ref[0, :, cols] = dy
                continue
            dy = dy.astype(jnp.float32)
            xf = x_ref[0, :, cols].astype(jnp.float32)
            rstd = _rstd(xf, eps)
            n = xf * rstd
            dw = dw + dy * n
            dn = dy * w
            dx = (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True)) * rstd
            dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        if normed:
            # rows fold onto eight sublanes with whole-register adds; XLA
            # sums the eight, the blocks and the batch
            dw_ref[0, 0] = dw.reshape(ts // _SUBLANES, _SUBLANES, d).sum(axis=0)


def _specs(ts: int, wq: int, wk: int, d: int):
    rows = lambda w: pl.BlockSpec((1, ts, w), lambda bi, si: (bi, si, 0))
    heads = lambda w: pl.BlockSpec((1, w // d, ts, d), lambda bi, si: (bi, 0, si, 0))
    return dict(q=rows(wq), k=rows(wk), table=rows(d), q_heads=heads(wq), k_heads=heads(wk),
                weight=pl.BlockSpec((1, d), lambda bi, si: (0, 0)),
                dw=pl.BlockSpec((1, 1, _SUBLANES, d), lambda bi, si: (bi, si, 0, 0)))


def _weights(call: _Call, wq, wk):
    return () if wq is None else (wq.reshape(1, call.d), wk.reshape(1, call.d))


def _heads_shape(x, d: int):
    b, s, width = x.shape
    return jax.ShapeDtypeStruct((b, width // d, s, d), x.dtype)


def _fwd(call: _Call, q, k, cos, sin, wq, wk):
    b, s, width_q = q.shape
    width_k = k.shape[-1]
    ts, normed = call.ts_fwd, wq is not None
    sp = _specs(ts, width_q, width_k, call.d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=call.d, eps=call.eps,
                          zero_centered=call.zero_centered, normed=normed),
        grid=(b, s // ts),
        in_specs=[sp["q"], sp["k"], sp["table"], sp["table"]] + [sp["weight"]] * (2 * normed),
        out_specs=[sp["q_heads"], sp["k_heads"]],
        out_shape=[_heads_shape(q, call.d), _heads_shape(k, call.d)],
        compiler_params=_compiler_params("fwd", ts, width_q + width_k, call.d, q.dtype, cos.dtype),
        interpret=_fa._interpret(),
        name="qk_norm_rope_fwd",  # observability/scopes.py::SCOPED_KERNEL_NAMES
    )(q, k, cos, sin, *_weights(call, wq, wk))


def _bwd(call: _Call, residuals, grads):
    q, k, cos, sin, wq, wk = residuals
    gq, gk = grads
    b, s, width_q = q.shape
    width_k = k.shape[-1]
    ts, normed = call.ts_bwd, wq is not None
    sp = _specs(ts, width_q, width_k, call.d)
    dw_shape = jax.ShapeDtypeStruct((b, s // ts, _SUBLANES, call.d), jnp.float32)
    dq, dk, *dws = pl.pallas_call(
        functools.partial(_bwd_kernel, d=call.d, eps=call.eps,
                          zero_centered=call.zero_centered, normed=normed),
        grid=(b, s // ts),
        in_specs=[sp["q"], sp["k"], sp["q_heads"], sp["k_heads"], sp["table"], sp["table"]]
        + [sp["weight"]] * (2 * normed),
        out_specs=[sp["q"], sp["k"]] + [sp["dw"]] * (2 * normed),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype)]
        + [dw_shape] * (2 * normed),
        compiler_params=_compiler_params("bwd", ts, width_q + width_k, call.d, q.dtype, cos.dtype),
        interpret=_fa._interpret(),
        name="qk_norm_rope_bwd",
    )(q, k, gq, gk, cos, sin, *_weights(call, wq, wk))
    dwq, dwk = (dw.sum(axis=(0, 1, 2)).astype(w.dtype).reshape(w.shape)
                for dw, w in zip(dws, (wq, wk))) if normed else (None, None)
    return dq, dk, None, None, dwq, dwk


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _qk_norm_rope(call: _Call, q, k, cos, sin, wq, wk):
    """q ``[B, S, Hq*D]``, k ``[B, S, Hk*D]`` to ``[B, Hq, S, D]``, ``[B, Hk, S, D]``."""
    return tuple(_fwd(call, q, k, cos, sin, wq, wk))


def _fwd_rule(call, q, k, cos, sin, wq, wk):
    return tuple(_fwd(call, q, k, cos, sin, wq, wk)), (q, k, cos, sin, wq, wk)


_qk_norm_rope.defvjp(_fwd_rule, _bwd)


def _handoff_reason(q, k, cos, sin, q_weight, k_weight, interleaved, d, dp, sp) -> Optional[str]:
    """Why this call cannot take the kernel (None: it can); ``dp`` and ``sp``
    are the mesh's extents over the batch and the rows (1 off a mesh)."""
    b, s, _ = q.shape
    if interleaved:
        return "interleaved rotary"
    if cos.shape[-1] != d:
        return f"partial rotary ({cos.shape[-1]} of {d})"
    if d % _fa._LANES:
        return f"head_dim {d} not a multiple of {_fa._LANES}"
    if (q_weight is None) != (k_weight is None):
        return "a norm weight for one of q and k only"
    if cos.shape != (b, s, d) or sin.shape != cos.shape or k.shape[:2] != (b, s):
        return "tables or k not [B, S, ...] like q"
    if b % dp:
        return f"batch not a multiple of the mesh's dp extent {dp}"
    if s % (sp * _fa._LANES):
        over = f" over the mesh's sp extent {sp}" if sp > 1 else ""
        return f"S{over} not a multiple of {_fa._LANES}"
    return None


@KERNEL_REGISTRY.register("qk_norm_rotary", "pallas", device_types=("tpu",), priority=10)
def qk_norm_rope(q, k, cos, sin, q_weight=None, k_weight=None, eps: float = 1e-6,
                 zero_centered: bool = False, interleaved: bool = False, head_dim=None):
    """The kernels behind ``ops.qk_norm_rotary``. What they write as
    ``[B, H, S, D]`` is handed back as its ``[B, S, H, D]`` view; the flash
    wrapper's own ``swapaxes`` undoes that and no copy is left. GSPMD cannot
    partition a Mosaic kernel, so under it on a multi-device mesh they run in
    a shard_map over the activation's own sharding (dp on batch, sp on
    sequence: the op is per token); inside a shard_map they are already
    per-device."""
    from veomni_tpu.parallel.parallel_state import gspmd_parallel_state

    d = head_dim_of(cos, q_weight, head_dim)
    pstate = gspmd_parallel_state()
    dp, sp = (pstate.dp_size, pstate.sp_size) if pstate is not None else (1, 1)
    reason = _handoff_reason(q, k, cos, sin, q_weight, k_weight, interleaved, d, dp, sp)
    if reason is None:
        width = q.shape[-1] + k.shape[-1]
        tiles = [_row_tile(kernel, q.shape[1] // sp, width, d, q.dtype, cos.dtype)
                 for kernel in ("fwd", "bwd")]
        if None in tiles:
            reason = "no row tile fits VMEM"
    if reason is not None:
        logger.info_once(
            "op qk_norm_rotary: pallas hands q%s k%s to xla (%s)",
            tuple(q.shape), tuple(k.shape), reason,
        )
        return _qk_norm_rotary_xla(q, k, cos, sin, q_weight, k_weight, eps, zero_centered,
                                   interleaved, head_dim)
    kernel = functools.partial(_qk_norm_rope, _Call(d, *tiles, float(eps), bool(zero_centered)))
    if pstate is not None:
        rows = P(pstate.dp_axes, pstate.sp_axes, None)
        heads = P(pstate.dp_axes, None, pstate.sp_axes, None)
        weights = None if q_weight is None else P()
        kernel = jax.shard_map(
            kernel, mesh=pstate.mesh, in_specs=(rows, rows, rows, rows, weights, weights),
            out_specs=(heads, heads), check_vma=False,
        )
    q, k = kernel(q, k, cos, sin, q_weight, k_weight)
    return jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
