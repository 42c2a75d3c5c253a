"""MLA's split, pairwise rope, head broadcast and relayout as one pass each
way (Pallas TPU).

What XLA made of the composition on a v5e (PERF.md, PR 35): whole-tensor
layout copies of both projection outputs, six gathers for the pairwise
rotation, f32 intermediates whose trailing dimension of 2 fills a tile's 128
lanes, and a last copy into the flash kernels' layout. Here:

* the kernels read the projections' own outputs. A head of q is a static
  ``dn + dr``-lane column group of ``[B, S, H*(dn+dr)]`` (128-aligned at even
  heads, at lane 64 at odd ones), a head of kv two aligned groups of ``dn`` and
  ``dv`` lanes. They write q, k ``[B, H, S, dn+dr]`` and v ``[B, H, S, dv]``,
  the layout the flash kernels read, and the backward reads its cotangents in
  that layout;
* ``mla_qkv_rope_fwd``, grid (batch, S / ts, H / hg): the nope lanes and v are
  copied untouched. The rope lanes are rotated in f32 on the whole 128-lane
  tile that holds them, ``y * cos + partner(y) * sin_signed``: the tables are
  laid into both halves of a tile and the rotation's sign folded into the sine
  once a block, ``partner`` is two lane rolls and a select (by 1 and the
  lane's parity for the pairwise rotation, by ``dr / 2`` otherwise), so no
  gather and no half-width array exists; one rounding to the input dtype, as
  the composition has. ``k_rope`` is rotated once a block and written into
  every head's rope lanes;
* ``mla_qkv_rope_bwd`` (a ``jax.custom_vjp``): the op is linear, so the
  residuals are the tables alone. It rotates the rope lanes of dQ back, sums
  dK's rope lanes over the heads in f32 (across the head groups in a scratch)
  and rotates the sum back with one rounding, and writes
  ``[B, S, H*(dn+dr)]``, ``[B, S, H*(dn+dv)]``, ``[B, S, dr]``. The tables get
  no gradient (they come from integer positions).

What the kernels do not take (``dn`` or ``dv`` no multiple of 128, ``dr`` not
half a lane tile, an odd number of heads, a ragged S) goes to the ``xla`` impl
with one log line naming the reason.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.ops.mla_qkv_rotary import _mla_qkv_rotary_xla
from veomni_tpu.ops.pallas import flash_attention as _fa
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_LANES = _fa._LANES


class _Call(NamedTuple):
    """The static half of a call."""

    dn: int               # nope lanes of a q or k head
    dr: int               # rope lanes
    dv: int               # lanes of a v head
    interleaved: bool     # pairwise rotation (deepseek's rope_interleave)
    ts: int               # row tile
    hg: int               # heads a grid step


def _padded(width: int) -> int:
    """``width`` lanes in whole lane tiles: what a block that wide takes."""
    return -(-width // _LANES) * _LANES


def _vmem_bytes(ts: int, hg: int, dn: int, dr: int, dv: int, dtype, table_dtype) -> int:
    """VMEM either kernel needs at row tile ``ts`` and ``hg`` heads a step
    (the backward's blocks are the forward's, read for written): every block
    twice (the pipeline double-buffers them) and eight f32 tile slabs (the
    tables, a tile, its partner and their products)."""
    item = jnp.dtype(dtype).itemsize
    rows = ts * hg * (2 * dn + dr + dv) * item
    heads = ts * hg * (2 * _padded(dn + dr) + dv) * item
    small = ts * _padded(dr) * (item + 2 * jnp.dtype(table_dtype).itemsize)
    return 2 * (rows + heads + small) + 8 * ts * _LANES * 4


def _tiles(s: int, h: int, *widths_and_dtypes) -> Optional[Tuple[int, int]]:
    """(row tile, heads a step): the largest row tile of flash's sizes that
    divides ``s``, and with it the largest even group of heads that fits
    flash's VMEM budget. The kernels move bytes and nothing else shows (the
    chip read every split of a block alike, PERF.md, PR 35), and a body is
    unrolled over its heads, so rows before heads keeps it short to trace,
    lower and compile. None where nothing fits."""
    groups = [g for g in range(h, 1, -2) if h % g == 0]
    return next(((t, g) for t in _fa._TILE_SIZES if s % t == 0 for g in groups
                 if _vmem_bytes(t, g, *widths_and_dtypes) <= _fa._VMEM_BUDGET), None)


def _compiler_params(call: _Call, dtype, table_dtype):
    need = _vmem_bytes(call.ts, call.hg, call.dn, call.dr, call.dv, dtype, table_dtype)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(2 * need, 16 * 2 ** 20), 100 * 2 ** 20)),
    )


def _both_halves(x):
    """``[ts, dr]`` laid into both halves of a 128-lane tile, f32."""
    x = x.astype(jnp.float32)
    return jnp.concatenate([x, x], axis=1)


class _Rotation(NamedTuple):
    """A block's tables, f32 ``[ts, 128]`` with the ``dr`` rope lanes' values
    in both halves of the tile: the rotation is
    ``y * cos + partner(y) * sin_signed``, where a lane's partner is the other
    member of its pair (``first`` says which of the two it is, ``shift`` how
    far apart they lie) and the sign of the rotation is folded into the sine."""

    cos: jax.Array
    sin_signed: jax.Array
    first: jax.Array
    shift: int

    def partner(self, y):
        return jnp.where(self.first, pltpu.roll(y, _LANES - self.shift, axis=1),
                         pltpu.roll(y, self.shift, axis=1))

    def forward(self, y):
        return y * self.cos + self.partner(y) * self.sin_signed

    def transposed(self, g):
        return g * self.cos + self.partner(g * self.sin_signed)


def _rotation(cos_ref, sin_ref, dr: int, interleaved: bool) -> _Rotation:
    cos, sin = _both_halves(cos_ref[0]), _both_halves(sin_ref[0])
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    # pairwise: (x[2i], x[2i+1]); else the halves of the rope lanes: (x[i], x[i + dr/2])
    first = (lane % 2 == 0) if interleaved else (lane % dr < dr // 2)
    return _Rotation(cos, jnp.where(first, -sin, sin), first, 1 if interleaved else dr // 2)


def _fwd_kernel(q_ref, kv_ref, kr_ref, cos_ref, sin_ref, oq_ref, ok_ref, ov_ref, *,
                dn, dr, dv, interleaved):
    rot = _rotation(cos_ref, sin_ref, dr, interleaved)
    k_rope = rot.forward(_both_halves(kr_ref[0]))[:, :dr].astype(ok_ref.dtype)
    for h in range(ov_ref.shape[1]):
        c = h * (dn + dv)
        ok_ref[0, h, :, :dn] = kv_ref[0, :, c:c + dn]
        ok_ref[0, h, :, dn:] = k_rope
        ov_ref[0, h] = kv_ref[0, :, c + dn:c + dn + dv]
        c = h * (dn + dr)
        oq_ref[0, h, :, :dn] = q_ref[0, :, c:c + dn]
        # the whole tile that holds the head's rope lanes (its lower half at
        # even heads, its upper half at odd ones) is rotated
        off = (c + dn) % _LANES
        y = rot.forward(q_ref[0, :, c + dn - off:c + dn - off + _LANES].astype(jnp.float32))
        oq_ref[0, h, :, dn:] = y[:, off:off + dr].astype(oq_ref.dtype)


def _bwd_kernel(gq_ref, gk_ref, gv_ref, cos_ref, sin_ref, dq_ref, dkv_ref, dkr_ref, acc_ref, *,
                dn, dr, dv, interleaved):
    rot = _rotation(cos_ref, sin_ref, dr, interleaved)
    group = pl.program_id(2)

    @pl.when(group == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc = acc_ref[...]
    for h in range(gv_ref.shape[1]):
        c = h * (dn + dv)
        dkv_ref[0, :, c:c + dn] = gk_ref[0, h, :, :dn]
        dkv_ref[0, :, c + dn:c + dn + dv] = gv_ref[0, h]
        acc = acc + gk_ref[0, h, :, dn:].astype(jnp.float32)
        c = h * (dn + dr)
        dq_ref[0, :, c:c + dn] = gq_ref[0, h, :, :dn]
        # both halves of the tile hold the head's rope lanes, so the half
        # that lies at the lanes written needs no shift
        off = (c + dn) % _LANES
        dy = rot.transposed(_both_halves(gq_ref[0, h, :, dn:]))
        dq_ref[0, :, c + dn:c + dn + dr] = dy[:, off:off + dr].astype(dq_ref.dtype)
    acc_ref[...] = acc

    @pl.when(group == pl.num_programs(2) - 1)
    def _():
        dkr_ref[0] = rot.transposed(_both_halves(acc))[:, :dr].astype(dkr_ref.dtype)


def _specs(call: _Call):
    ts, hg = call.ts, call.hg
    rows = lambda w: pl.BlockSpec((1, ts, hg * w), lambda bi, si, gi: (bi, si, gi))
    heads = lambda w: pl.BlockSpec((1, hg, ts, w), lambda bi, si, gi: (bi, gi, si, 0))
    return dict(q=rows(call.dn + call.dr), kv=rows(call.dn + call.dv),
                rope=pl.BlockSpec((1, ts, call.dr), lambda bi, si, gi: (bi, si, 0)),
                qk_heads=heads(call.dn + call.dr), v_heads=heads(call.dv))


def _fwd(call: _Call, q, kv, k_rope, cos, sin):
    b, s, width = q.shape
    h = width // (call.dn + call.dr)
    sp = _specs(call)
    qk_heads = jax.ShapeDtypeStruct((b, h, s, call.dn + call.dr), q.dtype)
    return tuple(pl.pallas_call(
        functools.partial(_fwd_kernel, dn=call.dn, dr=call.dr, dv=call.dv,
                          interleaved=call.interleaved),
        grid=(b, s // call.ts, h // call.hg),
        in_specs=[sp["q"], sp["kv"], sp["rope"], sp["rope"], sp["rope"]],
        out_specs=[sp["qk_heads"], sp["qk_heads"], sp["v_heads"]],
        out_shape=[qk_heads, qk_heads, jax.ShapeDtypeStruct((b, h, s, call.dv), q.dtype)],
        compiler_params=_compiler_params(call, q.dtype, cos.dtype),
        interpret=_fa._interpret(),
        name="mla_qkv_rope_fwd",  # observability/scopes.py::SCOPED_KERNEL_NAMES
    )(q, kv, k_rope, cos, sin))


def _bwd(call: _Call, tables, grads):
    cos, sin = tables
    gq, gk, gv = grads
    b, h, s, _ = gq.shape
    sp = _specs(call)
    rows = lambda w: jax.ShapeDtypeStruct((b, s, w), gq.dtype)
    dq, dkv, dk_rope = pl.pallas_call(
        functools.partial(_bwd_kernel, dn=call.dn, dr=call.dr, dv=call.dv,
                          interleaved=call.interleaved),
        grid=(b, s // call.ts, h // call.hg),
        in_specs=[sp["qk_heads"], sp["qk_heads"], sp["v_heads"], sp["rope"], sp["rope"]],
        out_specs=[sp["q"], sp["kv"], sp["rope"]],
        out_shape=[rows(h * (call.dn + call.dr)), rows(h * (call.dn + call.dv)), rows(call.dr)],
        scratch_shapes=[pltpu.VMEM((call.ts, call.dr), jnp.float32)],
        compiler_params=_compiler_params(call, gq.dtype, cos.dtype),
        interpret=_fa._interpret(),
        name="mla_qkv_rope_bwd",
    )(gq, gk, gv, cos, sin)
    return dq, dkv, dk_rope, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mla_qkv_rope(call: _Call, q, kv, k_rope, cos, sin):
    """q ``[B, S, H*(dn+dr)]``, kv ``[B, S, H*(dn+dv)]``, ``k_rope`` and the
    tables ``[B, S, dr]`` to q, k ``[B, H, S, dn+dr]`` and v ``[B, H, S, dv]``."""
    return _fwd(call, q, kv, k_rope, cos, sin)


def _fwd_rule(call, q, kv, k_rope, cos, sin):
    return _fwd(call, q, kv, k_rope, cos, sin), (cos, sin)


_mla_qkv_rope.defvjp(_fwd_rule, _bwd)


def _handoff_reason(q, kv, k_rope, cos, sin, dn, dr, dv, dp, sp) -> Optional[str]:
    """Why this call cannot take the kernel (None: it can); ``dp`` and ``sp``
    are the mesh's extents over the batch and the rows (1 off a mesh)."""
    b, s, width = q.shape
    if dn % _LANES:
        return f"qk_nope_head_dim {dn} not a multiple of {_LANES}"
    if dv % _LANES:
        return f"v_head_dim {dv} not a multiple of {_LANES}"
    if 2 * dr != _LANES:
        return f"qk_rope_head_dim {dr} not {_LANES // 2}"
    h = width // (dn + dr)
    if width % (dn + dr) or kv.shape != (b, s, h * (dn + dv)):
        return "q and kv not of the same heads"
    if h % 2:
        return f"an odd number of heads ({h})"
    if any(t.shape != (b, s, dr) for t in (k_rope, cos, sin)):
        return "k_rope or tables not [B, S, qk_rope_head_dim]"
    if not q.dtype == kv.dtype == k_rope.dtype:
        return "q, kv and k_rope of different dtypes"
    if b % dp:
        return f"batch not a multiple of the mesh's dp extent {dp}"
    if s % (sp * _LANES):
        over = f" over the mesh's sp extent {sp}" if sp > 1 else ""
        return f"S{over} not a multiple of {_LANES}"
    return None


@KERNEL_REGISTRY.register("mla_qkv_rotary", "pallas", device_types=("tpu",), priority=10)
def mla_qkv_rope(q, kv, k_rope, cos, sin, dn: int, dr: int, dv: int, interleaved: bool = False):
    """The kernels behind ``ops.mla_qkv_rotary``. What they write as
    ``[B, H, S, D]`` is handed back as its ``[B, S, H, D]`` view; the flash
    wrapper's own ``swapaxes`` undoes that and no copy is left. Under GSPMD on
    a multi-device mesh they run in a shard_map over the activation's own
    sharding (dp on batch, sp on sequence: the op is per token), as
    ``qk_norm_rope`` does. Each traced call counts once in the registry, under
    ``attn.mla_qkv_rope.calls_kernel`` or ``.calls_handed_over``."""
    from veomni_tpu.parallel.parallel_state import gspmd_parallel_state

    pstate = gspmd_parallel_state()
    dp, sp = (pstate.dp_size, pstate.sp_size) if pstate is not None else (1, 1)
    reason = _handoff_reason(q, kv, k_rope, cos, sin, dn, dr, dv, dp, sp)
    if reason is None:
        tiles = _tiles(q.shape[1] // sp, q.shape[-1] // (dn + dr), dn, dr, dv, q.dtype, cos.dtype)
        if tiles is None:
            reason = "no row tile fits VMEM"
    if reason is not None:
        get_registry().counter("attn.mla_qkv_rope.calls_handed_over").inc()
        logger.info_once(
            "op mla_qkv_rotary: pallas hands q%s kv%s to xla (%s)",
            tuple(q.shape), tuple(kv.shape), reason,
        )
        return _mla_qkv_rotary_xla(q, kv, k_rope, cos, sin, dn, dr, dv, interleaved)
    get_registry().counter("attn.mla_qkv_rope.calls_kernel").inc()
    kernel = functools.partial(_mla_qkv_rope, _Call(dn, dr, dv, bool(interleaved), *tiles))
    if pstate is not None:
        rows = P(pstate.dp_axes, pstate.sp_axes, None)
        heads = P(pstate.dp_axes, None, pstate.sp_axes, None)
        kernel = jax.shard_map(
            kernel, mesh=pstate.mesh, in_specs=(rows,) * 5, out_specs=(heads,) * 3,
            check_vma=False,
        )
    return tuple(jnp.swapaxes(x, 1, 2) for x in kernel(q, kv, k_rope, cos, sin))
