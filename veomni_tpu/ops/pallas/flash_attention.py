"""Flash attention (Pallas TPU): online-softmax fwd + custom-VJP bwd.

Reference capability: ``veomni/ops/kernels/attention/flash.py`` (adapter over
external flash-attn CUDA wheels, varlen via cu_seqlens). TPU-native design:

* packing is expressed with **segment ids** (cu_seqlens equivalent): tokens
  attend only within equal segment id; padding uses a sentinel that matches
  nothing.
* layout [B, H, S, D]; grid (batch, q_head, q_block, k_block) with the
  k_block axis sequential ("arbitrary") carrying the online-softmax state in
  VMEM scratch; causal k-blocks above the diagonal are skipped via pl.when.
* GQA: the kv BlockSpec index-maps q-head -> q_head // group, so no
  materialized head repeat.
* backward: two kernels (dkv per q-head then XLA group-sum; dq) using the
  saved LSE — the standard flash-v2 recomputation split.

Numerics: scores/softmax in f32 (MXU preferred_element_type), output cast
back to the input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30
_LANES = 128  # scratch lane width (TPU min tile)
_ROWS = 8     # lane width for row-stat (lse/delta) tensors: block lane dim
              # equal to the array dim satisfies the Mosaic tiling rule


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ==========================================================================
# Forward
# ==========================================================================
def _fwd_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,
    o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, bq: int, bk: int,
):
    iq, jk = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal
    work = True if not causal else (jk * bk <= iq * bq + bq - 1)

    @pl.when(work)
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        seg_q = seg_q_ref[0, :]  # [bq]
        seg_k = seg_k_ref[0, :]  # [bk]
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = mask & (rows >= cols)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(jk == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        m = m_scr[:, 0]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse[:, None], (lse.shape[0], _ROWS))


def _fwd(q, k, v, segment_ids, scale, causal, bq, bk):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    nq, nk = s // bq, s // bk

    grid = (b, hq, nq, nk)
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, iq, jk: (bi, hi // group, jk, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            # segment ids ride as [B, 1, S]: a squeezed-batch rank-2 block
            # (1, bq) would violate Mosaic's (8, 128) tiling rule; with the
            # unit middle dim the block's last-two dims are (1, bq) where
            # 1 == the array dim, which Mosaic accepts.
            pl.BlockSpec((None, 1, bq), lambda bi, hi, iq, jk: (bi, 0, iq)),
            pl.BlockSpec((None, 1, bk), lambda bi, hi, iq, jk: (bi, 0, jk)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bq, _ROWS), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, _ROWS), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_fwd",  # observability/scopes.py::KERNEL_NAMES
    )(segment_ids[:, None, :], segment_ids[:, None, :], q, k, v)
    return out, lse


# ==========================================================================
# Backward
# ==========================================================================
def _bwd_dkv_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, bq: int, bk: int,
):
    jk, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    work = True if not causal else (iq * bq + bq - 1 >= jk * bk)

    @pl.when(work)
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        mask = seg_q_ref[0, :][:, None] == seg_k_ref[0, :][None, :]
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = mask & (rows >= cols)
        lse_safe = jnp.where(lse <= _NEG_INF / 2, 0.0, lse)
        p = jnp.where(mask, jnp.exp(s - lse_safe[:, None]), 0.0)  # [bq, bk]

        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # p^T @ do -> [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # ds^T @ q -> [bk, d]

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *, scale: float, causal: bool, bq: int, bk: int,
):
    iq, jk = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    work = True if not causal else (jk * bk <= iq * bq + bq - 1)

    @pl.when(work)
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = seg_q_ref[0, :][:, None] == seg_k_ref[0, :][None, :]
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = mask & (rows >= cols)
        lse_safe = jnp.where(lse <= _NEG_INF / 2, 0.0, lse)
        p = jnp.where(mask, jnp.exp(s - lse_safe[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, d]

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _bwd(scale, causal, bq, bk, residuals, g):
    q, k, v, segment_ids, out, lse = residuals
    do = g[0] if isinstance(g, (tuple, list)) else g
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    nq, nk = s // bq, s // bk

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_ROWS,))  # [B,H,S,_ROWS]

    seg3 = segment_ids[:, None, :]  # [B, 1, S] — see fwd in_specs comment
    seg_specs = [
        pl.BlockSpec((None, 1, bq), lambda bi, hi, jk, iq: (bi, 0, iq)),
        pl.BlockSpec((None, 1, bk), lambda bi, hi, jk, iq: (bi, 0, jk)),
    ]
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, jk, iq: (bi, hi, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, jk, iq: (bi, hi // group, jk, 0))
    row_spec = pl.BlockSpec((1, 1, bq, _ROWS), lambda bi, hi, jk, iq: (bi, hi, iq, 0))

    dk_per_head, dv_per_head = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(b, hq, nk, nq),
        in_specs=[*seg_specs, q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, jk, iq: (bi, hi, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, jk, iq: (bi, hi, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, s, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(seg3, seg3, q, k, v, do, lse, delta)

    # GQA: fold the q-head group into the kv head grad
    dk = dk_per_head.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
    dv = dv_per_head.reshape(b, hkv, group, s, d).sum(axis=2).astype(v.dtype)

    q_spec2 = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, iq, jk: (bi, hi // group, jk, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, _ROWS), lambda bi, hi, iq, jk: (bi, hi, iq, 0))
    seg_specs2 = [
        pl.BlockSpec((None, 1, bq), lambda bi, hi, iq, jk: (bi, 0, iq)),
        pl.BlockSpec((None, 1, bk), lambda bi, hi, iq, jk: (bi, 0, jk)),
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(b, hq, nq, nk),
        in_specs=[*seg_specs2, q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(seg3, seg3, q, k, v, do, lse, delta)

    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd(q, k, v, segment_ids, scale, causal, bq, bk):
    out, _ = _fwd(q, k, v, segment_ids, scale, causal, bq, bk)
    return out


def _flash_fwd_rule(q, k, v, segment_ids, scale, causal, bq, bk):
    out, lse = _fwd(q, k, v, segment_ids, scale, causal, bq, bk)
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, causal, bq, bk, residuals, g):
    return _bwd(scale, causal, bq, bk, residuals, g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ==========================================================================
# Public op (registered)
# ==========================================================================
def _handoff_reason(q, k, v, sliding_window, sinks, bq, bk, pstate) -> Optional[str]:
    """Why this call cannot take the kernel (None: it can)."""
    b, s, hq, d = q.shape
    if sliding_window is not None:
        return "sliding_window"
    if sinks is not None:
        return "sinks"
    if v.shape[-1] != d:
        return "v head_dim != qk head_dim"
    if k.shape[1] != s:
        return "Sq != Sk"
    # lane-aligned blocks that tile the sequence exactly
    if s % bq or s % bk or bq % _LANES or bk % _LANES:
        return f"S not a multiple of {_LANES}"
    if hq % k.shape[2]:
        return "q heads not a multiple of kv heads"
    if pstate is not None and b % pstate.dp_size:
        return f"batch not a multiple of the mesh's dp extent {pstate.dp_size}"
    return None


@KERNEL_REGISTRY.register(
    "attention", "pallas_flash", device_types=("tpu",), priority=10
)
def flash_attention(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
):
    """[B, S, H, D] facade-layout wrapper. Shapes/features the kernel
    doesn't cover (sliding window, sinks, MLA's asymmetric v-dim, cross
    attention, tiny/ragged S) go to the XLA impl, with one log line naming
    the reason. GSPMD cannot partition a Mosaic kernel, so under it on a
    multi-device mesh the kernel runs in a shard_map over the batch (dp)
    axes; inside the Ulysses/ring shard_map it is already per-device.
    """
    from veomni_tpu.parallel.parallel_state import gspmd_parallel_state

    b, s, hq, d = q.shape
    bq, bk = min(block_q, s), min(block_k, s)
    pstate = gspmd_parallel_state()
    reason = _handoff_reason(q, k, v, sliding_window, sinks, bq, bk, pstate)
    if reason is not None:
        from veomni_tpu.ops.attention import _attention_xla

        logger.info_once(
            "op attention: pallas_flash hands q%s kv%s to xla (%s)",
            tuple(q.shape), tuple(k.shape), reason,
        )
        return _attention_xla(
            q, k, v, segment_ids=segment_ids, causal=causal,
            softmax_scale=softmax_scale, sliding_window=sliding_window,
            sinks=sinks,
        )
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)

    def kernel(q, k, v, seg):
        out = _flash_bhsd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            seg.astype(jnp.int32), scale, causal, bq, bk,
        )
        return jnp.swapaxes(out, 1, 2)

    if pstate is not None:
        qkv_spec = P(pstate.dp_axes, None, None, None)
        kernel = jax.shard_map(
            kernel, mesh=pstate.mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, P(pstate.dp_axes, None)),
            out_specs=qkv_spec, check_vma=False,
        )
    return kernel(q, k, v, segment_ids)
