"""Grouped matmul (Pallas TPU): variable-M expert GEMM for fused MoE.

Reference: ``veomni/ops/kernels/moe/_kernels/kernel/group_gemm.py:65-397``
(Triton group_gemm_same_nk / same_mn over the per-expert token cumsum).

Kernel shape: lhs [M, K] with rows sorted by expert, rhs [E, K, N],
group_sizes [E] -> out [M, N]. The grid runs (m_tile, n_tile, expert) with
the expert dim sequential; group start offsets ride in scalar-prefetch SMEM,
and a tile only does work for experts whose row range intersects it (rows
outside the expert are masked to zero before the MXU dot, so boundary tiles
stay correct without dynamic shapes). Index maps may only load SCALARS from
the prefetched refs (Mosaic: "Can only load scalars from SMEM"), so the
per-tile [first, last] intersecting-expert table is computed in XLA outside
the kernel and prefetched alongside the offsets.

Backward (custom VJP):
  dlhs = gmm(g, rhs^T)            -- the same kernel, weights transposed
  drhs = gmm_transpose(lhs, g)    -- [E,K,N] accumulation kernel below
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------- forward
def _gmm_kernel(gs_ref, tiles_ref, lhs_ref, rhs_ref, out_ref, acc_scr, *, bm, bn):
    i, e = pl.program_id(0), pl.program_id(2)
    ne = pl.num_programs(2)

    @pl.when(e == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = gs_ref[e]
    end = gs_ref[e + 1]
    tile_lo = i * bm

    @pl.when(jnp.logical_and(end > tile_lo, start < tile_lo + bm))
    def _work():
        rows = tile_lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)[:, 0]
        mask = (rows >= start) & (rows < end)
        x = jnp.where(mask[:, None], lhs_ref[...], 0)
        acc_scr[...] += jax.lax.dot_general(
            x, rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(e == ne - 1)
    def _emit():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def _tile_expert_range(group_starts, m: int, bm: int):
    """[2, m // bm] int32: per m-tile, the first and last expert whose row
    range can intersect the tile (clipped into [0, E-1], so padding tiles
    past the last group point at a real weight block)."""
    e = group_starts.shape[0] - 1
    lo = jnp.arange(m // bm, dtype=jnp.int32) * bm
    first = jnp.searchsorted(group_starts[1:], lo, side="right")
    last = jnp.searchsorted(group_starts[:-1], lo + bm, side="left") - 1
    first = jnp.clip(first, 0, e - 1)
    return jnp.stack([first, jnp.clip(last, first, e - 1)]).astype(jnp.int32)


def _effective_expert(i, e, tiles):
    """Avoid redundant weight DMA: (tile, expert) steps outside the tile's
    intersecting range clamp to its boundary expert, so the block index
    stays constant across skipped steps and Pallas reuses the resident
    block. Scalar loads only."""
    return jnp.minimum(jnp.maximum(e, tiles[0, i]), tiles[1, i])


def _gmm_raw(lhs, rhs, group_starts, bm: int, bn: int):
    m, k = lhs.shape
    e, _, n = rhs.shape
    grid = (m // bm, n // bn, e)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, bm=bm, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j, e, gs, tiles: (i, 0)),
                pl.BlockSpec(
                    (1, k, bn),
                    lambda i, j, e, gs, tiles: (_effective_expert(i, e, tiles), 0, j),
                ),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, e, gs, tiles: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="gmm_fwd",  # observability/scopes.py::KERNEL_NAMES
    )(group_starts, _tile_expert_range(group_starts, m, bm), lhs, rhs)


# ---------------------------------------------------------------- dlhs
def _gmm_dlhs_kernel(gs_ref, tiles_ref, g_ref, rhs_ref, out_ref, acc_scr, *, bm):
    """dlhs tile [bm, bk] = sum_e mask_e(g) @ rhs[e]^T, contracting over N
    inside the kernel (no materialized weight transpose)."""
    i, e = pl.program_id(0), pl.program_id(2)
    ne = pl.num_programs(2)

    @pl.when(e == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = gs_ref[e]
    end = gs_ref[e + 1]
    tile_lo = i * bm

    @pl.when(jnp.logical_and(end > tile_lo, start < tile_lo + bm))
    def _work():
        rows = tile_lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)[:, 0]
        mask = (rows >= start) & (rows < end)
        x = jnp.where(mask[:, None], g_ref[...], 0)  # [bm, N]
        acc_scr[...] += jax.lax.dot_general(
            x, rhs_ref[0], (((1,), (1,)), ((), ())),  # contract N -> [bm, bk]
            preferred_element_type=jnp.float32,
        )

    @pl.when(e == ne - 1)
    def _emit():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def _gmm_dlhs(g, rhs, group_starts, bm: int, bk: int):
    m, n = g.shape
    e, k, _ = rhs.shape
    grid = (m // bm, k // bk, e)
    return pl.pallas_call(
        functools.partial(_gmm_dlhs_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, n), lambda i, j, e_, gs, tiles: (i, 0)),
                pl.BlockSpec(
                    (1, bk, n),
                    lambda i, j, e_, gs, tiles: (_effective_expert(i, e_, tiles), j, 0),
                ),
            ],
            out_specs=pl.BlockSpec((bm, bk), lambda i, j, e_, gs, tiles: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, k), g.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="gmm_dlhs",
    )(group_starts, _tile_expert_range(group_starts, m, bm), g, rhs)


# ------------------------------------------------------------- drhs kernel
def _gmm_t_kernel(gs_ref, lhs_ref, g_ref, out_ref, acc_scr, *, bm):
    e, im = pl.program_id(0), pl.program_id(3)
    nm = pl.num_programs(3)

    @pl.when(im == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = gs_ref[e]
    end = gs_ref[e + 1]
    tile_lo = im * bm

    @pl.when(jnp.logical_and(end > tile_lo, start < tile_lo + bm))
    def _work():
        rows = tile_lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)[:, 0]
        mask = (rows >= start) & (rows < end)
        x = jnp.where(mask[:, None], lhs_ref[...], 0)
        acc_scr[...] += jax.lax.dot_general(
            x, g_ref[...], (((0,), (0,)), ((), ())),  # x^T @ g -> [bk, bn]
            preferred_element_type=jnp.float32,
        )

    @pl.when(im == nm - 1)
    def _emit():
        out_ref[0] = acc_scr[...].astype(out_ref.dtype)


def _gmm_transpose(lhs, g, group_starts, e: int, bm: int, bk: int, bn: int):
    """drhs [E, K, N] from lhs [M, K], g [M, N]."""
    m, k = lhs.shape
    n = g.shape[1]
    grid = (e, k // bk, n // bn, m // bm)
    return pl.pallas_call(
        functools.partial(_gmm_t_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda e, ik, jn, im, gs: (im, ik)),
                pl.BlockSpec((bm, bn), lambda e, ik, jn, im, gs: (im, jn)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn), lambda e, ik, jn, im, gs: (e, ik, jn)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="gmm_drhs",
    )(group_starts, lhs, g)


# ---------------------------------------------------------------- public op
_BM, _BN, _BK = 128, 128, 128


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _gmm(lhs, rhs, group_starts):
    return _gmm_raw(lhs, rhs, group_starts, _BM, _BN)


def _gmm_fwd(lhs, rhs, group_starts):
    return _gmm(lhs, rhs, group_starts), (lhs, rhs, group_starts)


def _gmm_bwd(res, g):
    lhs, rhs, group_starts = res
    dlhs = _gmm_dlhs(g, rhs, group_starts, _BM, _BK)
    drhs = _gmm_transpose(
        lhs, g, group_starts, rhs.shape[0], _BM, _BK, _BN
    ).astype(rhs.dtype)
    return dlhs.astype(lhs.dtype), drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@KERNEL_REGISTRY.register(
    "group_gemm", "pallas_gmm", device_types=("tpu",), priority=10,
)
def pallas_group_gemm(tokens, weights, group_sizes):
    return _pallas_group_gemm(tokens, weights, group_sizes)


# "pallas" alias matches the documented moe_implementation values
KERNEL_REGISTRY.register(
    "group_gemm", "pallas", device_types=("tpu",), priority=10,
)(pallas_group_gemm)


def _pallas_group_gemm(tokens, weights, group_sizes):
    """tokens [M,K] sorted by expert; weights [E,K,N]; group_sizes [E].

    Goes to the XLA ragged path, with one log line saying so, for shapes
    that don't tile (M/K/N not multiples of 128) and under GSPMD on a
    multi-device mesh: GSPMD cannot partition a Mosaic kernel, and rows
    sorted by expert across the whole mesh have no per-device split (the EP
    dispatch calls this inside its shard_map, where it is per-device).
    """
    from veomni_tpu.parallel.parallel_state import gspmd_parallel_state

    m, k = tokens.shape
    e, _, n = weights.shape
    reason = (
        f"M/K/N must be multiples of {_BM}" if m % _BM or n % _BN or k % _BK
        else "under GSPMD on a multi-device mesh, outside shard_map"
        if gspmd_parallel_state() is not None
        else None
    )
    if reason is not None:
        from veomni_tpu.ops.group_gemm import _group_gemm_ragged

        logger.info_once(
            "op group_gemm: pallas_gmm hands M=%d K=%d N=%d E=%d to "
            "xla_ragged (%s)", m, k, n, e, reason,
        )
        return _group_gemm_ragged(tokens, weights, group_sizes)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))]
    )
    return _gmm(tokens, weights, starts)
