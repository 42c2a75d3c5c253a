"""Grouped matmul (Pallas TPU): variable-M expert GEMM for fused MoE.

Reference: ``veomni/ops/kernels/moe/_kernels/kernel/group_gemm.py:65-397``
(Triton group_gemm_same_nk / same_mn over the per-expert token cumsum).

Shapes: lhs ``[M, K]`` with rows sorted by expert, rhs ``[E, K, N]``,
group_sizes ``[E]`` -> out ``[M, N]``. Three kernels, one schedule:

* ``gmm_fwd``  out  ``[M, N]``    = rows of lhs times their expert's rhs
* ``gmm_dlhs`` dlhs ``[M, K]``    = rows of g times their expert's rhs^T (the
  forward's kernel body contracting over N: no weight transpose in HBM)
* ``gmm_drhs`` drhs ``[E, K, N]`` = per expert, its rows of lhs^T times g

**The schedule.** A row tile of ``bm`` rows and an expert *meet* if the
expert's row range intersects the tile. Of the ``M/bm x E`` pairs at most
``M/bm + E - 1`` meet (each expert boundary inside a tile adds one), and only
those are walked: :func:`visit_table` lists them in row order, and the
kernels' innermost, sequential grid axis runs over that list (``gmm_fwd`` and
``gmm_dlhs``: grid ``(output column tiles, visits)``; ``gmm_drhs``:
``(k tiles, n tiles, visits)``). A visit accumulates in a float32 VMEM scratch;
the scratch is zeroed at the first visit of an output block (a row tile, or
for ``gmm_drhs`` an expert) and cast and written at its last. Rows of a tile
that belong to another expert, or to none, are masked out of the visit's
product, so a boundary inside a tile is exact. The list has a static length
and a live count: a visit past the count names the blocks of the last live
one, so the pipeline copies nothing for it, and computes nothing. Row tiles
wholly past the last group are in no visit at all. The tile sizes come from
the call's shape (:func:`choose_tiles`).

**The table is built in XLA, outside the kernel**: index maps and kernel
bodies may only load *scalars* from the prefetched SMEM refs (Mosaic: "Can
only load scalars from SMEM"), so what a visit names cannot be searched for
inside the kernel; a handful of small XLA ops over ``[E]`` and ``[visits]``
integers work it out once a call, and it is scalar-prefetched.

**The contract.** Rows sorted by expert, ``sum(group_sizes) <= M``. Rows past
the last group come out **zero** from ``gmm_fwd`` and ``gmm_dlhs``, whatever
the inputs hold there (an output block that no visit reaches is never
written, so the output starts as an aliased buffer of zeros). An expert with
no rows gets a **zero** weight gradient: ``gmm_drhs``'s table gives every
empty expert one visit that only writes its zeroed scratch. Accumulation is
float32, outputs are cast to the inputs' dtype. The table is never
optimistic: a pair that meets is always visited.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.ops.pallas.flash_attention import _LANES, _VMEM_BUDGET
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_ROW_TILES = (1024, 512, 256, 128)
_MAX_COLS = 2048  # the widest output block a kernel is offered


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ==========================================================================
# The schedule: tile sizes, the visit table
# ==========================================================================
class Tiles(NamedTuple):
    """Per kernel: (row tile, output column tile) of ``gmm_fwd`` and
    ``gmm_dlhs``, (row tile, k tile, n tile) of ``gmm_drhs``."""

    fwd: Tuple[int, int]
    dlhs: Tuple[int, int]
    drhs: Tuple[int, int, int]


def _rows_vmem_bytes(bm: int, contract: int, bo: int, itemsize: int) -> int:
    """VMEM of ``gmm_fwd`` / ``gmm_dlhs`` at a row tile ``bm``, the whole
    contraction ``contract`` and an output column tile ``bo``: the three blocks
    twice (the pipeline double-buffers them), the float32 scratch, and the
    visit's product with its masked copy."""
    blocks = (bm * contract + contract * bo + bm * bo) * itemsize
    return 2 * blocks + 3 * bm * bo * 4


def _drhs_vmem_bytes(bm: int, bk: int, bn: int, itemsize: int) -> int:
    """VMEM of ``gmm_drhs``: its blocks twice, the scratch and the visit's
    product in float32, and the masked copies of both operands."""
    blocks = (bm * bk + bm * bn + bk * bn) * itemsize
    return 2 * blocks + 2 * bk * bn * 4 + bm * (bk + bn) * itemsize


def _col_tiles(dim: int):
    """Multiples of 128 that divide ``dim``, widest first (768 gives 768, 384,
    256, 128)."""
    return [c for c in range(min(dim, _MAX_COLS), 0, -_LANES) if dim % c == 0]


@functools.lru_cache(maxsize=None)
def choose_tiles(m: int, k: int, n: int, e: int, dtype) -> Tiles:
    """Tile sizes of the three kernels for ``[m, k] x [e, k, n]``.

    The row tile is no longer than half the mean group (``m / e / 2``, at
    least 128): every expert boundary inside a tile costs one more visit that
    multiplies the whole tile, so a buffer of unaligned groups costs ``1 + e *
    bm / m`` times its rows, while a short tile feeds the MXU worse (on a v5e
    at M 8192, E 16: 256 beat 128 and 512 on unaligned groups, PERF.md, PR
    30). Under that, per kernel the tiles of the largest output block
    that divide the shape and fit the VMEM budget; of equal blocks the one
    that reads the operands the fewest times (``gmm_fwd`` and ``gmm_dlhs``
    hold the whole contraction, so they read the weights once; ``gmm_drhs``
    reads lhs once per n tile and g once per k tile)."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = [t for t in _ROW_TILES if m % t == 0 and t <= max(_LANES, m // e // 2)]

    def rows_kernel(contract, out_cols):
        fits = [(bm, bo) for bm in rows for bo in _col_tiles(out_cols)
                if _rows_vmem_bytes(bm, contract, bo, itemsize) <= _VMEM_BUDGET]
        return max(fits or [(_LANES, _LANES)], key=lambda t: (t[0] * t[1], t[1]))

    def reads(t):  # operand elements gmm_drhs copies in, up to a factor m
        return k * (n // t[2]) + n * (k // t[1])

    fits = [(bm, bk, bn) for bm in rows for bk in _col_tiles(k) for bn in _col_tiles(n)
            if _drhs_vmem_bytes(bm, bk, bn, itemsize) <= _VMEM_BUDGET]
    drhs = max(fits or [(_LANES,) * 3], key=lambda t: (t[1] * t[2], t[0], -reads(t)))
    return Tiles(fwd=rows_kernel(k, n), dlhs=rows_kernel(n, k), drhs=drhs)


def visit_table(group_starts, m: int, bm: int, empty_experts: bool = False):
    """The (row tile, expert) pairs a kernel walks, in row order: ``(tile
    [V], expert [V], count [1])`` int32 with ``V = m/bm + E - 1``, which
    bounds the pairs that meet. Visit ``v < count`` is the ``v``-th pair whose
    row ranges intersect; with ``empty_experts`` an expert with no rows has one
    visit too, in expert order (``gmm_drhs`` zeroes its block there), beside a
    tile its neighbours name. Visits from ``count`` on repeat the last live
    one (tile 0 of the last expert where there is none), so that their index
    maps name no new block."""
    e = group_starts.shape[0] - 1
    tiles = m // bm
    start, end = group_starts[:-1], group_starts[1:]
    first = start // bm
    n = jnp.where(end > start, (end - 1) // bm - first + 1, int(empty_experts))
    upto = jnp.cumsum(n)  # visits of the experts up to and including each
    count = upto[-1]
    v = jnp.minimum(jnp.arange(tiles + e - 1, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(upto, v, side="right"), e - 1)
    tile = jnp.clip(first[expert] + v - (upto[expert] - n[expert]), 0, tiles - 1)
    return tile.astype(jnp.int32), expert.astype(jnp.int32), count[None].astype(jnp.int32)


def tile_census(group_sizes, m: int, k: int, n: int, dtype):
    """(live visits, row tiles x experts) of one ``gmm_fwd`` call at its
    chosen row tile: what the schedule walks, and what a grid over every pair
    would (``moe.gmm.tile_visits`` / ``moe.gmm.tile_pairs``). (0, 0) where the
    kernel would not take the shape."""
    e = group_sizes.shape[0]
    if m % _LANES or k % _LANES or n % _LANES:
        return jnp.float32(0.0), jnp.float32(0.0)
    bm = choose_tiles(m, k, n, e, jnp.dtype(dtype)).fwd[0]
    count = visit_table(_group_starts(group_sizes), m, bm)[2][0]
    return count.astype(jnp.float32), jnp.float32(m // bm * e)


def _group_starts(group_sizes):
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))])


def _visit(gs_ref, tile_ref, expert_ref, count_ref, v, nv, bm: int, by_expert: bool):
    """What a kernel body needs of visit ``v`` (scalar loads only): is it the
    first / the last visit of its output block, does it compute, and the
    [bm, 1] mask of the tile's rows that are the expert's."""
    block_ref = expert_ref if by_expert else tile_ref
    block = block_ref[v]
    first = jnp.logical_or(v == 0, block_ref[jnp.maximum(v - 1, 0)] != block)
    last = jnp.logical_or(v == nv - 1, block_ref[jnp.minimum(v + 1, nv - 1)] != block)
    start, end = gs_ref[expert_ref[v]], gs_ref[expert_ref[v] + 1]
    lo = tile_ref[v] * bm
    # an empty expert's visit (gmm_drhs) is live and meets nothing
    live = jnp.logical_and(v < count_ref[0], jnp.logical_and(end > lo, start < lo + bm))
    rows = lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return first, last, live, (rows >= start) & (rows < end)


def _compiler_params(need: int, axes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (axes - 1) + ("arbitrary",),
        # twice the counted footprint, as flash_attention.py's: the compiler's
        # own temporaries are not all counted
        vmem_limit_bytes=int(min(max(2 * need, 16 * 2 ** 20), 100 * 2 ** 20)),
    )


# ==========================================================================
# gmm_fwd and gmm_dlhs: rows times their expert's weights
# ==========================================================================
def _rows_kernel(gs_ref, tile_ref, expert_ref, count_ref, x_ref, rhs_ref, zeros_ref,
                 out_ref, acc_scr, *, bm, dims):
    del zeros_ref  # the output's own buffer: what no visit writes stays zero
    v, nv = pl.program_id(1), pl.num_programs(1)
    first, last, live, keep = _visit(
        gs_ref, tile_ref, expert_ref, count_ref, v, nv, bm, by_expert=False)

    @pl.when(first)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _accumulate():
        # a row of the product comes from that row of x alone: masking the
        # product keeps other experts' rows (and whatever lies past the last
        # group) out of the sum. (A second, unmasked body for tiles wholly
        # inside an expert was no faster on a v5e: PERF.md, PR 30.)
        product = jax.lax.dot_general(
            x_ref[...], rhs_ref[0], dims, preferred_element_type=jnp.float32)
        acc_scr[...] += jnp.where(keep, product, 0.0)

    @pl.when(last)
    def _emit():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def _gmm_rows(x, rhs, group_starts, bm: int, bo: int, name: str):
    """The kernel ``name``: ``gmm_fwd`` (x = lhs [M, K], out [M, N]) or
    ``gmm_dlhs`` (x = g [M, N], out [M, K], contracting over N)."""
    m, contract = x.shape
    e = rhs.shape[0]
    if name == "gmm_dlhs":
        out_cols = rhs.shape[1]
        rhs_spec = pl.BlockSpec((1, bo, contract), lambda j, v, gs, t, ex, c: (ex[v], j, 0))
        dims = (((1,), (1,)), ((), ()))
    else:
        out_cols = rhs.shape[2]
        rhs_spec = pl.BlockSpec((1, contract, bo), lambda j, v, gs, t, ex, c: (ex[v], 0, j))
        dims = (((1,), (0,)), ((), ()))
    table = visit_table(group_starts, m, bm)
    need = _rows_vmem_bytes(bm, contract, bo, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rows_kernel, bm=bm, dims=dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(out_cols // bo, m // bm + e - 1),
            in_specs=[
                pl.BlockSpec((bm, contract), lambda j, v, gs, t, ex, c: (t[v], 0)),
                rhs_spec,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((bm, bo), lambda j, v, gs, t, ex, c: (t[v], j)),
            scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, out_cols), x.dtype),
        input_output_aliases={6: 0},  # the zeros, after the four prefetched
        compiler_params=_compiler_params(need, 2),
        interpret=_interpret(),
        name=name,  # observability/scopes.py::KERNEL_NAMES
    )(group_starts, *table, x, rhs, jnp.zeros((m, out_cols), x.dtype))


# ==========================================================================
# gmm_drhs: per expert, its rows of lhs^T times g
# ==========================================================================
def _drhs_kernel(gs_ref, tile_ref, expert_ref, count_ref, lhs_ref, g_ref, out_ref, acc_scr,
                 *, bm):
    v, nv = pl.program_id(2), pl.num_programs(2)
    first, last, live, keep = _visit(
        gs_ref, tile_ref, expert_ref, count_ref, v, nv, bm, by_expert=True)

    @pl.when(first)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _accumulate():
        # the rows are summed over: both operands lose the rows of other
        # experts, so that nothing a masked row holds reaches the sum
        acc_scr[...] += jax.lax.dot_general(  # x^T @ g -> [bk, bn]
            jnp.where(keep, lhs_ref[...], 0), jnp.where(keep, g_ref[...], 0),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(last)
    def _emit():
        out_ref[0] = acc_scr[...].astype(out_ref.dtype)


def _gmm_drhs(lhs, g, group_starts, bm: int, bk: int, bn: int):
    """drhs [E, K, N] from lhs [M, K], g [M, N]."""
    m, k = lhs.shape
    n = g.shape[1]
    e = group_starts.shape[0] - 1
    table = visit_table(group_starts, m, bm, empty_experts=True)
    need = _drhs_vmem_bytes(bm, bk, bn, lhs.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // bk, n // bn, m // bm + e - 1),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda ik, jn, v, gs, t, ex, c: (t[v], ik)),
                pl.BlockSpec((bm, bn), lambda ik, jn, v, gs, t, ex, c: (t[v], jn)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn), lambda ik, jn, v, gs, t, ex, c: (ex[v], ik, jn)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), lhs.dtype),
        compiler_params=_compiler_params(need, 3),
        interpret=_interpret(),
        name="gmm_drhs",
    )(group_starts, *table, lhs, g)


# ---------------------------------------------------------------- public op
def _tiles_of(lhs, rhs) -> Tiles:
    e, k, n = rhs.shape
    return choose_tiles(lhs.shape[0], k, n, e, lhs.dtype)


@jax.custom_vjp
def _gmm(lhs, rhs, group_starts):
    return _gmm_rows(lhs, rhs, group_starts, *_tiles_of(lhs, rhs).fwd, name="gmm_fwd")


def _gmm_fwd(lhs, rhs, group_starts):
    return _gmm(lhs, rhs, group_starts), (lhs, rhs, group_starts)


def _gmm_bwd(res, g):
    lhs, rhs, group_starts = res
    tiles = _tiles_of(lhs, rhs)
    dlhs = _gmm_rows(g, rhs, group_starts, *tiles.dlhs, name="gmm_dlhs")
    drhs = _gmm_drhs(lhs, g, group_starts, *tiles.drhs).astype(rhs.dtype)
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
        f"M/K/N must be multiples of {_LANES}" if m % _LANES or n % _LANES or k % _LANES
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
    return _gmm(tokens, weights, _group_starts(group_sizes))
