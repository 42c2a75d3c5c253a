"""Flash attention (Pallas TPU): online-softmax fwd + custom-VJP bwd.

Reference capability: ``veomni/ops/kernels/attention/flash.py`` (adapter over
external flash-attn CUDA wheels, varlen via cu_seqlens). TPU-native design:

* packing is expressed with **segment ids** (cu_seqlens equivalent): a token
  attends to the tokens of equal id, and to nothing else. Padding is one more
  id (the collator's 0): padding positions attend to each other, and their
  rows are dropped by the loss, not by the kernel.
* layout [B, H, S, D]; kernels ``flash_fwd``, ``flash_bwd_dkv`` and
  ``flash_bwd_dq`` (the flash-v2 recomputation from the saved LSE), each over
  a grid (batch, q_head, outer tile, inner tile) whose inner axis is
  sequential and carries its accumulators in VMEM scratch.
* **the backward has two forms, and the call's shape alone chooses**
  (:func:`_fuses_bwd`; no argument, variable or config key does).
  *Fused*: ONE call, named ``flash_bwd_dkv`` because it is that kernel's grid
  (batch, q head, kv tile, q tile), gives dK, dV and dQ: the scores, the
  exponentials, the masks, dP and dS are made once a tile pair (five matmuls
  and one elementwise chain, where the pair of kernels does seven and two).
  dK and dV gather over the inner q tiles as they always did; dQ of the whole
  row of the (batch, head) the grid is in gathers in an f32 VMEM scratch
  ``[S, d]`` over the outer kv tiles, which therefore run in order
  (``"arbitrary"``), and leaves through a ``[bq, d]`` output block of
  ``q.dtype`` while the last kv tile walks the row. That row costs
  ``S x lanes(d) x 4`` bytes plus the block twice (:func:`_dq_row_bytes`: 2.3
  MiB at 4096 x 128, 4.3 at 8192 x 64, 8.5 at 8192 x 192), counted against
  ``_DQ_ROW_CEILING`` beside, not inside, the budget the tiles are chosen
  under, so the fused kernel's tiles are ``Tiles.dkv``. *Split*: a row too
  long for the ceiling (64k of 128-wide heads: 32 MiB) keeps the pair,
  ``flash_bwd_dkv`` for dK, dV and ``flash_bwd_dq`` on its own grid (batch,
  q head, q tile, kv tile) with ``Tiles.dq``. Each traced backward counts
  once under ``attn.flash.bwd.calls_fused`` or ``.calls_split``.
* **tile schedule.** The tile sizes come from the call's shape
  (:func:`choose_tiles`: the largest that divide S and fit a VMEM budget, one
  pair per kernel). Which (q-tile, kv-tile) pairs hold any admitted
  (query, key) pair is worked out once a call in XLA from the segment ids and
  the causal mask (:func:`tile_liveness`) and handed to the kernels as a
  scalar-prefetched table: a dead step runs no body, and its index maps name
  the block the last live step named, so the pipeline copies nothing for it.
* GQA: the kv BlockSpec index-maps q-head -> q_head // group, so no
  materialized head repeat; dK/dV come out per q head and XLA sums the group.
* q and k may be wider than v (MLA's training form: 128 nope + 64 rope
  against 128): q, k, dQ, dK blocks are ``d`` wide, v, o, dO, dV blocks ``dv``
  wide. Where the two are equal every block, scratch and limit is what it was
  before the widths were told apart.

Numerics: scores/softmax in f32 (MXU preferred_element_type), output cast
back to the input dtype.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_NEG_INF = -1e30
_LANES = 128  # TPU lane width: the smallest tile, and what a narrow block pads to
_ROWS = 8     # lane width of the column-form row stats (lse, delta): a block
              # lane dim equal to the array dim satisfies the Mosaic tiling rule
_TILE_SIZES = (1024, 512, 256, 128)
_VMEM_BUDGET = 24 * 2 ** 20   # what a kernel's blocks, scratch and score-sized
                              # temporaries may come to (v5e: 128 MiB of VMEM)
_DQ_ROW_CEILING = 24 * 2 ** 20  # what the fused backward's resident dQ row may
                                # come to, beside (not inside) the tiles' budget
_TABLE_WORDS = 64 * 1024      # the liveness table lives in SMEM: past this
                              # many entries a call goes without one
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ==========================================================================
# The schedule: tile sizes, liveness, the prefetched table
# ==========================================================================
class Tiles(NamedTuple):
    """(q tile, kv tile) of each kernel."""

    fwd: Tuple[int, int]
    dkv: Tuple[int, int]
    dq: Tuple[int, int]


def _lane_padded(width: int) -> int:
    """A block's last dim as VMEM holds it: whole 128-lane tiles."""
    return -(-width // _LANES) * _LANES


def _vmem_bytes(kernel: str, bq: int, bk: int, d: int, itemsize: int,
                dv: Optional[int] = None) -> int:
    """VMEM a kernel needs at these tiles: its blocks twice (the pipeline
    double-buffers them), its scratch, and its score-sized f32 temporaries.
    ``d`` is the width of q and k, ``dv`` that of v and o. Where they differ
    (``dv`` given) a block's last dim counts as whole 128-lane tiles: 192 is
    256 in VMEM. (Equal widths count as they always did, so that no existing
    call's tiles or limit move.)"""
    col = lambda n: n * _LANES * 4           # an [n, 1] or [n, _ROWS] block pads to 128 lanes
    row = lambda n: 8 * n * 4                # a [1, n] block pads to 8 sublanes
    if dv is None:
        dv = d
    else:
        d, dv = _lane_padded(d), _lane_padded(dv)
    # q (or dQ), k (or dK) blocks; v (or dV), o (or dO) blocks
    q_blk, k_blk, o_blk, v_blk = bq * d * itemsize, bk * d * itemsize, bq * dv * itemsize, \
        bk * dv * itemsize
    if kernel == "fwd":
        blocks = q_blk + o_blk + k_blk + v_blk + 2 * col(bq) + row(bk)
        scratch = 2 * col(bq) + bq * dv * 4
        scores = 4 * bq * bk * 4
    elif kernel == "dq":
        blocks = 2 * q_blk + o_blk + k_blk + v_blk + 3 * col(bq) + row(bk)
        scratch = bq * d * 4
        scores = 6 * bq * bk * 4
    else:  # dkv
        blocks = q_blk + o_blk + k_blk + v_blk + bk * (d + dv) * 4 + 3 * row(bq) + col(bk)
        scratch = bk * (d + dv) * 4
        scores = 6 * bq * bk * 4
    return 2 * blocks + scratch + scores


def _dq_row_bytes(s: int, bq: int, d: int, itemsize: int) -> int:
    """VMEM the fused backward holds beside its tiles: dQ of the whole row of
    one (batch, head) in f32, ``s`` x the lanes ``d`` fills x 4, and the
    ``[bq, d]`` block it is handed out through, twice (4096 x 128: 2 MiB + 256
    KiB; 8192 x 192, which fills 256 lanes: 8 MiB + 512 KiB)."""
    return _lane_padded(d) * (s * 4 + 2 * bq * itemsize)


def _fuses_bwd(s: int, d: int, dtype, tiles: Tiles) -> bool:
    """Which backward a call takes, by its shape alone: the fused one where
    the resident dQ row fits its ceiling (a 32k row of 128-wide heads does, at
    16 MiB; a 64k row does not), the split pair elsewhere."""
    return _dq_row_bytes(s, tiles.dkv[0], d, jnp.dtype(dtype).itemsize) <= _DQ_ROW_CEILING


@functools.lru_cache(maxsize=None)
def choose_tiles(s: int, d: int, dtype, causal: bool, dv: Optional[int] = None) -> Tiles:
    """Tile sizes for a call of sequence length ``s`` and head dims ``d`` (q,
    k) and ``dv`` (v; ``d`` where not given):
    per kernel the pair of largest area among {1024, 512, 256, 128}^2 that
    divides ``s`` and fits the VMEM budget (the backward's kernels hold more
    score-sized temporaries than the forward, so theirs come out smaller); of
    equal areas the one with the longer kv tile. 128 where nothing larger
    divides ``s``. ``causal`` does not move the choice: the same tiles serve
    both, and a causal call's table skips what lies above the diagonal.
    (On a v5e the largest tile that fits won at every shape read, though it
    covers a packing more loosely: PERF.md, PR 28.)"""
    itemsize = jnp.dtype(dtype).itemsize
    sizes = [t for t in _TILE_SIZES if s % t == 0] or [s]

    def best(kernel):
        fits = [(bq, bk) for bq in sizes for bk in sizes
                if _vmem_bytes(kernel, bq, bk, d, itemsize, dv) <= _VMEM_BUDGET]
        return max(fits or [(sizes[-1], sizes[-1])], key=lambda t: (t[0] * t[1], t[1]))

    return Tiles(fwd=best("fwd"), dkv=best("dkv"), dq=best("dq"))


def tile_liveness(segment_ids, s: int, bq: int, bk: int, causal: bool):
    """``[B, s/bq, s/bk]`` bool (``[1, ...]`` without segment ids): may the
    (q-tile, kv-tile) pair hold a (query, key) pair that the segment mask and
    the causal mask admit? Never says no to a tile that holds one.

    Two range tests: a pair is live if the tiles' [min, max] of the ids
    overlap, and those of the ids less one (unsigned, so that 0 goes last)
    overlap too. Each is a necessary condition for an equal id on both sides,
    and is exact where the ids do not decrease along the row under its
    ordering: the first for sorted ids, the second for what the packing
    collator emits (documents 1, 2, ... then padding 0). Takes numpy or jax
    arrays, and gives back the same kind.
    """
    nq, nk = s // bq, s // bk
    live = np.ones((1, nq, nk), bool)
    if causal:
        iq, jk = np.arange(nq)[:, None], np.arange(nk)[None, :]
        live = (jk * bk <= iq * bq + bq - 1)[None]
    if segment_ids is None:
        return live
    xp = np if isinstance(segment_ids, np.ndarray) else jnp
    b = segment_ids.shape[0]
    ids = segment_ids.astype(xp.uint32)
    for key in (ids, ids - xp.uint32(1)):
        tq, tk = key.reshape(b, nq, bq), key.reshape(b, nk, bk)
        lo_q, hi_q = tq.min(-1)[:, :, None], tq.max(-1)[:, :, None]
        lo_k, hi_k = tk.min(-1)[:, None, :], tk.max(-1)[:, None, :]
        live = live & (lo_q <= hi_k) & (lo_k <= hi_q)
    return live


def tile_census(segment_ids, head_dim: int, dtype, causal: bool = True,
                v_head_dim: Optional[int] = None) -> Tuple[int, int]:
    """(tile pairs, live tile pairs) of the forward kernel over a host batch's
    rows ``[..., S]``, by the functions the kernel wrapper itself calls: what
    the trainer loop counts into ``attn.flash.tile_pairs[_live]``. (0, 0)
    where the kernel would not take the shape."""
    seg = np.asarray(segment_ids)
    s = seg.shape[-1]
    if s % _LANES:
        return 0, 0
    bq, bk = choose_tiles(s, head_dim, dtype, causal, v_head_dim).fwd
    live = tile_liveness(seg.reshape(-1, s), s, bq, bk, causal)
    return int(live.size), int(live.sum())


def _fetch_table(live):
    """The prefetched table of one kernel, ``[B * n_outer * n_inner]`` int32
    from ``live [B, n_outer, n_inner]``: the inner-axis block each step names.
    A live step names its own (so ``table[step] == inner`` says live); a dead
    one the last live step's before it in its row, or, before the row's first
    live step, that first one's. None where the table would not fit SMEM."""
    if live.size > _TABLE_WORDS:
        return None
    inner = jnp.arange(live.shape[-1], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, inner, -1), axis=2)
    first = jnp.argmax(live, axis=-1).astype(jnp.int32)[..., None]
    return jnp.where(last >= 0, last, first).reshape(-1)


def _table_index(bi, outer, inner, n_outer: int, n_inner: int, per_batch: bool):
    return ((bi if per_batch else 0) * n_outer + outer) * n_inner + inner


def _inner_block(tbl, bi, outer, inner, **where):
    """What the index maps name on the inner axis: the table's entry, or the
    step's own block where the call has no table (``tbl`` is the tuple of
    scalar-prefetch refs an index map is handed)."""
    return tbl[0][_table_index(bi, outer, inner, **where)] if tbl else inner


def _step_is_live(tbl_ref, bi, iq, jk, *, q_outer: bool, causal: bool, bq: int, bk: int, where):
    """Kernel half of the schedule: does this grid step compute? By the table
    (a live step's entry is its own inner tile), or without one by the causal
    mask alone."""
    if tbl_ref is not None:
        outer, inner = (iq, jk) if q_outer else (jk, iq)
        return tbl_ref[_table_index(bi, outer, inner, **where)] == inner
    return (jk * bk <= iq * bq + bq - 1) if causal else True


def _split_refs(refs, table: bool, segmented: bool):
    refs = list(refs)
    tbl_ref = refs.pop(0) if table else None
    seg_col_ref, seg_row_ref = (refs.pop(0), refs.pop(0)) if segmented else (None, None)
    return tbl_ref, seg_col_ref, seg_row_ref, refs


def _schedule(segment_ids, s: int, bq: int, bk: int, causal: bool, q_outer: bool):
    """(table or None, where) of a kernel whose outer grid axis walks the q
    tiles (forward, dQ) or the kv tiles (dKV); ``where`` is what
    :func:`_table_index` needs to find a step's entry."""
    n_outer, n_inner = (s // bq, s // bk) if q_outer else (s // bk, s // bq)
    where = dict(n_outer=n_outer, n_inner=n_inner, per_batch=segment_ids is not None)
    if segment_ids is None and not causal:
        return None, where  # every pair is live: no table, and no cost
    live = tile_liveness(segment_ids, s, bq, bk, causal)
    return _fetch_table(live if q_outer else jnp.swapaxes(live, 1, 2)), where


def _block_specs(bq: int, bk: int, d: int, group: int, segmented: bool, q_outer: bool, where,
                 dv: Optional[int] = None):
    """BlockSpecs over a grid (batch, q head, outer tile, inner tile): q-side
    blocks [bq, d] (``q``) and [bq, dv] (``o``), kv-side blocks [bk, d]
    (``kv``) and [bk, dv] (``v``), the q-side row stats as columns
    [bq, _ROWS] and as rows [1, bq], and the two segment-id blocks (the ids
    along the score block's rows as a column, those along its columns as a
    row). The inner tile is read through the table."""
    dv = dv or d

    def tiles(bi, outer, inner, tbl):  # (q tile, kv tile) a grid step names
        named = _inner_block(tbl, bi, outer, inner, **where)
        return (outer, named) if q_outer else (named, outer)

    def spec(block, index):
        return pl.BlockSpec(block, lambda bi, hi, o, i, *t: index(bi, hi, *tiles(bi, o, i, t)))

    seg_specs = []
    if segmented and q_outer:
        seg_specs = [spec((None, bq, 1), lambda bi, hi, tq, tk: (bi, tq, 0)),
                     spec((None, 1, bk), lambda bi, hi, tq, tk: (bi, 0, tk))]
    elif segmented:
        seg_specs = [spec((None, bk, 1), lambda bi, hi, tq, tk: (bi, tk, 0)),
                     spec((None, 1, bq), lambda bi, hi, tq, tk: (bi, 0, tq))]
    return dict(
        q=spec((1, 1, bq, d), lambda bi, hi, tq, tk: (bi, hi, tq, 0)),
        o=spec((1, 1, bq, dv), lambda bi, hi, tq, tk: (bi, hi, tq, 0)),
        kv=spec((1, 1, bk, d), lambda bi, hi, tq, tk: (bi, hi // group, tk, 0)),
        v=spec((1, 1, bk, dv), lambda bi, hi, tq, tk: (bi, hi // group, tk, 0)),
        q_cols=spec((1, 1, bq, _ROWS), lambda bi, hi, tq, tk: (bi, hi, tq, 0)),
        q_rows=spec((1, 1, 1, bq), lambda bi, hi, tq, tk: (bi, hi, 0, tq)),
        segs=seg_specs,
    )


def _admitted(seg_col, seg_row, q0, k0, shape, causal: bool, q_on_rows: bool):
    """The mask of a score block ``shape`` whose queries start at ``q0`` and
    keys at ``k0`` (None: everything is admitted). ``seg_col`` [n, 1] and
    ``seg_row`` [1, m] are the ids along the block's rows and columns."""
    mask = None
    if seg_col is not None:
        mask = seg_col == seg_row
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # query position >= key position
        tri = (rows - cols >= k0 - q0) if q_on_rows else (cols - rows >= k0 - q0)
        mask = tri if mask is None else mask & tri
    return mask


def _other_width(d: int, dv: int) -> Optional[int]:
    """v's width as the VMEM count takes it: None where it is q's and k's."""
    return None if dv == d else dv


def _compiler_params(kernel: str, bq: int, bk: int, d: int, dtype, dv: Optional[int] = None,
                     dq_row: int = 0):
    """``dq_row``: the bytes of the fused backward's resident dQ row (0: the
    kernel holds none). With one, the kv-tile axis carries it from tile to
    tile and is sequential too (a v5e has one core: nothing is lost)."""
    need = _vmem_bytes(kernel, bq, bk, d, jnp.dtype(dtype).itemsize, dv)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary" if dq_row else "parallel",
                             "arbitrary"),
        # twice the counted footprint: the compiler's own temporaries are not
        # all counted, and the limit only has to be one it can stay under
        vmem_limit_bytes=int(min(max(2 * need + dq_row, 16 * 2 ** 20), 100 * 2 ** 20)),
    )


# ==========================================================================
# Forward
# ==========================================================================
def _fwd_kernel(*refs, scale, causal, bq, bk, table, segmented, where):
    tbl_ref, seg_q_ref, seg_k_ref, refs = _split_refs(refs, table, segmented)
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    bi, iq, jk = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_step_is_live(tbl_ref, bi, iq, jk, q_outer=True, causal=causal, bq=bq, bk=bk,
                           where=where))
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
        mask = _admitted(
            seg_q_ref[...] if segmented else None, seg_k_ref[...] if segmented else None,
            iq * bq, jk * bk, s.shape, causal, True)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[...]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, _NEG_INF, m_scr[...] + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse, (bq, _ROWS))


def _seg_forms(segment_ids):
    """Segment ids as a column ``[B, S, 1]`` and a row ``[B, 1, S]`` (nothing
    without ids): a kernel compares the ids along its score block's rows with
    those along its columns, and takes each in the layout it is used in (a
    unit dim equal to the array's satisfies Mosaic's (8, 128) tiling rule)."""
    if segment_ids is None:
        return ()
    return segment_ids[:, :, None], segment_ids[:, None, :]


def _prefetch(tbl):
    return () if tbl is None else (tbl,)


def _fwd(q, k, v, segment_ids, scale, causal, tiles):
    b, hq, s, d = q.shape
    dv = v.shape[-1]
    bq, bk = tiles.fwd
    segmented = segment_ids is not None
    tbl, where = _schedule(segment_ids, s, bq, bk, causal, True)
    specs = _block_specs(bq, bk, d, hq // k.shape[1], segmented, True, where, dv)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          table=tbl is not None, segmented=segmented, where=where),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(tbl is not None),
            grid=(b, hq, s // bq, s // bk),
            in_specs=[*specs["segs"], specs["q"], specs["kv"], specs["v"]],
            out_specs=[specs["o"], specs["q_cols"]],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, _ROWS), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", bq, bk, d, q.dtype, _other_width(d, dv)),
        interpret=_interpret(),
        name="flash_fwd",  # observability/scopes.py::KERNEL_NAMES
    )(*_prefetch(tbl), *_seg_forms(segment_ids), q, k, v)
    return out, lse


# ==========================================================================
# Backward
# ==========================================================================
def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, table, segmented, where, fused):
    """dK, dV of one kv tile, summed over the q tiles (the inner axis). The
    scores are computed transposed, [bk, bq]: p^T and ds^T are then the left
    operands of plain matmuls, and lse and delta ride as rows.

    ``fused``: dQ too, on the same walk. The whole row's dQ of the (batch,
    head) the grid is in stays in an f32 scratch ``[S, d]``: a q tile's rows
    are zeroed when the first kv tile visits them, take ``ds k`` (``ds^T``
    turned round once) on every live step, and go out through ``dq_ref`` when
    the last kv tile visits them, live step or not."""
    tbl_ref, seg_k_ref, seg_q_ref, refs = _split_refs(refs, table, segmented)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *refs = refs
    dq_ref, dk_scr, dv_scr, dq_scr = refs if fused else (None, *refs, None)
    bi, jk, iq = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if fused:
        rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)  # this q tile's, in the dQ row

        @pl.when(jk == 0)
        def _init_dq():
            dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]), dq_scr.dtype)

    @pl.when(_step_is_live(tbl_ref, bi, iq, jk, q_outer=False, causal=causal, bq=bq, bk=bk,
                           where=where))
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]       # [1, bq]
        delta = delta_ref[0, 0, :, :]
        st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        lse_safe = jnp.where(lse <= _NEG_INF / 2, 0.0, lse)
        pt = jnp.exp(st - lse_safe)     # [bk, bq]
        mask = _admitted(
            seg_k_ref[...] if segmented else None, seg_q_ref[...] if segmented else None,
            iq * bq, jk * bk, st.shape, causal, False)
        if mask is not None:
            pt = jnp.where(mask, pt, 0.0)
        # p and ds go to the MXU as they are: at default precision Mosaic
        # rounds an f32 operand to bf16 itself, bit for bit what a cast to
        # the inputs' dtype gives, and here the cast only cost time (in the
        # forward it saves some: PERF.md, PR 28)
        dv_scr[...] += jax.lax.dot_general(
            pt, do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            dst, q, _NN, preferred_element_type=jnp.float32)
        if fused:
            # ds k with ds^T as the left operand, contracted on its rows; an
            # explicit transpose, of the block as it is or rounded to the
            # inputs' dtype first, read the same to 0.6% (PERF.md, PR 39)
            dq_scr[rows, :] += jax.lax.dot_general(
                dst, k, _TN, preferred_element_type=jnp.float32)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)

    if fused:
        @pl.when(jk == pl.num_programs(2) - 1)
        def _finish_dq():
            dq_ref[0, 0, :, :] = dq_scr[rows, :].astype(dq_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, bq, bk, table, segmented, where):
    tbl_ref, seg_q_ref, seg_k_ref, refs = _split_refs(refs, table, segmented)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    bi, iq, jk = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_step_is_live(tbl_ref, bi, iq, jk, q_outer=True, causal=causal, bq=bq, bk=bk,
                           where=where))
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, 0:1]     # [bq, 1]
        delta = delta_ref[0, 0, :, 0:1]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
        lse_safe = jnp.where(lse <= _NEG_INF / 2, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        mask = _admitted(
            seg_q_ref[...] if segmented else None, seg_k_ref[...] if segmented else None,
            iq * bq, jk * bk, s.shape, causal, True)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k, _NN, preferred_element_type=jnp.float32)

    @pl.when(jk == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _bwd(scale, causal, tiles, residuals, g):
    q, k, v, segment_ids, out, lse = residuals
    do = g[0] if isinstance(g, (tuple, list)) else g
    b, hq, s, d = q.shape
    dv_ = v.shape[-1]
    hkv = k.shape[1]
    group = hq // hkv
    segmented = segment_ids is not None
    segs = _seg_forms(segment_ids)
    fused = _fuses_bwd(s, d, q.dtype, tiles)
    get_registry().counter(
        "attn.flash.bwd.calls_fused" if fused else "attn.flash.bwd.calls_split").inc()

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,H,S]
    # the split dQ takes the row stats as columns [B,H,S,_ROWS], dKV as rows [B,H,1,S]
    lse_rows, delta_rows = lse[..., 0][:, :, None, :], delta[:, :, None, :]

    # ---- dK, dV (and, fused, dQ): grid (b, h, kv tile, q tile)
    bq, bk = tiles.dkv
    tbl, where = _schedule(segment_ids, s, bq, bk, causal, False)
    specs = _block_specs(bq, bk, d, group, segmented, False, where, dv_)
    out_specs = [pl.BlockSpec((1, 1, bk, d), lambda bi, hi, jk, iq, *t: (bi, hi, jk, 0)),
                 pl.BlockSpec((1, 1, bk, dv_), lambda bi, hi, jk, iq, *t: (bi, hi, jk, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, hq, s, d), jnp.float32),
                 jax.ShapeDtypeStruct((b, hq, s, dv_), jnp.float32)]
    scratch_shapes = [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, dv_), jnp.float32)]
    dq_row = 0
    if fused:
        dq_row = _dq_row_bytes(s, bq, d, q.dtype.itemsize)
        last_kv = s // bk - 1
        # the step's own q tile (not the table's: a dead step hands over too)
        # while the last kv tile walks the row; block 0, unwritten, till then
        out_specs.append(pl.BlockSpec(
            (1, 1, bq, d), lambda bi, hi, jk, iq, *t: (bi, hi, jnp.where(jk == last_kv, iq, 0), 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, hq, s, d), q.dtype))
        scratch_shapes.append(pltpu.VMEM((s, d), jnp.float32))
    dk_per_head, dv_per_head, *dq = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          table=tbl is not None, segmented=segmented, where=where, fused=fused),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(tbl is not None),
            grid=(b, hq, s // bk, s // bq),
            in_specs=[*specs["segs"], specs["q"], specs["kv"], specs["v"], specs["o"],
                      specs["q_rows"], specs["q_rows"]],
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params("dkv", bq, bk, d, q.dtype, _other_width(d, dv_), dq_row),
        interpret=_interpret(),
        # the fused call too: it is this kernel's grid, one event a backward
        # call, and the benchmark reads the backward by this name
        name="flash_bwd_dkv",
    )(*_prefetch(tbl), *segs, q, k, v, do, lse_rows, delta_rows)

    # GQA: fold the q-head group into the kv head grad
    dk = dk_per_head.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
    dv = dv_per_head.reshape(b, hkv, group, s, dv_).sum(axis=2).astype(v.dtype)
    if fused:
        return dq[0], dk, dv, None

    # ---- dQ, split: grid (b, h, q tile, kv tile)
    bq, bk = tiles.dq
    delta_cols = jnp.broadcast_to(delta[..., None], delta.shape + (_ROWS,))
    tbl, where = _schedule(segment_ids, s, bq, bk, causal, True)
    specs = _block_specs(bq, bk, d, group, segmented, True, where, dv_)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          table=tbl is not None, segmented=segmented, where=where),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(tbl is not None),
            grid=(b, hq, s // bq, s // bk),
            in_specs=[*specs["segs"], specs["q"], specs["kv"], specs["v"], specs["o"],
                      specs["q_cols"], specs["q_cols"]],
            out_specs=specs["q"],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        compiler_params=_compiler_params("dq", bq, bk, d, q.dtype, _other_width(d, dv_)),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(*_prefetch(tbl), *segs, q, k, v, do, lse, delta_cols)

    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_bhsd(q, k, v, segment_ids, scale, causal, tiles):
    """The kernels on [B, H, S, D]; ``segment_ids`` [B, S] int32 or None,
    ``tiles`` a static :class:`Tiles`."""
    out, _ = _fwd(q, k, v, segment_ids, scale, causal, tiles)
    return out


def _flash_fwd_rule(q, k, v, segment_ids, scale, causal, tiles):
    out, lse = _fwd(q, k, v, segment_ids, scale, causal, tiles)
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, causal, tiles, residuals, g):
    return _bwd(scale, causal, tiles, residuals, g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ==========================================================================
# Public op (registered)
# ==========================================================================
def _handoff_reason(q, k, v, sliding_window, sinks, pstate) -> Optional[str]:
    """Why this call cannot take the kernel (None: it can)."""
    b, s, hq, d = q.shape
    if sliding_window is not None:
        return "sliding_window"
    if sinks is not None:
        return "sinks"
    if k.shape[1] != s:
        return "Sq != Sk"
    # lane-aligned tiles that tile the sequence exactly
    if s % _LANES:
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
):
    """[B, S, H, D] facade-layout wrapper. Shapes/features the kernel
    doesn't cover (sliding window, sinks, cross
    attention, tiny/ragged S) go to the XLA impl, with one log line naming
    the reason. GSPMD cannot partition a Mosaic kernel, so under it on a
    multi-device mesh the kernel runs in a shard_map over the batch (dp)
    axes; inside the Ulysses/ring shard_map it is already per-device.
    """
    from veomni_tpu.parallel.parallel_state import gspmd_parallel_state

    b, s, hq, d = q.shape
    pstate = gspmd_parallel_state()
    reason = _handoff_reason(q, k, v, sliding_window, sinks, pstate)
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
    tiles = choose_tiles(s, d, q.dtype, causal, _other_width(d, v.shape[-1]))

    def kernel(q, k, v, *seg):
        out = _flash_bhsd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            seg[0].astype(jnp.int32) if seg else None, scale, causal, tiles,
        )
        return jnp.swapaxes(out, 1, 2)

    seg = () if segment_ids is None else (segment_ids,)
    if pstate is not None:
        qkv_spec = P(pstate.dp_axes, None, None, None)
        kernel = jax.shard_map(
            kernel, mesh=pstate.mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec) + (P(pstate.dp_axes, None),) * len(seg),
            out_specs=qkv_spec, check_vma=False,
        )
    return kernel(q, k, v, *seg)
