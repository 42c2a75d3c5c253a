"""Ulysses sequence parallelism: all-to-all head-scatter / seq-gather.

Reference: ``veomni/distributed/sequence_parallel/ulysses.py:34-403``
(_SeqAllToAll custom autograd Functions around flash attention) and the
SP-aware attention facade ``ops/kernels/attention/ulysses.py:27-91``.

TPU design (SURVEY.md §7.1): one ``shard_map`` region over the mesh in which
``jax.lax.all_to_all`` swaps the head and sequence dims across the
``ulysses`` axis — JAX AD transposes the collective automatically, so the
reference's four hand-written autograd Functions collapse into this single
wrapper. The GQA head-repeat (when ulysses_size > kv_heads) mirrors
``attention/ulysses.py:42-48``.

Two implementations share the layout math in :class:`UlyssesLayout` and the
``a2a_scatter_heads`` / ``a2a_gather_heads`` helpers, selected through the
kernel registry (op ``"ulysses"``):

* ``monolithic`` (this module) — one a2a per q/k/v tensor over the full
  head dim, then the inner attention on all local heads at once;
* ``ulysses_async`` (``parallel/async_ulysses.py``) — the head dim split
  into K chunks whose a2a is software-pipelined against the previous
  chunk's attention compute (the TPU analogue of the reference's
  ``async_ulysses.py`` hand-overlapped engine).

Loss reduction over SP ranks (reference ``sequence_parallel/loss.py``) needs
no counterpart: the loss is a token *sum* computed on globally-sharded
arrays inside jit — GSPMD inserts the psum.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.parallel.parallel_state import AXIS_CP, AXIS_ULYSSES, ParallelState
from veomni_tpu.parallel.ring_attention import ring_attention_local
from veomni_tpu.utils.env import env_bool, get_env
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _repeat_heads(x, factor: int):
    if factor == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, factor, d)).reshape(
        b, s, h * factor, d
    )


# --------------------------------------------------------------------------
# Shared a2a layout math (both the monolithic and async-chunked paths)
# --------------------------------------------------------------------------
def a2a_scatter_heads(x, axis_name: str = AXIS_ULYSSES):
    """[b, s_local, h, d] -> [b, s_local*u, h/u, d]: heads scattered across
    the axis, sequence gathered (each rank reassembles the full — or, under
    cp, its cp-chunk of the — sequence for its head slice)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)


def a2a_gather_heads(x, axis_name: str = AXIS_ULYSSES):
    """Inverse of :func:`a2a_scatter_heads`:
    [b, s_local*u, h/u, d] -> [b, s_local, h, d]."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)


@dataclass(frozen=True)
class UlyssesLayout:
    """Head/sequence layout bookkeeping for one Ulysses a2a region.

    The a2a requires every tensor's head dim to be divisible by ``u``; GQA kv
    heads are first repeated by ``kv_rep`` (the minimal factor making
    ``hkv * kv_rep`` a multiple of ``u``, reference ``ulysses.py:42-48``).
    Head-chunked pipelining additionally requires the chunk boundaries to
    respect both the a2a divisibility and the q->kv GQA block mapping, which
    :meth:`max_chunks` encodes.
    """

    u: int
    hq: int
    hkv: int

    def __post_init__(self):
        if self.hq % self.u:
            raise ValueError(
                f"num_attention_heads {self.hq} must be divisible by "
                f"ulysses {self.u}"
            )

    @property
    def kv_rep(self) -> int:
        """GQA repeat factor making the kv head dim a multiple of u."""
        return self.u // math.gcd(self.hkv, self.u)

    @property
    def hkv_rep(self) -> int:
        return self.hkv * self.kv_rep

    @property
    def hq_local(self) -> int:
        """Per-rank q heads after the scatter a2a."""
        return self.hq // self.u

    @property
    def max_chunks(self) -> int:
        """Largest head-chunk count K such that every chunk (a) still has
        head counts divisible by u for the per-chunk a2a and (b) covers
        whole GQA groups so q chunk i attends exactly its kv chunk i."""
        return math.gcd(self.hq // self.u, self.hkv_rep // self.u)

    def clamp_chunks(self, requested: int) -> int:
        """Largest feasible K <= requested (>= 1)."""
        best = 1
        for k in range(1, min(requested, self.max_chunks) + 1):
            if self.max_chunks % k == 0:
                best = k
        return best

    def sink_slice(self, sinks, chunk: int, n_chunks: int, rank):
        """This rank's slice of the per-q-head sink logits [hq] for head
        chunk ``chunk`` of ``n_chunks`` (chunk/rank may be traced)."""
        per_chunk = self.hq // n_chunks
        per_rank = per_chunk // self.u
        start = chunk * per_chunk + rank * per_rank
        return jax.lax.dynamic_slice_in_dim(sinks, start, per_rank, axis=0)


def sp_specs(pstate: ParallelState, have_sinks: bool, sinks_replicated: bool):
    """(qkv_spec, seg_spec, sinks_spec) for the Ulysses shard_map region."""
    dp, spx = pstate.dp_axes, pstate.sp_axes
    qkv_spec = P(dp, spx, None, None)
    seg_spec = P(dp, spx)
    if not have_sinks:
        sinks_spec = None
    elif sinks_replicated or pstate.ulysses_size == 1:
        sinks_spec = P()
    else:
        sinks_spec = P(AXIS_ULYSSES)
    return qkv_spec, seg_spec, sinks_spec


# --------------------------------------------------------------------------
# Monolithic implementation (the default)
# --------------------------------------------------------------------------
@KERNEL_REGISTRY.register("ulysses", "monolithic", priority=1)
def ulysses_monolithic(
    inner_attention: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    pstate: ParallelState,
    **attn_kwargs,
):
    """q [B, S, Hq, D] / k,v [B, S, Hkv, D] globally shaped, sequence-sharded
    over the sp axes. Inside one shard_map region:

    * ``ulysses`` a2a trades this rank's sequence slice for a head slice,
      reassembling each cp rank's contiguous sequence chunk;
    * if ``cp > 1``, ring attention (``ring_attention_local``) rotates KV
      chunks over the ``cp`` axis — total sequence parallelism is then
      ``ulysses * cp`` with the ulysses degree bounded by the head count and
      the ring degree unbounded (the reference has no CP at all);
    * otherwise the resolved inner attention runs on the full sequence.

    Returns [B, S, Hq, D] with the input sharding.
    """
    u, cp = pstate.ulysses_size, pstate.cp_size
    if u == 1 and cp == 1:
        return inner_attention(q, k, v, segment_ids=segment_ids, **attn_kwargs)

    layout = UlyssesLayout(u=u, hq=q.shape[2], hkv=k.shape[2])

    sinks = attn_kwargs.pop("sinks", None)
    qkv_spec, seg_spec, sinks_spec = sp_specs(
        pstate, have_sinks=sinks is not None, sinks_replicated=False
    )
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    def body(q, k, v, seg, snk):
        # local shapes: [b, s/(u*cp), h, d]; snk holds this rank's head slice
        if u > 1:
            k = _repeat_heads(k, layout.kv_rep)
            v = _repeat_heads(v, layout.kv_rep)
            # heads -> scattered, seq -> gathered over ulysses only; what
            # remains sharded on dim 1 is the cp chunk
            q = a2a_scatter_heads(q)   # [b, s/cp, hq/u, d]
            k = a2a_scatter_heads(k)
            v = a2a_scatter_heads(v)
            seg = jax.lax.all_gather(seg, AXIS_ULYSSES, axis=1, tiled=True)
        if cp > 1:
            out = ring_attention_local(
                q, k, v, seg, axis_name=AXIS_CP, sinks=snk, **attn_kwargs
            )
        else:
            out = inner_attention(q, k, v, segment_ids=seg, sinks=snk, **attn_kwargs)
        if u > 1:
            out = a2a_gather_heads(out)  # [b, s/sp, hq, d]
        return out

    in_specs = (qkv_spec, qkv_spec, qkv_spec, seg_spec, sinks_spec)
    fn = jax.shard_map(
        body,
        mesh=pstate.mesh,
        in_specs=in_specs,
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids, sinks)


# --------------------------------------------------------------------------
# Dispatcher
# --------------------------------------------------------------------------
def _resolve_async_chunks(async_chunks: Optional[int]) -> int:
    """Requested head-chunk count for the async path; 0 means monolithic.

    Precedence: registry pin (ops_implementation config) > explicit
    ``async_chunks`` (model-config plumbing) > ``VEOMNI_ULYSSES_ASYNC`` env.
    """
    # pinned() validates against the registered impls — a typo'd pin fails
    # fast instead of silently training on the monolithic path
    pin = KERNEL_REGISTRY.pinned("ulysses")
    if pin == "monolithic":
        return 0
    # default chunk count is only parsed when something requests async —
    # a malformed env value must not break monolithic-path runs
    default_k = lambda: int(get_env("VEOMNI_ULYSSES_ASYNC_CHUNKS"))
    if pin == "ulysses_async":
        # an explicit per-model chunk count still wins under the pin —
        # including the documented "1 = force monolithic" escape hatch
        return async_chunks if async_chunks else default_k()
    if async_chunks is not None:
        return async_chunks if async_chunks > 1 else 0
    if env_bool("VEOMNI_ULYSSES_ASYNC"):
        return default_k()
    return 0


def sp_attention(
    inner_attention: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    pstate: ParallelState,
    async_chunks: Optional[int] = None,
    **attn_kwargs,
):
    """SP attention dispatcher: routes to the monolithic Ulysses wrap or the
    chunked async pipeline (``parallel/async_ulysses.py``) per the kernel
    registry / ``async_chunks`` / env knobs. See :func:`ulysses_monolithic`
    for the tensor contract."""
    # import for registration side effect (op "ulysses" impl "ulysses_async")
    from veomni_tpu.parallel import async_ulysses

    chunks = _resolve_async_chunks(async_chunks)
    if chunks > 1 and pstate.ulysses_size > 1:
        layout = UlyssesLayout(u=pstate.ulysses_size, hq=q.shape[2], hkv=k.shape[2])
        eff = layout.clamp_chunks(chunks)
        if eff > 1:
            return async_ulysses.async_ulysses_attention(
                inner_attention, q, k, v, segment_ids, pstate,
                chunks=eff, **attn_kwargs,
            )
        logger.info_once(
            "ulysses_async requested (chunks=%d) but head layout "
            "(hq=%d, hkv=%d, u=%d) admits no chunking; using monolithic",
            chunks, layout.hq, layout.hkv, layout.u,
        )
    return ulysses_monolithic(
        inner_attention, q, k, v, segment_ids, pstate, **attn_kwargs
    )


# Backwards-compatible name (ulysses-only callers)
ulysses_attention = sp_attention


def sp_pad_length(seq_len: int, sp_size: int) -> int:
    """Pad target so the sequence divides evenly across SP ranks (reference
    ``sp_pad_and_slice``, sequence_parallel/data.py)."""
    return (-seq_len) % sp_size
