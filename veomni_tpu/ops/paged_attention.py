"""Paged KV-cache attention: attend a decode query against gathered blocks.

The serving engine (``veomni_tpu/serving/``) carves the KV cache into a
global pool of fixed-size blocks ``[num_blocks, block_size, hkv, d]`` with
per-sequence block tables — the vLLM PagedAttention layout translated to a
static-shape XLA program. ``paged_attend`` gathers each slot's blocks into a
contiguous context (block-table order IS sequence order, so gathered index
``j`` sits at absolute position ``j``) and runs the same masked dense
softmax the contiguous decode cache uses — decode T is 1, the context is
the long axis, so the dense math is the right shape regime and the gather
is the only paging-specific step.

``cache_attend`` is that shared softmax: ``models/decode.py`` calls it for
the contiguous cache and this module calls it for the gathered one, so the
sink / GQA-repeat / masking semantics can never drift between the two
decode paths. Registered as op ``paged_attention`` (impl ``xla_gather``) so
an ops-config pin can swap in a fused Pallas kernel later without touching
the serving engine.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op
from veomni_tpu.ops.quantization import QuantizedKV


def cache_attend(
    q,
    k_cache,
    v_cache,
    valid_mask,
    *,
    num_rep: int = 1,
    scale: float,
    sinks: Optional[jax.Array] = None,
):
    """q [B,T,hq,d] against a cache [B,M,hkv,d]; valid_mask [B,T,M] bool
    (causal+window+length, broadcastable over B/T). Dense math — decode T is
    1 (or the short prefill), the cache is the long axis. ``sinks`` [hq] are
    learned attention-sink logits folded into the softmax denominator
    (gpt_oss family)."""
    if num_rep > 1:
        b, m, hk, d = k_cache.shape
        k_cache = jnp.broadcast_to(
            k_cache[:, :, :, None, :], (b, m, hk, num_rep, d)
        ).reshape(b, m, hk * num_rep, d)
        v_cache = jnp.broadcast_to(
            v_cache[:, :, :, None, :], (b, m, hk, num_rep, d)
        ).reshape(b, m, hk * num_rep, d)
    s = jnp.einsum("bthd,bmhd->bhtm", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid_mask[:, None], s, -jnp.inf)
    m_ = jnp.max(s, axis=-1, keepdims=True)
    if sinks is not None:
        sink = sinks.astype(jnp.float32)[None, :, None, None]
        m_ = jnp.maximum(m_, sink)
    p = jnp.exp(s - m_)
    l = p.sum(-1)
    if sinks is not None:
        l = l + jnp.exp(sink[..., 0] - m_[..., 0])
    o = jnp.einsum("bhtm,bmhd->bthd", p.astype(q.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def gather_block_kv(k_pool, v_pool, block_tables):
    """Gather per-slot KV contexts from the block pool.

    k_pool/v_pool [NB, BS, hkv, d]; block_tables [S, nb] int32 (padded with
    the null block 0 past each sequence's allocation) ->
    (k [S, nb*BS, hkv, d], v [S, nb*BS, hkv, d]). Rows gathered through
    padding entries hold garbage; the caller's valid mask hides them
    (their gathered index exceeds every live position)."""
    nb_, bs, hkv, d = k_pool.shape
    s, nb = block_tables.shape
    k = k_pool[block_tables].reshape(s, nb * bs, hkv, d)
    v = v_pool[block_tables].reshape(s, nb * bs, hkv, d)
    return k, v


def gather_block_kv_q8(k_pool, v_pool, block_tables, dtype):
    """Quantized-pool variant of :func:`gather_block_kv`: gather the int8
    payload and the f32 scale sidecar through the block table FIRST (a
    quarter of the bytes a dense gather moves), then dequantize the
    gathered context. Padding-entry rows dequantize to garbage exactly as
    the dense path gathers garbage — the caller's valid mask hides them."""
    nb_, bs, hkv, d = k_pool.shape
    s, nb = block_tables.shape

    def one(pool):
        data = pool.data[block_tables]          # [S, nb, BS, hkv, d] int8
        scale = pool.scale[block_tables]        # [S, nb, BS, hkv] f32
        ctx = data.astype(jnp.float32) * scale[..., None]
        return ctx.astype(dtype).reshape(s, nb * bs, hkv, d)

    return one(k_pool), one(v_pool)


def _gather_then_attend(gather, q, k_pool, v_pool, block_tables, valid_mask, **attend):
    """The two stages every ``xla_gather*`` impl has, under the scope names
    a trace tells them apart by (observability/scopes.py)."""
    with jax.named_scope("paged.gather"):
        k_ctx, v_ctx = gather(k_pool, v_pool, block_tables)
    with jax.named_scope("paged.attend"):
        return cache_attend(q, k_ctx, v_ctx, valid_mask, **attend)


@KERNEL_REGISTRY.register("paged_attention", "xla_gather")
def _paged_attend_xla(
    q,
    k_pool,
    v_pool,
    block_tables,
    valid_mask,
    *,
    num_rep: int = 1,
    scale: float,
    sinks: Optional[jax.Array] = None,
):
    return _gather_then_attend(
        gather_block_kv, q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )


@KERNEL_REGISTRY.register("paged_attention", "xla_gather_q8")
def _paged_attend_xla_q8(
    q,
    k_pool,
    v_pool,
    block_tables,
    valid_mask,
    *,
    num_rep: int = 1,
    scale: float,
    sinks: Optional[jax.Array] = None,
):
    """int8-KV decode/verify attention: gathered-dequantize, then the SAME
    ``cache_attend`` softmax as ``xla_gather`` — the only non-bit-exactness
    is the int8 rounding on the cache rows themselves."""
    return _gather_then_attend(
        functools.partial(gather_block_kv_q8, dtype=q.dtype),
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )


def _resolve_paged(op: str, k_pool):
    """Storage-aware dispatch for the paged-attention ops: an ops-config pin
    wins unconditionally (same precedence as every other op — the operator
    pinning a dense impl against a quantized pool is an error at their
    door), otherwise the POOL TYPE selects the impl: a ``QuantizedKV`` pool
    takes the ``xla_gather_q8`` impl, a dense pool the normal
    priority-resolved one."""
    if KERNEL_REGISTRY.pinned(op) is None and isinstance(k_pool, QuantizedKV):
        return KERNEL_REGISTRY.impls(op)["xla_gather_q8"].fn
    return resolve_op(op)


def paged_attend(q, k_pool, v_pool, block_tables, valid_mask, *,
                 num_rep: int = 1, scale: float,
                 sinks: Optional[jax.Array] = None):
    """q [S,T,hq,d] + pool [NB,BS,hkv,d] + block_tables [S,nb] ->
    [S,T,hq,d]. valid_mask [S,T,nb*BS] in gathered (== absolute)
    positions. T is 1 for the plain decode step and KB (committed token +
    drafted continuation) for the speculative verify step — the math is
    identical per query row, so the two paths can never drift."""
    inner = _resolve_paged("paged_attention", k_pool)
    return inner(
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )


@KERNEL_REGISTRY.register("paged_prefill_attention", "xla_gather")
def _paged_prefill_attend_xla(
    q,
    k_pool,
    v_pool,
    block_tables,
    valid_mask,
    *,
    num_rep: int = 1,
    scale: float,
    sinks: Optional[jax.Array] = None,
):
    return _gather_then_attend(
        gather_block_kv, q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )


@KERNEL_REGISTRY.register("paged_prefill_attention", "xla_gather_q8")
def _paged_prefill_attend_xla_q8(
    q,
    k_pool,
    v_pool,
    block_tables,
    valid_mask,
    *,
    num_rep: int = 1,
    scale: float,
    sinks: Optional[jax.Array] = None,
):
    """int8-KV chunked-prefill attention: each chunk row attends over the
    dequantized gathered context — including the chunk's OWN rows, which
    were quantized on the scatter that preceded this attend, so chunked and
    monolithic prefill see the identical (rounded) cache."""
    return _gather_then_attend(
        functools.partial(gather_block_kv_q8, dtype=q.dtype),
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )


def paged_prefill_attend(q, k_pool, v_pool, block_tables, valid_mask, *,
                         num_rep: int = 1, scale: float,
                         sinks: Optional[jax.Array] = None):
    """Chunked-prefill attention: a T-token chunk of ONE sequence attends
    over its whole context (already-cached prefix blocks + the chunk's own
    freshly written rows) through the block table.

    q [1,T,hq,d] + pool [NB,BS,hkv,d] + block_tables [1,nb] -> [1,T,hq,d].
    valid_mask [1,T,nb*BS] in gathered (== absolute) positions — the causal
    mask caps each chunk row at its own absolute position, so the math is
    identical to a monolithic prefill over the same context. Registered as
    its own op (impl ``xla_gather``) so a fused Pallas prefill kernel can
    later replace the gather without touching the decode op's pin."""
    inner = _resolve_paged("paged_prefill_attention", k_pool)
    return inner(
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=num_rep, scale=scale, sinks=sinks,
    )
