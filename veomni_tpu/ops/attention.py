"""Attention facade with packing (segment-id) support.

Reference: ``veomni/ops/kernels/attention/`` — flash-attn adapter with varlen
cu_seqlens + Ulysses wrapping. TPU translation: packed sequences are masked
via *segment ids* (the TPU-native equivalent of cu_seqlens: tokens attend
only within their own segment), which both the XLA impl and the Pallas flash
kernel consume. Ulysses wrapping lives in ``parallel/sequence_parallel.py``
and calls this op on gathered-sequence/scattered-head tensors.

``mask_mod`` is the FlexAttention analogue (reference
``ops/kernels/attention/flex.py`` mask mods): a callable
``mask_mod(q_idx, k_idx) -> bool`` over broadcastable position index arrays
(close over per-batch tensors for data-dependent masks, e.g. prefix-LM
boundaries — the closure runs inside the jitted program, so GSPMD-sharded
batch tensors are fine; under sequence parallelism the predicate receives
GLOBAL positions — gathered sequence for ulysses, chunk-offset indices for
ring CP) that composes with the causal/window/segment masks. XLA fuses
the predicate into the masked softmax the same way flex compiles a block
mask — no kernel authoring needed on TPU.

Layouts: q [B, S, Hq, D]; k/v [B, S, Hkv, D]; segment_ids [B, S] int32
(0 is a valid segment; padding should use a dedicated segment value and be
masked out by the loss). Returns [B, S, Hq, D].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def _normalize_mask_mod(mm):
    """Accept [Sq,Sk] / [B,Sq,Sk] / [B,H,Sq,Sk] mask_mod results and lift
    them to the [B,H,q,k]-broadcastable rank used by every impl."""
    import jax.numpy as _jnp

    mm = _jnp.asarray(mm)
    if mm.ndim == 3:
        mm = mm[:, None]
    while mm.ndim < 4:
        mm = mm[None]
    return mm


def _best_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (chunked attention block size)."""
    best = 1
    for c in range(1, min(n, target) + 1):
        if n % c == 0:
            best = c
    return best


@KERNEL_REGISTRY.register("attention", "xla_chunked")
def _attention_xla_chunked(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    mask_mod=None,
):
    """Blockwise online-softmax attention in pure XLA (flash-attention
    algorithm, no Pallas): O(S * chunk) live memory instead of the dense
    impl's [B, H, S, S] f32 score tensor, with ``lax.cond``-skipped
    fully-non-causal blocks so the causal half costs no FLOPs. The pure-XLA
    answer to long-context varlen flash attention (reference
    ``ops/kernels/attention/flash.py``): where the ``xla`` impl goes above
    ``VEOMNI_ATTN_CHUNK_THRESHOLD``, and with it whatever the Pallas
    wrapper hands over; each block body is remat'd so the backward
    recomputes block scores exactly like a flash backward.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    cq = _best_chunk(sq, q_chunk)
    ck = _best_chunk(sk, k_chunk)
    if cq < 128 or ck < 128:
        # pathological (prime-ish) lengths: blockwise gains nothing
        return _attention_dense(q, k, v, segment_ids, causal, softmax_scale,
                                sliding_window, sinks, mask_mod=mask_mod)
    n_rep = hq // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    nq, nk = sq // cq, sk // ck
    # [B,H,n,C,D] block layout; compute in the input dtype, accumulate f32
    qt = q.transpose(0, 2, 1, 3).reshape(b, hq, nq, cq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b, hq, nk, ck, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b, hq, nk, ck, d)
    seg_q = seg_k = None
    if segment_ids is not None:
        seg_q = segment_ids.reshape(b, nq, cq)
        seg_k = segment_ids.reshape(b, nk, ck)

    neg = jnp.float32(-1e30)

    def kv_block(carry, j, *, qi, i, sq_i):
        acc, m, l = carry
        kj = kt[:, :, j]
        vj = vt[:, :, j]
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
        qpos = i * cq + jnp.arange(cq)[:, None]
        kpos = j * ck + jnp.arange(ck)[None, :]
        mask = jnp.ones((cq, ck), bool)
        if causal:
            mask = qpos >= kpos
            if sliding_window is not None:
                in_window = (qpos - kpos < sliding_window) | jnp.less_equal(
                    sliding_window, 0
                )
                mask = mask & in_window
        mask = jnp.broadcast_to(mask[None, None], (b, hq, cq, ck))
        if seg_q is not None:
            mask = mask & (sq_i[:, None, :, None] == seg_k[:, j][:, None, None, :])
        if mask_mod is not None:
            mask = mask & _normalize_mask_mod(mask_mod(qpos, kpos))
        s_blk = jnp.where(mask, s_blk, neg)
        m_new = jnp.maximum(m, s_blk.max(-1))
        p = jnp.where(mask, jnp.exp(s_blk - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(q.dtype), vj,
            preferred_element_type=jnp.float32,
        )
        return (acc, m_new, l)

    def q_block(_, i):
        qi = qt[:, :, i]
        sq_i = seg_q[:, i] if seg_q is not None else None
        init = (
            jnp.zeros((b, hq, cq, d), jnp.float32),
            jnp.full((b, hq, cq), neg),
            jnp.zeros((b, hq, cq), jnp.float32),
        )

        def inner(carry, j):
            body = jax.checkpoint(
                lambda c, jj: kv_block(c, jj, qi=qi, i=i, sq_i=sq_i)
            )
            if causal:
                # whole block strictly above the diagonal: skip at runtime
                needed = (j * ck) <= (i * cq + cq - 1)
                carry = jax.lax.cond(
                    needed, lambda c: body(c, j), lambda c: c, carry
                )
            else:
                carry = body(carry, j)
            return carry, None

        (acc, m, l), _ = jax.lax.scan(inner, init, jnp.arange(nk))
        if sinks is not None:
            l = l + jnp.exp(
                sinks.astype(jnp.float32)[None, :, None] - m
            )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, out_blocks = jax.lax.scan(q_block, None, jnp.arange(nq))
    # out_blocks [nq, B, H, Cq, D] -> [B, S, H, D]
    out = out_blocks.transpose(1, 0, 3, 2, 4).reshape(b, sq, hq, d)
    return out


@KERNEL_REGISTRY.register("attention", "xla_twopass", priority=2,
                          device_types=("tpu",))
def _attention_xla_twopass(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    q_chunk: int = 2048,
    mask_mod=None,
):
    """HBM-lean attention in pure XLA: q-chunked, scores computed TWICE.

    On TPU, matmul outputs always round-trip through HBM, so the dense
    impl's f32 [B,H,S,S] score tensor costs ~12 bytes/element of HBM
    traffic. Computing QK^T a second time trades +50% attention FLOPs for a
    fused pipeline where the first pass feeds only a row-max *reduction*
    (fusion root: no score materialization) and the second pass
    materializes just bf16 probabilities (2B/element) consumed once by PV:
    ~4 bytes/element of traffic. Its speed against the dense impl and
    against the Pallas flash kernel (which outranks it by priority on TPU)
    is not measured.

    Chunking over q bounds live probs to [B,H,cq,S] and the backward
    (jax.checkpoint per chunk) recomputes scores flash-style.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    # bound live probs to [B, H, cq, Sk] with cq*Sk <= ~8M elements; at
    # very long Sk the divisor-constrained cq collapses and the online-
    # softmax chunked path (O(cq*ck) blocks) takes over instead
    cq = _best_chunk(sq, min(q_chunk, max(1, 8_388_608 // max(sk, 1))))
    if cq < 256 and sq > 256:
        return _attention_xla_chunked(q, k, v, segment_ids, causal,
                                      softmax_scale, sliding_window, sinks,
                                      mask_mod=mask_mod)
    n_rep = hq // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    nq = sq // cq

    kpos = jnp.arange(sk)[None, :]
    seg_k = segment_ids  # [B, Sk]

    def chunk_body(qi, seg_qi, i):
        # qi [B, cq, Hq, D]; seg_qi [B, cq] or None; i chunk index
        qpos = i * cq + jnp.arange(cq)[:, None]
        mask = None
        if causal:
            mask = qpos >= kpos
            if sliding_window is not None:
                in_window = (qpos - kpos < sliding_window) | jnp.less_equal(
                    sliding_window, 0
                )
                mask = mask & in_window
            mask = mask[None, None]
        if seg_qi is not None:
            seg = seg_qi[:, None, :, None] == seg_k[:, None, None, :]
            mask = seg if mask is None else (mask & seg)
        if mask_mod is not None:
            mm = _normalize_mask_mod(mask_mod(qpos, kpos))
            mask = mm if mask is None else (mask & mm)

        def scores():
            return jnp.einsum(
                "bqhd,bkhd->bhqk", qi, k, preferred_element_type=jnp.float32
            ) * scale

        s1 = scores()
        if mask is not None:
            s1 = jnp.where(mask, s1, -1e30)
        m = jnp.max(s1, axis=-1, keepdims=True)  # [B,H,cq,1] fused reduce
        if sinks is not None:
            sink = sinks.astype(jnp.float32)[None, :, None, None]
            m = jnp.maximum(m, sink)
        m = jax.lax.stop_gradient(m)
        # mask BEFORE the exp: a masked-out score can exceed the (masked)
        # row max by > ln(f32 max) and overflow exp to inf — the forward
        # would be saved by a post-exp where(), but the exp VJP's 0 * inf
        # then NaNs the grads (cf. _attention_dense, which masks scores)
        s2 = scores()
        if mask is not None:
            s2 = jnp.where(mask, s2, -jnp.inf)
        p = jnp.exp(s2 - m)
        l = p.sum(-1)  # [B,H,cq]
        if sinks is not None:
            l = l + jnp.exp(sink[..., 0] - m[..., 0])
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                       preferred_element_type=jnp.float32)
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    if nq == 1:
        return chunk_body(q, segment_ids, 0)

    qs = jnp.moveaxis(q.reshape(b, nq, cq, hq, d), 1, 0)
    seg_qs = (
        jnp.moveaxis(segment_ids.reshape(b, nq, cq), 1, 0)
        if segment_ids is not None else None
    )

    def body(_, args):
        if seg_qs is not None:
            qi, seg_qi, i = args
        else:
            qi, i = args
            seg_qi = None
        return None, jax.checkpoint(chunk_body)(qi, seg_qi, i)

    xs = (qs, seg_qs, jnp.arange(nq)) if seg_qs is not None else (qs, jnp.arange(nq))
    _, out = jax.lax.scan(body, None, xs)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, d)


@KERNEL_REGISTRY.register("attention", "xla", priority=1)
def _attention_xla(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,  # python int OR traced int32 scalar (0/<=0 = full)
    sinks: Optional[jax.Array] = None,  # [Hq] learned sink logits (gpt_oss)
    mask_mod=None,
):
    from veomni_tpu.utils.env import get_env

    threshold = int(get_env("VEOMNI_ATTN_CHUNK_THRESHOLD"))
    if q.shape[1] > threshold:
        return _attention_xla_chunked(q, k, v, segment_ids, causal,
                                      softmax_scale, sliding_window, sinks,
                                      mask_mod=mask_mod)
    return _attention_dense(q, k, v, segment_ids, causal, softmax_scale,
                            sliding_window, sinks, mask_mod=mask_mod)


def _attention_dense(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,  # [B, Sq, Sk] additive (DSA top-k mask)
    mask_mod=None,                     # (q_idx, k_idx) -> bool, broadcastable
):
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    n_rep = hq // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        # clamp -inf bias to a finite floor so fully-masked rows stay NaN-free
        scores = scores + jnp.maximum(bias[:, None], -1e30)
    mask = None
    if causal:
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(sk)[None, :]
        mask = qi >= ki
        if sliding_window is not None:
            # traced windows encode "full attention" as <= 0
            in_window = (qi - ki < sliding_window) | jnp.less_equal(sliding_window, 0)
            mask = mask & in_window
        mask = mask[None, None]
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        seg = jnp.swapaxes(seg, -1, -2)  # [B,1,q,k]
        mask = seg if mask is None else (mask & seg)
    if mask_mod is not None:
        mm = _normalize_mask_mod(
            mask_mod(jnp.arange(sq)[:, None], jnp.arange(sk)[None, :])
        )
        mask = mm if mask is None else (mask & mm)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    if sinks is not None:
        # per-head sink logit participates in the softmax denominator only
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None, None], (b, hq, sq, 1)
        )
        full = jnp.concatenate([scores, sink], axis=-1)
        probs = jax.nn.softmax(full, axis=-1)[..., :sk].astype(q.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        if mask is not None:
            # a row fully masked out (reachable via mask_mod) must emit 0,
            # matching the blockwise impls, not a uniform average of V
            probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(
    q,
    k,
    v,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    mask_mod=None,
    ulysses_async_chunks: Optional[int] = None,
):
    """SP-aware facade (reference ``ops/kernels/attention/__init__.py:30-86``):
    under an ambient ParallelState with ulysses > 1, wraps the resolved
    kernel in the Ulysses a2a shard_map — either the monolithic wrap or the
    chunked async pipeline (``parallel/async_ulysses.py``), selected by the
    ``ulysses`` kernel-registry entry / ``ulysses_async_chunks`` (model
    config plumbing; None defers to registry pin + env knobs). ``mask_mod``
    pins the XLA impls (the Pallas flash kernel doesn't take flex masks) and
    composes with sequence parallelism too: the ulysses a2a gathers the full
    sequence before the inner impl builds its position grids, and the
    ring-CP path evaluates the predicate on global (chunk-offset) positions
    — so a positional mask_mod sees GLOBAL q/k indices under every layout.
    Batch-dependent masks (a closure returning a per-batch [B,...] mask)
    do NOT compose with SP: shard_map would replicate the closed-over
    tensor against the local batch slice — rejected here with a clear
    error instead of a deep trace failure."""
    inner = resolve_op("attention")
    kwargs = dict(causal=causal, softmax_scale=softmax_scale,
                  sliding_window=sliding_window, sinks=sinks)
    if mask_mod is not None:
        kwargs["mask_mod"] = mask_mod
        inner = _attention_xla
    from veomni_tpu.parallel.parallel_state import get_parallel_state_or_none

    pstate = get_parallel_state_or_none()
    if pstate is not None and (pstate.ulysses_size > 1 or pstate.cp_size > 1):
        if mask_mod is not None:
            # shape-only probe (no compute): a mask with a real batch dim
            # would be captured whole by the shard_map closure and collide
            # with the body's local batch slice — fail here, legibly
            sq = q.shape[1]
            mm_abs = jax.eval_shape(
                lambda qi, ki: _normalize_mask_mod(mask_mod(qi, ki)),
                jax.ShapeDtypeStruct((sq, 1), jnp.int32),
                jax.ShapeDtypeStruct((1, sq), jnp.int32),
            )
            if mm_abs.shape[0] > 1:
                raise NotImplementedError(
                    "batch-dependent mask_mod under sequence parallelism: "
                    "the closed-over per-batch tensor would be replicated "
                    "against the shard_map-local batch slice. Use a "
                    "positional (batch-free) mask, or run with sp=1."
                )
        from veomni_tpu.parallel.sequence_parallel import sp_attention

        return sp_attention(inner, q, k, v, segment_ids, pstate,
                            async_chunks=ulysses_async_chunks, **kwargs)
    return inner(q, k, v, segment_ids=segment_ids, **kwargs)
