"""Ring-attention context parallelism over the ``cp`` mesh axis.

The reference stubs CP entirely (``veomni/distributed/parallel_state.py:81-82``
raises ``NotImplementedError("Ring attention is not supported yet.")``) and
serves long context with Ulysses only — whose degree is capped by the KV-head
count. This module implements the missing capability TPU-natively:

* each cp rank holds a contiguous sequence chunk of q/k/v; the KV chunks (plus
  their segment ids) rotate around the ring via ``lax.ppermute`` over ICI;
* the online-softmax state (acc, m, l) for the *local* q chunk is carried
  across ring steps — the ring loop is literally the outer KV loop of flash
  attention, so no lse-merge pass is needed and JAX AD differentiates the
  whole ``lax.scan`` (ppermute transposes automatically);
* within a chunk pair the score computation is blocked (q/k sub-chunks, each
  block ``jax.checkpoint``-ed) so live memory stays O(S_local * block), and
  whole KV chunks strictly above the causal diagonal are skipped with
  ``lax.cond`` — rank r computes r+1 of cp chunk-pairs, the classic ring
  causal schedule.

Composes with Ulysses: ``sequence_parallel.sp_attention`` runs the head
all-to-all over ``ulysses`` first, then calls this over ``cp``, giving
``sp = ulysses * cp`` total sequence parallelism (the "USP" layout) with the
ulysses degree bounded by heads and the ring degree unbounded.

Masking is position-based (global positions reconstructed from the rank's
chunk offset), so packing (segment ids), causal, and sliding windows all work
across chunk boundaries; gpt_oss attention sinks enter the softmax denominator
once at finalization.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG = -1e30  # plain float: a jnp scalar here would claim a device at import


def _best_chunk(n: int, target: int) -> int:
    best = 1
    for c in range(1, min(n, target) + 1):
        if n % c == 0:
            best = c
    return best


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    *,
    axis_name: str,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    sliding_window=None,
    sinks: Optional[jax.Array] = None,
    mask_mod=None,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
):
    """Per-shard ring attention; must be called inside ``shard_map``.

    q [B, Sl, Hq, D]; k/v [B, Sl, Hkv, D]; segment_ids [B, Sl] — the local
    contiguous chunk of the global sequence (chunk index = this rank's
    position along ``axis_name``). Returns [B, Sl, Hq, D].
    """
    cp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sl, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    cq = _best_chunk(sl, q_chunk)
    ck = _best_chunk(sl, k_chunk)
    nq, nk = sl // cq, sl // ck

    if segment_ids is None:
        segment_ids = jnp.zeros((b, sl), jnp.int32)

    # [B, H, nq, Cq, D] block layout for the local q chunk
    qt = q.transpose(0, 2, 1, 3).reshape(b, hq, nq, cq, d)

    def pair_update(carry, kv_chunk, seg_k, src):
        """Online-softmax update of the whole local q chunk against one
        (rotated-in) KV chunk that originated on cp rank ``src``."""
        acc, m, l = carry  # [b,hq,nq,cq,d], [b,hq,nq,cq], [b,hq,nq,cq]
        k_c, v_c = kv_chunk
        kt = k_c.transpose(0, 2, 1, 3).reshape(b, hkv, nk, ck, d)
        vt = v_c.transpose(0, 2, 1, 3).reshape(b, hkv, nk, ck, d)
        seg_kb = seg_k.reshape(b, nk, ck)
        seg_qb = segment_ids.reshape(b, nq, cq)

        q_off = my * sl
        k_off = src * sl

        def kv_block(inner, j, *, qi, i, sq_i):
            a, mm, ll = inner
            kj = jnp.broadcast_to(
                kt[:, :, None, j], (b, hkv, n_rep, ck, d)
            ).reshape(b, hq, ck, d)
            vj = jnp.broadcast_to(
                vt[:, :, None, j], (b, hkv, n_rep, ck, d)
            ).reshape(b, hq, ck, d)
            s_blk = jnp.einsum(
                "bhqd,bhkd->bhqk", qi, kj, preferred_element_type=jnp.float32
            ) * scale
            qpos = q_off + i * cq + jnp.arange(cq)[:, None]
            kpos = k_off + j * ck + jnp.arange(ck)[None, :]
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask = qpos >= kpos
                if sliding_window is not None:
                    in_win = (qpos - kpos < sliding_window) | jnp.less_equal(
                        sliding_window, 0
                    )
                    mask = mask & in_win
            mask = jnp.broadcast_to(mask[None, None], (b, hq, cq, ck))
            mask = mask & (
                sq_i[:, None, :, None] == seg_kb[:, j][:, None, None, :]
            )
            if mask_mod is not None:
                # qpos/kpos are GLOBAL indices (chunk offsets above), so a
                # flex mask composes across ring rotation unchanged
                from veomni_tpu.ops.attention import _normalize_mask_mod

                mask = mask & _normalize_mask_mod(mask_mod(qpos, kpos))
            s_blk = jnp.where(mask, s_blk, _NEG)
            m_new = jnp.maximum(mm, s_blk.max(-1))
            p = jnp.where(mask, jnp.exp(s_blk - m_new[..., None]), 0.0)
            alpha = jnp.exp(mm - m_new)
            ll = ll * alpha + p.sum(-1)
            a = a * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(q.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            return (a, m_new, ll)

        def q_block(_, i):
            qi = qt[:, :, i]
            sq_i = seg_qb[:, i]
            inner0 = (acc[:, :, i], m[:, :, i], l[:, :, i])

            def step(inner, j):
                body = jax.checkpoint(
                    lambda c, jj: kv_block(c, jj, qi=qi, i=i, sq_i=sq_i)
                )
                if causal:
                    # runtime skip of blocks strictly above the causal
                    # diagonal (global positions; src > my chunks were
                    # already skipped wholesale by the caller)
                    needed = (k_off + j * ck) <= (q_off + i * cq + cq - 1)
                    inner = jax.lax.cond(
                        needed, lambda c: body(c, j), lambda c: c, inner
                    )
                else:
                    inner = body(inner, j)
                return inner, None

            out_i, _ = jax.lax.scan(step, inner0, jnp.arange(nk))
            return None, out_i

        _, (acc_n, m_n, l_n) = jax.lax.scan(q_block, None, jnp.arange(nq))
        # scan stacks the q-block axis first: [nq, b, hq, cq, *]
        acc_n = jnp.moveaxis(acc_n, 0, 2)
        m_n = jnp.moveaxis(m_n, 0, 2)
        l_n = jnp.moveaxis(l_n, 0, 2)
        return acc_n, m_n, l_n

    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def ring_step(carry, t):
        acc, m, l, k_t, v_t, seg_t = carry
        src = (my - t) % cp  # origin rank of the KV chunk currently held

        def compute(c):
            return pair_update(c, (k_t, v_t), seg_t, src)

        if causal:
            acc, m, l = jax.lax.cond(
                src <= my, compute, lambda c: c, (acc, m, l)
            )
        else:
            acc, m, l = compute((acc, m, l))
        # rotate: every rank passes its chunk to the next rank, so at step
        # t+1 this rank holds the chunk of rank (my - t - 1) % cp
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        seg_t = jax.lax.ppermute(seg_t, axis_name, perm)
        return (acc, m, l, k_t, v_t, seg_t), None

    init = (
        jnp.zeros((b, hq, nq, cq, d), jnp.float32),
        jnp.full((b, hq, nq, cq), _NEG),
        jnp.zeros((b, hq, nq, cq), jnp.float32),
        k,
        v,
        segment_ids,
    )
    (acc, m, l, _, _, _), _ = jax.lax.scan(ring_step, init, jnp.arange(cp))

    if sinks is not None:
        l = l + jnp.exp(
            sinks.astype(jnp.float32)[None, :, None, None] - m
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [b,hq,nq,cq,d]
    out = out.reshape(b, hq, sl, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
