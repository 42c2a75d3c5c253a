"""KV-cache greedy decoding for the dense/MoE transformer families.

Reference parity: the reference's ``tasks/infer/infer_text.py`` delegates to
HF ``model.generate()``, which carries a KV cache; this module is the
TPU-native equivalent — a jitted prefill that records per-layer k/v, and a
``lax.scan`` decode loop over a static-shape cache (XLA-friendly: no dynamic
shapes, one compile per (prompt_bucket, max_new) pair).

Scope: the standard-attention dialect set of ``models/transformer.py``
(GQA + qk-norm, partial/dual rotary, sliding windows, sinks, sandwich
norms, dense or MoE MLP). MLA (deepseek), DSA, hybrid linear-attention
(qwen3_next, kimi_linear) and state-space (granitemoehybrid) families fall back to the
caller's rescoring path — ``supports_cached_decode`` says which, and
``no_cached_decode_reason`` why.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from veomni_tpu import ops
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.models.transformer import (
    _moe_mlp,
    _norm,
    gated_act,
    lm_head_kernel,
)


def no_cached_decode_reason(cfg) -> str:
    """Why a config that :func:`supports_cached_decode` turns away has no
    cached-decode path ("" where it has one or the reason is its class)."""
    if getattr(cfg, "mamba_n_heads", 0) or getattr(cfg, "model_type", "") == "granitemoehybrid":
        return ("its state-space layers carry a recurrent state and conv taps per "
                "request, which the KV-cache engine does not hold")
    if getattr(cfg, "model_type", "") == "qwen3_next":
        return "its linear-attention layers carry a recurrent state per request"
    if getattr(cfg, "linear_attn_config", None) or getattr(cfg, "model_type", "") == "kimi_linear":
        return ("its Kimi Delta Attention layers carry a recurrent state and three convs' taps "
                "per request, which the KV-cache engine does not hold")
    return ""


def supports_cached_decode(cfg) -> bool:
    """Fail-safe gate: True only for plain TransformerConfig dialects whose
    every decode-relevant knob ``_layer`` implements. Composite configs
    (VLM/omni/dit), MLA/DSA, hybrid linear attention, state-space layers, and
    mrope rope scaling (decode builds 1-D positions) fall back to the
    caller's rescoring path — which is always correct, just O(n^2)."""
    if type(cfg) is not TransformerConfig:
        return False
    if (
        getattr(cfg, "use_mla", False)
        or getattr(cfg, "use_dsa", False)
        or no_cached_decode_reason(cfg)
        or getattr(cfg, "linear_attn_layers", None)
    ):
        return False
    rs = getattr(cfg, "rope_scaling", None) or {}
    if "mrope" in str(rs.get("type", rs.get("rope_type", ""))) or rs.get(
        "mrope_section"
    ):
        return False
    return True


def _compute_cast(params, cfg: TransformerConfig):
    """Cast the param tree to the compute dtype, passing int8
    :class:`~veomni_tpu.ops.QuantizedWeight` leaves through untouched — a
    blind ``astype`` would silently widen the int8 payload back to the
    compute dtype and forfeit both the storage win and the registry
    dispatch (``decode_matmul/xla_q8`` dequantizes in-kernel instead)."""
    qw = ops.QuantizedWeight
    return jax.tree.map(
        lambda p: p if isinstance(p, qw) else p.astype(cfg.dtype),
        params,
        is_leaf=lambda x: isinstance(x, qw),
    )


def _rope_tables(cfg: TransformerConfig, positions: jax.Array):
    """(cos_g, sin_g, cos_l, sin_l) for global + (optional) local rope."""
    rope_dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    cos_g, sin_g = ops.rotary_tables(
        positions, rope_dim, cfg.rope_theta, rope_scaling=cfg.rope_scaling
    )
    if cfg.rope_local_base_freq:
        cos_l, sin_l = ops.rotary_tables(positions, rope_dim, cfg.rope_local_base_freq)
    else:
        cos_l, sin_l = cos_g, sin_g
    to = lambda t: t.astype(cfg.dtype)
    return to(cos_g), to(sin_g), to(cos_l), to(sin_l)


def _qkv(x, lp, cfg: TransformerConfig, cos, sin):
    """x [B,T,H] -> q [B,T,hq,d], k/v [B,T,hkv,d] with norms + rope applied."""
    b, t, _ = x.shape
    q = ops.decode_dot(x, lp["q_proj"])
    k = ops.decode_dot(x, lp["k_proj"])
    v = ops.decode_dot(x, lp["v_proj"])
    if cfg.attention_bias:
        q, k, v = q + lp["q_bias"], k + lp["k_bias"], v + lp["v_bias"]
    q = q.reshape(b, t, cfg.num_attention_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.num_key_value_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.num_key_value_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _norm(q, lp["q_norm"], cfg)
        k = _norm(k, lp["k_norm"], cfg)
    rot = cos.shape[-1]
    if rot < cfg.head_dim:
        q_r, k_r = ops.apply_rotary(q[..., :rot], k[..., :rot], cos, sin)
        q = jnp.concatenate([q_r, q[..., rot:]], axis=-1)
        k = jnp.concatenate([k_r, k[..., rot:]], axis=-1)
    else:
        q, k = ops.apply_rotary(q, k, cos, sin)
    return q, k, v


def _attn_params(cfg: TransformerConfig) -> Tuple[int, float]:
    """(GQA repeat factor, softmax scale) shared by the contiguous and paged
    cache-attention paths."""
    nrep = cfg.num_attention_heads // cfg.num_key_value_heads
    scale = (
        cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar
        else cfg.head_dim ** -0.5
    )
    return nrep, scale


def _cache_attend(q, k_cache, v_cache, valid_mask, cfg: TransformerConfig,
                  sinks=None):
    """q [B,T,hq,d] against the full static cache [B,M,hkv,d]; valid_mask
    [B,T,M] bool (causal+window+length). The math lives in
    ``ops.cache_attend`` so the paged (block-table) path shares it."""
    nrep, scale = _attn_params(cfg)
    return ops.cache_attend(
        q, k_cache, v_cache, valid_mask, num_rep=nrep, scale=scale, sinks=sinks
    )


def _mlp(x, lp, cfg: TransformerConfig, is_moe: bool):
    if is_moe:
        b, t, h = x.shape
        out, _ = _moe_mlp(x.reshape(b * t, h), lp, cfg)
        return out.reshape(b, t, h)
    gate = ops.decode_dot(x, lp["gate_proj"])
    up = ops.decode_dot(x, lp["up_proj"])
    if cfg.mlp_bias:
        gate, up = gate + lp["gate_bias"], up + lp["up_bias"]
    o = ops.decode_dot(gated_act(gate, up, cfg), lp["down_proj"])
    if cfg.mlp_bias:
        o = o + lp["down_bias"]
    return o


def _layer_tail(hidden, attn, lp, cfg: TransformerConfig, is_moe):
    """Everything after attention (o_proj + residual + FFN), shared by the
    contiguous and paged layer variants."""
    b, t, _, _ = attn.shape
    out = ops.decode_dot(attn.reshape(b, t, cfg.q_dim), lp["o_proj"])
    if "o_bias" in lp:
        out = out + lp["o_bias"]
    if cfg.sandwich_norms:
        out = _norm(out, lp["post_attention_layernorm"], cfg)
    hidden = hidden + out
    pre = (lp["pre_feedforward_layernorm"] if cfg.sandwich_norms
           else lp["post_attention_layernorm"])
    x = _norm(hidden, pre, cfg)
    out = _mlp(x, lp, cfg, is_moe)
    if cfg.sandwich_norms:
        out = _norm(out, lp["post_feedforward_layernorm"], cfg)
    return hidden + out


def _layer(hidden, lp, cfg: TransformerConfig, cos, sin, k_cache, v_cache,
           valid_mask, write_idx, is_moe):
    """One decoder layer against the cache. Returns (hidden, k_cache,
    v_cache) with this layer's new k/v written at ``write_idx``."""
    x = _norm(hidden, lp["input_layernorm"], cfg)
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, write_idx, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, write_idx, 1)
    attn = _cache_attend(q, k_cache, v_cache, valid_mask, cfg,
                         sinks=lp.get("sinks"))
    return _layer_tail(hidden, attn, lp, cfg, is_moe), k_cache, v_cache


def _paged_layer(hidden, lp, cfg: TransformerConfig, cos, sin, k_pool, v_pool,
                 block_tables, write_block, write_off, valid_mask, is_moe):
    """One decoder layer against the paged block pool: single-token decode
    only (T==1). The new k/v row is scattered to each slot's
    (write_block, write_off) BEFORE attending, preserving the contiguous
    path's write-before-attend invariant. Inactive slots point at the
    reserved null block 0 — duplicate scatter indices there leave garbage no
    live query can see (the valid mask caps every slot at its own position).
    """
    x = _norm(hidden, lp["input_layernorm"], cfg)
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    k_pool = k_pool.at[write_block, write_off].set(k_new[:, 0])
    v_pool = v_pool.at[write_block, write_off].set(v_new[:, 0])
    nrep, scale = _attn_params(cfg)
    attn = ops.paged_attend(
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=nrep, scale=scale, sinks=lp.get("sinks"),
    )
    return _layer_tail(hidden, attn, lp, cfg, is_moe), k_pool, v_pool


def _paged_verify_layer(hidden, lp, cfg: TransformerConfig, cos, sin,
                        k_pool, v_pool, block_tables, write_blocks,
                        write_offs, valid_mask, is_moe):
    """One decoder layer over a speculative **verify** batch against the
    paged pool: KB candidate rows per slot (the committed last token plus
    the drafted continuation). Every row's k/v is scattered to its
    (block, offset) BEFORE attending — the same write-before-attend
    invariant as the decode path, so row j can attend to the draft rows
    0..j-1 of its own slot as well as the committed prefix. Rows past each
    slot's real input length are routed to the reserved null block 0
    (garbage no live query can see)."""
    x = _norm(hidden, lp["input_layernorm"], cfg)
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    k_pool = k_pool.at[write_blocks, write_offs].set(k_new)
    v_pool = v_pool.at[write_blocks, write_offs].set(v_new)
    nrep, scale = _attn_params(cfg)
    attn = ops.paged_attend(
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=nrep, scale=scale, sinks=lp.get("sinks"),
    )
    return _layer_tail(hidden, attn, lp, cfg, is_moe), k_pool, v_pool


def _paged_prefill_layer(hidden, lp, cfg: TransformerConfig, cos, sin,
                         k_pool, v_pool, block_tables, write_blocks,
                         write_offs, valid_mask, is_moe):
    """One decoder layer over a prefill **chunk** against the paged pool:
    T chunk rows of a single sequence (B==1). Every chunk row's k/v is
    scattered to its (block, offset) BEFORE attending — the same
    write-before-attend invariant as the contiguous path, so a chunk row
    can attend to earlier rows of its own chunk as well as the cached
    prefix. Rows past the real chunk length are routed to the reserved
    null block 0 (garbage no live query can see)."""
    x = _norm(hidden, lp["input_layernorm"], cfg)
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    k_pool = k_pool.at[write_blocks, write_offs].set(k_new[0])
    v_pool = v_pool.at[write_blocks, write_offs].set(v_new[0])
    nrep, scale = _attn_params(cfg)
    attn = ops.paged_prefill_attend(
        q, k_pool, v_pool, block_tables, valid_mask,
        num_rep=nrep, scale=scale, sinks=lp.get("sinks"),
    )
    return _layer_tail(hidden, attn, lp, cfg, is_moe), k_pool, v_pool


def _layer_meta(cfg: TransformerConfig):
    """Per-layer static arrays: window sizes [L] (0 = full) and local-rope
    flags [L]; plus the (possibly two-segment) stacked param trees."""
    L = cfg.num_hidden_layers
    windows = jnp.asarray(
        [cfg.window_for_layer(i) or 0 for i in range(L)], jnp.int32
    )
    local = jnp.asarray(
        [bool(cfg.rope_local_base_freq) and (cfg.window_for_layer(i) or 0) > 0
         for i in range(L)]
    )
    return windows, local


def _segment_scan(compute, cfg: TransformerConfig, hidden, k_all, v_all,
                  layer_body):
    """Scan all layers (dense segment then MoE segment), threading the
    per-layer k/v stacks — the walk skeleton every decode-path variant
    (contiguous, paged decode, paged prefill, speculative verify) shares,
    so a masking/segment fix can never drift between paths that must stay
    bit-identical.

    ``layer_body(hidden, lp, k, v, window, local_rope, is_moe) ->
    (hidden, k, v)`` supplies the variant-specific math (rope selection,
    mask construction, cache write + attend)."""
    windows, local_flags = _layer_meta(cfg)
    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0
    segments = []
    if k_dense:
        segments.append(("dense_layers", 0, k_dense, False))
    segments.append(("layers", k_dense, L - k_dense, cfg.is_moe))

    for name, offset, count, is_moe_seg in segments:
        tree = compute[name]

        def body(carry, xs, is_moe_seg=is_moe_seg):
            hidden, = carry
            lp, k_c, v_c, win, loc = xs
            hidden, k_c, v_c = layer_body(hidden, lp, k_c, v_c, win, loc,
                                          is_moe_seg)
            return (hidden,), (k_c, v_c)

        sl = slice(offset, offset + count)
        (hidden,), (k_seg, v_seg) = jax.lax.scan(
            body, (hidden,),
            (tree, k_all[sl], v_all[sl], windows[sl], local_flags[sl]),
        )
        k_all = k_all.at[sl].set(k_seg)
        v_all = v_all.at[sl].set(v_seg)
    return hidden, (k_all, v_all)


def _walk(compute, cfg: TransformerConfig, hidden, caches, write_idx,
          cos_g, sin_g, cos_l, sin_l, valid_base):
    """Scan all layers (dense segment then MoE segment), threading caches.

    caches: (k [L,B,M,hkv,d], v [L,B,M,hkv,d]); valid_base [B,T,M] is the
    causal+length mask — per-layer windows are AND-ed inside the scan."""
    k_all, v_all = caches
    M = k_all.shape[2]
    kpos = jnp.arange(M)[None, None]  # [1,1,M]
    t = hidden.shape[1]
    qpos = write_idx + jnp.arange(t)[None, :, None]  # [1,T,1]

    def layer_body(hidden, lp, k_c, v_c, win, loc, is_moe_seg):
        cos = jnp.where(loc, cos_l, cos_g)
        sin = jnp.where(loc, sin_l, sin_g)
        in_window = jnp.where(win > 0, qpos - kpos < win, True)
        mask = valid_base & in_window
        return _layer(hidden, lp, cfg, cos, sin, k_c, v_c, mask, write_idx,
                      is_moe_seg)

    return _segment_scan(compute, cfg, hidden, k_all, v_all, layer_body)


def _paged_walk(compute, cfg: TransformerConfig, hidden, pools, block_tables,
                positions, cos_g, sin_g, cos_l, sin_l):
    """Paged analogue of ``_walk``: scan all layers (dense segment then MoE
    segment) threading the block pools.

    pools: (k [L,NB,BS,hkv,d], v [L,NB,BS,hkv,d]); block_tables [S,nb];
    positions [S] is each slot's write position (== its query position).
    Block-table order is sequence order, so gathered context index j sits at
    absolute position j and the causal/window masks are identical to the
    contiguous path's."""
    k_all, v_all = pools
    bs = k_all.shape[2]  # [L, NB, BS, hkv, d]
    ctx = block_tables.shape[1] * bs
    kpos = jnp.arange(ctx)[None, None]  # [1,1,ctx]
    qpos = positions[:, None, None]  # [S,1,1]
    valid_base = kpos <= qpos
    write_block = jnp.take_along_axis(
        block_tables, (positions // bs)[:, None], axis=1
    )[:, 0]
    write_off = positions % bs

    def layer_body(hidden, lp, k_p, v_p, win, loc, is_moe_seg):
        cos = jnp.where(loc, cos_l, cos_g)
        sin = jnp.where(loc, sin_l, sin_g)
        in_window = jnp.where(win > 0, qpos - kpos < win, True)
        mask = valid_base & in_window
        return _paged_layer(hidden, lp, cfg, cos, sin, k_p, v_p,
                            block_tables, write_block, write_off, mask,
                            is_moe_seg)

    return _segment_scan(compute, cfg, hidden, k_all, v_all, layer_body)


def _paged_verify_walk(compute, cfg: TransformerConfig, hidden, pools,
                       block_tables, positions, n_input, cos_g, sin_g,
                       cos_l, sin_l):
    """Verify-step analogue of ``_paged_walk``: scan all layers (dense
    segment then MoE segment) threading the block pools, with KB candidate
    queries per slot instead of one.

    pools: (k [L,NB,BS,hkv,d], v); block_tables [S,nb] (null-padded);
    positions [S,KB] are each slot's candidate rows' absolute write/query
    positions (``pos + arange(KB)``); n_input [S] is the real candidate
    count per slot (1 committed token + drafted tokens). Block-table order
    is sequence order, so gathered context index j sits at absolute
    position j and the causal/window masks are identical to the decode
    path's — row j of a slot sees exactly the context the non-speculative
    engine would have at that position."""
    k_all, v_all = pools
    bs = k_all.shape[2]  # [L, NB, BS, hkv, d]
    nb = block_tables.shape[1]
    ctx = nb * bs
    kb = positions.shape[1]
    kpos = jnp.arange(ctx)[None, None]  # [1,1,ctx]
    qpos = positions[:, :, None]  # [S,KB,1]
    valid_base = kpos <= qpos
    # rows past each slot's real input (bucket padding) write their garbage
    # into the null block; real rows land at (table[pos // bs], pos % bs).
    # The clip keeps the table gather in bounds for padded rows whose
    # position overruns the table — they are rerouted to block 0 anyway.
    real = jnp.arange(kb)[None, :] < n_input[:, None]  # [S,KB]
    blk_idx = jnp.clip(positions // bs, 0, nb - 1)
    write_blocks = jnp.where(
        real, jnp.take_along_axis(block_tables, blk_idx, axis=1), 0
    )
    write_offs = positions % bs

    def layer_body(hidden, lp, k_p, v_p, win, loc, is_moe_seg):
        cos = jnp.where(loc, cos_l, cos_g)
        sin = jnp.where(loc, sin_l, sin_g)
        in_window = jnp.where(win > 0, qpos - kpos < win, True)
        mask = valid_base & in_window
        return _paged_verify_layer(hidden, lp, cfg, cos, sin, k_p, v_p,
                                   block_tables, write_blocks, write_offs,
                                   mask, is_moe_seg)

    return _segment_scan(compute, cfg, hidden, k_all, v_all, layer_body)


def _paged_prefill_walk(compute, cfg: TransformerConfig, hidden, pools,
                        block_tables, positions, chunk_len, cos_g, sin_g,
                        cos_l, sin_l):
    """Chunk-prefill analogue of ``_paged_walk``: scan all layers (dense
    segment then MoE segment) threading the block pools, with T chunk
    queries instead of one decode query per slot.

    pools: (k [L,NB,BS,hkv,d], v); block_tables [1,nb] (null-padded);
    positions [CB] are the chunk rows' absolute write/query positions
    (``start + arange(CB)``); chunk_len (traced) is the real chunk length.
    Block-table order is sequence order, so gathered context index j sits
    at absolute position j and the causal/window masks are identical to
    the contiguous prefill's."""
    k_all, v_all = pools
    bs = k_all.shape[2]  # [L, NB, BS, hkv, d]
    nb = block_tables.shape[1]
    ctx = nb * bs
    kpos = jnp.arange(ctx)[None, None]  # [1,1,ctx]
    qpos = positions[None, :, None]  # [1,CB,1]
    valid_base = kpos <= qpos
    cb = positions.shape[0]
    real = jnp.arange(cb) < chunk_len  # rows actually in this chunk
    # rows past chunk_len (bucket padding) write their garbage into the
    # null block; real rows land at (table[pos // bs], pos % bs). The clip
    # keeps the table gather in bounds for padded rows whose position
    # overruns the table — they are rerouted to block 0 anyway.
    blk_idx = jnp.clip(positions // bs, 0, nb - 1)
    write_blocks = jnp.where(real, block_tables[0][blk_idx], 0)
    write_offs = positions % bs

    def layer_body(hidden, lp, k_p, v_p, win, loc, is_moe_seg):
        cos = jnp.where(loc, cos_l, cos_g)
        sin = jnp.where(loc, sin_l, sin_g)
        in_window = jnp.where(win > 0, qpos - kpos < win, True)
        mask = valid_base & in_window
        return _paged_prefill_layer(hidden, lp, cfg, cos, sin, k_p, v_p,
                                    block_tables, write_blocks, write_offs,
                                    mask, is_moe_seg)

    return _segment_scan(compute, cfg, hidden, k_all, v_all, layer_body)


def paged_prefill_step(params, cfg: TransformerConfig, pools, block_table,
                       start_pos, tokens, chunk_len, chunk_bucket: int):
    """Prefill one chunk of ONE sequence against the paged block pool.

    tokens [CB] int32 (the chunk's token ids, zero-padded past
    ``chunk_len``); block_table [nb] int32 covering the sequence's whole
    allocation (null-padded); ``start_pos``/``chunk_len`` are traced,
    ``chunk_bucket`` (== CB) is the static compile bucket. Writes the
    chunk's KV rows at absolute positions [start_pos, start_pos+chunk_len)
    and attends each row over the full prefix — cached blocks included —
    via the block table. Returns (logits of the last real chunk row
    [1,V] f32, pools); intermediate chunks ignore the logits, the final
    chunk's sample the first generated token."""
    compute = _compute_cast(params, cfg)
    positions = start_pos + jnp.arange(chunk_bucket, dtype=jnp.int32)
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions[None])
    hidden = compute["embed_tokens"][tokens[None]]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    hidden, pools = _paged_prefill_walk(
        compute, cfg, hidden, pools, block_table[None], positions,
        chunk_len, cos_g, sin_g, cos_l, sin_l,
    )
    last = jax.lax.dynamic_slice_in_dim(hidden, chunk_len - 1, 1, axis=1)
    logits = _logits(params, compute, cfg, last)
    return logits[:, 0].astype(jnp.float32), pools


def copy_block(pools, src, dst):
    """Copy-on-write: duplicate one pool block's rows (all layers) from
    ``src`` to ``dst`` so a sequence can overwrite its divergence row
    without corrupting the shared cached block. The engine jits this with
    the pools donated; src/dst are traced scalars — one compile total."""
    k_pool, v_pool = pools
    return (
        k_pool.at[:, dst].set(k_pool[:, src]),
        v_pool.at[:, dst].set(v_pool[:, src]),
    )


def paged_decode_step(params, cfg: TransformerConfig, pools, block_tables,
                      positions, tokens):
    """One batched decode step over the slot batch.

    tokens [S] (each slot's most recent token), positions [S] (where that
    token is written and attends from), block_tables [S,nb] int32 padded
    with the null block 0. Returns (logits [S,V] f32, pools). The serving
    engine jits this with the pools donated; the gathered-context width
    nb*BS is the compile bucket."""
    compute = _compute_cast(params, cfg)
    positions_2d = positions[:, None]
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions_2d)
    hidden = compute["embed_tokens"][tokens[:, None]]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    hidden, pools = _paged_walk(compute, cfg, hidden, pools, block_tables,
                                positions, cos_g, sin_g, cos_l, sin_l)
    logits = _logits(params, compute, cfg, hidden)
    return logits[:, 0].astype(jnp.float32), pools


def paged_verify_step(params, cfg: TransformerConfig, pools, block_tables,
                      positions, tokens, n_input):
    """One batched speculative **verify** step over the slot batch.

    tokens [S,KB] (column 0 is each slot's committed last token, columns
    1..n_input-1 its drafted continuation, zero-padded past ``n_input``);
    positions [S] (column 0's write position — the same position the
    non-speculative decode step would write); block_tables [S,nb] int32
    padded with the null block 0; n_input [S] in [1, KB]. Returns
    (logits [S,KB,V] f32, pools): logits[:, j] is the next-token
    distribution AFTER candidate row j, computed with the draft rows
    0..j written — so as long as the drafts up to j are accepted, it is
    bit-for-bit the distribution the one-token path would have produced.
    The serving engine jits this with the pools donated; (KB, gathered
    context width) are the compile buckets."""
    compute = _compute_cast(params, cfg)
    kb = tokens.shape[1]
    pos_rows = positions[:, None] + jnp.arange(kb, dtype=jnp.int32)[None, :]
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, pos_rows)
    hidden = compute["embed_tokens"][tokens]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    hidden, pools = _paged_verify_walk(
        compute, cfg, hidden, pools, block_tables, pos_rows, n_input,
        cos_g, sin_g, cos_l, sin_l,
    )
    logits = _logits(params, compute, cfg, hidden)
    return logits.astype(jnp.float32), pools


def verify_accept(logits, tokens, n_input, keys, temperature, top_k, top_p):
    """Vectorized accept-prefix selection for a speculative verify step.

    logits [S,KB,V] f32 from :func:`paged_verify_step`; tokens [S,KB] its
    inputs (committed token in column 0, drafts after); n_input [S];
    keys [S,2] the per-slot PRNG carries; temperature/top_p [S] f32,
    top_k [S] int32. Returns ``(targets [S,KB], n_emit [S],
    new_keys [S,2])``.

    ``targets[:, j]`` is the token the NON-speculative engine would emit as
    this tick's (j+1)-th token: each column is sampled with the same
    per-step key schedule the one-token path uses (split carry/sample once
    per emitted token), so greedy slots reproduce the argmax chain exactly
    and sampled slots reproduce the categorical draw chain exactly. Draft
    column j+1 is accepted iff it equals target j AND every earlier draft
    was accepted; ``n_emit = accepted + 1`` counts the accepted prefix plus
    the bonus token (the target after the last accepted draft), so the
    emitted tokens are simply ``targets[:, :n_emit]`` and ``new_keys`` is
    the carry advanced by exactly ``n_emit`` splits — byte-identical PRNG
    state to emitting those tokens one step at a time."""
    s, kb, _ = logits.shape
    carry = jnp.asarray(keys, jnp.uint32)
    target_cols, carry_cols = [], [carry]
    for j in range(kb):  # kb is the static compile bucket: unrolled
        split = jax.vmap(lambda k: jax.random.split(k, 2))(carry)
        target_cols.append(sample_tokens(
            logits[:, j], split[:, 1], temperature, top_k, top_p
        ))
        carry = split[:, 0]
        carry_cols.append(carry)
    targets = jnp.stack(target_cols, axis=1)  # [S,KB]
    carries = jnp.stack(carry_cols, axis=1)  # [S,KB+1,2]
    if kb > 1:
        in_draft = jnp.arange(1, kb)[None, :] < n_input[:, None]
        match = (tokens[:, 1:] == targets[:, :-1]) & in_draft
        accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        accepted = jnp.zeros((s,), jnp.int32)
    n_emit = accepted + 1
    new_keys = carries[jnp.arange(s), n_emit]  # carry after n_emit splits
    return targets, n_emit, new_keys


def scatter_prompt_cache(pools, prompt_caches, block_ids):
    """Write a contiguous prefill cache into pool blocks.

    prompt_caches: (k [L,1,PB,hkv,d], v) from ``_prefill_impl`` with
    max_len == PB (the prompt bucket); block_ids [PB/BS] int32 — the
    sequence's allocated blocks, padded with the null block 0 for the
    all-garbage tail blocks past ceil(prompt_len/BS). The boundary block's
    garbage rows in [prompt_len, PB) are harmless for the same reason as the
    contiguous path: decode overwrites row ``pos`` at step ``pos`` before
    attending to it."""
    k_pool, v_pool = pools
    k_c, v_c = prompt_caches
    L, _, pb, hkv, d = k_c.shape
    bs = k_pool.shape[2]
    nb = pb // bs
    k_pool = k_pool.at[:, block_ids].set(k_c[:, 0].reshape(L, nb, bs, hkv, d))
    v_pool = v_pool.at[:, block_ids].set(v_c[:, 0].reshape(L, nb, bs, hkv, d))
    return k_pool, v_pool


def _logits(params, compute, cfg: TransformerConfig, hidden):
    hidden = _norm(hidden, compute["norm"], cfg)
    kernel = lm_head_kernel(params, cfg).astype(cfg.dtype)
    logits = jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(
            logits / cfg.final_logit_softcap
        )
    return logits


def _prefill_impl(params, cfg: TransformerConfig, tokens, prompt_len,
                  prompt_bucket: int, max_len: int):
    """tokens [B,max_len] (prompt in [:prompt_len], zero-padded through
    [:prompt_bucket]) -> (last-prompt-token logits, caches).

    ``prompt_bucket`` (static) is the power-of-two compile bucket;
    ``prompt_len`` (traced) is the real length. The padded tail rows write
    garbage k/v into the cache at [prompt_len, prompt_bucket) — harmless:
    causal masking hides a cache row from every query at position < row, and
    the decode loop overwrites row ``pos`` at step ``pos`` BEFORE attending
    to it, so a garbage row is never visible to any real query."""
    compute = _compute_cast(params, cfg)
    b = tokens.shape[0]
    hd, hkv = cfg.head_dim, cfg.num_key_value_heads
    L = cfg.num_hidden_layers
    k_all = jnp.zeros((L, b, max_len, hkv, hd), cfg.dtype)
    v_all = jnp.zeros_like(k_all)

    ids = tokens[:, :prompt_bucket]
    hidden = compute["embed_tokens"][ids]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(prompt_bucket), (b, prompt_bucket))
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions)

    kpos = jnp.arange(max_len)[None, None]
    qpos = jnp.arange(prompt_bucket)[None, :, None]
    valid = kpos <= qpos  # causal over the cache; future rows still zero
    hidden, caches = _walk(compute, cfg, hidden, (k_all, v_all), 0,
                           cos_g, sin_g, cos_l, sin_l, valid)
    last = jax.lax.dynamic_slice_in_dim(hidden, prompt_len - 1, 1, axis=1)
    logits = _logits(params, compute, cfg, last)
    return logits[:, 0], caches


def _nucleus_mask(logits, top_p):
    """Mask logits outside the top-p nucleus to -inf. HF TopPLogitsWarper
    semantics: sort descending, keep the smallest prefix whose cumulative
    probability reaches top_p (the crossing token included; the top-1 token
    always survives). top_p broadcasts [()] or [B]."""
    sl = jnp.sort(logits, axis=-1)[..., ::-1]
    p = jax.nn.softmax(sl, axis=-1)
    cum = jnp.cumsum(p, axis=-1)
    keep = (cum - p) < jnp.asarray(top_p, jnp.float32)[..., None]
    nkeep = jnp.maximum(keep.sum(-1), 1)
    thresh = jnp.take_along_axis(sl, (nkeep - 1)[..., None], axis=-1)
    return jnp.where(logits < thresh, -jnp.inf, logits)


def _select_token(logits, rng, temperature: float, top_k: int,
                  top_p: float = 1.0):
    """[B,V] f32 -> [B] int32. temperature<=0 means greedy; top_k>0 keeps
    only the k highest logits before sampling (HF generate semantics,
    including the clamp: top_k > vocab means "keep everything" rather than
    a lax.top_k error); top_p<1 then keeps the nucleus whose cumulative
    probability reaches top_p (HF warper order: temperature, top_k, top_p).
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    top_k = min(top_k, logits.shape[-1])
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p < 1.0:
        logits = _nucleus_mask(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


@jax.named_scope("sampler")  # observability/scopes.py
def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Per-slot sampling for the serving engine: every parameter is a traced
    per-row array, so one compiled program honors any mix of per-request
    sampling params. logits [S,V] f32; keys [S,2] uint32 (one PRNG key per
    slot — sampling is reproducible per request regardless of what else is
    in the batch); temperature/top_p [S] f32; top_k [S] int32.

    Per-slot semantics match ``_select_token``: temperature<=0 is greedy,
    top_k<=0 keeps everything (clamped to vocab), top_p>=1 keeps everything.
    """
    v = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    l = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # ONE full-vocab sort serves both filters (this is the per-token decode
    # hot path): top-k keeps a prefix of the sorted order and the nucleus
    # keeps a prefix of THAT, so both reduce to one threshold from ``sl``.
    sl = jnp.sort(l, axis=-1)[..., ::-1]
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v) - 1
    in_k = jnp.arange(v)[None] <= k_idx[:, None]
    p = jax.nn.softmax(jnp.where(in_k, sl, -jnp.inf), axis=-1)
    cum = jnp.cumsum(p, axis=-1)
    keep = in_k & ((cum - p) < top_p[:, None])
    nkeep = jnp.maximum(keep.sum(-1), 1)
    thresh = jnp.take_along_axis(sl, (nkeep - 1)[:, None], axis=-1)
    l = jnp.where(l < thresh, -jnp.inf, l)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(keys, l).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _decode_impl(params, cfg: TransformerConfig, caches, first_token,
                 start_pos, rng, n_steps: int, temperature: float,
                 top_k: int, top_p: float):
    """Scan decode: emit n_steps tokens starting from first_token at
    start_pos (the prompt length). Greedy when temperature<=0, else
    temperature/top-k/top-p sampling with a PRNG carry."""
    compute = _compute_cast(params, cfg)
    max_len = caches[0].shape[2]
    kpos = jnp.arange(max_len)[None, None]

    def step(carry, _):
        token, pos, caches, rng = carry
        positions = jnp.full((token.shape[0], 1), pos, jnp.int32)
        cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions)
        hidden = compute["embed_tokens"][token[:, None]]
        if cfg.embed_scale:
            hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
        valid = kpos <= pos  # [1,1,M] broadcasts over [B,1,M]
        hidden, caches = _walk(compute, cfg, hidden, caches, pos,
                               cos_g, sin_g, cos_l, sin_l, valid)
        logits = _logits(params, compute, cfg, hidden)
        rng, sub = jax.random.split(rng)
        nxt = _select_token(logits[:, 0], sub, temperature, top_k, top_p)
        return (nxt, pos + 1, caches, rng), nxt

    (_, _, _, _), out = jax.lax.scan(
        step, (first_token, jnp.int32(start_pos), caches, rng), None,
        length=n_steps,
    )
    return out.T  # [B, n_steps]


# jitted entry points cached per config CONTENT (TransformerConfig is a
# mutable dataclass, so the key is (id, field-repr hash): mutating a config
# in place retraces instead of silently reusing pre-mutation semantics;
# jax's own shape cache handles the (prompt_bucket, max_len) buckets).
# Bounded: oldest entry evicted past _JIT_CACHE_MAX configs.
_JIT_CACHE: Dict[Tuple, Tuple] = {}
_JIT_CACHE_MAX = 8

# trace-time counters (python side effects run once per compile, never on
# cache hits): tests assert the bucket scheme keeps these flat across
# distinct prompt lengths (each retrace on TPU costs 20-40s)
TRACE_COUNTS = {"prefill": 0, "decode": 0, "paged_decode": 0,
                "paged_prefill": 0, "paged_verify": 0}


def _bucket_pow2(n: int, floor: int = 16) -> int:
    """Smallest power of two >= n (>= floor): the compile bucket for
    prompt/cache lengths, so nearby lengths share one jit specialization
    (masking already hides the padded cache rows)."""
    b = floor
    while b < n:
        b *= 2
    return b


def _jitted(cfg: TransformerConfig):
    key = (id(cfg), hash(repr(cfg)))
    if key not in _JIT_CACHE:

        def prefill_impl(params, cfg, *args):
            TRACE_COUNTS["prefill"] += 1
            return _prefill_impl(params, cfg, *args)

        def decode_impl(params, cfg, *args):
            TRACE_COUNTS["decode"] += 1
            return _decode_impl(params, cfg, *args)

        prefill = jax.jit(
            lambda params, tokens, pl, pb, ml: prefill_impl(
                params, cfg, tokens, pl, pb, ml
            ),
            static_argnums=(3, 4),
        )
        decode = jax.jit(
            lambda params, caches, tok, pos, rng, n, temp, tk, tp: decode_impl(
                params, cfg, caches, tok, pos, rng, n, temp, tk, tp
            ),
            static_argnums=(5, 6, 7, 8),
        )
        # cost census (observability/cost.py): per-bucket XLA FLOPs/bytes +
        # compile wall-time for every prefill/decode specialization —
        # identity under VEOMNI_COST_CENSUS=0
        from veomni_tpu.observability.cost import instrument_jit

        prefill = instrument_jit(
            "prefill", prefill, static_argnums=(3, 4),
            bucket_fn=lambda a: f"pb{a[3]}_ml{a[4]}",
        )
        decode = instrument_jit(
            "decode", decode, static_argnums=(5, 6, 7, 8),
            bucket_fn=lambda a: f"b{a[2].shape[0]}_n{a[5]}",
        )
        while len(_JIT_CACHE) >= _JIT_CACHE_MAX:
            _JIT_CACHE.pop(next(iter(_JIT_CACHE)))
        _JIT_CACHE[key] = (prefill, decode)
    return _JIT_CACHE[key]


def greedy_generate(params, cfg: TransformerConfig, prompt_ids,
                    max_new_tokens: int = 64, eos_id: int = -1,
                    temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 1.0, seed: int = 0):
    """Prompt token list -> full id list (prompt + generated, trimmed at
    eos). One prefill + one scan decode; static shapes throughout.
    temperature<=0 (default) is greedy; otherwise temperature/top-k/top-p
    sampling (HF generate's do_sample analogue)."""
    import numpy as np

    ids = [int(x) for x in prompt_ids]
    if max_new_tokens <= 0:
        return ids
    prompt_len = len(ids)
    # power-of-two compile buckets: every distinct prompt length would
    # otherwise retrace prefill AND decode (20-40s each on TPU); the padded
    # rows are invisible (see _prefill_impl)
    prompt_bucket = _bucket_pow2(prompt_len)
    max_len = _bucket_pow2(prompt_len + max_new_tokens)
    tokens = jnp.zeros((1, max_len), jnp.int32).at[0, :prompt_len].set(
        jnp.asarray(ids, jnp.int32)
    )
    prefill, decode = _jitted(cfg)
    logits, caches = prefill(params, tokens, jnp.int32(prompt_len),
                             prompt_bucket, max_len)
    rng = jax.random.PRNGKey(seed)
    rng, sub = jax.random.split(rng)
    first = _select_token(
        logits.astype(jnp.float32), sub, float(temperature), int(top_k),
        float(top_p),
    )
    rest = (decode(params, caches, first, prompt_len, rng,
                   max_new_tokens - 1, float(temperature), int(top_k),
                   float(top_p))
            if max_new_tokens > 1 else None)
    out = [int(first[0])]
    if rest is not None:
        out += [int(x) for x in np.asarray(rest[0])]
    if eos_id >= 0 and eos_id in out:
        out = out[: out.index(eos_id) + 1]
    return ids + out
