"""KV-cache greedy decoding for the dense/MoE transformer families.

Reference parity: the reference's ``tasks/infer/infer_text.py`` delegates to
HF ``model.generate()``, which carries a KV cache; this module is the
TPU-native equivalent — a jitted prefill that records per-layer k/v, and a
``lax.scan`` decode loop over a static-shape cache (XLA-friendly: no dynamic
shapes, one compile per (prompt_bucket, max_new) pair).

Scope: the standard-attention dialect set of ``models/transformer.py``
(GQA + qk-norm, partial/dual rotary, sliding windows, sinks, sandwich
norms, dense or MoE MLP), and stacks whose ``layer_types`` put gated short
convolutions between such attention layers (``models/lfm2_moe.py``): a
convolution layer carries a fixed-size state a request (its last taps), held
beside the keys and values (:func:`init_layer_state`), and the walk goes
layer by layer through the kinds (:func:`_kind_walk`). MLA (deepseek), DSA,
hybrid linear-attention (qwen3_next, kimi_linear) and state-space
(granitemoehybrid) families fall back to the caller's rescoring path —
``supports_cached_decode`` says which, and ``no_cached_decode_reason`` why.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from veomni_tpu import ops
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.models.hybrid_common import CONV_ATTN_KINDS, conv_attn_layer_kinds, conv_taps
from veomni_tpu.models.transformer import (
    _moe_mlp,
    _norm,
    gated_act,
    lm_head_kernel,
    moe_mlp_with_counts,
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
    if _names_conv_layers(cfg) and not walks_by_kind(cfg):
        return "its layer_types name convolution layers and its dialect group holds no conv_L_cache"
    return ""


def _names_conv_layers(cfg) -> bool:
    return "conv" in (getattr(cfg, "layer_types", None) or ())


def walks_by_kind(cfg) -> bool:
    """Does the stack hold layers that are not attention and that the cached
    path can carry: ``layer_types`` names a ``"conv"`` layer and the config
    says how many taps it has (``conv_L_cache`` in its ``dialect`` group). Such
    a stack is walked a layer at a time through its kinds, with the
    convolutions' taps as a second state."""
    return _names_conv_layers(cfg) and "conv_L_cache" in (getattr(cfg, "dialect", None) or {})


def kv_layers(cfg) -> int:
    """The layers that own a slice of the K/V cache: every layer, or, for a
    stack that :func:`walks_by_kind`, its attention layers alone."""
    if walks_by_kind(cfg):
        return sum(t != "conv" for t in cfg.layer_types)
    return cfg.num_hidden_layers


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


def _layer_pages(k_all, v_all, layer, block_tables):
    """Layer ``layer``'s pages of the pool stack ``[L,NB,BS,hkv,d]`` as the
    paged ops take them: the stack through the free view ``[L*NB,BS,hkv,d]``
    (a bitcast; a ``QuantizedKV`` stack takes it on payload and sidecar) and
    the tables moved to the layer's pages, ``block_tables + layer * NB``. The
    null block of layer ``l`` is page ``l * NB``. No ``[NB,BS,hkv,d]`` slice
    of the pool is made: the kernel's DMAs and ``xla_gather``'s gather
    address the pages where they lie."""
    num_layers, nb = k_all.shape[:2]
    flat = (num_layers * nb,) + tuple(k_all.shape[2:])
    return k_all.reshape(flat), v_all.reshape(flat), block_tables + layer * nb


def _cache_attention(x, lp, cfg: TransformerConfig, cos, sin, k_all, v_all,
                     layer, valid_mask, write_idx):
    """A layer's attention against the contiguous cache stack
    ``[L,B,M,hkv,d]``; ``x [B,T,H]`` is the normed input. The layer's new k/v
    are written at ``(layer, :, write_idx)`` with one ``dynamic_update_slice``
    each, in place on a loop carry, BEFORE attending; what is attended is that
    layer's cache alone, one ``dynamic_index_in_dim`` (the context it has to
    read anyway). Returns (context [B,T,hq,d], k_all, v_all)."""
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    at = (layer, 0, write_idx, 0, 0)
    k_all = jax.lax.dynamic_update_slice(k_all, k_new[None], at)
    v_all = jax.lax.dynamic_update_slice(v_all, v_new[None], at)
    attn = _cache_attend(
        q, jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False),
        jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False),
        valid_mask, cfg, sinks=lp.get("sinks"))
    return attn, k_all, v_all


def _paged_attention(x, lp, cfg: TransformerConfig, cos, sin, k_all, v_all,
                     layer, block_tables, write_blocks, write_offs, valid_mask,
                     attend_op):
    """A layer's attention against the paged pool stack ``[L,NB,BS,hkv,d]``,
    for the three paged steps: decode (one row a slot, ``write_blocks`` /
    ``write_offs`` ``[S]``), speculative verify (KB candidate rows a slot,
    ``[S,KB]``) and a prefill chunk (T rows of ONE sequence, ``[T]``, attended
    by ``ops.paged_prefill_attend``). ``x [B,T,H]`` is the normed input.

    The new k/v rows are scattered to ``(layer, block, offset)`` straight in
    the stack BEFORE attending, preserving the contiguous path's
    write-before-attend invariant: a verify or chunk row attends to the
    earlier rows of its own step as well as the cached prefix. Inactive slots,
    and rows past a slot's or a chunk's real length, point at the reserved
    null block 0 of this layer: duplicate scatter indices there leave garbage
    no live query can see (the valid mask caps every row at its own
    position). The pool is read through :func:`_layer_pages`; nothing of its
    size is sliced, copied or stacked. Returns (context [B,T,hq,d], k_all,
    v_all)."""
    q, k_new, v_new = _qkv(x, lp, cfg, cos, sin)
    rows = write_blocks.shape + k_new.shape[2:]  # [S,1,..] or [1,T,..] to the indices' shape
    k_all = k_all.at[layer, write_blocks, write_offs].set(k_new.reshape(rows))
    v_all = v_all.at[layer, write_blocks, write_offs].set(v_new.reshape(rows))
    nrep, scale = _attn_params(cfg)
    attn = attend_op(
        q, *_layer_pages(k_all, v_all, layer, block_tables), valid_mask,
        num_rep=nrep, scale=scale, sinks=lp.get("sinks"),
    )
    return attn, k_all, v_all


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


def init_layer_state(cfg: TransformerConfig, num_slots: int, num_blocks: int = 0):
    """The state beside keys and values, for a stack :func:`walks_by_kind`
    (None for every other): a fixed-size state a layer a request, here a
    convolution layer's last ``K - 1`` inputs ``g`` (its taps).

    ``slots [Lc, num_slots, K-1, C]``: the running state of the request in
    each slot (a row of the contiguous path's batch), as of its last position.
    ``blocks [Lc, num_blocks, K-1, C]`` (paged path only): a snapshot a pool
    block, the state as of the block's LAST position, written when that
    position is, so that a request admitted behind cached blocks starts from
    the snapshot of the last of them and computes none of their positions
    again. ``moe_counts [expert layers, experts]``: the assignments each
    expert got in the last step (the engine's routing counters read it)."""
    if not walks_by_kind(cfg):
        return None
    kinds = conv_attn_layer_kinds(cfg)
    lc = sum(k.startswith("conv") for k in kinds)
    shape = (conv_taps(cfg) - 1, cfg.hidden_size)
    state = {"slots": jnp.zeros((lc, num_slots) + shape, cfg.dtype),
             "moe_counts": jnp.zeros((sum(not k.endswith("_dense") for k in kinds),
                                      max(cfg.num_experts, 1)), jnp.int32)}
    if num_blocks:
        state["blocks"] = jnp.zeros((lc, num_blocks) + shape, cfg.dtype)
    return state


def restore_slot_state(state, slot, block, hit):
    """A slot's state at admission: the snapshot of pool block ``block`` (the
    last cached block of the prompt) where ``hit``, zeros (a sequence's start)
    otherwise. The engine jits this with the state donated."""
    snap = state["blocks"][:, block]
    return {**state, "slots": state["slots"].at[:, slot].set(
        jnp.where(hit, snap, jnp.zeros_like(snap)))}


def _block_end_states(ext, pos0, n_real, tables, bs: int, k1: int):
    """The states as of every block's last position among rows
    ``[pos0, pos0 + n_real)``: ``ext [B, K-1 + T, C]`` is the state before
    row 0 followed by the rows' inputs, so the state as of row ``r`` is
    ``ext[r + 1 : r + K]``. Returns (snapshots ``[B, nj, K-1, C]``, the pool
    block of each ``[B, nj]``: the null block where the row is no real one)."""
    t = ext.shape[1] - k1
    nj = -(-t // bs)
    nb = tables.shape[1]
    first = pos0 // bs  # [B]
    j = jnp.arange(nj)[None, :]
    rel = (first[:, None] + j + 1) * bs - 1 - pos0[:, None]  # [B, nj]
    real = rel < n_real[:, None]
    rows = jnp.clip(rel, 0, t - 1)[..., None] + 1 + jnp.arange(k1)  # [B, nj, K-1]
    snaps = jax.vmap(lambda e, r: e[r])(ext, rows)
    blk = jnp.take_along_axis(tables, jnp.clip(first[:, None] + j, 0, nb - 1), axis=1)
    return snaps, jnp.where(real, blk, 0)


def _conv_op(x, lp, taps_io, slots_l, blocks_l):
    """The gated short convolution against its carried taps. ``taps_io(g
    [B,T,C], slots_l, blocks_l) -> (ext [B, K-1 + T, C], slots_l, blocks_l)``
    hands back the taps in front of the new inputs and the layer's state as
    the variant keeps it. The taps' sum is ``hybrid_common.causal_conv1d``'s,
    term for term. Returns (the mixer's output, slots_l, blocks_l)."""
    with jax.named_scope("conv.proj"):
        b_, c_, z_ = jnp.split(ops.decode_dot(x, lp["in_proj"]), 3, axis=-1)
    with jax.named_scope("conv.mix"):
        ext, slots_l, blocks_l = taps_io(b_ * z_, slots_l, blocks_l)
        w = lp["conv_weight"]
        t = x.shape[1]
        y = c_ * sum(w[None, None, :, i] * ext[:, i:i + t, :] for i in range(w.shape[-1]))
    with jax.named_scope("conv.proj"):
        return ops.decode_dot(y, lp["out_proj"]), slots_l, blocks_l


def _kind_walk(compute, cfg: TransformerConfig, hidden, pools, attend, taps_io):
    """The walk of a stack :func:`walks_by_kind`: a layer at a time through
    ``hybrid_common.conv_attn_layer_kinds`` (no scan: the kinds' order is the model's), the
    attention layers each owning a layer of the K/V stack, the convolution
    layers each a slice of the state.

    ``attend(x, lp, k_all, v_all, layer) -> (context [B,T,hq,d], k_all,
    v_all)`` takes the whole stack and the (static) index of the attention
    layer among the stack's, writes that layer's rows into it and reads that
    layer's pages or cache out of it, as the scanned walks' layer bodies do
    (:func:`_segment_scan`): the pool is addressed by ``(layer, block,
    offset)`` and never sliced. ``taps_io(g, slots_l, blocks_l) -> (ext,
    slots_l, blocks_l)`` is the convolutions' side of the same. Together they
    supply what differs between the contiguous, paged-decode and paged-prefill
    variants (where the new rows are written, what is attended, which state is
    read and kept). Returns (hidden, (k, v, state)) with the step's per-expert
    assignment counts in ``state["moe_counts"]``."""
    k_all, v_all, state = pools
    slots, blocks = state["slots"], state.get("blocks")
    at, ia, ic, counts = {}, 0, 0, []
    for kind in conv_attn_layer_kinds(cfg):
        i = at.get(kind, 0)
        at[kind] = i + 1
        stack = compute[CONV_ATTN_KINDS[kind]]
        # the experts stay in their stack (moe_mlp_with_counts' ``stacked``)
        lp = jax.tree.map(lambda leaf: leaf[i], {k: v for k, v in stack.items() if k != "experts"})
        if kind.startswith("attn"):
            x = _norm(hidden, lp["input_layernorm"], cfg)
            ctx, k_all, v_all = attend(x, lp, k_all, v_all, ia)
            ia += 1
            b, t = ctx.shape[:2]
            hidden = hidden + ops.decode_dot(ctx.reshape(b, t, cfg.q_dim), lp["o_proj"])
        else:
            with jax.named_scope("conv"):
                x = _norm(hidden, lp["input_layernorm"], cfg)
                mixed, slots_l, blocks_l = _conv_op(
                    x, lp, taps_io, slots[ic], None if blocks is None else blocks[ic])
                hidden = hidden + mixed
                slots = slots.at[ic].set(slots_l)
                if blocks is not None:
                    blocks = blocks.at[ic].set(blocks_l)
            ic += 1
        if kind.endswith("_dense"):
            with jax.named_scope("mlp"):
                x = _norm(hidden, lp["post_attention_layernorm"], cfg)
                hidden = hidden + _mlp(x, lp, cfg, False)
        else:
            with jax.named_scope("moe.route"):
                x = _norm(hidden, lp["post_attention_layernorm"], cfg)
            b, t, h = x.shape
            out, _, _, cnt = moe_mlp_with_counts(x.reshape(b * t, h), lp, cfg,
                                                 stacked=(stack["experts"], i))
            counts.append(cnt.astype(jnp.int32))
            with jax.named_scope("moe.combine"):
                hidden = hidden + out.reshape(b, t, h)
    state = {**state, "slots": slots}
    if blocks is not None:
        state["blocks"] = blocks
    if counts:
        state["moe_counts"] = jnp.stack(counts)
    return hidden, (k_all, v_all, state)


def _segment_scan(compute, cfg: TransformerConfig, hidden, k_all, v_all,
                  layer_body):
    """Scan all layers (dense segment then MoE segment) with the whole K/V
    stack as a CARRY of the scan, beside the hidden state: the walk skeleton
    every decode-path variant (contiguous, paged decode, paged prefill,
    speculative verify) shares, so a masking/segment fix can never drift
    between paths that must stay bit-identical.

    The stack (``[L,NB,BS,hkv,d]`` paged, ``[L,B,M,hkv,d]`` contiguous) is
    never a scanned input nor a stacked output: a layer handed a slice of it
    has to be given a copy (it writes into it), and the slices stacked back
    are a second pool (22 GB moved a decode tick at 28 layers of 64 MiB,
    PERF.md PR 48). What is scanned is ``(params, layer index, window, local
    flag)``, and the layer's index goes into the addresses of what the layer
    writes and reads.

    ``layer_body(hidden, lp, k_all, v_all, layer, window, local_rope, is_moe)
    -> (hidden, k_all, v_all)`` supplies the variant-specific math (rope
    selection, mask construction, cache write + attend); ``layer`` is traced."""
    windows, local_flags = _layer_meta(cfg)
    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0
    segments = []
    if k_dense:
        segments.append(("dense_layers", 0, k_dense, False))
    segments.append(("layers", k_dense, L - k_dense, cfg.is_moe))

    for name, offset, count, is_moe_seg in segments:

        def body(carry, xs, is_moe_seg=is_moe_seg):
            hidden, k_all, v_all = carry
            lp, layer, win, loc = xs
            return layer_body(hidden, lp, k_all, v_all, layer, win, loc,
                              is_moe_seg), None

        sl = slice(offset, offset + count)
        (hidden, k_all, v_all), _ = jax.lax.scan(
            body, (hidden, k_all, v_all),
            (compute[name], jnp.arange(offset, offset + count, dtype=jnp.int32),
             windows[sl], local_flags[sl]),
        )
    return hidden, (k_all, v_all)


def _layer_rope_and_mask(loc, win, ropes, qpos, kpos, valid_base):
    """What a scanned layer's flags select: the local or the global rope
    tables, and the causal mask AND-ed with the layer's window (0 = full)."""
    cos_g, sin_g, cos_l, sin_l = ropes
    in_window = jnp.where(win > 0, qpos - kpos < win, True)
    return jnp.where(loc, cos_l, cos_g), jnp.where(loc, sin_l, sin_g), valid_base & in_window


def _paged_scan(compute, cfg: TransformerConfig, hidden, pools, block_tables,
                write_blocks, write_offs, qpos, valid_base, ropes, attend_op):
    """The scanned walk of the three paged steps, which differ in their rows
    alone: where each is written (``write_blocks`` / ``write_offs``), where it
    attends from (``qpos``, broadcastable to ``valid_base [.., .., nb*BS]``)
    and the op that attends. The pool stack is the scan's carry and every
    layer writes its rows and reads its pages in place (:func:`_segment_scan`,
    :func:`_paged_attention`)."""
    kpos = jnp.arange(valid_base.shape[-1])[None, None]

    def layer_body(hidden, lp, k_all, v_all, layer, win, loc, is_moe_seg):
        cos, sin, mask = _layer_rope_and_mask(loc, win, ropes, qpos, kpos, valid_base)
        x = _norm(hidden, lp["input_layernorm"], cfg)
        attn, k_all, v_all = _paged_attention(
            x, lp, cfg, cos, sin, k_all, v_all, layer, block_tables,
            write_blocks, write_offs, mask, attend_op)
        return _layer_tail(hidden, attn, lp, cfg, is_moe_seg), k_all, v_all

    return _segment_scan(compute, cfg, hidden, *pools[:2], layer_body)


def _walk(compute, cfg: TransformerConfig, hidden, caches, write_idx,
          cos_g, sin_g, cos_l, sin_l, valid_base, n_real=None):
    """Scan all layers (dense segment then MoE segment) with the caches
    carried whole: layer ``l`` writes its rows at ``(l, :, write_idx)`` and
    attends its own cache (:func:`_cache_attention`).

    caches: (k [L,B,M,hkv,d], v [L,B,M,hkv,d]); valid_base [B,T,M] is the
    causal+length mask — per-layer windows are AND-ed inside the scan. For a
    stack that :func:`walks_by_kind`, caches carry the state third, ``L`` counts
    the attention layers and ``n_real`` (traced; all T rows when None) says how
    many of the rows are real: the state kept is as of the last of them."""
    if walks_by_kind(cfg):
        t = hidden.shape[1]
        n = jnp.full((hidden.shape[0],), t if n_real is None else n_real, jnp.int32)

        def attend(x, lp, k_all, v_all, layer):
            return _cache_attention(x, lp, cfg, cos_g, sin_g, k_all, v_all, layer,
                                    valid_base, write_idx)

        def taps_io(g, slots_l, _blocks):
            ext = jnp.concatenate([slots_l, g], axis=1)
            k1 = slots_l.shape[1]
            kept = jax.vmap(lambda e, m: jax.lax.dynamic_slice_in_dim(e, m, k1, 0))(ext, n)
            return ext, kept, None

        return _kind_walk(compute, cfg, hidden, caches, attend, taps_io)
    k_all, v_all = caches
    M = k_all.shape[2]
    kpos = jnp.arange(M)[None, None]  # [1,1,M]
    t = hidden.shape[1]
    qpos = write_idx + jnp.arange(t)[None, :, None]  # [1,T,1]
    ropes = (cos_g, sin_g, cos_l, sin_l)

    def layer_body(hidden, lp, k_all, v_all, layer, win, loc, is_moe_seg):
        cos, sin, mask = _layer_rope_and_mask(loc, win, ropes, qpos, kpos, valid_base)
        x = _norm(hidden, lp["input_layernorm"], cfg)
        attn, k_all, v_all = _cache_attention(x, lp, cfg, cos, sin, k_all, v_all, layer,
                                              mask, write_idx)
        return _layer_tail(hidden, attn, lp, cfg, is_moe_seg), k_all, v_all

    return _segment_scan(compute, cfg, hidden, k_all, v_all, layer_body)


def _paged_walk(compute, cfg: TransformerConfig, hidden, pools, block_tables,
                positions, cos_g, sin_g, cos_l, sin_l):
    """Paged analogue of ``_walk``, the decode tick: scan all layers (dense
    segment then MoE segment) with the block pools carried whole.

    pools: (k [L,NB,BS,hkv,d], v [L,NB,BS,hkv,d]); block_tables [S,nb];
    positions [S] is each slot's write position (== its query position).
    Layer ``l`` writes each slot's row at ``(l, table[pos // BS], pos % BS)``
    and attends pages ``block_tables + l * NB`` of the stack's free view
    ``[L*NB,BS,hkv,d]``: the pool is addressed by (layer, block, offset) and no
    layer's ``[NB,BS,hkv,d]`` is ever sliced out of it, written back or copied
    (:func:`_segment_scan`). Block-table order is sequence order, so gathered
    context index j sits at absolute position j and the causal/window masks
    are identical to the contiguous path's."""
    bs = pools[0].shape[2]  # [L, NB, BS, hkv, d]
    ctx = block_tables.shape[1] * bs
    kpos = jnp.arange(ctx)[None, None]  # [1,1,ctx]
    qpos = positions[:, None, None]  # [S,1,1]
    valid_base = kpos <= qpos
    write_block = jnp.take_along_axis(
        block_tables, (positions // bs)[:, None], axis=1
    )[:, 0]
    write_off = positions % bs
    if walks_by_kind(cfg):
        # a block's snapshot is the state as of its last position
        end_block = jnp.where(write_off == bs - 1, write_block, 0)

        def attend(x, lp, k_all, v_all, layer):
            return _paged_attention(x, lp, cfg, cos_g, sin_g, k_all, v_all, layer, block_tables,
                                    write_block, write_off, valid_base, ops.paged_attend)

        # a slot that is not decoding (its table is all the null block) may be
        # mid-prefill, its state a chunk's to go on from: it keeps it
        decoding = (write_block != 0)[:, None, None]

        def taps_io(g, slots_l, blocks_l):
            ext = jnp.concatenate([slots_l, g], axis=1)
            kept = jnp.where(decoding, ext[:, 1:], slots_l)
            return ext, kept, blocks_l.at[end_block].set(kept)

        return _kind_walk(compute, cfg, hidden, pools, attend, taps_io)

    return _paged_scan(compute, cfg, hidden, pools, block_tables, write_block, write_off,
                       qpos, valid_base, (cos_g, sin_g, cos_l, sin_l), ops.paged_attend)


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
    if walks_by_kind(cfg):
        raise NotImplementedError(
            "speculative verify over a stack with convolution layers: which of a slot's "
            "candidate rows are kept is known only after the step, and the taps kept have "
            "to be those of the last accepted row")
    bs = pools[0].shape[2]  # [L, NB, BS, hkv, d]
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
    return _paged_scan(compute, cfg, hidden, pools, block_tables, write_blocks, write_offs,
                       qpos, valid_base, (cos_g, sin_g, cos_l, sin_l), ops.paged_attend)


def _paged_prefill_walk(compute, cfg: TransformerConfig, hidden, pools,
                        block_tables, positions, chunk_len, cos_g, sin_g,
                        cos_l, sin_l, slot=None):
    """Chunk-prefill analogue of ``_paged_walk``: scan all layers (dense
    segment then MoE segment) threading the block pools, with T chunk
    queries instead of one decode query per slot.

    pools: (k [L,NB,BS,hkv,d], v); block_tables [1,nb] (null-padded);
    positions [CB] are the chunk rows' absolute write/query positions
    (``start + arange(CB)``); chunk_len (traced) is the real chunk length.
    Block-table order is sequence order, so gathered context index j sits
    at absolute position j and the causal/window masks are identical to
    the contiguous prefill's."""
    bs = pools[0].shape[2]  # [L, NB, BS, hkv, d]
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
    if walks_by_kind(cfg):

        def attend(x, lp, k_all, v_all, layer):
            return _paged_attention(x, lp, cfg, cos_g, sin_g, k_all, v_all, layer, block_tables,
                                    write_blocks, write_offs, valid_base,
                                    ops.paged_prefill_attend)

        def taps_io(g, slots_l, blocks_l):
            # the chunk goes on from its slot's state (the snapshot of the last
            # cached block, the chunk before, or a sequence's zeros) and leaves
            # the state as of its last real row there
            k1 = slots_l.shape[1]
            ext = jnp.concatenate([slots_l[slot][None], g], axis=1)
            kept = jax.lax.dynamic_slice_in_dim(ext[0], chunk_len, k1, 0)
            snaps, blk = _block_end_states(ext, positions[:1], jnp.reshape(chunk_len, (1,)),
                                           block_tables, bs, k1)
            return ext, slots_l.at[slot].set(kept), blocks_l.at[blk[0]].set(snaps[0])

        return _kind_walk(compute, cfg, hidden, pools, attend, taps_io)

    return _paged_scan(compute, cfg, hidden, pools, block_tables, write_blocks, write_offs,
                       qpos, valid_base, (cos_g, sin_g, cos_l, sin_l),
                       ops.paged_prefill_attend)


def paged_prefill_step(params, cfg: TransformerConfig, pools, block_table,
                       start_pos, tokens, chunk_len, chunk_bucket: int, slot=None):
    """Prefill one chunk of ONE sequence against the paged block pool.

    tokens [CB] int32 (the chunk's token ids, zero-padded past
    ``chunk_len``); block_table [nb] int32 covering the sequence's whole
    allocation (null-padded); ``start_pos``/``chunk_len`` are traced,
    ``chunk_bucket`` (== CB) is the static compile bucket. Writes the
    chunk's KV rows at absolute positions [start_pos, start_pos+chunk_len)
    and attends each row over the full prefix — cached blocks included —
    via the block table. Returns (logits of the last real chunk row
    [1,V] f32, pools); intermediate chunks ignore the logits, the final
    chunk's sample the first generated token. For a stack that
    :func:`walks_by_kind`, ``pools`` carry the state third and ``slot``
    (traced) names the slot whose running state the chunk goes on from."""
    compute = _compute_cast(params, cfg)
    positions = start_pos + jnp.arange(chunk_bucket, dtype=jnp.int32)
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions[None])
    hidden = compute["embed_tokens"][tokens[None]]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    hidden, pools = _paged_prefill_walk(
        compute, cfg, hidden, pools, block_table[None], positions,
        chunk_len, cos_g, sin_g, cos_l, sin_l, slot,
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
    nb*BS is the compile bucket.

    The pools ``[L,NB,BS,hkv,d]`` are carried through the layer scan and
    addressed by (layer, block, offset): every layer scatters its ``S`` new
    rows into the donated buffer and reads its pages where they lie, so the
    step holds the pool once and moves nothing of its size
    (:func:`_paged_walk`)."""
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
    state = init_layer_state(cfg, b)
    k_all = jnp.zeros((kv_layers(cfg), b, max_len, hkv, hd), cfg.dtype)
    v_all = jnp.zeros_like(k_all)
    caches = (k_all, v_all) if state is None else (k_all, v_all, state)

    ids = tokens[:, :prompt_bucket]
    hidden = compute["embed_tokens"][ids]
    if cfg.embed_scale:
        hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(prompt_bucket), (b, prompt_bucket))
    cos_g, sin_g, cos_l, sin_l = _rope_tables(cfg, positions)

    kpos = jnp.arange(max_len)[None, None]
    qpos = jnp.arange(prompt_bucket)[None, :, None]
    valid = kpos <= qpos  # causal over the cache; future rows still zero
    hidden, caches = _walk(compute, cfg, hidden, caches, 0,
                           cos_g, sin_g, cos_l, sin_l, valid, n_real=prompt_len)
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


#: what a ``sample_tokens`` call has to do, least first: ``argmax`` alone;
#: a categorical draw and no threshold; a threshold for the rows that filter
SAMPLER_PATHS = ("greedy", "unfiltered", "filtered")


def sampler_path(temperature, top_k, top_p, vocab: int):
    """Index into ``SAMPLER_PATHS`` of the most work any row of a call asks
    for. Written in what numpy arrays and traced ones share, so the engine's
    count on the host (``serve.sampler_ticks``) and the ``lax.switch`` inside
    ``sample_tokens`` are one predicate and cannot drift."""
    samples = temperature > 0.0
    filters = samples & (((top_k > 0) & (top_k < vocab)) | (top_p < 1.0))
    return samples.any() * 1 + filters.any() * 1


def _ordered_of_float(x):
    """The place of float32 ``x`` among all float32 bit patterns, lowest
    value first, as uint32 (-inf is 0x007fffff, +inf 0xff800000)."""
    sign = jnp.uint32(0x80000000)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def _float_of_ordered(u):
    sign = jnp.uint32(0x80000000)
    bits = jnp.where(u >= sign, u ^ sign, ~u)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _lowest_value(row_max, keeps):
    """Per row, the lowest float32 ``x`` with ``keeps(x [S]) -> bool [S]``,
    given that it holds at ``row_max`` and is monotone in ``x``. Where
    ``keeps`` reads the row through sums over ``row > x`` alone, it is
    constant between two neighbouring values of the row, so the answer IS
    one of the row's values: a selection by value, 32 halvings over
    float32's ordered bit patterns at one pass over the row each, where a
    sort would order the whole vocabulary."""
    hi = _ordered_of_float(row_max)
    lo = jnp.full_like(hi, jnp.uint32(0x007FFFFF))  # -inf: no logit lies below it

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo) // 2
        ok = keeps(_float_of_ordered(mid))
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    return _float_of_ordered(jax.lax.fori_loop(0, 32, halve, (lo, hi))[1])


def _filter_threshold(l, top_k, top_p):
    """[S] the lowest logit each row of ``l`` [S,V] keeps under HF's rule
    (top-k, then top-p over the top-k's renormalised mass; the crossing
    token and all its ties kept; top-1 always survives), -inf for a row that
    sets no filter. The threshold is the lowest value ``x`` of the row with
    fewer than ``k`` logits above it and less than ``top_p`` of the kept
    mass above it, and both counts only grow as ``x`` falls."""
    v = l.shape[-1]
    top = l.max(axis=-1)
    cuts = (top_k > 0) & (top_k < v)
    k = jnp.where(cuts, top_k, v)

    def above(x):
        return l > x[:, None]

    kth = _lowest_value(top, lambda x: above(x).sum(-1) < k)
    # the mass of exactly k logits, as a sort's first k hold it: all above
    # the k-th value, and as many of its ties as fill the count
    e = jnp.exp(l - top[:, None])
    n_above = above(kth).sum(-1)
    mass_k = (jnp.where(above(kth), e, 0.0).sum(-1)
              + (k - n_above) * jnp.exp(kth - top))
    crossing = _lowest_value(top, lambda x: (x >= top) | (
        jnp.where(above(x), e, 0.0).sum(-1) < top_p * mass_k))
    return jnp.maximum(jnp.where(cuts, kth, -jnp.inf),
                       jnp.where(top_p < 1.0, crossing, -jnp.inf))


@jax.named_scope("sampler")  # observability/scopes.py
def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Per-slot sampling for the serving engine: every parameter is a traced
    per-row array, so one compiled program honors any mix of per-request
    sampling params. logits [S,V] f32; keys [S,2] uint32 (one PRNG key per
    slot — sampling is reproducible per request regardless of what else is
    in the batch); temperature/top_p [S] f32; top_k [S] int32.

    Per-slot semantics match ``_select_token``: temperature<=0 is greedy,
    top_k<=0 keeps everything (clamped to vocab), top_p>=1 keeps everything.

    The call does what its rows ask for and no more (``sampler_path``, read
    from the three arrays at run time inside the one program): ``argmax``
    alone where no row samples, the categorical draw with no threshold where
    no sampling row filters, ``_filter_threshold`` where one does. No path
    sorts the vocabulary.
    """
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(filtered: bool):
        l = logits / jnp.maximum(temperature, 1e-6)[:, None]
        if filtered:
            l = jnp.where(l < _filter_threshold(l, top_k, top_p)[:, None],
                          -jnp.inf, l)
        sampled = jax.vmap(
            lambda key, row: jax.random.categorical(key, row)
        )(keys, l).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.switch(
        sampler_path(temperature, top_k, top_p, logits.shape[-1]),
        (lambda: greedy, lambda: draw(False), lambda: draw(True)),
    )


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
