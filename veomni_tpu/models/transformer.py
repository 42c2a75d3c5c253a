"""Functional decoder-only transformer core (llama / qwen2 / qwen3 / qwen3_moe).

Reference behavior: the generated modeling files under
``veomni/models/transformers/<family>/generated/`` (e.g.
``patched_modeling_qwen3_gpu.py``) — embedding -> N decoder layers
(rmsnorm, GQA attention w/ rotary, SwiGLU MLP or MoE) -> final norm ->
fused-linear CE loss. TPU-first design decisions:

* **Params are a plain pytree** with per-layer tensors *stacked on a leading
  layer dim* and the forward is a ``lax.scan`` over that dim: one compiled
  layer body regardless of depth (fast compiles, weight-stationary layout),
  with ``jax.checkpoint`` on the body for rematerialized activations.
* Mixed precision: master params in ``param_dtype`` (f32), cast once to
  ``dtype`` (bf16) at step start — this is what FSDP2's mp_policy does via
  per-layer casts in the reference (``torch_parallelize.py:401-405``).
* Packing: segment_ids mask cross-document attention (the cu_seqlens varlen
  contract of the reference collator, ``data/data_collator.py:50-106``).
* MoE layers compute via token-sort + grouped GEMM (``ops.group_gemm``); the
  EP-distributed dispatch wraps this under ``shard_map`` in
  ``parallel/moe.py``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from veomni_tpu import ops
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.ops.pallas.grouped_gemm import tile_census

Params = Dict[str, Any]


def _remat_policy(cfg: TransformerConfig):
    """Map cfg.remat_policy to a jax.checkpoint policy (the TPU analogue of
    the reference's activation-offload contexts, ``offloading.py:32-74``).

    Policies, by what the backward keeps (what each costs on the chip is in
    docs/performance.md, "Remat policy guide": only "nothing" is measured):
    - "dots": every no-batch-dim dot output.
    - "ctx": ONLY the attention context (the post-softmax [B,S,nh*hd]
      tensor, named "attn_ctx") + scan-carry layer boundaries. Backward
      re-runs the projection/FFN matmuls but never the O(S^2) attention.
    - "ctx_offload": same saves, parked in pinned host RAM.
    - "offload": dot saves of "dots" parked in pinned host RAM.
    - "nothing": full recompute; what both benchmark cells run.
    """
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "ctx":
        return jax.checkpoint_policies.save_only_these_names("attn_ctx")
    if cfg.remat_policy == "ctx_offload":
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["attn_ctx"],
            offload_src="device", offload_dst="pinned_host",
        )
    if cfg.remat_policy == "offload":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    return jax.checkpoint_policies.nothing_saveable


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def _dense_init(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_params(keys, cfg: TransformerConfig, L: int, pd) -> Params:
    h = cfg.hidden_size
    s = cfg.initializer_range
    p: Params = {"input_layernorm": jnp.ones((L, h), pd)}
    if cfg.use_mla:
        # deepseek MLA: low-rank q/kv compression + rope/nope split
        nh, qk, vd = cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            p["q_a_proj"] = _dense_init(next(keys), (L, h, cfg.q_lora_rank), pd, s)
            p["q_a_layernorm"] = jnp.ones((L, cfg.q_lora_rank), pd)
            p["q_b_proj"] = _dense_init(next(keys), (L, cfg.q_lora_rank, nh * qk), pd, s)
        else:
            p["q_proj"] = _dense_init(next(keys), (L, h, nh * qk), pd, s)
        p["kv_a_proj_with_mqa"] = _dense_init(
            next(keys), (L, h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), pd, s
        )
        p["kv_a_layernorm"] = jnp.ones((L, cfg.kv_lora_rank), pd)
        p["kv_b_proj"] = _dense_init(
            next(keys), (L, cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + vd)), pd, s
        )
        p["o_proj"] = _dense_init(next(keys), (L, nh * vd, h), pd, s)
        if cfg.use_dsa:
            # DSA lightning indexer (glm_moe_dsa): lightweight side scorer
            inh, ihd = cfg.index_n_heads, cfg.index_head_dim
            p["indexer"] = {
                "wq_b": _dense_init(next(keys), (L, cfg.q_lora_rank, inh * ihd), pd, s),
                "wk": _dense_init(next(keys), (L, h, ihd), pd, s),
                "k_norm_w": jnp.ones((L, ihd), pd),
                "k_norm_b": jnp.zeros((L, ihd), pd),
                "weights_proj": _dense_init(next(keys), (L, h, inh), pd, s),
            }
    else:
        qd, kvd = cfg.q_dim, cfg.kv_dim
        p["q_proj"] = _dense_init(next(keys), (L, h, qd), pd, s)
        p["k_proj"] = _dense_init(next(keys), (L, h, kvd), pd, s)
        p["v_proj"] = _dense_init(next(keys), (L, h, kvd), pd, s)
        p["o_proj"] = _dense_init(next(keys), (L, qd, h), pd, s)
        if cfg.attention_bias:
            p["q_bias"] = jnp.zeros((L, qd), pd)
            p["k_bias"] = jnp.zeros((L, kvd), pd)
            p["v_bias"] = jnp.zeros((L, kvd), pd)
        if cfg.o_bias:
            p["o_bias"] = jnp.zeros((L, h), pd)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((L, cfg.head_dim), pd)
            p["k_norm"] = jnp.ones((L, cfg.head_dim), pd)
        if cfg.attention_sinks:
            p["sinks"] = jnp.zeros((L, cfg.num_attention_heads), pd)
    p["post_attention_layernorm"] = jnp.ones((L, h), pd)
    if cfg.sandwich_norms:
        p["pre_feedforward_layernorm"] = jnp.ones((L, h), pd)
        p["post_feedforward_layernorm"] = jnp.ones((L, h), pd)
    return p


def _dense_mlp_params(keys, cfg: TransformerConfig, L: int, pd) -> Params:
    h, inter = cfg.hidden_size, cfg.intermediate_size
    s = cfg.initializer_range
    p = {
        "gate_proj": _dense_init(next(keys), (L, h, inter), pd, s),
        "up_proj": _dense_init(next(keys), (L, h, inter), pd, s),
        "down_proj": _dense_init(next(keys), (L, inter, h), pd, s),
    }
    if cfg.mlp_bias:
        p["gate_bias"] = jnp.zeros((L, inter), pd)
        p["up_bias"] = jnp.zeros((L, inter), pd)
        p["down_bias"] = jnp.zeros((L, h), pd)
    return p


def _moe_params(keys, cfg: TransformerConfig, L: int, pd) -> Params:
    h = cfg.hidden_size
    s = cfg.initializer_range
    im = cfg.moe_intermediate_size or cfg.intermediate_size
    # the router is as wide as the model has experts; the expert tensors hold
    # this chip's share of them (all, unless cfg.moe_experts_held says fewer)
    e, held = cfg.num_experts, cfg.experts_held
    p: Params = {
        "router": _dense_init(next(keys), (L, h, e), pd, s),
        **({"router_bias": jnp.zeros((L, e), pd)} if cfg.router_bias else {}),
        "experts": {
            "gate_proj": _dense_init(next(keys), (L, held, h, im), pd, s),
            "up_proj": _dense_init(next(keys), (L, held, h, im), pd, s),
            "down_proj": _dense_init(next(keys), (L, held, im, h), pd, s),
        },
    }
    if cfg.scoring_func == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((L, e), pd)
    if cfg.mlp_bias:
        p["experts"]["gate_bias"] = jnp.zeros((L, held, im), pd)
        p["experts"]["up_bias"] = jnp.zeros((L, held, im), pd)
        p["experts"]["down_bias"] = jnp.zeros((L, held, h), pd)
    if cfg.n_shared_experts or cfg.shared_expert_intermediate_size:
        si = cfg.shared_expert_intermediate_size or im * cfg.n_shared_experts
        p["shared_experts"] = {
            "gate_proj": _dense_init(next(keys), (L, h, si), pd, s),
            "up_proj": _dense_init(next(keys), (L, h, si), pd, s),
            "down_proj": _dense_init(next(keys), (L, si, h), pd, s),
        }
        if cfg.shared_expert_gated:
            # qwen2-moe/qwen3_next: scalar sigmoid gate on the shared expert
            p["shared_expert_gate"] = _dense_init(next(keys), (L, h, 1), pd, s)
    return p


def _mtp_params(keys, cfg: TransformerConfig, D: int, pd) -> Params:
    """The multi-token-prediction modules, stacked over their depth like a
    segment of layers: the two input norms, the projection of
    [embedding ; hidden] back to hidden, one decoder layer of the model's last
    kind, and the norm before the (shared) head. Checkpoint names:
    ``model.layers.{L + d}.{enorm,hnorm,eh_proj,shared_head.norm,...}``."""
    h = cfg.hidden_size
    return {
        "enorm": jnp.ones((D, h), pd),
        "hnorm": jnp.ones((D, h), pd),
        "eh_proj": _dense_init(next(keys), (D, 2 * h, h), pd, cfg.initializer_range),
        **_attn_params(keys, cfg, D, pd),
        **(_moe_params(keys, cfg, D, pd) if cfg.is_moe
           else _dense_mlp_params(keys, cfg, D, pd)),
        "norm": jnp.ones((D, h), pd),
    }


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Random init with HF-compatible structure (stacked layer dim first).

    With ``first_k_dense_replace`` (deepseek), the leading dense layers live
    in a separate stacked subtree ``dense_layers`` so both segments scan
    homogeneously; with ``num_nextn_predict_layers`` the MTP modules are a
    third stacked subtree, ``mtp``.
    """
    h = cfg.hidden_size
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 64))
    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0

    params: Params = {
        "embed_tokens": _dense_init(next(keys), (cfg.vocab_size, h), pd, cfg.initializer_range),
        "norm": jnp.ones((h,), pd),
    }
    if k_dense:
        params["dense_layers"] = {
            **_attn_params(keys, cfg, k_dense, pd),
            **_dense_mlp_params(keys, cfg, k_dense, pd),
        }
    main_L = L - k_dense
    params["layers"] = {
        **_attn_params(keys, cfg, main_L, pd),
        **(_moe_params(keys, cfg, main_L, pd) if cfg.is_moe
           else _dense_mlp_params(keys, cfg, main_L, pd)),
    }
    if cfg.num_nextn_predict_layers:
        params["mtp"] = _mtp_params(keys, cfg, cfg.num_nextn_predict_layers, pd)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _dense_init(
            next(keys), (h, cfg.vocab_size), pd, cfg.initializer_range
        )
    return params


def abstract_params(cfg: TransformerConfig) -> Params:
    """Shape/dtype tree without allocation (for sharding resolution/loading)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------
def gated_act(gate, up, cfg: TransformerConfig):
    """Gated-MLP activation dialects."""
    if cfg.hidden_act == "gpt_oss_glu":
        # gpt_oss: clamped glu with alpha=1.702 and (up + 1) gating
        limit = 7.0
        gate = jnp.clip(gate, max=limit)
        up = jnp.clip(up, min=-limit, max=limit)
        glu = gate * jax.nn.sigmoid(gate * 1.702)
        return (up + 1.0) * glu
    if cfg.hidden_act in ("gelu_pytorch_tanh", "gelu"):
        return jax.nn.gelu(gate, approximate=cfg.hidden_act != "gelu") * up
    return ops.swiglu(gate, up)


def route_tokens(x, lp, cfg: TransformerConfig):
    """Router dialects -> (topk_idx [T,K], topk_weights [T,K], aux_loss).

    softmax (llama4/qwen-moe lineage): softmax -> topk (-> renorm).
    sigmoid (deepseek_v3 noaux-tc): sigmoid scores + correction bias,
    group-limited top-k, weights from raw scores, routed scaling.
    """
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    router_logits = jnp.dot(x, lp["router"], preferred_element_type=jnp.float32)
    if cfg.router_bias:
        router_logits = router_logits + lp["router_bias"].astype(jnp.float32)
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(router_logits)
        choice = scores + lp["e_score_correction_bias"].astype(jnp.float32)
        if cfg.n_group and cfg.topk_group and cfg.n_group > 1:
            t = x.shape[0]
            grouped = choice.reshape(t, cfg.n_group, e // cfg.n_group)
            group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [T, n_group]
            _, top_groups = jax.lax.top_k(group_scores, cfg.topk_group)
            group_mask = jnp.zeros_like(group_scores).at[
                jnp.arange(t)[:, None], top_groups
            ].set(1.0)
            choice = jnp.where(
                jnp.repeat(group_mask, e // cfg.n_group, axis=1) > 0, choice, -jnp.inf
            )
        _, topk_idx = jax.lax.top_k(choice, k)
        topk_w = jnp.take_along_axis(scores, topk_idx, axis=-1)
        if cfg.norm_topk_prob:
            topk_w = topk_w / (topk_w.sum(-1, keepdims=True) + 1e-20)
        topk_w = topk_w * cfg.routed_scaling_factor
        aux = ops.load_balancing_loss(scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-20),
                                      topk_idx, e)
        return topk_idx, topk_w, aux
    probs = jax.nn.softmax(router_logits, axis=-1)
    if cfg.model_type == "gpt_oss":
        # gpt_oss: topk on logits, softmax over the selected k
        topk_logits, topk_idx = jax.lax.top_k(router_logits, k)
        topk_w = jax.nn.softmax(topk_logits, axis=-1)
    else:
        choice = probs
        if cfg.n_group and cfg.topk_group and cfg.n_group > 1:
            # deepseek_v2 group_limited_greedy: keep topk_group groups by max
            t = x.shape[0]
            grouped = choice.reshape(t, cfg.n_group, e // cfg.n_group)
            group_scores = grouped.max(-1)
            _, top_groups = jax.lax.top_k(group_scores, cfg.topk_group)
            group_mask = jnp.zeros_like(group_scores).at[
                jnp.arange(t)[:, None], top_groups
            ].set(1.0)
            choice = jnp.where(
                jnp.repeat(group_mask, e // cfg.n_group, axis=1) > 0, choice, 0.0
            )
        topk_w, topk_idx = jax.lax.top_k(choice, k)
        if cfg.norm_topk_prob:
            topk_w = topk_w / jnp.clip(topk_w.sum(-1, keepdims=True), 1e-9)
        if cfg.routed_scaling_factor != 1.0:
            topk_w = topk_w * cfg.routed_scaling_factor
    aux = ops.load_balancing_loss(probs, topk_idx, e)
    return topk_idx, topk_w, aux


def _expert_bias(experts: Params, name: str, expert_of_row):
    if name in experts:
        return experts[name][expert_of_row]
    return 0.0


def experts_apply_sorted(xs, experts: Params, group_sizes, expert_of_row, cfg):
    """Grouped-GEMM expert MLP on expert-sorted tokens (shared by the local
    and EP-dispatch paths)."""
    gate = ops.group_gemm(xs, experts["gate_proj"], group_sizes)
    up = ops.group_gemm(xs, experts["up_proj"], group_sizes)
    gate = gate + _expert_bias(experts, "gate_bias", expert_of_row)
    up = up + _expert_bias(experts, "up_bias", expert_of_row)
    act = gated_act(gate, up, cfg).astype(xs.dtype)
    out = ops.group_gemm(act, experts["down_proj"], group_sizes)
    return out + _expert_bias(experts, "down_bias", expert_of_row)


def _shared_experts_out(x, lp, cfg):
    se = lp["shared_experts"]
    out = jnp.dot(gated_act(jnp.dot(x, se["gate_proj"]), jnp.dot(x, se["up_proj"]), cfg),
                  se["down_proj"])
    if "shared_expert_gate" in lp:
        out = out * jax.nn.sigmoid(jnp.dot(x, lp["shared_expert_gate"]))
    return out


# set by utils/moe_monitor.capture_routing to collect per-layer expert
# choices during an eager (non-jit) replay forward
ROUTER_CAPTURE: Optional[list] = None


def held_rows(cfg: TransformerConfig, t: int) -> int:
    """Rows of the expert-sorted buffer when the layer holds a share of the
    experts: this rank's capacity, ``cfg.moe_capacity_factor`` times what an
    even routing would send it (the factor's one meaning, here as in
    ``parallel/moe.py``), in whole 128-row tiles (the grouped GEMM's); every
    assignment (``t * k``) where the factor is <= 0 (dropless) or gives more."""
    total = t * cfg.num_experts_per_tok
    if not cfg.moe_capacity_factor or cfg.moe_capacity_factor <= 0:
        return total
    rows = math.ceil(cfg.moe_capacity_factor * total * cfg.experts_held / cfg.num_experts)
    return min(-(-rows // 128) * 128, total)


def moe_mlp_with_stats(x, lp, cfg: TransformerConfig):
    """Single-device MoE: route -> sort by expert -> grouped GEMM -> unsort.
    x: [T, H]. (Reference eager MoE semantics per dialect.)

    Where the layer holds a share of the experts (``cfg.moe_experts_held``),
    routing is over all ``num_experts`` and only the assignments to held
    experts are gathered, sorted and multiplied: the layer gives its own
    experts' part of the result (what expert parallelism asks of one rank,
    without the exchange). Returns (out [T, H], aux, stats) with stats =
    (dropped held assignments, held assignments, the grouped GEMM's live tile
    visits and its row tiles x experts, the busiest held expert's rows over
    the mean)."""
    t, h = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n_held = cfg.experts_held
    with jax.named_scope("moe.route"):
        topk_idx, topk_w, aux = route_tokens(x, lp, cfg)
        if ROUTER_CAPTURE is not None:
            ROUTER_CAPTURE.append(jax.lax.stop_gradient(topk_idx))
        topk_w = topk_w.astype(x.dtype)

    with jax.named_scope("moe.dispatch"):
        flat_expert = topk_idx.reshape(-1)  # [T*K]
        if n_held == e:
            sort_idx = jnp.argsort(flat_expert)  # stable
            expert_of_row = flat_expert[sort_idx]
            counts = group_sizes = jnp.bincount(flat_expert, length=e)
            dropped = jnp.float32(0.0)
        else:
            local = flat_expert - cfg.moe_experts_held_first
            mine = (local >= 0) & (local < n_held)
            counts = jnp.bincount(jnp.where(mine, local, n_held), length=n_held + 1)[:n_held]
            # the rank's capacity fills in the order the positions come (as
            # parallel/moe.py's does): a held assignment past it is dropped
            rows = held_rows(cfg, t)
            kept = mine & (jnp.cumsum(mine) <= rows)
            # what is not kept sorts last, and past the buffer
            local = jnp.where(kept, local, n_held)
            sort_idx = jnp.argsort(local)[:rows]  # stable
            expert_of_row = local[sort_idx]
            group_sizes = jnp.bincount(local, length=n_held + 1)[:n_held]
            dropped = (counts.sum() - group_sizes.sum()).astype(jnp.float32)
        token_idx = sort_idx // k
        xs = x[token_idx]  # [rows, H] sorted by expert
        held = counts.sum().astype(jnp.float32)
        load = counts.max() * (counts.shape[0] / jnp.maximum(held, 1.0))
        visits, pairs = tile_census(
            group_sizes, xs.shape[0], *lp["experts"]["gate_proj"].shape[1:], xs.dtype)
    with jax.named_scope("moe.experts"):
        out = experts_apply_sorted(xs, lp["experts"], group_sizes, expert_of_row, cfg)

    with jax.named_scope("moe.combine"):
        weight = topk_w.reshape(-1)[sort_idx][:, None]
        if n_held != e:
            # rows past the last group belong to no held expert
            keep = (jnp.arange(out.shape[0]) < group_sizes.sum())[:, None]
            out, weight = jnp.where(keep, out, 0), jnp.where(keep, weight, 0)
        combined = jnp.zeros((t, h), out.dtype).at[token_idx].add(out * weight)
        if cfg.n_shared_experts or cfg.shared_expert_intermediate_size:
            combined = combined + _shared_experts_out(x, lp, cfg)
    return combined, aux, (dropped, held, visits, pairs, load)


def _moe_mlp(x, lp, cfg: TransformerConfig):
    out, aux, _ = moe_mlp_with_stats(x, lp, cfg)
    return out, aux


def _activation_constraint():
    """Pin [B,S,H] activations to (dp, sp, None) so GSPMD keeps FSDP
    semantics (gather weights, never reshard activations onto fsdp axes).
    No-op when no ParallelState is active (pure single-device use)."""
    from veomni_tpu.parallel.parallel_state import get_parallel_state_or_none

    ps = get_parallel_state_or_none()
    if ps is None:
        return lambda x: x
    sharding = ps.sharding(ps.dp_axes, ps.sp_axes, None)
    return lambda x: jax.lax.with_sharding_constraint(x, sharding)


def _norm(x, w, cfg: TransformerConfig):
    return ops.rms_norm(x, w, cfg.rms_norm_eps, zero_centered=cfg.norm_zero_centered)


def _standard_attention(x, lp, cfg: TransformerConfig, cos, sin, segment_ids, window, sinks):
    b, s, _ = x.shape
    with jax.named_scope("attn.qkv"):
        q = jnp.dot(x, lp["q_proj"])
        kk = jnp.dot(x, lp["k_proj"])
        v = jnp.dot(x, lp["v_proj"])
        if cfg.attention_bias:
            q = q + lp["q_bias"]
            kk = kk + lp["k_bias"]
            v = v + lp["v_bias"]
        # the q/k norm and rope in one op, from the projections' own outputs
        # to the [B, S, H, D] the attention op takes
        q, kk = ops.qk_norm_rotary(
            q, kk, cos, sin,
            lp["q_norm"] if cfg.qk_norm else None, lp["k_norm"] if cfg.qk_norm else None,
            eps=cfg.rms_norm_eps, zero_centered=cfg.norm_zero_centered, head_dim=cfg.head_dim,
        )
        v = v.reshape(b, s, cfg.num_key_value_heads, cfg.head_dim)
    scale = (
        cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar
        else cfg.head_dim ** -0.5
    )
    with jax.named_scope("attn.flash"):
        attn = ops.attention(
            q, kk, v, segment_ids=segment_ids, causal=True,
            softmax_scale=scale, sliding_window=window, sinks=sinks,
            # 0 = defer to registry/env; >=1 forces a path (see models/config.py)
            ulysses_async_chunks=cfg.ulysses_async_chunks or None,
        )
    attn = checkpoint_name(attn, "attn_ctx")
    with jax.named_scope("attn.out"):
        out = jnp.dot(attn.reshape(b, s, cfg.q_dim), lp["o_proj"])
        if "o_bias" in lp:
            out = out + lp["o_bias"]
    return out


def mla_rope_interleaved(cfg: TransformerConfig) -> bool:
    """Does MLA rotate adjacent pairs (x[2i], x[2i+1]) by the i-th frequency
    (deepseek's ``rope_interleave``)? The tables then repeat every frequency
    twice, side by side, which is the layout ``apply_rotary(interleaved=True)``
    multiplies with. (With the half-rotation tables the two members of a pair
    were turned by two different angles: nothing at seeded weights showed it
    until a reference with the published rope was held against the gradients.)"""
    return bool(cfg.use_mla and cfg.rope_interleave)


def _dsa_bias(x, lp, cfg: TransformerConfig, cos, sin, segment_ids):
    """DSA lightning-indexer top-k KEEP mask [B,S,S] bool (glm_moe_dsa;
    reference ``GlmMoeDsaIndexer`` at ``glm_moe_dsa/generated/...:123``).

    The indexer runs no-grad (``@torch.no_grad`` upstream): token selection
    is non-differentiable and its params train separately. Rope on the
    leading ``qk_rope_head_dim`` channels, NON-interleaved (NeoX) regardless
    of the main attention's interleave."""
    b, s, _ = x.shape
    inh, ihd, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    idx = lp["indexer"]
    q_resid = _norm(jnp.dot(x, lp["q_a_proj"]), lp["q_a_layernorm"], cfg)
    q = jnp.dot(q_resid, idx["wq_b"]).reshape(b, s, inh, ihd)
    k = jnp.dot(x, idx["wk"])
    kf = k.astype(jnp.float32)
    kf = (kf - kf.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        kf.var(-1, keepdims=True) + 1e-6
    )
    k = (kf * idx["k_norm_w"] + idx["k_norm_b"]).astype(x.dtype)
    if mla_rope_interleaved(cfg):
        # the indexer's half-rotation wants each frequency once per half
        cos, sin = (jnp.concatenate([t[..., ::2], t[..., ::2]], axis=-1) for t in (cos, sin))
    q_pe, k_pe = ops.apply_rotary(
        q[..., :dr], k[..., :dr].reshape(b, s, 1, dr), cos, sin, interleaved=False
    )
    q = jnp.concatenate([q_pe, q[..., dr:]], axis=-1)
    k = jnp.concatenate([k_pe[:, :, 0], k[..., dr:]], axis=-1)
    scores = jax.nn.relu(
        jnp.einsum("bshd,btd->bsht", q.astype(jnp.float32), k.astype(jnp.float32))
    ) * (ihd ** -0.5)
    w = jnp.dot(x, idx["weights_proj"]).astype(jnp.float32) * (inh ** -0.5)
    index_scores = jnp.einsum("bsht,bsh->bst", scores, w)

    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    allowed = (ki <= qi)[None]
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    index_scores = jnp.where(allowed, index_scores, -jnp.inf)
    top_k = min(cfg.index_topk, s)
    kth = jax.lax.top_k(index_scores, top_k)[0][..., -1:]
    # boolean keep mask (NOT an additive bias): 4x smaller as a scan carry
    # and consumable by the chunked attention's mask_mod hook at long S
    return jax.lax.stop_gradient((index_scores >= kth) & allowed)


def _mla_attention(x, lp, cfg: TransformerConfig, cos, sin, segment_ids, window,
                   dsa_bias=None):
    """DeepSeek MLA (training form): materialize per-head k/v from the
    low-rank kv latent; rope applies to the shared rope-part only.
    (Reference: deepseek_v3 generated modeling.) With ``dsa_bias`` the
    top-k-sparse selection applies as an additive mask on the dense XLA
    path — the TPU fallback for the reference's flashmla_cudnn kernel."""
    b, s, _ = x.shape
    nh = cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    with jax.named_scope("attn.qkv"):
        if cfg.q_lora_rank:
            q = jnp.dot(_norm(jnp.dot(x, lp["q_a_proj"]), lp["q_a_layernorm"], cfg),
                        lp["q_b_proj"])
        else:
            q = jnp.dot(x, lp["q_proj"])
        kv_a = jnp.dot(x, lp["kv_a_proj_with_mqa"])  # [B,S, kvlr + dr]
        c_kv, k_rope = kv_a[..., : cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
        kv = jnp.dot(_norm(c_kv, lp["kv_a_layernorm"], cfg), lp["kv_b_proj"])
        # the splits, rope on the rope lanes, k_rope to every head: one op
        q, k, v = ops.mla_qkv_rotary(q, kv, k_rope, cos, sin, dn, dr, dv,
                                     interleaved=cfg.rope_interleave)
    from veomni_tpu.ops.rotary import yarn_attention_factor

    scale = (dn + dr) ** -0.5 * yarn_attention_factor(cfg.rope_scaling, dr)
    if dsa_bias is not None:
        from veomni_tpu.ops.attention import _attention_xla
        from veomni_tpu.parallel.parallel_state import get_parallel_state_or_none

        ps = get_parallel_state_or_none()
        if ps is not None and (ps.ulysses_size > 1 or ps.cp_size > 1):
            raise NotImplementedError(
                "DSA sparse attention under ulysses/ring SP: gather-based "
                "mask plumbing is a follow-up; run DSA models with sp=1"
            )
        # the boolean keep mask rides the mask_mod hook, so long sequences
        # take the blockwise online-softmax path instead of materializing
        # a dense [B,H,S,S] score tensor
        with jax.named_scope("attn.flash"):
            attn = _attention_xla(
                q, k, v, segment_ids=segment_ids, causal=True,
                softmax_scale=scale, sliding_window=window,
                mask_mod=lambda qi, ki: dsa_bias[:, qi, ki],
            )
    else:
        with jax.named_scope("attn.flash"):
            attn = ops.attention(
                q, k, v, segment_ids=segment_ids, causal=True,
                softmax_scale=scale, sliding_window=window,
                ulysses_async_chunks=cfg.ulysses_async_chunks or None,
            )
    attn = checkpoint_name(attn, "attn_ctx")
    with jax.named_scope("attn.out"):
        return jnp.dot(attn.reshape(b, s, nh * dv), lp["o_proj"])


def feed_forward(hidden, lp, cfg: TransformerConfig, is_moe: bool):
    """The second half of a decoder layer: ``hidden + mlp(norm(hidden))``,
    dense or sparse, whatever mixer came before it (``models/kimi_linear.py``
    puts it behind its two). Returns (hidden, stats ``[6]``: aux, then the
    five of :func:`moe_mlp_with_stats`, zeros for a dense MLP)."""
    b, s, h = hidden.shape
    constrain = _activation_constraint()
    hidden = constrain(hidden)
    pre_norm = (
        lp["pre_feedforward_layernorm"] if cfg.sandwich_norms
        else lp["post_attention_layernorm"]
    )
    # the feed-forward's own norm and residual add go with its first and
    # last stage: "mlp" when dense, "moe.route" / "moe.combine" when sparse
    with jax.named_scope("moe.route" if is_moe else "mlp"):
        x = _norm(hidden, pre_norm, cfg)
    # (dropped assignments, held assignments, the grouped GEMM's tile visits
    # and tile pairs, busiest expert's rows over the mean)
    moe_stats = (jnp.float32(0.0),) * 5
    if is_moe:
        from veomni_tpu.parallel.parallel_state import get_parallel_state_or_none

        ps = get_parallel_state_or_none()
        if ps is not None and ps.ep_enabled:
            from veomni_tpu.parallel.moe import ep_moe_mlp

            if cfg.experts_held != cfg.num_experts:
                raise ValueError(
                    "moe_experts_held describes one chip's share without the "
                    "exchange; with expert_parallel_size > 1 the mesh holds "
                    "every expert: leave it at 0")
            out, aux, dropped, load = ep_moe_mlp(x, lp, cfg, ps, with_load=True)
            assigned = jnp.float32(b * s * cfg.num_experts_per_tok)
            # the mesh holds them all; the dispatch's own kernels are not counted
            moe_stats = (dropped * assigned, assigned, 0.0, 0.0, load)
        else:
            out, aux, moe_stats = moe_mlp_with_stats(x.reshape(b * s, h), lp, cfg)
            out = out.reshape(b, s, h)
    else:

        def dense_mlp(xc):
            gate = jnp.dot(xc, lp["gate_proj"])
            up = jnp.dot(xc, lp["up_proj"])
            if cfg.mlp_bias:
                gate = gate + lp["gate_bias"]
                up = up + lp["up_bias"]
            o = jnp.dot(gated_act(gate, up, cfg), lp["down_proj"])
            if cfg.mlp_bias:
                o = o + lp["down_bias"]
            return o

        c = cfg.chunk_mbs
        if c and s > c and s % c:
            # round down to the largest divisor of s so chunking engages
            # instead of silently no-op'ing on non-multiple lengths
            c = next((d for d in range(c, 1, -1) if s % d == 0), 0)
        with jax.named_scope("mlp"):
            if c and 1 < c < s:
                # ChunkMBS (reference chunk_mbs.py:145): bound the [B,S,inter]
                # intermediate to [B,c,inter]; lax.map serializes the chunks and
                # jax.checkpoint keeps the bwd recompute chunked too.
                xs = jnp.moveaxis(x.reshape(b, s // c, c, h), 1, 0)
                out = jax.lax.map(jax.checkpoint(dense_mlp), xs)
                out = jnp.moveaxis(out, 0, 1).reshape(b, s, h)
            else:
                out = dense_mlp(x)
        aux = jnp.float32(0.0)
    with jax.named_scope("moe.combine" if is_moe else "mlp"):
        if cfg.sandwich_norms:
            out = _norm(out, lp["post_feedforward_layernorm"], cfg)
        hidden = constrain(hidden + out)
    stats = jnp.stack([aux, *moe_stats]).astype(jnp.float32)
    return hidden, stats


def _decoder_layer(
    hidden, lp, dsa_prev=None, dsa_shared=None, *, cfg: TransformerConfig,
    cos, sin, segment_ids, window=None, is_moe_segment=None,
):
    is_moe = cfg.is_moe if is_moe_segment is None else is_moe_segment
    constrain = _activation_constraint()
    hidden = constrain(hidden)
    with jax.named_scope("attn.qkv"):
        x = _norm(hidden, lp["input_layernorm"], cfg)
    dsa_bias = None
    if cfg.use_dsa:
        # "shared" layers reuse the previous layer's top-k selection
        # (reference skip_topk, arXiv:2603.12201); lax.cond skips the
        # indexer compute at runtime on those layers. The [B,S,S] carry only
        # exists when the config actually has shared layers.
        if dsa_shared is None:
            dsa_bias = _dsa_bias(x, lp, cfg, cos, sin, segment_ids)
        else:
            dsa_bias = jax.lax.cond(
                dsa_shared,
                lambda: dsa_prev,
                lambda: _dsa_bias(x, lp, cfg, cos, sin, segment_ids),
            )
    if cfg.use_mla:
        attn_out = _mla_attention(x, lp, cfg, cos, sin, segment_ids, window,
                                  dsa_bias=dsa_bias)
    else:
        attn_out = _standard_attention(
            x, lp, cfg, cos, sin, segment_ids, window, lp.get("sinks")
        )
    with jax.named_scope("attn.out"):
        if cfg.sandwich_norms:
            attn_out = _norm(attn_out, lp["post_attention_layernorm"], cfg)
        hidden = hidden + attn_out

    hidden, stats = feed_forward(hidden, lp, cfg, is_moe)
    if dsa_prev is not None:  # carry mode (configs with "shared" layers)
        return hidden, stats, dsa_bias
    return hidden, stats


def _add_layer_stats(total, stats):
    """Fold layers' ``[n, 6]`` stats (aux, dropped, held, tile visits, tile
    pairs, load) into the running ``[6]``: sums, and the largest load."""
    with jax.named_scope("moe.route"):
        return jnp.concatenate([total[:5] + stats[:, :5].sum(0),
                                jnp.maximum(total[5:], stats[:, 5:].max(0))])


def forward_hidden(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,          # [B,S] int32
    position_ids: jax.Array,       # [B,S] int32
    segment_ids: Optional[jax.Array] = None,  # [B,S] int32
    inputs_embeds: Optional[jax.Array] = None,  # [B,S,H] overrides embedding
    post_layer_residuals: Optional[jax.Array] = None,  # [K,B,S,H]
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (final_hidden [B,S,H] in cfg.dtype, moe_aux_loss scalar,
    moe_dropped_frac scalar — the share of the held assignments a capacity
    bound dropped, 0 when dropless). See :func:`forward_layers`."""
    out = forward_layers(params, cfg, input_ids, position_ids, segment_ids,
                         inputs_embeds, post_layer_residuals)
    return out["hidden"], out["moe_aux"], out["moe_dropped_frac"]


def forward_layers(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,
    position_ids: jax.Array,
    segment_ids: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    post_layer_residuals: Optional[jax.Array] = None,
    with_mtp: bool = False,
) -> Dict[str, Any]:
    """The decoder stack. Returns ``hidden`` (final normed [B,S,H] in
    cfg.dtype), ``moe_aux``, ``moe_dropped_frac`` (dropped over held) and, for
    a MoE model, ``moe_assignment_counts`` (a vector, so that the train step
    SUMS it over micro-steps: token-expert assignments routed, those to
    experts this model holds, those of them a rank capacity dropped, and the
    grouped GEMM's live tile visits and tile pairs, summed over the MoE
    layers), ``moe_load_max_over_mean`` (largest over the layers); with
    ``with_mtp``, ``mtp_hidden``: one final normed hidden per multi-token-
    prediction module (:func:`_mtp_hidden`), whose MoE layers count too.

    ``inputs_embeds`` lets composite models (VLM/omni) inject merged
    multimodal embeddings while sharing the decoder stack.

    ``post_layer_residuals``: deepstack-style injection (qwen3-vl,
    reference ``qwen3_vl/generated/patched_modeling_qwen3_vl_gpu.py:1481``
    ``_deepstack_process``) — residual ``[i]`` is added to the hidden state
    after decoder layer ``i`` for the first K layers (already scattered to
    sequence positions; zeros elsewhere)."""
    compute = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
    if inputs_embeds is not None:
        hidden = inputs_embeds.astype(cfg.dtype)
    else:
        with jax.named_scope("embed"):
            hidden = compute["embed_tokens"][input_ids]
            if cfg.embed_scale:
                hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)

    rope_dim = (
        cfg.qk_rope_head_dim if cfg.use_mla
        else int(cfg.head_dim * cfg.partial_rotary_factor)
    )
    cos_g, sin_g = ops.rotary_tables(
        position_ids, rope_dim, cfg.rope_theta, rope_scaling=cfg.rope_scaling,
        interleaved=mla_rope_interleaved(cfg),
    )
    cos_g, sin_g = cos_g.astype(cfg.dtype), sin_g.astype(cfg.dtype)
    dual_rope = bool(cfg.rope_local_base_freq)
    if dual_rope:
        cos_l, sin_l = ops.rotary_tables(position_ids, rope_dim, cfg.rope_local_base_freq)
        cos_l, sin_l = cos_l.astype(cfg.dtype), sin_l.astype(cfg.dtype)

    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0

    def run_segment(hidden, layer_tree, offset, count, is_moe_seg, dsa_carry):
        """Scan consecutive layers; *static* per-run window/rope signature so
        full-attention layers keep the flash-kernel fast path (per-layer
        patterns like gemma3's 5:1 sliding:full become a few short scans)."""
        sigs = [
            (cfg.window_for_layer(offset + i),
             dual_rope and cfg.window_for_layer(offset + i) > 0)
            for i in range(count)
        ]
        runs = []  # (start, n, window, local_rope)
        for i, sig in enumerate(sigs):
            if runs and (runs[-1][2], runs[-1][3]) == sig:
                runs[-1][1] += 1
            else:
                runs.append([i, 1, *sig])

        total = jnp.zeros((6,), jnp.float32)
        for start, n, window, local in runs:
            sub = (
                layer_tree if n == count
                else jax.tree.map(lambda t: t[start:start + n], layer_tree)
            )
            cos, sin = (cos_l, sin_l) if local else (cos_g, sin_g)
            body = partial(
                _decoder_layer, cfg=cfg, cos=cos, sin=sin,
                segment_ids=segment_ids, window=window or None,
                is_moe_segment=is_moe_seg,
            )
            if cfg.remat:
                body = jax.checkpoint(body, policy=_remat_policy(cfg))
            if dsa_carry is not None:
                flags = jnp.asarray([
                    cfg.indexer_types[offset + start + i] == "shared"
                    for i in range(n)
                ])

                def scan_body(carry, xs_):
                    lp, fl = xs_
                    h2, stats, new_bias = body(carry[0], lp, carry[1], fl)
                    return (h2, new_bias), stats

                (hidden, dsa_carry), stats = jax.lax.scan(
                    scan_body, (hidden, dsa_carry), (sub, flags)
                )
            else:
                hidden, stats = jax.lax.scan(
                    lambda c, lp: body(c, lp), hidden, sub
                )
            if is_moe_seg:  # a dense segment's are zeros: nothing to fold
                total = _add_layer_stats(total, stats)
        return hidden, total, dsa_carry

    stats_total = jnp.zeros((6,), jnp.float32)
    K_inject = 0 if post_layer_residuals is None else post_layer_residuals.shape[0]
    # DSA "shared" layers reuse the previous layer's selection; the [B,S,S]
    # carry (threaded across run/segment boundaries, zeros before the first
    # indexer) only exists when the config actually has shared layers —
    # all-"full" DSA configs keep the plain scan
    if cfg.use_dsa and tuple(cfg.indexer_types or ())[:1] == ("shared",):
        raise ValueError(
            "indexer_types[0] == 'shared' has no provider layer — the "
            "first DSA layer would silently reuse an all-pass mask"
        )
    dsa_carry = (
        jnp.zeros((hidden.shape[0], hidden.shape[1], hidden.shape[1]), bool)
        if cfg.use_dsa and "shared" in tuple(cfg.indexer_types or ())
        else None
    )

    segments = []
    if k_dense:
        segments.append(("dense_layers", 0, k_dense, False))
    segments.append(("layers", k_dense, L - k_dense, cfg.is_moe))
    for name, offset, count, is_moe_seg in segments:
        tree = compute[name]
        start = 0
        while start < count:
            g = offset + start  # global layer index
            n = 1 if g < K_inject else count - start
            sub = (
                tree if (start == 0 and n == count)
                else jax.tree.map(lambda t: t[start:start + n], tree)
            )
            hidden, stats, dsa_carry = run_segment(
                hidden, sub, g, n, is_moe_seg, dsa_carry
            )
            if is_moe_seg:
                stats_total = _add_layer_stats(stats_total, stats[None])
            if g < K_inject:
                hidden = hidden + post_layer_residuals[g].astype(hidden.dtype)
            start += n
    n_moe = (L - k_dense) if cfg.is_moe else 0
    out: Dict[str, Any] = {}
    if with_mtp and cfg.num_nextn_predict_layers:
        if inputs_embeds is not None:
            raise NotImplementedError("multi-token prediction over inputs_embeds")
        with jax.named_scope("mtp"):
            out["mtp_hidden"], stats = _mtp_hidden(
                compute, cfg, hidden, input_ids, cos_g, sin_g, segment_ids)
        if cfg.is_moe:
            stats_total = _add_layer_stats(stats_total, stats)
            n_moe += cfg.num_nextn_predict_layers
    with jax.named_scope("lm_head_loss"):
        hidden = _norm(hidden, compute["norm"], cfg)
    out.update(
        hidden=hidden, moe_aux=stats_total[0],
        # dropped over held assignments, all MoE layers together (diagnostic)
        moe_dropped_frac=stats_total[1] / jnp.maximum(stats_total[2], 1.0),
    )
    if cfg.is_moe:
        routed = n_moe * hidden.shape[0] * hidden.shape[1] * cfg.num_experts_per_tok
        with jax.named_scope("moe.route"):
            out["moe_assignment_counts"] = jnp.stack(
                [jnp.float32(routed), stats_total[2], stats_total[1], *stats_total[3:5]])
        out["moe_load_max_over_mean"] = stats_total[5]
    return out


def _shift_left(x, n: int, fill):
    """``x [B, S]`` moved ``n`` positions towards the row's start."""
    return jnp.concatenate([x[:, n:], jnp.full_like(x[:, :n], fill)], axis=1)


def _mtp_hidden(compute: Params, cfg: TransformerConfig, hidden, input_ids, cos, sin,
                segment_ids):
    """DeepSeek-V3's multi-token prediction, module after module: at depth
    ``d`` position ``i`` joins the embedding of token ``i + d`` with the
    representation below it, ``h' = eh_proj [enorm(emb) ; hnorm(h)]`` (the
    order of the released checkpoints' ``eh_proj``), runs one decoder layer
    over the row (the row's own segment ids and rope) and norms the result for
    the model's head. ``hidden`` is the last layer's output before the final
    norm. Returns ([one normed hidden per module], the layers' stats [D, 6])."""
    mp = compute["mtp"]
    body = partial(_decoder_layer, cfg=cfg, cos=cos, sin=sin, segment_ids=segment_ids,
                   window=None, is_moe_segment=cfg.is_moe)
    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    normed, stats = [], []
    for d in range(cfg.num_nextn_predict_layers):
        lp = jax.tree.map(lambda t: t[d], mp)
        emb = compute["embed_tokens"][_shift_left(input_ids, d + 1, 0)]
        if cfg.embed_scale:
            emb = emb * jnp.asarray(cfg.embed_scale, cfg.dtype)
        joined = jnp.concatenate(
            [_norm(emb, lp["enorm"], cfg), _norm(hidden, lp["hnorm"], cfg)], axis=-1)
        hidden, st = body(jnp.dot(joined, lp["eh_proj"]), lp)
        normed.append(_norm(hidden, lp["norm"], cfg))
        stats.append(st)
    return normed, jnp.stack(stats)


def mtp_labels(labels, segment_ids, depth: int):
    """Labels of the MTP module at ``depth`` (from 1): position ``i`` predicts
    token ``i + depth + 1``, which ``labels`` (pre-shifted: ``labels[i]`` is
    token ``i + 1``) holds at ``i + depth``. In a packed row it counts only
    where that token lies in ``i``'s own document (then every token between
    does too)."""
    out = _shift_left(labels, depth, -100)
    if segment_ids is not None:
        same = _shift_left(segment_ids, depth + 1, 0) == segment_ids
        out = jnp.where(same & (segment_ids > 0), out, -100)
    return out


def lm_head_kernel(params: Params, cfg: TransformerConfig):
    if cfg.tie_word_embeddings:
        return params["embed_tokens"].T
    return params["lm_head"]


def forward_logits(params, cfg, input_ids, position_ids, segment_ids=None):
    hidden, _, _ = forward_hidden(params, cfg, input_ids, position_ids, segment_ids)
    kernel = lm_head_kernel(params, cfg).astype(cfg.dtype)
    logits = jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
    return logits


def sequence_logprob_sums(
    params: Params,
    cfg: TransformerConfig,
    batch: Dict[str, jax.Array],
) -> jax.Array:
    """Per-row sum of label log-probs [B] (the per-sample logit gather of the
    reference RL/DPO trainers, ``base_rl_trainer.py:15-113``)."""
    hidden, _, _ = forward_hidden(
        params, cfg, batch["input_ids"], batch["position_ids"], batch.get("segment_ids")
    )
    kernel = lm_head_kernel(params, cfg).astype(cfg.dtype)

    def row_nll(h_row, l_row):
        loss_sum, _ = ops.fused_linear_cross_entropy(h_row, kernel, l_row)
        return loss_sum

    nll = jax.vmap(row_nll)(hidden, batch["labels"])
    return -nll


def _head_ce(params: Params, cfg: TransformerConfig, hidden, labels):
    """(sum of the next-token NLL, count of predicting positions) of normed
    ``hidden [B,S,H]`` through the model's head."""
    b, s, h = hidden.shape
    with jax.named_scope("lm_head_loss"):
        kernel = lm_head_kernel(params, cfg).astype(cfg.dtype)
        return ops.fused_linear_cross_entropy(
            hidden.reshape(b * s, h), kernel, labels.reshape(b * s),
            logit_softcap=cfg.final_logit_softcap or None,
        )


def head_loss(
    params: Params, cfg: TransformerConfig, hidden: jax.Array, labels: jax.Array,
    moe_aux: jax.Array, moe_dropped: jax.Array = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """lm-head + CE in token-sum space, shared by text/VLM/omni loss fns."""
    loss_sum, ntokens = _head_ce(params, cfg, hidden, labels)
    metrics = {"loss_sum": loss_sum, "ntokens": ntokens, "moe_aux_loss": moe_aux}
    if moe_dropped is not None:
        metrics["moe_dropped_frac"] = moe_dropped
    total = loss_sum
    if cfg.is_moe and cfg.router_aux_loss_coef:
        # aux loss is per-token-mean-like already; scale by token count to stay
        # in sum space so dp/sp reduction normalizes both terms identically.
        total = total + cfg.router_aux_loss_coef * moe_aux * ntokens
    return total, metrics


def loss_fn(
    params: Params,
    cfg: TransformerConfig,
    batch: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sum-NLL + valid-token count (caller normalizes, possibly across dp/sp).

    batch: input_ids/position_ids/segment_ids [B,S], labels [B,S] pre-shifted
    with -100 padding (collator contract, reference data_collator.py:371-428).
    """
    out = forward_layers(
        params, cfg, batch["input_ids"], batch["position_ids"], batch.get("segment_ids"),
        with_mtp=True,
    )
    total, metrics = head_loss(
        params, cfg, out["hidden"], batch["labels"], out["moe_aux"], out["moe_dropped_frac"])
    if cfg.is_moe:
        # read by observability/callback.py (moe.assignments* counters, the gauge)
        metrics.update({k: out[k] for k in ("moe_assignment_counts", "moe_load_max_over_mean")})
    if "mtp_hidden" in out:
        # L = L_main + lambda * mean over depths of L_mtp (DeepSeek-V3, eq. 25),
        # each a mean over its own valid positions; in the step's token-sum
        # space that mean is scaled by the main loss's token count
        mtp_loss = jnp.float32(0.0)
        with jax.named_scope("mtp"):
            for d, hidden in enumerate(out["mtp_hidden"]):
                labels = mtp_labels(batch["labels"], batch.get("segment_ids"), d + 1)
                loss_d, n_d = _head_ce(params, cfg, hidden, labels)
                mtp_loss = mtp_loss + loss_d / jnp.maximum(n_d, 1)
        mtp_loss = mtp_loss / len(out["mtp_hidden"])
        metrics["mtp_loss"] = mtp_loss
        total = total + cfg.mtp_loss_weight * mtp_loss * metrics["ntokens"]
    return total, metrics
