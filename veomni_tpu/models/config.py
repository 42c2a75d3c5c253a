"""Model configuration: one dataclass covering the llama-family dialects.

Reference: per-model HF configs under ``veomni/models/transformers/<name>/``.
We keep HF *checkpoint/config* compatibility (``from_hf_config`` consumes an
HF config.json dict) while owning the modeling code (SURVEY.md §7.1: no
patchgen — native model zoo).

Dialect switches:
  llama:      defaults
  qwen2:      attention_bias=True (qkv bias)
  qwen3:      qk_norm=True, head_dim explicit
  qwen3_moe:  qk_norm=True + MoE fields (num_experts, top_k, norm_topk_prob)
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax.numpy as jnp


@dataclass
class TransformerConfig:
    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 0  # 0 -> hidden // heads
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None  # HF rope_scaling dict
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    o_bias: bool = False  # bias on o_proj too (gpt_oss; qwen2 has qkv only)
    # rope covers only the first head_dim*factor dims (glm4_moe: 0.5)
    partial_rotary_factor: float = 1.0
    mlp_bias: bool = False
    qk_norm: bool = False
    sliding_window: Optional[int] = None
    # per-layer attention pattern: list of "sliding_attention"/"full_attention"
    # (gemma3 / gpt_oss alternating local-global); None -> uniform
    layer_types: Optional[List[str]] = None
    rope_local_base_freq: float = 0.0  # gemma3: separate theta for sliding layers
    # activation / norms / scaling dialects
    hidden_act: str = "silu"            # silu | gelu_pytorch_tanh | gelu
    norm_zero_centered: bool = False    # gemma family: weight is (1 + w)
    sandwich_norms: bool = False        # gemma3 post-attn/pre+post-ffw norms
    embed_scale: float = 0.0            # gemma: sqrt(hidden); 0 = off
    final_logit_softcap: float = 0.0
    query_pre_attn_scalar: float = 0.0  # gemma3: softmax scale = qpas^-0.5
    attention_sinks: bool = False       # gpt_oss learned per-head sink logit
    router_bias: bool = False           # gpt_oss router linear has a bias
    # MLA (deepseek_v3): kv/q low-rank compression + rope/nope head split
    rope_interleave: bool = False  # deepseek pairwise rope layout
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DSA lightning indexer (glm_moe_dsa / DeepSeek-V3.2 sparse attention):
    # per-token top-k KV selection scored by a lightweight side network
    # (reference ``glm_moe_dsa/generated/...:123`` GlmMoeDsaIndexer)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0            # 0 -> DSA off
    indexer_types: Any = ()        # per-layer "full" | "shared" (reuse prev)
    # MoE (num_experts == 0 -> dense MLP)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    # deepseek routing dialect
    scoring_func: str = "softmax"       # softmax | sigmoid (w/ correction bias)
    routed_scaling_factor: float = 1.0
    n_group: int = 0                    # group-limited routing (noaux-tc)
    topk_group: int = 0
    n_shared_experts: int = 0
    # qwen2-moe / qwen3_next style shared expert: explicit intermediate size
    # (overrides moe_intermediate_size * n_shared_experts) + sigmoid gate
    shared_expert_intermediate_size: int = 0
    shared_expert_gated: bool = False
    first_k_dense_replace: int = 0      # leading dense layers (deepseek)
    # the chip's share of the routed experts (one expert-parallel rank's view
    # on a single chip): the router stays ``num_experts`` wide, the layer holds
    # and computes experts [first, first + held). 0 = all of them
    moe_experts_held: int = 0
    moe_experts_held_first: int = 0
    # multi-token prediction (deepseek_v3): modules after the last layer, each
    # one decoder layer predicting one token further; lambda of their loss
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # qwen3_next hybrid GatedDeltaNet (reference models/transformers/qwen3_5/,
    # ops/kernels/gated_delta_rule/): periodic linear-attention layers with a
    # full-attention layer every `full_attention_interval` layers
    linear_num_value_heads: int = 0     # 0 -> no linear-attention layers
    linear_num_key_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    full_attention_interval: int = 4
    attn_output_gate: bool = False      # full-attn layers: out *= sigmoid(gate)
    # granitemoehybrid (models/granite_hybrid.py): Mamba-2 state-space layers
    # and attention layers in the order ``layer_types`` gives ("mamba" |
    # "attention"), its period found from the list itself
    mamba_n_heads: int = 0              # 0 -> no state-space layers
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    position_embedding_type: str = "rope"  # "nope": attention with no rotary
    attention_multiplier: float = 0.0   # softmax scale; 0 -> head_dim ** -0.5
    residual_multiplier: float = 1.0    # h += multiplier * sublayer(norm(h))
    logits_scaling: float = 1.0         # logits = head(h) / logits_scaling
    # kimi_linear (models/kimi_linear.py): Kimi Delta Attention layers and MLA
    # layers where config.json's ``linear_attn_config`` says (its 1-based
    # ``kda_layers`` / ``full_attn_layers``, ``num_heads``, ``head_dim``,
    # ``short_conv_kernel_size``), kept as published
    linear_attn_config: Optional[Dict[str, Any]] = None
    mla_use_nope: bool = False          # MLA with no rotary on either part
    # EP dispatch capacity factor; <= 0 means dropless (see parallel/moe.py)
    moe_capacity_factor: float = 0.0
    # HF checkpoint expert-tensor layout: "" = auto by model_type
    # (gpt_oss -> fused_interleaved, else per_expert); "fused_chunked" is the
    # qwen3_vl_moe layout (gate_up_proj [E, H, 2I] with gate then up halves)
    expert_layout: str = ""
    # numerics
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.float32  # master param dtype
    remat: bool = True              # jax.checkpoint each decoder layer
    # remat policy: "nothing" (full recompute), "dots" (save matmul outputs),
    # "offload" (save dots to host memory — the TPU analogue of the
    # reference's CPU activation offload, distributed/offloading.py:74)
    remat_policy: str = "nothing"
    # ChunkMBS analogue (reference distributed/chunk_mbs.py:145): sequence
    # chunk length for the per-layer MLP compute. The [B, S, intermediate]
    # activation — the largest per-layer tensor at long context — is bounded
    # to [B, chunk_mbs, intermediate] by a lax.map over sequence chunks
    # (fwd AND the remat'd bwd recompute). 0 disables.
    chunk_mbs: int = 0
    # Ulysses SP a2a/compute overlap (parallel/async_ulysses.py): head-chunk
    # count for the chunked async pipeline. 0 = defer to the kernel-registry
    # pin / VEOMNI_ULYSSES_ASYNC env; 1 = force monolithic; >= 2 = pipeline
    # with that many chunks (clamped to the head layout's feasible maximum).
    ulysses_async_chunks: int = 0
    initializer_range: float = 0.02

    def __post_init__(self):
        if not self.head_dim:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if isinstance(self.dtype, str):
            self.dtype = getattr(jnp, self.dtype)
        if isinstance(self.param_dtype, str):
            self.param_dtype = getattr(jnp, self.param_dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this model holds."""
        return self.moe_experts_held or self.num_experts

    @property
    def use_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def use_dsa(self) -> bool:
        return self.index_topk > 0

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def qk_head_dim(self) -> int:
        """MLA query/key head dim (nope + rope parts)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def window_for_layer(self, i: int) -> int:
        """Per-layer sliding window (0 = full attention)."""
        if self.layer_types is not None:
            sliding = self.layer_types[i] == "sliding_attention"
        else:
            sliding = self.sliding_window is not None
        return int(self.sliding_window or 0) if sliding else 0


    # ------------------------------------------------------------------ HF io
    _HF_FIELDS = (
        "vocab_size hidden_size intermediate_size num_hidden_layers "
        "num_attention_heads num_key_value_heads rms_norm_eps rope_theta "
        "max_position_embeddings tie_word_embeddings sliding_window "
        "num_experts_per_tok moe_intermediate_size norm_topk_prob "
        "router_aux_loss_coef initializer_range layer_types hidden_act "
        "rope_local_base_freq q_lora_rank kv_lora_rank qk_nope_head_dim "
        "qk_rope_head_dim v_head_dim routed_scaling_factor n_group "
        "topk_group n_shared_experts first_k_dense_replace scoring_func "
        "mlp_bias attention_bias partial_rotary_factor "
        "num_nextn_predict_layers"
    ).split()
    # model types whose config keys are deepseek_v3's
    _DEEPSEEK_V3_DIALECT = ("deepseek_v3", "joyai_llm_flash")

    @staticmethod
    def deepseek_defaults(model_type: str) -> Dict[str, Any]:
        """What the deepseek dialects mean where their config.json has no key
        (``from_hf_config``, and ``build_config`` for the v3 dialect): v3
        routes on sigmoid scores + correction bias (noaux-tc), v2 on plain
        softmax scores; both train bias-update balancing, not an aux loss term."""
        return dict(
            scoring_func="softmax" if model_type == "deepseek_v2" else "sigmoid",
            norm_topk_prob=True, router_aux_loss_coef=0.0, rope_interleave=True)

    _GRANITE_HYBRID_FIELDS = (
        "mamba_n_heads mamba_d_head mamba_d_state mamba_n_groups mamba_d_conv "
        "mamba_expand mamba_chunk_size mamba_conv_bias mamba_proj_bias "
        "position_embedding_type attention_multiplier residual_multiplier "
        "logits_scaling"
    ).split()
    # config.json's spelling -> the field it sets here
    _GRANITE_HYBRID_RENAMED = {
        "embedding_multiplier": "embed_scale",
        "shared_intermediate_size": "intermediate_size",  # the one MLP a layer has
        "num_local_experts": "num_experts",
    }

    @classmethod
    def granite_hybrid_fields(cls, hf: Dict[str, Any]) -> Dict[str, Any]:
        """The granitemoehybrid keys of ``hf`` (a config.json, or overrides in
        its spelling) as this class's fields."""
        kw = {k: hf[k] for k in cls._GRANITE_HYBRID_FIELDS if hf.get(k) is not None}
        kw.update({ours: hf[theirs] for theirs, ours in cls._GRANITE_HYBRID_RENAMED.items()
                   if hf.get(theirs) is not None})
        return kw

    # kimi_linear: config.json's spelling -> the field it sets here
    _KIMI_LINEAR_RENAMED = {
        "num_experts_per_token": "num_experts_per_tok",
        "moe_router_activation_func": "scoring_func",
        "moe_renormalize": "norm_topk_prob",
        "num_shared_experts": "n_shared_experts",
        "num_expert_group": "n_group",
    }

    @classmethod
    def kimi_linear_fields(cls, hf: Dict[str, Any]) -> Dict[str, Any]:
        """The kimi_linear keys of ``hf`` (a config.json, or overrides in its
        spelling) as this class's fields. The family balances its experts by
        updating the correction bias, not by a loss term."""
        kw = {ours: hf[theirs] for theirs, ours in cls._KIMI_LINEAR_RENAMED.items()
              if hf.get(theirs) is not None}
        kw.update({k: hf[k] for k in ("linear_attn_config", "mla_use_nope")
                   if hf.get(k) is not None})
        if not hf.get("use_grouped_topk", True):
            kw["n_group"] = kw["topk_group"] = 1
        kw["router_aux_loss_coef"] = hf.get("router_aux_loss_coef", 0.0)
        return kw

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any], **overrides) -> "TransformerConfig":
        mt = hf.get("model_type", "llama")
        if isinstance(hf.get("text_config"), dict):
            # multimodal wrappers (gemma3, *-vl) nest the LM dialect
            hf = {**hf, **hf["text_config"]}
            mt = hf.get("model_type", mt)
        kw: Dict[str, Any] = {"model_type": mt}
        for name in cls._HF_FIELDS:
            if name in hf and hf[name] is not None:
                kw[name] = hf[name]
        if hf.get("head_dim"):
            kw["head_dim"] = hf["head_dim"]
        if hf.get("rope_scaling"):
            kw["rope_scaling"] = dict(hf["rope_scaling"])
        if hf.get("hidden_activation"):  # gemma naming
            kw["hidden_act"] = hf["hidden_activation"]
        if mt in ("qwen2",):
            kw["attention_bias"] = True
        if mt in ("qwen3", "qwen3_moe"):
            kw["qk_norm"] = True
        if "attention_bias" in hf:
            kw["attention_bias"] = hf["attention_bias"]
        # expert count: our exports use "num_experts"; HF dialects vary
        for key in ("num_experts", "n_routed_experts", "num_local_experts"):
            if hf.get(key):
                kw["num_experts"] = hf[key]
                break
        if mt in ("gemma3", "gemma3_text"):
            kw.update(
                model_type="gemma3",
                qk_norm=True,
                norm_zero_centered=True,
                sandwich_norms=True,
                embed_scale=hf["hidden_size"] ** 0.5,
                query_pre_attn_scalar=hf.get("query_pre_attn_scalar", 256),
                tie_word_embeddings=hf.get("tie_word_embeddings", True),
            )
            if hf.get("final_logit_softcapping"):
                kw["final_logit_softcap"] = hf["final_logit_softcapping"]
        if mt == "gpt_oss":
            kw.update(attention_sinks=True, attention_bias=True, o_bias=True,
                      mlp_bias=True, hidden_act="gpt_oss_glu", router_bias=True,
                      num_experts=hf.get("num_local_experts", 0))
        for key in ("moe_experts_held", "moe_experts_held_first"):
            if hf.get(key):
                kw[key] = hf[key]
        if mt in cls._DEEPSEEK_V3_DIALECT + ("deepseek_v2",):
            d = cls.deepseek_defaults(mt)
            kw["scoring_func"] = hf.get("scoring_func", d["scoring_func"])
            kw["norm_topk_prob"] = hf.get("norm_topk_prob", d["norm_topk_prob"])
            kw["router_aux_loss_coef"] = hf.get("aux_loss_alpha", d["router_aux_loss_coef"])
            kw["rope_interleave"] = hf.get("rope_interleave", d["rope_interleave"])
        if mt == "seed_oss":
            kw["attention_bias"] = hf.get("attention_bias", True)
            kw["o_bias"] = hf.get("attention_out_bias", False)
        if mt in ("glm4_moe", "glm_moe"):
            kw.update(
                model_type="glm4_moe",
                qk_norm=hf.get("use_qk_norm", False),
                scoring_func="sigmoid",       # Glm4MoeTopkRouter: sigmoid + bias
                router_aux_loss_coef=0.0,     # bias-update balancing, no aux term
                norm_topk_prob=hf.get("norm_topk_prob", True),
            )
        if mt == "glm_moe_dsa":
            # MLA (deepseek-v3.2 lineage) + DSA indexer + glm4_moe routing
            kw.update(
                expert_layout="fused_chunked",
                scoring_func="sigmoid",
                router_aux_loss_coef=hf.get(
                    "router_aux_loss_coef", hf.get("aux_loss_alpha", 0.0)
                ),
                norm_topk_prob=hf.get("norm_topk_prob", True),
                rope_interleave=hf.get("rope_interleave", True),
                index_n_heads=hf.get("index_n_heads", 0),
                index_head_dim=hf.get("index_head_dim", 0),
                index_topk=hf.get("index_topk", 0),
                indexer_types=tuple(hf.get("indexer_types") or ()),
            )
            mlt = hf.get("mlp_layer_types")
            if mlt and "first_k_dense_replace" not in hf:
                k_dense = 0
                while k_dense < len(mlt) and mlt[k_dense] == "dense":
                    k_dense += 1
                if any(t == "dense" for t in mlt[k_dense:]):
                    raise ValueError(
                        "glm_moe_dsa mlp_layer_types with non-prefix dense "
                        "layers is unsupported (first_k_dense layout only)"
                    )
                kw["first_k_dense_replace"] = k_dense
        if mt in ("qwen3_next", "qwen3_5", "qwen3_5_moe"):
            # hybrid GatedDeltaNet (models/qwen3_next.py); layer pattern comes
            # from full_attention_interval, not HF layer_types
            kw.pop("layer_types", None)
            kw.update(
                model_type="qwen3_next",
                # Qwen3NextRMSNorm is zero-centered ((1 + w), zeros init);
                # the GATED delta-net norm is standard and handled separately
                norm_zero_centered=True,
                linear_num_value_heads=hf.get("linear_num_value_heads", 0),
                linear_num_key_heads=hf.get("linear_num_key_heads", 0),
                linear_key_head_dim=hf.get("linear_key_head_dim", 0),
                linear_value_head_dim=hf.get("linear_value_head_dim", 0),
                linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
                full_attention_interval=hf.get("full_attention_interval", 4) or 4,
                attn_output_gate=True,
                partial_rotary_factor=hf.get("partial_rotary_factor", 0.25),
                shared_expert_intermediate_size=hf.get(
                    "shared_expert_intermediate_size", 0
                ),
                shared_expert_gated=bool(
                    hf.get("shared_expert_intermediate_size", 0)
                ),
                router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0)
                if hf.get("output_router_logits") else 0.0,
            )
        if mt == "granitemoehybrid":
            kw.update(cls.granite_hybrid_fields(hf))
        if mt == "kimi_linear":
            kw.update(cls.kimi_linear_fields(hf))
        if not hf.get("use_sliding_window", True) and mt.startswith("qwen"):
            kw["sliding_window"] = None
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def from_pretrained(cls, path: str, **overrides) -> "TransformerConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_config(json.load(f), **overrides)

    # inverse of the expert-count probing in from_hf_config: the key HF
    # transformers expects for each MoE dialect (extra keys are tolerated by
    # HF, but the canonical one must be present for the count to round-trip)
    _HF_EXPERT_KEY = {
        "deepseek_v2": "n_routed_experts",
        "deepseek_v3": "n_routed_experts",
        "joyai_llm_flash": "n_routed_experts",
        "gpt_oss": "num_local_experts",
        "mixtral": "num_local_experts",
    }
    # internal dialect activation names -> HF spellings
    _HF_ACT_SPELLING = {"gpt_oss_glu": "silu"}

    def to_hf_config(self) -> Dict[str, Any]:
        hf = {"model_type": self.model_type, "head_dim": self.head_dim,
              "attention_bias": self.attention_bias}
        if self.rope_scaling:
            hf["rope_scaling"] = self.rope_scaling
        for name in self._HF_FIELDS:
            hf[name] = getattr(self, name)
        hf["hidden_act"] = self._HF_ACT_SPELLING.get(self.hidden_act, self.hidden_act)
        if self.is_moe:
            hf[self._HF_EXPERT_KEY.get(self.model_type, "num_experts")] = self.num_experts
        if self.experts_held != self.num_experts:
            # not an HF key: a checkpoint of one chip's share says which
            hf["moe_experts_held"] = self.moe_experts_held
            hf["moe_experts_held_first"] = self.moe_experts_held_first
        if self.model_type in ("gemma3", "gemma3_text"):
            hf["hidden_activation"] = hf.pop("hidden_act")
            hf["query_pre_attn_scalar"] = self.query_pre_attn_scalar
            if self.final_logit_softcap:
                hf["final_logit_softcapping"] = self.final_logit_softcap
        if self.model_type in self._DEEPSEEK_V3_DIALECT + ("deepseek_v2",):
            hf["aux_loss_alpha"] = hf.pop("router_aux_loss_coef")
        if self.use_dsa:
            hf.update(
                index_n_heads=self.index_n_heads,
                index_head_dim=self.index_head_dim,
                index_topk=self.index_topk,
                indexer_types=list(self.indexer_types),
                rope_interleave=self.rope_interleave,
            )
        if self.model_type == "granitemoehybrid":
            hf.update({k: getattr(self, k) for k in self._GRANITE_HYBRID_FIELDS})
            hf.update({theirs: getattr(self, ours)
                       for theirs, ours in self._GRANITE_HYBRID_RENAMED.items()})
        if self.model_type == "kimi_linear":
            for theirs, ours in self._KIMI_LINEAR_RENAMED.items():
                hf[theirs] = hf.pop(ours, getattr(self, ours))
            hf.update(linear_attn_config=self.linear_attn_config,
                      mla_use_nope=self.mla_use_nope, use_grouped_topk=True)
        if self.model_type == "qwen3_next":
            hf.update(
                linear_num_value_heads=self.linear_num_value_heads,
                linear_num_key_heads=self.linear_num_key_heads,
                linear_key_head_dim=self.linear_key_head_dim,
                linear_value_head_dim=self.linear_value_head_dim,
                linear_conv_kernel_dim=self.linear_conv_kernel_dim,
                full_attention_interval=self.full_attention_interval,
                shared_expert_intermediate_size=self.shared_expert_intermediate_size,
                partial_rotary_factor=self.partial_rotary_factor,
            )
        return hf
