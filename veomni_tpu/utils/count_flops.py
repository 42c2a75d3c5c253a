"""Analytic per-step FLOPs counter.

Reference: ``veomni/utils/count_flops.py:60-988`` (``VeomniFlopsCounter``) —
per-architecture formulas used by the MFU meter. Implemented terms:

* dense GQA transformer (llama/qwen lineage), incl. partial-rotary and the
  qwen3_next gated-attention q_proj doubling;
* MLA (deepseek q/kv low-rank compression — NOT approximated as plain
  ``nh * head_dim`` projections);
* MoE (top-k routed + shared experts + router; leading dense layers; where a
  chip holds a share of the experts, that share of the routed term);
* multi-token prediction (one layer, the [2H, H] projection and the head
  again per module);
* qwen3_next GatedDeltaNet linear-attention layers (chunkwise cost model);
* Mamba-2 state-space layers (granitemoehybrid: projections, conv, the
  chunked scan's four matmuls), counted per ``layer_types``;
* Kimi Delta Attention layers (kimi_linear: projections, convs, the chunked
  recurrence's matmuls), counted per ``linear_attn_config``;
* ViT towers (per-patch, window or full attention) and DiT blocks via the
  dedicated helpers, fed to the meter as ``extra_flops``.

Counts follow the standard factorization: matmul fwd = 2*M*N*K, backward =
2x forward (dgrad + wgrad), so total = 3x forward.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass
class FlopsCounter:
    """Promised forward FLOPs per token for the language model."""

    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    # MoE (0 => dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    shared_expert_intermediate_size: int = 0
    first_k_dense_replace: int = 0   # leading layers with the dense MLP
    # routed experts this chip holds (0 => all): the routed term counts the
    # share of a token's top-k that an even routing sends to held experts
    num_experts_held: int = 0
    # multi-token-prediction modules: one more layer, projection and head each
    num_nextn_predict_layers: int = 0
    tie_word_embeddings: bool = False
    # MLA (deepseek); kv_lora_rank > 0 switches the attention-projection term
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # qwen3_next hybrid: every `full_attention_interval`-th layer is full
    # attention, the rest are GatedDeltaNet linear attention
    linear_num_value_heads: int = 0
    linear_num_key_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    full_attention_interval: int = 0
    attn_output_gate: bool = False
    delta_chunk: int = 64
    # granitemoehybrid: Mamba-2 state-space layers where ``layer_types`` says
    # "mamba" (``n_ssm_layers`` of ``num_layers``), attention elsewhere
    n_ssm_layers: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # kimi_linear: Kimi Delta Attention layers where ``linear_attn_config``
    # lists them (``n_kda_layers`` of ``num_layers``), MLA elsewhere
    n_kda_layers: int = 0
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_chunk: int = 64

    # ------------------------------------------------------------- per-term
    def _attn_proj_flops(self) -> float:
        """q/k/v/o projections per token (fwd)."""
        h = self.hidden_size
        if self.kv_lora_rank:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            nh, vd = self.num_heads, self.v_head_dim
            q = (
                2 * h * self.q_lora_rank + 2 * self.q_lora_rank * nh * qk
                if self.q_lora_rank
                else 2 * h * nh * qk
            )
            kv_a = 2 * h * (self.kv_lora_rank + self.qk_rope_head_dim)
            kv_b = 2 * self.kv_lora_rank * nh * (self.qk_nope_head_dim + vd)
            o = 2 * nh * vd * h
            return q + kv_a + kv_b + o
        q_dim = self.num_heads * self.head_dim
        kv_dim = self.num_kv_heads * self.head_dim
        q_mult = 2 if self.attn_output_gate else 1
        return 2 * h * (q_mult * q_dim + 2 * kv_dim + q_dim)

    def _attn_score_flops(self, seq_len: int) -> float:
        """scores + context per token (fwd); causal halves the window."""
        if self.kv_lora_rank:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            per_head = 2 * (qk + self.v_head_dim) * (seq_len / 2)
            return self.num_heads * per_head
        return 2 * 2 * self.num_heads * self.head_dim * (seq_len / 2)

    def _mlp_flops(self, dense: bool = False) -> float:
        h = self.hidden_size
        if self.num_experts and self.num_experts_per_tok and not dense:
            inter = self.moe_intermediate_size or self.intermediate_size
            held = (self.num_experts_held or self.num_experts) / self.num_experts
            mlp = 2 * 3 * h * inter * self.num_experts_per_tok * held
            shared = self.shared_expert_intermediate_size or (
                inter * self.num_shared_experts
            )
            if shared:
                mlp += 2 * 3 * h * shared + (2 * h if self.shared_expert_intermediate_size else 0)
            mlp += 2 * h * self.num_experts  # router
            return mlp
        return 2 * 3 * h * self.intermediate_size

    def _linear_attn_flops(self) -> float:
        """GatedDeltaNet per-token fwd cost: projections + conv + chunkwise
        delta rule (in-chunk attn/UT-transform + state update)."""
        h = self.hidden_size
        nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
        dk, dv = self.linear_key_head_dim, self.linear_value_head_dim
        key_dim, value_dim = nk * dk, nv * dv
        conv_dim = 2 * key_dim + value_dim
        proj = 2 * h * (2 * key_dim + 2 * value_dim)      # in_proj_qkvz
        proj += 2 * h * 2 * nv                             # in_proj_ba
        proj += 2 * value_dim * h                          # out_proj
        conv = 2 * conv_dim * self.linear_conv_kernel_dim
        c = self.delta_chunk
        # per token, per v-head: in-chunk score/attn matrices ~ 4*C*dk +
        # 2*C*dv (kk^T, T-solve amortized, attn@v), state ops ~ 6*dk*dv
        delta = nv * (4 * c * dk + 2 * c * dv + 6 * dk * dv)
        return proj + conv + delta

    def _ssm_flops(self) -> float:
        """Mamba-2 mixer per-token fwd cost: in_proj and out_proj, the conv,
        and the chunked scan's matmuls (C B^T and its product with x inside a
        chunk of c tokens; the state read by C and written by B)."""
        h, nh, p, n = self.hidden_size, self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state
        d_inner, bc = nh * p, self.mamba_n_groups * n
        proj = 2 * h * (2 * d_inner + 2 * bc + nh) + 2 * d_inner * h
        conv = 2 * (d_inner + 2 * bc) * self.mamba_d_conv
        c = self.mamba_chunk_size
        scan = 2 * c * bc + 2 * c * d_inner + 2 * 2 * d_inner * n
        return proj + conv + scan

    def _kda_flops(self) -> float:
        """Kimi Delta Attention mixer per-token fwd cost: q, k, v, the two
        low-rank pairs, beta and the output projection; three convs; the
        chunked recurrence (inside a chunk of c tokens the two pair terms, the
        triangular system applied once and its rows against P: 8 c d a head;
        the state read through k and q and written: 6 d^2)."""
        h, nh, d, c = self.hidden_size, self.kda_num_heads, self.kda_head_dim, self.kda_chunk
        proj = 2 * h * 3 * nh * d + 2 * (2 * h * d + 2 * d * nh * d) + 2 * h * nh + 2 * nh * d * h
        conv = 2 * 3 * nh * d * self.kda_conv_kernel
        return proj + conv + nh * (8 * c * d + 6 * d * d)

    # ------------------------------------------------------------ aggregate
    def flops_per_token_fwd(self, seq_len: int) -> float:
        mlp = self._mlp_flops()
        full_layer = self._attn_proj_flops() + self._attn_score_flops(seq_len) + mlp
        if self.n_ssm_layers:
            body = (self.n_ssm_layers * (self._ssm_flops() + mlp)
                    + (self.num_layers - self.n_ssm_layers) * full_layer)
        elif self.n_kda_layers:
            body = (self.n_kda_layers * (self._kda_flops() + mlp)
                    + (self.num_layers - self.n_kda_layers) * full_layer)
        elif self.full_attention_interval and self.linear_num_value_heads:
            n_full = self.num_layers // self.full_attention_interval
            n_lin = self.num_layers - n_full
            lin_layer = self._linear_attn_flops() + mlp
            body = n_full * full_layer + n_lin * lin_layer
        else:
            body = self.num_layers * full_layer
        lm_head = 2 * self.hidden_size * self.vocab_size
        if self.num_experts and self.first_k_dense_replace:
            body += min(self.first_k_dense_replace, self.num_layers) * (
                self._mlp_flops(dense=True) - mlp)
        if self.num_nextn_predict_layers:
            h = self.hidden_size
            body += self.num_nextn_predict_layers * (full_layer + 2 * 2 * h * h + lm_head)
        return body + lm_head

    def batch_flops(self, total_tokens: int, seq_len: int, include_backward: bool = True) -> float:
        fwd = total_tokens * self.flops_per_token_fwd(seq_len)
        return fwd * 3.0 if include_backward else fwd

    @classmethod
    def from_config(cls, cfg) -> "FlopsCounter":
        """Build from any model config exposing llama-family field names.
        Composite (VLM/omni) configs contribute their LM via ``cfg.text``;
        tower FLOPs are fed separately (``vit_flops_fwd``)."""
        if hasattr(cfg, "text") and hasattr(cfg.text, "hidden_size"):
            cfg = cfg.text
        g = lambda n, d=0: getattr(cfg, n, d)
        head_dim = g("head_dim") or (g("hidden_size") // max(1, g("num_attention_heads", 1)))
        return cls(
            hidden_size=g("hidden_size"),
            intermediate_size=g("intermediate_size"),
            num_layers=g("num_hidden_layers"),
            num_heads=g("num_attention_heads"),
            num_kv_heads=g("num_key_value_heads") or g("num_attention_heads"),
            head_dim=head_dim,
            vocab_size=g("vocab_size"),
            num_experts=g("num_experts", 0) or g("n_routed_experts", 0),
            num_experts_per_tok=g("num_experts_per_tok", 0),
            moe_intermediate_size=g("moe_intermediate_size", 0),
            num_shared_experts=g("n_shared_experts", 0),
            shared_expert_intermediate_size=g("shared_expert_intermediate_size", 0),
            first_k_dense_replace=g("first_k_dense_replace", 0),
            num_experts_held=g("moe_experts_held", 0),
            num_nextn_predict_layers=g("num_nextn_predict_layers", 0),
            tie_word_embeddings=g("tie_word_embeddings", False),
            q_lora_rank=g("q_lora_rank", 0),
            kv_lora_rank=g("kv_lora_rank", 0),
            qk_nope_head_dim=g("qk_nope_head_dim", 0),
            qk_rope_head_dim=g("qk_rope_head_dim", 0),
            v_head_dim=g("v_head_dim", 0),
            linear_num_value_heads=g("linear_num_value_heads", 0),
            linear_num_key_heads=g("linear_num_key_heads", 0),
            linear_key_head_dim=g("linear_key_head_dim", 0),
            linear_value_head_dim=g("linear_value_head_dim", 0),
            linear_conv_kernel_dim=g("linear_conv_kernel_dim", 4),
            full_attention_interval=(
                g("full_attention_interval", 0) if g("linear_num_value_heads", 0) else 0
            ),
            attn_output_gate=g("attn_output_gate", False),
            n_ssm_layers=(
                list(g("layer_types", None) or ()).count("mamba") if g("mamba_n_heads", 0) else 0
            ),
            mamba_n_heads=g("mamba_n_heads", 0),
            mamba_d_head=g("mamba_d_head", 0),
            mamba_d_state=g("mamba_d_state", 0),
            mamba_n_groups=g("mamba_n_groups", 1),
            mamba_d_conv=g("mamba_d_conv", 4),
            mamba_chunk_size=g("mamba_chunk_size", 256),
            **cls._kda_fields(g("linear_attn_config", None)),
        )

    @staticmethod
    def _kda_fields(linear_attn_config) -> dict:
        if not linear_attn_config:
            return {}
        from veomni_tpu.ops.kda import CHUNK

        return dict(n_kda_layers=len(linear_attn_config["kda_layers"]),
                    kda_num_heads=linear_attn_config["num_heads"],
                    kda_head_dim=linear_attn_config["head_dim"],
                    kda_conv_kernel=linear_attn_config["short_conv_kernel_size"],
                    kda_chunk=CHUNK)


def vit_flops_fwd(vision_cfg, n_patches: int, window_seq: Optional[int] = None) -> float:
    """Forward FLOPs of a ViT tower on ``n_patches`` patches (reference
    ``count_flops.py`` ViT terms for the qwen-vl families).

    window_seq: attention span per patch (window attention); defaults to
    n_patches (full attention among all patches — an upper bound when
    multiple images are packed)."""
    g = lambda n, d=0: getattr(vision_cfg, n, d)
    h = g("hidden_size")
    inter = g("intermediate_size") or 4 * h
    layers = g("depth", 0) or g("num_hidden_layers", 0)
    span = window_seq if window_seq else n_patches
    per_patch = 2 * h * 4 * h                 # qkv + o projections
    per_patch += 2 * 2 * h * span             # scores + context
    per_patch += 2 * 3 * h * inter if g("gated_mlp", True) else 2 * 2 * h * inter
    body = layers * per_patch * n_patches
    # patch embed + merger
    in_dim = g("in_channels", 3) * g("temporal_patch_size", 1) * g("patch_size", 14) ** 2
    embed = 2 * in_dim * h * n_patches
    merge = g("merge_unit", 4)
    out_h = g("out_hidden_size", h)
    merger = 2 * (h * merge) * out_h * (n_patches // max(merge, 1))
    return body + embed + merger


def dit_flops_fwd(cfg, n_tokens: int) -> float:
    """Forward FLOPs of a DiT on ``n_tokens`` latent tokens per sample."""
    g = lambda n, d=0: getattr(cfg, n, d)
    h = g("hidden_size")
    inter = g("intermediate_size") or 4 * h
    layers = g("num_hidden_layers", 0) or g("depth", 0)
    per_tok = 2 * h * 4 * h + 2 * 2 * h * n_tokens + 2 * 2 * h * inter
    per_tok += 2 * h * 6 * h  # adaLN modulation
    return layers * per_tok * n_tokens
