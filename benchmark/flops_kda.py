"""Operations and bytes of the ``kimi_linear`` family, from shapes alone: Kimi
Delta Attention layers and NoPE latent-attention layers in the order
``layer_kinds_run`` names, a dense SwiGLU in the leading layers and a
sigmoid-routed expert layer of which this chip holds a share in the others, an
untied head. ``flops.py``, ``flops_mla_moe.py`` and ``flops_ssm.py`` beside
this file count the other families; nothing of them is changed.

The model arithmetic counts what a token needs ON THIS CHIP (the router at its
published width, the shared expert, and of the token's top-k experts the share
an even routing sends to experts held here). A matmul forward is 2*M*N*K, the
backward twice that; recomputed operations are never counted.
``tests/test_kda_hybrid.py`` checks the scan's count by hand, and
``tests/test_kimi_linear.py`` (the program's) holds the model arithmetic
against the program's own counter (``veomni_tpu/utils/count_flops.py``).
"""

from __future__ import annotations


def _kinds(cfg: dict):
    return cfg["layer_kinds_run"].split(",")


def kda_scan_flops(cfg: dict) -> float:
    """Forward operations a token of one layer's chunked KDA recurrence
    (:func:`kda_scan_ops_bytes` says which matmuls)."""
    c, d = cfg["kda_chunk"], cfg["kda_head_dim"]
    return cfg["kda_num_heads"] * (8 * c * d + 6 * d * d)


def kda_mixer_flops(cfg: dict) -> dict:
    """Forward operations a token of one KDA mixer: q, k, v, the two low-rank
    pairs (decay, output gate), beta and the output projection; the three
    depthwise convs; the chunked recurrence."""
    h, heads, d = cfg["hidden_size"], cfg["kda_num_heads"], cfg["kda_head_dim"]
    proj = heads * d
    return {"proj": 2 * h * 3 * proj + 2 * (2 * h * d + 2 * d * proj) + 2 * h * heads + 2 * proj * h,
            "conv": 2 * 3 * proj * cfg["kda_conv_kernel"],
            "scan": kda_scan_flops(cfg)}


def mla_mixer_flops(cfg: dict, seq_len: int) -> float:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    proj = (2 * h * nh * (dn + dr) + 2 * h * (cfg["kv_lora_rank"] + dr)
            + 2 * cfg["kv_lora_rank"] * nh * (dn + dv) + 2 * nh * dv * h)
    return proj + nh * 2 * (dn + dr + dv) * (seq_len / 2)  # causal: half of the square


def expert_layer_flops(cfg: dict) -> float:
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    published = cfg.get("num_experts_published", cfg["num_experts"])
    routed = 2 * 3 * h * im * cfg["num_experts_per_token"] * cfg["num_experts"] / published
    return routed + 2 * 3 * h * im * cfg["num_shared_experts"] + 2 * h * published


def fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["hidden_size"]
    kda = sum(kda_mixer_flops(cfg).values())
    total = 2 * h * cfg["vocab_size"]
    for i, kind in enumerate(_kinds(cfg)):
        total += kda if kind == "kda" else mla_mixer_flops(cfg, seq_len)
        total += (2 * 3 * h * cfg["intermediate_size"] if i < cfg["first_k_dense_replace"]
                  else expert_layer_flops(cfg))
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * fwd_flops_per_token(cfg, seq_len)


def kda_scan_ops_bytes(*, tokens: float, heads: int, head_dim: int, chunk: int,
                       backward: bool = False, dtype_bytes: int = 2) -> dict:
    """Least work of ONE KDA recurrence (one layer, forward or backward) over
    ``tokens`` positions in chunks of ``chunk``, ``heads`` heads whose keys and
    values are both ``head_dim`` wide.

    Forward, a token and head: the two pair terms inside its chunk, ``K K^T``
    and ``Q K^T`` with the decays folded into their operands (2 c d each); the
    triangular system applied to the chunk's right-hand side as one ``[c, c]``
    product (2 c d; building the inverse is not counted: a substitution needs
    no more); ``P U`` (2 c d); the carried state read through ``K`` and through
    ``Q`` and written through ``K^T U`` (2 d d each). Backward: each of those
    twice (one for each operand's gradient) and the two pair terms once more,
    since nothing of size c^2 is kept. Bytes: the forward reads q, k, v
    (``dtype_bytes``), the log-decay (f32, one a key channel) and beta (f32)
    and writes o; the backward reads those and do and writes dq, dk, dv, dg
    and dbeta. Exponents, the inverse and one state a chunk never leave the
    chip's fast memory in the least a kernel could do."""
    c, d = chunk, head_dim
    fwd_ops = heads * (8 * c * d + 6 * d * d)
    if backward:
        ops = 2 * fwd_ops + heads * 4 * c * d
        nbytes = heads * (dtype_bytes * 7 * d + 4 * 2 * d + 4 * 2)
    else:
        ops = fwd_ops
        nbytes = heads * (dtype_bytes * 4 * d + 4 * d + 4)
    return {"ops": float(ops) * tokens, "bytes": float(nbytes) * tokens}
