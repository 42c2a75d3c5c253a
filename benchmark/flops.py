"""Operations and bytes the algorithm needs, from shapes alone.

The model arithmetic is a copy of the program's
``veomni_tpu/utils/count_flops.py::FlopsCounter`` for the family the
benchmark runs (dense GQA decoder): a matmul forward is 2*M*N*K, the backward
twice that, so training is three forwards.
Recomputed operations (gradient checkpointing) are never counted.
``tests`` hold the two in agreement for as long as the program keeps its
copy.
"""

from __future__ import annotations


def fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations per token of the language model at a context of
    ``seq_len`` (causal attention: half of the square)."""
    h = cfg["hidden_size"]
    nq, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    qd, kvd = nq * d, nkv * d
    attn_proj = 2 * h * (qd + 2 * kvd + qd)
    attn_score = 2 * 2 * nq * d * (seq_len / 2)
    mlp = 2 * 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn_proj + attn_score + mlp) + 2 * h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * fwd_flops_per_token(cfg, seq_len)


def flash_attention_ops_bytes(*, pairs: float, tokens: float, num_q_heads: int,
                              num_kv_heads: int, head_dim: int, layers: int = 1,
                              backward: bool = True, dtype_bytes: int = 2) -> dict:
    """Least work of flash attention over ``pairs`` (query, key) pairs that
    the mask admits and ``tokens`` positions, per layer times ``layers``.

    Forward: QK^T and PV, 2 matmuls of 2*D each per pair and head. Backward:
    5 such matmuls (recomputed scores, dV, dP, dQ, dK). Bytes: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o, do and writes
    dq, dk, dv; nothing of size S^2 ever leaves the chip's fast memory.
    """
    per_pair = 2 * num_q_heads * head_dim
    ops = (2 + (5 if backward else 0)) * per_pair * pairs
    q_bytes = tokens * num_q_heads * head_dim * dtype_bytes
    kv_bytes = tokens * num_kv_heads * head_dim * dtype_bytes
    nbytes = 2 * q_bytes + 2 * kv_bytes
    if backward:
        nbytes += 4 * q_bytes + 2 * kv_bytes + 2 * kv_bytes
    return {"ops": float(ops) * layers, "bytes": float(nbytes) * layers}


def roofline_seconds(ops_bytes: dict, peaks: dict) -> dict:
    """Least time on one chip and which bound applies."""
    t_ops = ops_bytes["ops"] / peaks["bf16_flops"]
    t_mem = ops_bytes["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_mem), "bound": "compute" if t_ops >= t_mem else "memory"}
