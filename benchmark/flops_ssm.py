"""Operations and bytes of the hybrid state-space family (``granitemoehybrid``,
dense members), from shapes alone: Mamba-2 layers and attention layers in the
order ``layer_types_run`` names, a SwiGLU MLP in each, a tied head.
``flops.py`` and ``flops_mla_moe.py`` beside this file count the other
families; nothing of them is changed.

A matmul forward is 2*M*N*K, the backward twice that; recomputed operations
are never counted. ``tests/test_ssm_hybrid.py`` holds the model arithmetic
against the program's own counter (``veomni_tpu/utils/count_flops.py``) for as
long as the program keeps one.
"""

from __future__ import annotations


def _kinds(cfg: dict):
    return cfg["layer_types_run"].split(",")


def ssm_mixer_flops(cfg: dict) -> dict:
    """Forward operations a token of one Mamba-2 mixer: its two projections,
    the depthwise conv, and the chunked scan's four matmuls."""
    h, heads, p, n = (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"])
    d_inner, bc = heads * p, cfg["mamba_n_groups"] * n
    c = cfg["mamba_chunk_size"]
    return {"proj": 2 * h * (2 * d_inner + 2 * bc + heads) + 2 * d_inner * h,
            "conv": 2 * (d_inner + 2 * bc) * cfg["mamba_d_conv"],
            "scan": 2 * c * bc + 2 * c * d_inner + 2 * 2 * d_inner * n}


def fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["hidden_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nq
    mlp = 2 * 3 * h * cfg["shared_intermediate_size"]
    attention = 2 * h * (2 * nq * d + 2 * nkv * d) + 2 * 2 * nq * d * (seq_len / 2)
    kinds = _kinds(cfg)
    n_ssm = kinds.count("mamba")
    return (n_ssm * (sum(ssm_mixer_flops(cfg).values()) + mlp)
            + (len(kinds) - n_ssm) * (attention + mlp) + 2 * h * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * fwd_flops_per_token(cfg, seq_len)


def ssd_scan_ops_bytes(*, tokens: float, heads: int, head_dim: int, state: int, groups: int,
                       chunk: int, backward: bool = False, dtype_bytes: int = 2) -> dict:
    """Least work of ONE state-space scan (one layer, forward or backward)
    over ``tokens`` positions in chunks of ``chunk``.

    Forward, a token: ``C B^T`` inside its chunk (2 c G N), that matrix times
    ``dt x`` (2 c H P), the carried state read by ``C`` and written through
    ``B`` (2 H P N each). Backward: each of those four matmuls twice (one for
    each operand's gradient) and ``C B^T`` once more, since nothing of size
    c^2 is kept. Bytes: the forward reads x, B, C (``dtype_bytes``) and dt
    (f32) and writes y; the backward reads those and dy and writes dx, dB, dC
    and ddt. Decays, masks and one state a chunk never leave the chip's fast
    memory in the least a kernel could do."""
    d_inner, bc = heads * head_dim, groups * state
    fwd_ops = 2 * chunk * bc + 2 * chunk * d_inner + 2 * 2 * d_inner * state
    if backward:
        ops = 2 * fwd_ops + 2 * chunk * bc
        nbytes = dtype_bytes * (3 * d_inner + 4 * bc) + 4 * 2 * heads
    else:
        ops = fwd_ops
        nbytes = dtype_bytes * (2 * d_inner + 2 * bc) + 4 * heads
    return {"ops": float(ops) * tokens, "bytes": float(nbytes) * tokens}


def gqa_flash_ops_bytes(*, pairs: float, tokens: float, num_q_heads: int, num_kv_heads: int,
                        head_dim: int, backward: bool = False, dtype_bytes: int = 2) -> dict:
    """Least work of ONE call (one layer, forward or backward) of flash
    attention with grouped keys and values over ``pairs`` (query, key) pairs
    the mask admits and ``tokens`` positions: ``flops.py::
    flash_attention_ops_bytes``' counts, a call at a time. Forward: QK^T and
    PV. Backward: the scores again, dV, dP, dQ, dK. Bytes: the forward reads
    q, k, v and writes o; the backward reads q, k, v, o, do and writes dq,
    dk, dv."""
    per_pair = 2 * num_q_heads * head_dim
    q_bytes = tokens * num_q_heads * head_dim * dtype_bytes
    kv_bytes = tokens * num_kv_heads * head_dim * dtype_bytes
    if backward:
        return {"ops": 5.0 * per_pair * pairs, "bytes": float(4 * q_bytes + 4 * kv_bytes)}
    return {"ops": 2.0 * per_pair * pairs, "bytes": float(2 * q_bytes + 2 * kv_bytes)}
