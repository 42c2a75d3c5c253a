"""Operations and bytes of the DeepSeek-V3 block family, from shapes alone:
latent attention (MLA), a sigmoid-routed expert layer of which this chip may
hold a share, multi-token prediction. ``flops.py`` beside this file counts the
dense GQA family; nothing of it is changed.

The model arithmetic counts what a token needs ON THIS CHIP: the router at its
published width, the shared expert, and of the token's top-k experts the
share an even routing sends to experts held here (``n_routed_experts`` of
``n_routed_experts_published``); each multi-token-prediction module is one
more layer, its ``[2H, H]`` projection and the head again. A matmul forward
is 2*M*N*K, the backward twice that; recomputed operations are never counted.
``tests/test_mla_moe.py`` holds it against the program's own counter
(``veomni_tpu/utils/count_flops.py``) for as long as the program keeps one.
"""

from __future__ import annotations


def _attention_flops(cfg: dict, seq_len: int) -> float:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    proj = (2 * h * cfg["q_lora_rank"] + 2 * cfg["q_lora_rank"] * nh * qk
            + 2 * h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + 2 * cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"] + dv)
            + 2 * nh * dv * h)
    scores = nh * 2 * (qk + dv) * (seq_len / 2)  # causal: half of the square
    return proj + scores


def _expert_layer_flops(cfg: dict) -> float:
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    published = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    held_share = cfg["n_routed_experts"] / published
    routed = 2 * 3 * h * im * cfg["num_experts_per_tok"] * held_share
    shared = 2 * 3 * h * im * cfg["n_shared_experts"]
    return routed + shared + 2 * h * published


def fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["hidden_size"]
    attention = _attention_flops(cfg, seq_len)
    dense = cfg["first_k_dense_replace"]
    head = 2 * h * cfg["vocab_size"]
    sparse_layer = attention + _expert_layer_flops(cfg)
    body = (dense * (attention + 2 * 3 * h * cfg["intermediate_size"])
            + (cfg["num_hidden_layers"] - dense) * sparse_layer)
    mtp = cfg.get("num_nextn_predict_layers", 0) * (sparse_layer + 2 * 2 * h * h + head)
    return body + mtp + head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * fwd_flops_per_token(cfg, seq_len)


def mla_flash_ops_bytes(*, pairs: float, tokens: float, num_heads: int, qk_head_dim: int,
                        v_head_dim: int, backward: bool = False,
                        dtype_bytes: int = 2) -> dict:
    """Least work of ONE call (one layer, forward or backward) of flash
    attention whose q and k are ``qk_head_dim`` wide and whose v is
    ``v_head_dim`` wide, over ``pairs`` (query, key) pairs the mask admits and
    ``tokens`` positions, every head with keys and values of its own.

    Forward: QK^T (2*Dqk a pair and head) and PV (2*Dv). Backward: the scores
    again, dQ and dK (2*Dqk each), dP and dV (2*Dv each). Bytes: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o, do and writes
    dq, dk, dv. Nothing of size S^2 leaves the chip's fast memory.
    """
    qk, v = num_heads * qk_head_dim, num_heads * v_head_dim
    if backward:
        ops = (3 * 2 * qk + 2 * 2 * v) * pairs
        nbytes = tokens * dtype_bytes * (2 * qk + 3 * v + 2 * qk + v)
    else:
        ops = (2 * qk + 2 * v) * pairs
        nbytes = tokens * dtype_bytes * (2 * qk + 2 * v)
    return {"ops": float(ops), "bytes": float(nbytes)}


def gmm_ops_bytes(*, tokens: float, top_k: int, held_share: float, hidden_size: int,
                  expert_width: int, experts: int, backward: bool = False,
                  dtype_bytes: int = 2) -> dict:
    """Least work of ONE grouped matmul of an expert layer over the rows the
    held experts really got: ``tokens * top_k * held_share`` rows against
    ``experts`` weights of ``hidden_size x expert_width``. A layer makes three
    (gate, up: H -> I; down: I -> H): one call is a third of the three, so
    that its bytes are the mean of their row traffic. ``backward``: one
    ``gmm_dlhs`` and one ``gmm_drhs`` together (twice the operations; both
    read the rows again, one reads the weights and one writes their
    gradient)."""
    rows = tokens * top_k * held_share  # held_share: of all assignments, those multiplied here
    one = 2.0 * rows * hidden_size * expert_width
    row_bytes = rows * (hidden_size + expert_width) * dtype_bytes  # lhs in, out (or back)
    weight_bytes = experts * hidden_size * expert_width * dtype_bytes
    if backward:
        # dlhs: g in, weights in, dlhs out; drhs: lhs in, g in, dW out
        nbytes = row_bytes + weight_bytes + row_bytes + weight_bytes
        return {"ops": 2 * one, "bytes": float(nbytes)}
    return {"ops": one, "bytes": float(row_bytes + weight_bytes)}
