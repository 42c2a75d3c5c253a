"""Operations and bytes a served dense GQA decoder needs, from shapes alone
(``flops.py`` is the train path's).

A forward pass of one token at a context of ``c`` cached positions (its own
included) is the layers' projections and MLP, attention's two matmuls over
the ``c`` positions it reads, and, where the token's logits are wanted, the
head: a third of ``flops.py``'s train count, with attention over the context
read and not over half a fixed row. Nothing run again (a prefix served from
the cache and prefilled anew after an eviction, a request preempted and
recomputed) counts twice: the caller counts each token's forward once.
"""

from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's projections and MLP (norms left out)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (qd + 2 * kvd) + qd * h + 3 * h * cfg["intermediate_size"]


def forward_flops(cfg: dict, *, tokens: float, context_sum: float, logit_rows: float) -> float:
    """``tokens`` forward passes whose contexts (each token's cached positions
    read, its own included) add up to ``context_sum``, and ``logit_rows``
    rows of the head."""
    nq, d = cfg["num_attention_heads"], cfg["head_dim"]
    per_token = 2 * layer_matmul_params(cfg)
    per_pair = 2 * 2 * nq * d  # QK^T and PV, each 2 * d a head
    return (cfg["num_hidden_layers"] * (per_token * tokens + per_pair * context_sum)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * logit_rows)


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Every weight a decode tick multiplies by, once: the layers' matrices
    and norms, the final norm, and the head (with tied embeddings the
    embedding table IS the head; untied, the lookup reads a row a slot, which
    is left out)."""
    h, d, layers = cfg["hidden_size"], cfg["head_dim"], cfg["num_hidden_layers"]
    norms = layers * (2 * h + (2 * d if cfg.get("qk_norm", True) else 0)) + h
    return dtype_bytes * (layers * layer_matmul_params(cfg) + norms + h * cfg["vocab_size"])


def kv_bytes_per_position(cfg: dict, dtype_bytes: int = 2) -> int:
    """A cached position's keys and values over all layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * dtype_bytes)


def decode_tick_least_bytes(cfg: dict, *, context_positions: float, dtype_bytes: int = 2) -> float:
    """The least a decode tick must read from HBM: the weights once and each
    running request's own cached keys and values once (``context_positions``:
    the running requests' contexts added up)."""
    return weight_bytes(cfg, dtype_bytes) + kv_bytes_per_position(cfg, dtype_bytes) * context_positions
