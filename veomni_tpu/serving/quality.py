"""Fixed-seed quality gate for non-bit-exact serving features.

The quantized serving tier (int8 KV blocks, int8 decode weights) is the
first serving feature that is deliberately NOT token-exact with the f32
engine. Exact parity drills can't certify it, so it ships behind this
gate: score a fixed-seed corpus **teacher-forced** through the reference
and the quantized decode path and bound two deltas —

- **perplexity delta**: relative change in teacher-forced perplexity
  (``exp(mean NLL)`` of each next token under the previous position's
  logits). Bounds the aggregate likelihood damage.
- **top-k overlap**: mean ``|topk(ref) ∩ topk(quant)| / k`` over
  positions. Bounds per-position ranking damage — a model can hold its
  perplexity while reshuffling the argmax neighborhood, and it is the
  argmax neighborhood that greedy/top-k serving actually samples from.

Scoring runs the REAL paged serving path, not a surrogate: one
:func:`~veomni_tpu.models.decode.paged_verify_step` call per sequence
(S=1, all T tokens as one verify batch) against freshly scattered pools in
the requested storage mode, so the quantize-on-write and
dequantize-in-attend code under test is exactly the code the engine runs.

``tests/tools/quality_gate.py`` wraps this with the pinned repo-wide
bounds, so a capacity win is never accepted without its quality cost.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.models import decode as decode_mod
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.ops.quantization import make_kv_pool, quantize_decode_params


def fixed_corpus(vocab_size: int, *, n_seqs: int = 4, length: int = 24,
                 seed: int = 0) -> List[List[int]]:
    """The gate's fixed-seed token corpus: deterministic across runs and
    machines (numpy Philox via default_rng), tokens in [1, vocab)."""
    rng = np.random.default_rng(seed)
    return [
        [int(t) for t in rng.integers(1, vocab_size, size=length)]
        for _ in range(n_seqs)
    ]


def teacher_forced_logits(params, cfg: TransformerConfig,
                          tokens: Sequence[int], *,
                          kv_quant: str = "none",
                          block_size: int = 16) -> np.ndarray:
    """Per-position next-token logits [T, V] f32 for one sequence, scored
    through the paged serving path in the requested KV storage mode.

    One eager ``paged_verify_step`` call with S=1 and all T tokens as the
    verify batch: row j's logits are computed with rows 0..j written to the
    (possibly quantized) pool and attended through the block table — the
    exact cache state the engine would have after token j."""
    t = len(tokens)
    nb = -(-t // block_size)
    L = cfg.num_hidden_layers
    shape = (L, nb + 1, block_size, cfg.num_key_value_heads, cfg.head_dim)
    pools = (
        make_kv_pool(shape, kv_quant, cfg.dtype),
        make_kv_pool(shape, kv_quant, cfg.dtype),
    )
    # block 0 is the null block; the sequence owns blocks 1..nb
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    positions = jnp.zeros((1,), jnp.int32)
    toks = jnp.asarray(tokens, jnp.int32)[None]
    n_input = jnp.full((1,), t, jnp.int32)
    logits, _ = decode_mod.paged_verify_step(
        params, cfg, pools, table, positions, toks, n_input
    )
    return np.asarray(logits[0], np.float32)


def _ppl(logits: np.ndarray, tokens: Sequence[int]) -> float:
    """Teacher-forced perplexity: exp(mean NLL of tokens[j+1] under
    logits[j])."""
    lp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    nxt = jnp.asarray(tokens[1:], jnp.int32)
    nll = -jnp.take_along_axis(lp[:-1], nxt[:, None], axis=-1)[:, 0]
    return float(jnp.exp(nll.mean()))


def _topk_overlap(ref: np.ndarray, quant: np.ndarray, k: int) -> float:
    """Mean |topk(ref) ∩ topk(quant)| / k over positions."""
    ri = np.argsort(-ref, axis=-1)[:, :k]
    qi = np.argsort(-quant, axis=-1)[:, :k]
    inter = [
        len(set(r.tolist()) & set(q.tolist())) for r, q in zip(ri, qi)
    ]
    return float(np.mean(inter) / k)


def quality_stats(params, cfg: TransformerConfig,
                  corpus: Sequence[Sequence[int]], *,
                  kv_quant: str = "none", weight_quant: str = "none",
                  top_k: int = 8, block_size: int = 16) -> Dict[str, float]:
    """Score ``corpus`` through the f32 reference path and the quantized
    path; return the gate's statistics.

    Returns ``{ppl_ref, ppl_quant, ppl_rel_delta, topk_overlap}`` where
    ``ppl_rel_delta = |ppl_quant - ppl_ref| / ppl_ref`` (aggregated over
    the whole corpus) and ``topk_overlap`` is the per-position mean. The
    reference is always the unquantized path over the same corpus, so the
    stats isolate the quantization damage from the model itself."""
    qparams = (
        quantize_decode_params(params) if weight_quant == "int8" else params
    )
    nll_ref: List[float] = []
    nll_q: List[float] = []
    overlaps: List[float] = []
    for tokens in corpus:
        ref = teacher_forced_logits(params, cfg, tokens,
                                    kv_quant="none", block_size=block_size)
        qnt = teacher_forced_logits(qparams, cfg, tokens,
                                    kv_quant=kv_quant,
                                    block_size=block_size)
        nll_ref.append(np.log(_ppl(ref, tokens)))
        nll_q.append(np.log(_ppl(qnt, tokens)))
        overlaps.append(_topk_overlap(ref, qnt, top_k))
    ppl_ref = float(np.exp(np.mean(nll_ref)))
    ppl_quant = float(np.exp(np.mean(nll_q)))
    return {
        "ppl_ref": ppl_ref,
        "ppl_quant": ppl_quant,
        "ppl_rel_delta": abs(ppl_quant - ppl_ref) / ppl_ref,
        "topk_overlap": float(np.mean(overlaps)),
    }
