"""Pinned quality-gate bounds for non-bit-exact serving features.

The core scoring lives in ``veomni_tpu/serving/quality.py`` (the engine
uses it too); this helper pins the REPO-WIDE bounds and gives
tests a one-call assertion. Any future deliberately-non-bit-exact feature
(fp8 KV, quantized lm head, approximate attention) should certify itself
through :func:`assert_quality_gate` rather than inventing its own
tolerance — one gate, one place to argue about bounds.

Bound provenance (2026-08, CPU, f32 reference, fixed_corpus seed 0 over
the qwen3 / gpt_oss_ish / qwen3_moe tier-1 dialect trio): worst observed
``ppl_rel_delta`` was 2.5e-4 and worst ``topk_overlap`` 0.988 across
int8-KV, int8-weight, and combined modes. The pins below leave ~80x
headroom on perplexity and accept up to one swapped token per top-8
neighborhood — loose enough to survive BLAS/backend drift, tight enough
that a real quantization bug (wrong scale axis, garbage rows leaking into
the attend) blows through them immediately.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from veomni_tpu.serving.quality import fixed_corpus, quality_stats

#: max relative teacher-forced perplexity change vs the f32 path
PPL_REL_DELTA_BOUND = 0.02
#: min mean top-k overlap vs the f32 path (k = TOP_K)
TOPK_OVERLAP_BOUND = 0.90
#: neighborhood size the overlap bound is pinned against
TOP_K = 8


def assert_quality_gate(params, cfg, *, kv_quant: str = "none",
                        weight_quant: str = "none", block_size: int = 16,
                        corpus: Optional[Sequence[Sequence[int]]] = None,
                        ) -> Dict[str, float]:
    """Score the quantized path against the f32 reference on the fixed-seed
    corpus and assert the pinned bounds; returns the stats for the test to
    inspect/print. ``corpus=None`` uses the standard
    :func:`~veomni_tpu.serving.quality.fixed_corpus` for the config's
    vocab."""
    if corpus is None:
        corpus = fixed_corpus(cfg.vocab_size)
    stats = quality_stats(
        params, cfg, corpus, kv_quant=kv_quant, weight_quant=weight_quant,
        top_k=TOP_K, block_size=block_size,
    )
    assert stats["ppl_rel_delta"] <= PPL_REL_DELTA_BOUND, (
        f"quality gate: ppl_rel_delta {stats['ppl_rel_delta']:.5f} exceeds "
        f"{PPL_REL_DELTA_BOUND} (kv_quant={kv_quant}, "
        f"weight_quant={weight_quant}; ppl {stats['ppl_ref']:.4f} -> "
        f"{stats['ppl_quant']:.4f})"
    )
    assert stats["topk_overlap"] >= TOPK_OVERLAP_BOUND, (
        f"quality gate: top-{TOP_K} overlap {stats['topk_overlap']:.4f} "
        f"below {TOPK_OVERLAP_BOUND} (kv_quant={kv_quant}, "
        f"weight_quant={weight_quant})"
    )
    return stats
