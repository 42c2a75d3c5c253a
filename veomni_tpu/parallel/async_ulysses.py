"""Async Ulysses: chunked all-to-all / attention-compute software pipeline.

Reference: ``veomni/distributed/sequence_parallel/async_ulysses.py:48-506`` —
a 1076-LoC engine that splits the Ulysses head<->sequence all-to-all into
chunks and hand-overlaps each chunk's NCCL a2a with the previous chunk's
flash-attention GEMMs on a side CUDA stream. T3 (arXiv:2401.16677) measures
this fine-grained collective/compute fusion as the main MFU lever once
per-op overlap is exhausted.

TPU translation: there are no streams to program — overlap must be *latent
in the program structure* so GSPMD + the latency-hiding scheduler
(arXiv:2105.04663; ``utils/xla_flags.py``) can convert each ``all-to-all``
into an async start/done pair spanning the neighbouring chunk's dot-generals.
This module builds exactly that structure inside one ``shard_map`` region:

* the (GQA-repeated) q/k/v head dim is split into K chunks whose boundaries
  respect both the a2a divisibility (``u | heads_per_chunk``) and the GQA
  q->kv group mapping (``UlyssesLayout.max_chunks``), so per-chunk attention
  is *bitwise* the monolithic computation restricted to a head slice;
* a ``lax.scan`` software pipeline: the carry holds chunk *i*'s
  already-a2a'ed (double-buffered) q/k/v while the step body issues chunk
  *i+1*'s scatter a2a — which has **no data dependency** on chunk *i*'s
  attention compute or its gather a2a, the property the scheduler needs;
* warm-up (chunk 0's a2a before the scan) and drain (chunk K-1's attention
  after it) epilogues complete the pipeline;
* attention sinks enter replicated and are sliced per (chunk, rank) — under
  chunking a rank's sink heads differ per chunk, so the monolithic path's
  static ``P(ulysses)`` shard does not apply;
* ``cp > 1`` composes as in the monolithic path: each head chunk's gathered
  slice runs ring attention over the ``cp`` axis.

Verified by ``tests/test_async_ulysses.py``: exact parity with the
monolithic path (GQA + sinks) and an HLO census
(``utils/overlap_evidence.py``) proving the chunked program exposes at least
as many overlappable collective/compute pairs as the monolithic one.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.parallel.parallel_state import AXIS_CP, AXIS_ULYSSES, ParallelState
from veomni_tpu.parallel.ring_attention import ring_attention_local
from veomni_tpu.parallel.sequence_parallel import (
    UlyssesLayout,
    _repeat_heads,
    a2a_gather_heads,
    a2a_scatter_heads,
    sp_specs,
    ulysses_monolithic,
)


@KERNEL_REGISTRY.register("ulysses", "ulysses_async")
def async_ulysses_attention(
    inner_attention: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    pstate: ParallelState,
    *,
    chunks: int = 4,
    **attn_kwargs,
):
    """Chunked-pipeline Ulysses attention; same contract as
    :func:`~veomni_tpu.parallel.sequence_parallel.ulysses_monolithic`.

    ``chunks`` is clamped to the head layout's feasible maximum; with an
    effective chunk count of 1 (or ``ulysses == 1``) this falls back to the
    monolithic path — numerics are identical either way.
    """
    u, cp = pstate.ulysses_size, pstate.cp_size
    if u == 1:
        return ulysses_monolithic(
            inner_attention, q, k, v, segment_ids, pstate, **attn_kwargs
        )
    layout = UlyssesLayout(u=u, hq=q.shape[2], hkv=k.shape[2])
    n_chunks = layout.clamp_chunks(max(int(chunks), 1))
    if n_chunks < 2:
        return ulysses_monolithic(
            inner_attention, q, k, v, segment_ids, pstate, **attn_kwargs
        )

    sinks = attn_kwargs.pop("sinks", None)
    qkv_spec, seg_spec, sinks_spec = sp_specs(
        pstate, have_sinks=sinks is not None, sinks_replicated=True
    )
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    hq, kv_rep, hkv_rep = layout.hq, layout.kv_rep, layout.hkv_rep
    qh = hq // n_chunks        # q heads per chunk (pre-a2a)
    kh = hkv_rep // n_chunks   # repeated-kv heads per chunk (pre-a2a)

    def body(q, k, v, seg, snk):
        # local shapes: q [b, s/(u*cp), hq, d]; k/v [..., hkv, d]
        b, sl, _, d = q.shape
        k = _repeat_heads(k, kv_rep)
        v = _repeat_heads(v, kv_rep)
        # the segment gather is chunk-invariant: do it once, outside the loop
        seg_full = jax.lax.all_gather(seg, AXIS_ULYSSES, axis=1, tiled=True)
        rank = jax.lax.axis_index(AXIS_ULYSSES)

        # chunk-major stacks: [K, b, s_local, qh|kh, d]
        qc = jnp.moveaxis(q.reshape(b, sl, n_chunks, qh, d), 2, 0)
        kc = jnp.moveaxis(k.reshape(b, sl, n_chunks, kh, d), 2, 0)
        vc = jnp.moveaxis(v.reshape(b, sl, n_chunks, kh, d), 2, 0)

        def scatter(qi, ki, vi):
            return (
                a2a_scatter_heads(qi),  # [b, s/cp, qh/u, d]
                a2a_scatter_heads(ki),
                a2a_scatter_heads(vi),
            )

        def attend(qg, kg, vg, c):
            snk_c = None
            if snk is not None:
                snk_c = layout.sink_slice(snk, c, n_chunks, rank)
            if cp > 1:
                out = ring_attention_local(
                    qg, kg, vg, seg_full, axis_name=AXIS_CP, sinks=snk_c,
                    **attn_kwargs,
                )
            else:
                out = inner_attention(
                    qg, kg, vg, segment_ids=seg_full, sinks=snk_c, **attn_kwargs
                )
            return a2a_gather_heads(out)  # [b, s_local, qh, d]

        # ---- software pipeline -------------------------------------------
        # warm-up: chunk 0's scatter a2a runs before any compute
        buffered = scatter(qc[0], kc[0], vc[0])

        def step(carry, xs):
            qg, kg, vg = carry                 # chunk c, already a2a'ed
            (qn, kn, vn), c = xs               # chunk c+1, pre-a2a
            nxt = scatter(qn, kn, vn)          # comm: chunk c+1 (independent
            out = attend(qg, kg, vg, c)        # of chunk c's compute)
            return nxt, out

        (qg, kg, vg), outs = jax.lax.scan(
            step, buffered,
            ((qc[1:], kc[1:], vc[1:]), jnp.arange(n_chunks - 1)),
        )
        # drain: last chunk's attention with no a2a left to hide
        last = attend(qg, kg, vg, n_chunks - 1)
        # outs [K-1, b, s_local, qh, d] -> [b, s_local, (K-1)*qh, d]
        outs = jnp.moveaxis(outs, 0, 2).reshape(b, sl, (n_chunks - 1) * qh, d)
        return jnp.concatenate([outs, last], axis=2)  # original head order

    in_specs = (qkv_spec, qkv_spec, qkv_spec, seg_spec, sinks_spec)
    fn = jax.shard_map(
        body,
        mesh=pstate.mesh,
        in_specs=in_specs,
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids, sinks)
