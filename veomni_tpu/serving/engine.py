"""In-process continuous-batching inference engine over a paged KV cache.

Execution model (one ``step()`` tick):

0. **Expire**: waiting (and still-prefilling) requests past their
   ``deadline_s`` are cancelled — blocks released, terminal ``deadline``
   status — before they can burn pool capacity nobody is waiting for.
1. **Admit**: free slots are filled from the waiting queue by the QoS pick
   (per-class stride weights, per-tenant round robin — plain FIFO with a
   single configured class); admission matches each prompt against the
   prefix cache (when enabled) and allocates only the uncached suffix's
   blocks — shared prompt blocks are referenced, not recomputed. Intake is
   bounded: past ``queue_bound`` waiting requests (or a tenant's
   ``tenant_max_inflight``), ``submit()`` load-sheds — the request comes
   back as a terminal ``rejected`` output (429-equivalent) instead of
   growing the queue without bound.
2. **Prefill (chunked)**: every admitted-but-unfinished prefill advances by
   ONE chunk per tick, so a long arriving prompt never blocks the running
   requests' next token for more than a chunk's worth of work. The chunk
   attends over the already-cached prefix through the sequence's block
   table (``paged_prefill_step``); the final chunk's logits sample the
   first token. With chunking off the whole uncached suffix is one chunk,
   and with the cache off too the path is the original monolithic prefill
   (``models/decode.py``'s jit + ``scatter_prompt_cache``) — byte-identical
   to the pre-cache engine.
3. **Capacity**: every decoding sequence is grown to cover its next write
   position; when blocks run out, cached (refcount-0) blocks are evicted
   LRU first, and only a truly dry pool preempts LIFO (recompute).
4. **Batched decode**: one jitted ``paged_decode_step`` over the fixed slot
   batch — per-slot positions, block tables, PRNG keys and sampling params.
   The gathered-context width (``nbb * block_size``, ``nbb`` the
   power-of-two bucket of the widest running block table) is the only shape
   that varies, so the compile count is bounded by the bucket count — never
   by request count or arrival pattern (``TRACE_COUNTS["paged_decode"]``).
   Chunked prefill adds one more bucketed program
   (``TRACE_COUNTS["paged_prefill"]``) over (chunk bucket, table bucket).
   With ``spec_k > 0`` the decode tick becomes **draft-then-verify**: a
   host-side drafting op (``spec_draft`` registry dispatch,
   ``serving/spec_decode.py``) proposes up to k tokens per slot, blocks for
   the drafted positions are claimed best-effort (never preempting), and
   ONE jitted ``paged_verify_step`` scores all k+1 positions — emitting
   1..k+1 tokens per slot per tick while staying token-exact with the
   one-token path (greedy AND seeded sampling; the verify step replays the
   same per-token PRNG key schedule). Rejected drafts roll their claimed
   blocks back the same tick. The verify program's compile count is
   bounded by (verify-width bucket x table bucket):
   ``TRACE_COUNTS["paged_verify"]`` is O(log2 k x log2 table-width). A
   tick where no slot drafts anything (or ``spec_k == 0``, the default)
   runs the plain decode step — byte-identical to the non-speculative
   engine.

Shapes the XLA programs see: slot batch ``S`` (static per engine), prompt
and chunk buckets (power-of-two), context buckets (power-of-two blocks).
Everything else — arrivals, lengths, finishes, preemptions, cache hits —
is host bookkeeping.
"""

from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.models import decode as decode_mod
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.ops.paged_attention import paged_impl_name
from veomni_tpu.ops.quantization import make_kv_pool, quantize_decode_params
from veomni_tpu.models.decode import no_cached_decode_reason, supports_cached_decode
from veomni_tpu.observability.metrics import LabelledRegistry, get_registry
from veomni_tpu.observability.request_trace import RequestTracer
from veomni_tpu.observability.spans import span
from veomni_tpu.resilience.faults import fault_point
from veomni_tpu.serving.api import (
    Request,
    RequestOutput,
    SamplingParams,
    StreamEvent,
)
from veomni_tpu.serving.kv_block_manager import KVBlockManager
from veomni_tpu.serving.prefix_cache import PrefixCache
from veomni_tpu.serving.scheduler import (
    Scheduler,
    SequenceState,
    parse_classes,
)
from veomni_tpu.utils.helper import host_floats
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# decode ticks the gauge moe.experts_touched_share looks back over: a live
# reading of the steady state, without the engine's first lone requests
ROUTING_WINDOW = 256


@dataclass
class EngineConfig:
    """Static engine shape knobs (all become compile-time constants)."""

    num_slots: int = 4  # decode batch width
    block_size: int = 16  # cache positions per KV block (power of two)
    max_model_len: int = 2048  # prompt + generated ceiling per request
    num_blocks: int = 0  # 0 -> 1 + num_slots * blocks(max_model_len)
    log_every_steps: int = 0  # 0 disables periodic metric logging
    # share full prompt blocks across requests (radix prefix cache over the
    # block pool; refcounted, LRU-evicted under pressure). OFF restores the
    # pre-cache engine exactly: exclusive blocks, monolithic prefill.
    prefix_cache: bool = True
    # prefill at most this many tokens per step() tick (0 = the whole
    # uncached suffix in one go). Bounds how long a newly arrived long
    # prompt can stall every running request's next token.
    prefill_chunk: int = 0
    # speculative decoding (draft-then-verify): propose up to spec_k tokens
    # per running slot per tick via the spec_draft strategy and verify them
    # in ONE batched jitted step — multi-token decode ticks, token-exact
    # with the one-token path. 0 (the default) keeps the seed decode path
    # byte-identical; the `off` strategy disables drafting even with k > 0.
    spec_k: int = 0
    spec_draft: str = "ngram"  # registry impl name (serving/spec_decode.py)
    # QoS classes, "name:weight,..." highest priority first (parsed by
    # scheduler.parse_classes). Two defaults ship: interactive (weight 4)
    # and batch (1). A SINGLE-class spec (e.g. "default") restores the
    # seed FIFO scheduler exactly and admits any priority label; with the
    # default two-class spec, an all-interactive stream (every Request's
    # default) is likewise behavior-identical to the seed.
    classes: str = "interactive:4,batch:1"
    # admission control / load-shedding: max waiting requests before
    # submit() sheds (terminal "rejected" status). 0 = unbounded (seed).
    queue_bound: int = 0
    # per-tenant cap on waiting+running requests. 0 = uncapped (seed).
    tenant_max_inflight: int = 0
    # KV-cache block storage mode: "none" keeps the dense compute-dtype
    # pool (bit-identical to the seed engine); "int8" stores blocks as an
    # int8 payload + per-(layer, block, row, kv-head) f32 scale sidecar —
    # ~4x the concurrent sequences per pool byte at f32, dequantized inside
    # the gathered attend (`paged_attention/xla_gather_q8`). "fp8" is
    # scaffolded behind the same interface but not yet shipped. Non-"none"
    # modes are NOT bit-exact: they ship under the fixed-seed quality gate
    # (serving/quality.py; docs/serving.md "Quantized serving tier").
    kv_quant: str = "none"
    # decode-path weight storage: "int8" stores the dense q/k/v/o and
    # gate/up/down projections as int8 + per-output-channel f32 scales,
    # dequantized in-kernel through the `decode_matmul/xla_q8` registry
    # impl. Embeddings, norms, biases, the lm head, routers and the MoE
    # expert stacks stay full-width.
    weight_quant: str = "none"
    # serving-side recompile detection: after this many step() ticks the
    # decode/prefill TRACE_COUNTS baselines are armed, and any later bucket
    # growth emits the trainer's loud rank-0 RECOMPILE warning + the
    # `recompiles` counter (a serving compile storm was previously
    # invisible — the trainer's detector deliberately watches only
    # train_step). 0 disables. The grace window absorbs the legitimate
    # warmup compiles of the pow2 bucket ladder.
    recompile_warmup_ticks: int = 256
    # metric-instance label: with N in-process engines (the scale-out
    # router) each engine's serve.* instruments get this label inserted
    # after the family prefix (serve.queue_depth -> serve.r0.queue_depth)
    # so replicas stop clobbering each other's process-wide gauges. ""
    # (the default) keeps the single-engine names byte-identical.
    metrics_label: str = ""

    def __post_init__(self):
        if self.block_size < 1 or (self.block_size & (self.block_size - 1)):
            raise ValueError("block_size must be a power of two")
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 disables)")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables)")
        if self.queue_bound < 0:
            raise ValueError("queue_bound must be >= 0 (0 = unbounded)")
        if self.tenant_max_inflight < 0:
            raise ValueError(
                "tenant_max_inflight must be >= 0 (0 = uncapped)"
            )
        if self.kv_quant not in ("none", "int8", "fp8"):
            raise ValueError(
                f"kv_quant must be 'none', 'int8' or 'fp8', got "
                f"{self.kv_quant!r}"
            )
        if self.weight_quant not in ("none", "int8"):
            raise ValueError(
                f"weight_quant must be 'none' or 'int8', got "
                f"{self.weight_quant!r}"
            )
        if self.metrics_label and not all(
            c.isalnum() or c in "_-" for c in self.metrics_label
        ):
            raise ValueError(
                f"metrics_label must be [A-Za-z0-9_-]*, got "
                f"{self.metrics_label!r}"
            )
        # malformed class specs fail at construction, not mid-serve
        parse_classes(self.classes)
        if self.num_blocks <= 0:
            per_seq = -(-self.max_model_len // self.block_size)
            self.num_blocks = 1 + self.num_slots * per_seq


@dataclass
class SharedPrograms:
    """The engine's compiled-program bundle, shareable across replicas.

    Every jitted step the engine builds closes over ``cfg`` ONLY — slot
    count, bucket widths and sampling state all arrive as (bucketed)
    arguments. Data-parallel replicas of the same model therefore trace
    and compile the exact same programs; without sharing, each replica
    re-traces its own copies and an N-replica router multiplies the warmup
    compile bill N ways (``TRACE_COUNTS`` counts traces, so the router's
    compile-count gate would catch it). The first replica builds the
    bundle, later replicas receive it via ``InferenceEngine(programs=...)``
    — adding a replica adds ZERO compiles. Donation is per-call, so a
    shared program donates each caller's own pool buffers safely."""

    cfg: TransformerConfig
    prefill: Any
    scatter: Any
    sample: Any
    decode_step: Any
    prefill_chunk_step: Any
    verify_step: Any
    cow: Any
    # a slot's state beside keys and values set at admission (None for a
    # model that has none: models/decode.py::init_layer_state)
    restore_state: Any = None


class InferenceEngine:
    """Continuous-batching generation over a fixed slot batch.

    ``submit()`` enqueues, ``step()`` advances every in-flight request by
    one token (and every in-flight prefill by one chunk), ``generate()``
    streams events, ``run()`` drains to completion. Single-threaded by
    design: callers own the pump loop.

    Threading contract (lock-discipline audit, docs/static-analysis.md):
    the engine holds no locks because only the pump thread touches its
    state. Anything another thread needs — the exporter's HTTP handlers,
    a load driver — goes through the thread-safe surfaces the engine
    *publishes into*: the metrics registry gauges/counters and the
    RequestTracer (both internally locked). Do not hand live engine or
    scheduler attributes to another thread."""

    def __init__(self, params, cfg: TransformerConfig,
                 config: Optional[EngineConfig] = None,
                 programs: Optional[SharedPrograms] = None):
        if not supports_cached_decode(cfg):
            why = no_cached_decode_reason(cfg)
            raise ValueError(
                f"config {cfg.model_type!r} has no cached-decode path"
                + (f" ({why})" if why else "")
                + "; the serving engine requires supports_cached_decode(cfg)"
            )
        self.cfg = cfg
        self.config = config or EngineConfig()
        ec = self.config
        # int8 decode weights are quantized ONCE at construction; the jitted
        # steps receive the QuantizedWeight leaves and dispatch the
        # decode-path matmuls through decode_matmul/xla_q8 (dequantizing
        # in-kernel). weight_quant="none" keeps the params bit-identical.
        self.params = (
            quantize_decode_params(params) if ec.weight_quant == "int8"
            else params
        )

        # what the engine holds follows from the config's layer kinds: keys
        # and values for the attention layers, and for a stack with
        # convolution layers their taps beside them (a running state a slot,
        # a snapshot a pool block: models/decode.py::init_layer_state)
        self.layer_state = decode_mod.init_layer_state(
            cfg, ec.num_slots, ec.num_blocks)
        if self.layer_state is not None and (
                ec.kv_quant != "none" or ec.weight_quant != "none"):
            raise ValueError(
                f"config {cfg.model_type!r} holds a state beside keys and "
                "values; kv_quant / weight_quant are not implemented for it"
            )
        shape = (decode_mod.kv_layers(cfg), ec.num_blocks, ec.block_size,
                 cfg.num_key_value_heads, cfg.head_dim)
        # kv_quant="int8" allocates QuantizedKV pools (int8 payload + f32
        # scale sidecar) behind the same pytree surface; every jitted step,
        # the CoW copy and the prefill scatter thread them unchanged
        self.k_pool = make_kv_pool(shape, ec.kv_quant, cfg.dtype)
        self.v_pool = make_kv_pool(shape, ec.kv_quant, cfg.dtype)
        logger.info(
            "decode attention: op paged_attention -> impl %s",
            paged_impl_name("paged_attention", self.k_pool),
        )
        self.blocks = KVBlockManager(ec.num_blocks, ec.block_size)
        # a state snapshot exists at block ends only: a hit resumes there
        self.prefix_cache = (
            PrefixCache(self.blocks,
                        resume_inside_block=self.layer_state is None)
            if ec.prefix_cache else None
        )
        # weight-publication epoch: bumped by swap_weights() even when the
        # prefix cache is disabled, so "which weights produced this
        # engine's KV" is always observable
        self.cache_epoch = 0
        # observability registry view: with a metrics_label every serve.*
        # instrument this engine (and its tracer) creates carries the
        # instance label — N router replicas stop clobbering each other's
        # process-wide gauges; unlabelled stays the plain shared registry
        reg = get_registry()
        if ec.metrics_label:
            reg = LabelledRegistry(reg, ec.metrics_label)
        self._registry = reg
        # per-request lifecycle tracing (request_trace.py): the scheduler
        # reports queued/admitted/preempted, the engine reports prefill/
        # first-token/finished — together they feed serve.queue_wait_s and
        # serve.tpot_s and the /debug/requests timelines
        self.tracer = RequestTracer(ec.num_slots, registry=reg)
        # draft-then-verify speculation: resolve the drafting strategy up
        # front (a typo'd spec_draft fails at construction, not mid-serve)
        # and widen admission headroom for the per-tick k-token growth. An
        # ops-config pin outranks the engine knob — including for the
        # enabled/disabled decision, so a pinned `off` also releases the
        # admission headroom and the per-tick draft calls, and a pinned
        # real strategy can switch speculation ON over a spec_draft="off"
        # engine (spec_k still gates: k=0 never speculates).
        from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
        from veomni_tpu.serving.spec_decode import resolve_draft_fn

        effective_draft = (
            KERNEL_REGISTRY.pinned("spec_draft") or ec.spec_draft
        )
        self._spec_enabled = ec.spec_k > 0 and effective_draft != "off"
        self._draft_fn = (
            resolve_draft_fn(ec.spec_draft) if self._spec_enabled else None
        )
        spec_headroom = (
            -(-ec.spec_k // ec.block_size) if self._spec_enabled else 0
        )
        if self._spec_enabled and self.layer_state is not None:
            raise ValueError(
                f"config {cfg.model_type!r}: speculative decoding (spec_k > "
                "0) over convolution layers is not implemented — the taps "
                "kept would have to be those of the last ACCEPTED row"
            )
        self.scheduler = Scheduler(ec.num_slots, self.blocks,
                                   tracer=self.tracer,
                                   prefix_cache=self.prefix_cache,
                                   spec_headroom_blocks=spec_headroom,
                                   classes=parse_classes(ec.classes),
                                   queue_bound=ec.queue_bound,
                                   tenant_max_inflight=ec.tenant_max_inflight)

        # compiled-program bundle: built once here, or adopted from a peer
        # replica with the same model config (SharedPrograms) so adding a
        # data-parallel replica adds zero traces/compiles
        if programs is not None:
            if programs.cfg != cfg:
                raise ValueError(
                    "SharedPrograms built for a different model config; "
                    "replicas can only share programs for the same model"
                )
            self.programs = programs
        else:
            self.programs = SharedPrograms(
                cfg=cfg,
                # prefill is the SAME jitted program greedy_generate uses
                # (shared prompt buckets, shared TRACE_COUNTS["prefill"])
                prefill=decode_mod._jitted(cfg)[0],
                scatter=jax.jit(
                    decode_mod.scatter_prompt_cache, donate_argnums=(0,)
                ),
                sample=jax.jit(decode_mod.sample_tokens),
                decode_step=self._build_decode_step(),
                prefill_chunk_step=self._build_prefill_chunk_step(),
                # built unconditionally — jit tracing is lazy, so a
                # non-speculative engine never pays for it, and a
                # speculative peer can adopt the bundle
                verify_step=self._build_verify_step(),
                # copy-on-write block duplication: src/dst are traced
                # scalars, so this compiles exactly once per bundle
                cow=jax.jit(
                    lambda k, v, src, dst: decode_mod.copy_block(
                        (k, v), src, dst
                    ),
                    donate_argnums=(0, 1),
                ),
                restore_state=(
                    None if self.layer_state is None else jax.jit(
                        decode_mod.restore_slot_state, donate_argnums=(0,))
                ),
            )
        self._prefill = self.programs.prefill
        self._scatter = self.programs.scatter
        self._sample = self.programs.sample
        self._decode_step = self.programs.decode_step
        self._prefill_chunk_step = self.programs.prefill_chunk_step
        self._verify_step = (
            self.programs.verify_step if self._spec_enabled else None
        )
        self._cow = self.programs.cow
        self._restore_state = self.programs.restore_state

        self._outputs: Dict[str, RequestOutput] = {}
        self._req_counter = 0
        self._step_counter = 0
        # metrics: TTFT accumulators (lifetime + window) + a
        # decode-throughput window + prefix-cache totals.
        #
        # The WINDOW accumulators are the one engine surface read AND
        # reset from outside the pump thread: metrics(reset_window=True)
        # from two concurrent scrapers (router poll + exporter) used to
        # race the reset — scraper A computes rates, scraper B zeroes the
        # window under it, A's reset then re-zeroes a window B already
        # claimed and a whole window of tokens vanishes from both
        # readings. Snapshot+reset is now atomic under _metrics_lock
        # (pump-side increments take it too; it is uncontended off-scrape).
        self._metrics_lock = threading.Lock()
        self._ttft_sum = 0.0
        self._ttft_n = 0
        self._win_ttft_sum = 0.0  # guarded-by: _metrics_lock
        self._win_ttft_n = 0  # guarded-by: _metrics_lock
        self._total_generated = 0
        self._window_tokens = 0  # guarded-by: _metrics_lock
        self._window_t0 = time.perf_counter()  # guarded-by: _metrics_lock
        self._prompt_tokens_total = 0
        self._cached_tokens_total = 0
        self._prefill_chunks_total = 0
        # speculative-decoding accounting: lifetime totals + a window pair
        # for the acceptance-rate gauge (resets with the metrics window)
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._win_spec_proposed = 0  # guarded-by: _metrics_lock
        self._win_spec_accepted = 0  # guarded-by: _metrics_lock
        # QoS / overload accounting: load-shed + deadline outcomes
        # (lifetime totals) and the goodput window — tokens from requests
        # that finished WITHIN their deadline (deadline-free requests
        # always qualify), attributed to the window their finish lands in
        self._rejected_total = 0
        self._shed_tokens_total = 0
        self._deadline_miss_total = 0
        self._goodput_tokens_total = 0
        self._win_goodput_tokens = 0  # guarded-by: _metrics_lock
        self._m_requests = reg.counter("serve.requests")
        self._m_tokens = reg.counter("serve.generated_tokens")
        self._m_ttft = reg.histogram("serve.ttft_s")
        self._m_queue = reg.gauge("serve.queue_depth")
        self._m_running = reg.gauge("serve.num_running")
        self._m_kv = reg.gauge("serve.kv_utilization")
        # the decode tick's live pages over its block-table entries: what
        # the paged_attend kernel reads of what a gather to the table's
        # width would have copied
        self._m_live_pages = reg.gauge("serve.paged_live_page_share")
        # decode ticks by the work their slots ask of the sampler: the same
        # predicate the program's own switch reads (decode.sampler_path), on
        # the same vocabulary, the head's output width
        self._head_width = jax.eval_shape(
            lambda p: decode_mod.lm_head_kernel(p, cfg), params).shape[-1]
        self._m_sampler_ticks = tuple(
            reg.counter(f"serve.sampler_ticks.{path}")
            for path in decode_mod.SAMPLER_PATHS
        )
        self._m_preempt = reg.gauge("serve.preemptions")
        self._m_tps = reg.gauge("serve.decode_tokens_per_sec")
        self._m_hit_rate = reg.gauge("serve.prefix_hit_rate")
        self._m_cached_tokens = reg.counter("serve.cached_tokens")
        # admissions that found a prefix in the cache
        self._m_hit_admissions = reg.counter("serve.prefix_hit_admissions")
        self._m_chunks = reg.counter("serve.prefill_chunks")
        # speculative decoding: drafted tokens sent to verification, how
        # many were accepted, and the window acceptance rate — the live
        # "is speculation paying for its verify width" gauges
        self._m_spec_proposed = reg.counter("serve.spec_proposed")
        self._m_spec_accepted = reg.counter("serve.spec_accepted")
        self._m_spec_rate = reg.gauge("serve.spec_acceptance_rate")
        # overload / QoS outcomes: requests load-shed at submit (the
        # 429-equivalent), the offered tokens those sheds turned away,
        # deadline outcomes (cancelled waiting/prefilling + finished-late),
        # and goodput — tokens from requests that met their deadline
        self._m_rejected = reg.counter("serve.rejected")
        self._m_shed_tokens = reg.counter("serve.shed_tokens")
        self._m_deadline_misses = reg.counter("serve.deadline_misses")
        self._m_goodput = reg.gauge("serve.goodput_tokens_per_sec")
        # HBM capacity accounting (observability/devmem.py): pool bytes are
        # static per engine; the concurrent-sequence estimates answer "how
        # many max-length users fit" (total, and with the blocks free now)
        self._m_kv_pool_bytes = reg.gauge("serve.kv_pool_bytes")
        self._m_kv_block_bytes = reg.gauge("serve.kv_block_bytes")
        self._m_kv_max_seqs = reg.gauge("serve.kv_max_concurrent_seqs")
        self._m_kv_free_seqs = reg.gauge("serve.kv_free_concurrent_seqs")
        if self.layer_state is not None:
            # the state beside keys and values: its bytes; admissions behind
            # a cached prefix that handed the device the uncached positions
            # alone (their state came from the last cached block's snapshot:
            # the rows each slot's admission has computed are counted here);
            # and the decode ticks' routing census (assignments, experts
            # with a row a layer a tick, their share of the experts there
            # are over the last ROUTING_WINDOW ticks, the busiest over the mean)
            reg.gauge("serve.conv_state_bytes").set(float(sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.layer_state))))
            self._m_taps_restored = reg.counter("serve.conv_taps_restored")
            self._prefill_rows = np.zeros(ec.num_slots, np.int64)
            self._m_moe_assignments = reg.counter("moe.assignments")
            self._m_moe_touched = reg.counter("moe.experts_touched")
            self._m_moe_touched_share = reg.gauge("moe.experts_touched_share")
            self._routing_window = collections.deque(maxlen=ROUTING_WINDOW)
            self._m_moe_load = reg.gauge("moe.load_max_over_mean")
        cap = self.kv_capacity()
        self._m_kv_pool_bytes.set(cap["pool_bytes"])
        self._m_kv_block_bytes.set(cap["block_bytes"])
        self._m_kv_max_seqs.set(cap["max_concurrent_seqs"])
        self._m_kv_free_seqs.set(cap["free_concurrent_seqs"])
        # serving-side recompile detection over the decode-bucket trace
        # counters: armed after the warmup grace window (step()), so a
        # mid-run compile storm gets the same loud RECOMPILE treatment the
        # train step has had since PR 4
        self._recompile_detector = None
        if ec.recompile_warmup_ticks > 0:
            from veomni_tpu.observability.goodput import RecompileDetector

            # the WHOLE trace-count dict is watched (no key filter): every
            # engine-side compile counter — including the chunked-prefill
            # one, TRACE_COUNTS["paged_prefill"], and any counter a future
            # prefill/decode path adds — is storm-detected without anyone
            # remembering to extend a key list. Chunked-prefill coverage is
            # pinned by a regression test (test_fleet_observatory.py).
            self._recompile_detector = RecompileDetector(
                [("serve_decode", decode_mod.TRACE_COUNTS)], registry=reg,
            )

    # ------------------------------------------------------------ jit plumbing
    def _build_decode_step(self):
        cfg = self.cfg

        def impl(params, k_pool, v_pool, tables, positions, tokens, keys,
                 temps, top_ks, top_ps, *state):
            # ``state``: the layer state, last, for a model that has one (it
            # comes back last too); no argument at all for one that has none
            decode_mod.TRACE_COUNTS["paged_decode"] += 1  # trace-time only
            logits, pools = decode_mod.paged_decode_step(
                params, cfg, (k_pool, v_pool, *state), tables, positions,
                tokens
            )
            # per-slot key split mirrors the scan decode's (carry, sample)
            split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            nxt = decode_mod.sample_tokens(
                logits, split[:, 1], temps, top_ks, top_ps
            )
            return (nxt, split[:, 0], *pools)

        from veomni_tpu.observability.cost import instrument_jit

        donate = (1, 2) if self.layer_state is None else (1, 2, 10)
        return instrument_jit(
            "paged_decode", jax.jit(impl, donate_argnums=donate),
            # args: (params, k_pool, v_pool, tables, ...) — the table width
            # bucket is the only varying shape
            bucket_fn=lambda a: f"s{a[3].shape[0]}_nbb{a[3].shape[1]}",
        )

    def _build_prefill_chunk_step(self):
        cfg = self.cfg

        def impl(params, k_pool, v_pool, table, start, tokens, chunk_len,
                 chunk_bucket, *state_slot):
            # ``state_slot``: (the layer state, the sequence's slot) for a
            # model that has a state; nothing for one that has none
            decode_mod.TRACE_COUNTS["paged_prefill"] += 1  # trace-time only
            return decode_mod.paged_prefill_step(
                params, cfg, (k_pool, v_pool, *state_slot[:1]), table, start,
                tokens, chunk_len, chunk_bucket, *state_slot[1:],
            )

        from veomni_tpu.observability.cost import instrument_jit

        donate = (1, 2) if self.layer_state is None else (1, 2, 8)
        return instrument_jit(
            "paged_prefill",
            jax.jit(impl, static_argnums=(7,), donate_argnums=donate),
            static_argnums=(7,),
            # (chunk bucket, table-width bucket) — the two compile axes
            bucket_fn=lambda a: f"cb{a[7]}_nbb{a[3].shape[0]}",
        )

    def _build_verify_step(self):
        cfg = self.cfg

        def impl(params, k_pool, v_pool, tables, positions, tokens, n_input,
                 keys, temps, top_ks, top_ps):
            decode_mod.TRACE_COUNTS["paged_verify"] += 1  # trace-time only
            logits, (k_pool, v_pool) = decode_mod.paged_verify_step(
                params, cfg, (k_pool, v_pool), tables, positions, tokens,
                n_input,
            )
            targets, n_emit, new_keys = decode_mod.verify_accept(
                logits, tokens, n_input, keys, temps, top_ks, top_ps
            )
            return targets, n_emit, new_keys, k_pool, v_pool

        from veomni_tpu.observability.cost import instrument_jit

        return instrument_jit(
            "paged_verify", jax.jit(impl, donate_argnums=(1, 2)),
            # args: (params, k_pool, v_pool, tables, positions, tokens, ...)
            # — (table-width bucket, verify-width bucket) are the two
            # varying shapes, each a power of two: O(log2 x log2) compiles
            bucket_fn=lambda a: (
                f"s{a[3].shape[0]}_nbb{a[3].shape[1]}_kb{a[5].shape[1]}"
            ),
        )

    # ----------------------------------------------------------------- intake
    def submit(self, request: Union[Request, Iterable[int]],
               sampling: Optional[SamplingParams] = None) -> str:
        """Enqueue a request (a ``Request`` or a bare prompt-id iterable).
        Returns the request id; tokens arrive via ``step()`` events.

        Under overload (waiting queue at ``queue_bound`` or the tenant at
        ``tenant_max_inflight``) the request is **load-shed**: the returned
        id's ``RequestOutput`` is already terminal with
        ``finish_reason="rejected"`` (the 429-equivalent; no exception — an
        overloaded server refusing work is an outcome, not an error).
        Malformed requests (empty prompt, over-length, unknown priority
        class) still raise ``ValueError``."""
        fault_point("serve.admit")
        if not isinstance(request, Request):
            request = Request(prompt_ids=[int(t) for t in request],
                              sampling=sampling or SamplingParams())
        if not request.request_id:
            # skip over user-supplied ids that happen to look like ours
            while f"req-{self._req_counter}" in self._outputs:
                self._req_counter += 1
            request.request_id = f"req-{self._req_counter}"
            self._req_counter += 1
        if request.request_id in self._outputs:
            raise ValueError(f"duplicate request id {request.request_id!r}")
        if not request.prompt_ids:
            raise ValueError("empty prompt")
        sp = request.sampling
        if sp.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(request.prompt_ids) + sp.max_new_tokens
        if total > self.config.max_model_len:
            raise ValueError(
                f"prompt+max_new_tokens={total} exceeds max_model_len="
                f"{self.config.max_model_len}"
            )
        if self.blocks.blocks_for(total) > self.config.num_blocks - 1:
            raise ValueError(
                f"request needs {self.blocks.blocks_for(total)} blocks; pool "
                f"has {self.config.num_blocks - 1}"
            )
        if request.deadline_s is not None and request.deadline_s < 0:
            raise ValueError("deadline_s must be >= 0 (None disables)")
        seq = SequenceState(
            request=request,
            rng=np.asarray(jax.random.PRNGKey(sp.seed)),
        )
        out = RequestOutput(
            request_id=request.request_id,
            prompt_ids=list(request.prompt_ids),
        )
        # may raise ValueError (unknown priority class) BEFORE the output
        # registers — malformed is an error, overloaded is an outcome
        accepted = self.scheduler.add(seq)
        self._outputs[request.request_id] = out
        self._m_requests.inc()
        if not accepted:
            # load-shed: terminal REJECTED, counted with the offered work
            # (prompt + requested generation) it turned away
            out.finished = True
            out.finish_reason = "rejected"
            shed = len(request.prompt_ids) + sp.max_new_tokens
            self._rejected_total += 1
            self._shed_tokens_total += shed
            self._m_rejected.inc()
            self._m_shed_tokens.inc(shed)
            self.tracer.on_rejected(request.request_id)
            return request.request_id
        self._m_queue.set(self.scheduler.queue_depth)
        return request.request_id

    # ---------------------------------------------------------- weight swap
    def swap_weights(self, params) -> Dict[str, int]:
        """Hot-swap the engine's weights in place, invalidating all cached
        KV. ``params`` is the UNQUANTIZED pytree; the engine re-applies
        its own ``weight_quant`` storage transform exactly as at
        construction, so a quantized tier swaps quantized buffers.

        Contract (docs/serving.md "Versioned weight publication"):

        * the engine must be drained — no waiting or running sequences
          (the router's PUBLISHING state guarantees this; a direct caller
          gets a hard error, never a mid-stream weight change);
        * the payload must be shape/dtype-congruent with the current
          weights — a mismatched payload is a different model, refused
          before any state changes (it would also silently retrace every
          jitted program);
        * the prefix cache is flushed under a bumped ``cache_epoch``
          (stale KV from the old weights becomes unreachable) and the
          block-manager no-leak identity is conserved across the flush;
        * ZERO new traces: the jitted steps take params as per-call
          arguments, so congruent buffers reuse every compiled program.

        Returns ``{"flushed_blocks": n, "cache_epoch": e}``.
        """
        if self.scheduler.has_work:
            raise RuntimeError(
                "swap_weights on a busy engine: drain waiting/running "
                "sequences first (the router's PUBLISHING state does this)"
            )
        new_params = (
            quantize_decode_params(params)
            if self.config.weight_quant == "int8" else params
        )
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if old_def != new_def:
            raise ValueError(
                "swap_weights payload tree structure differs from the "
                "serving weights: a publish must carry the same model"
            )
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            o_sig = (getattr(o, "shape", None), getattr(o, "dtype", None))
            n_sig = (getattr(n, "shape", None), getattr(n, "dtype", None))
            if o_sig != n_sig:
                raise ValueError(
                    f"swap_weights payload leaf {i} is {n_sig}, serving "
                    f"weights have {o_sig}: shape/dtype-incongruent "
                    "payloads are refused (they would retrace)"
                )
        self.params = new_params
        flushed = (
            self.prefix_cache.flush() if self.prefix_cache is not None
            else 0
        )
        self.cache_epoch += 1
        self._registry.counter("serve.weights_swaps").inc()
        self._registry.counter("serve.weights_flushed_blocks").inc(flushed)
        return {"flushed_blocks": flushed, "cache_epoch": self.cache_epoch}

    # ------------------------------------------------------------------ drive
    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def step(self) -> List[StreamEvent]:
        """One engine tick: expire deadlines, admit, advance every in-flight
        prefill by one chunk, secure blocks, batched decode. Returns every
        token event produced this tick (cancellations produce none — their
        terminal status lands on the RequestOutput)."""
        events: List[StreamEvent] = []
        self._expire_deadlines()
        for seq in self.scheduler.admit():
            self._start_prefill(seq)
        # one chunk per prefilling sequence per tick: decode of running
        # requests interleaves between chunks, so a long prompt's TTFT cost
        # to everyone else is bounded by a chunk, not by the prompt
        prefilling = [s for _, s in self.scheduler.running() if s.prefilling]
        for seq in prefilling:
            with span("serve.prefill"):
                events.extend(self._prefill_tick(seq))
        self.scheduler.ensure_decode_capacity()
        decodable = [(i, s) for i, s in self.scheduler.running()
                     if not s.prefilling]
        if decodable:
            with span("serve.decode"):
                events.extend(self._decode_tick(decodable))
        elif not events and not prefilling and self.scheduler.has_work:
            raise RuntimeError(
                "scheduler stalled: waiting requests but nothing running "
                "and nothing admissible (pool misconfigured?)"
            )
        self._step_counter += 1
        self._m_queue.set(self.scheduler.queue_depth)
        self._m_running.set(self.scheduler.num_running)
        self._m_kv.set(self.blocks.utilization())
        self._m_preempt.set(self.scheduler.preemption_count)
        per_seq = max(1, self.blocks.blocks_for(self.config.max_model_len))
        self._m_kv_free_seqs.set(self.blocks.num_free // per_seq)
        det = self._recompile_detector
        if det is not None:
            grace = self.config.recompile_warmup_ticks
            if self._step_counter == grace:
                det.arm()  # warmup bucket compiles absorbed
            elif self._step_counter > grace:
                det.check()
        le = self.config.log_every_steps
        if le and self._step_counter % le == 0:
            # non-resetting read: periodic logging must not clobber the
            # throughput window of an external metrics() consumer
            m = self.metrics(reset_window=False)
            logger.info(
                "serve step %d | %s", self._step_counter,
                " ".join(f"{k}={v:.4g}" for k, v in sorted(m.items())),
            )
        return events

    def generate(self, requests: Optional[Iterable] = None
                 ) -> Iterator[StreamEvent]:
        """Streaming interface: submit ``requests`` (if given), then yield
        token events until all in-flight work drains. More requests may be
        ``submit()``-ed between yields."""
        for r in requests or ():
            self.submit(r)
        while self.has_work:
            yield from self.step()

    def run(self, requests: Optional[Iterable] = None
            ) -> Dict[str, RequestOutput]:
        """Drain ``generate()`` and return {request_id: RequestOutput} for
        every finished request, handing ownership to the caller — retained
        outputs are released so a long-running pump loop doesn't accumulate
        one token list per request ever served."""
        for _ in self.generate(requests):
            pass
        done = {rid: o for rid, o in self._outputs.items() if o.finished}
        for rid in done:
            del self._outputs[rid]
        return done

    def backdate_submit_time(self, request_id: str,
                             submit_time: float) -> None:
        """Rewind a just-submitted request's deadline clock to an upstream
        arrival time. ``deadline_s`` measures from when the USER submitted;
        a front door (the scale-out router) that held the request in its
        own QoS queue forwards the original intake time here so router
        wait counts against the deadline exactly like engine queue wait.
        Only ever moves the clock BACK (min), and only while the request
        is still in flight."""
        seq = self._find_seq(request_id)
        if seq is not None:
            seq.submit_time = min(seq.submit_time, float(submit_time))

    def get_output(self, request_id: str) -> Optional[RequestOutput]:
        """Read-only peek at a request's output, in flight or finished,
        without releasing it. The router's replica-kill path uses this to
        decide each stranded request's fate: no tokens yet -> safe to
        re-dispatch to a survivor; tokens already streamed -> terminal
        ``cancelled`` (re-running it elsewhere would duplicate output)."""
        return self._outputs.get(request_id)

    def pop_output(self, request_id: str) -> Optional[RequestOutput]:
        """Release and return one finished request's output (streaming
        callers pop after seeing its finished event). Refuses while the
        request is in flight — the engine still appends tokens to it."""
        out = self._outputs.get(request_id)
        if out is not None and not out.finished:
            raise ValueError(f"request {request_id!r} is still in flight")
        return self._outputs.pop(request_id, None)

    # ----------------------------------------------------- QoS / cancellation
    def cancel(self, request_id: str, reason: str = "cancelled") -> bool:
        """Cancel an in-flight (waiting, prefilling, or decoding) request:
        its blocks — including partially-claimed chunked-prefill blocks and
        a pinned copy-on-write source — return to the pool, and its output
        turns terminal with ``finish_reason=reason``. Tokens already
        emitted stay on the output. Returns False when the id is unknown
        or already finished."""
        out = self._outputs.get(request_id)
        if out is None or out.finished:
            return False
        seq = self._find_seq(request_id)
        if seq is None:
            return False
        self._cancel_seq(seq, reason)
        return True

    def _find_seq(self, request_id: str) -> Optional[SequenceState]:
        for s in self.scheduler.waiting:
            if s.seq_id == request_id:
                return s
        for _, s in self.scheduler.running():
            if s.seq_id == request_id:
                return s
        return None

    def _expire_deadlines(self) -> None:
        """Cancel waiting/prefilling requests past their deadline (terminal
        ``deadline`` status) so pool capacity goes to requests that can
        still meet theirs. Runs at the top of every tick, BEFORE admission,
        so freed blocks admit someone else the same tick."""
        for seq in self.scheduler.expired():
            self._cancel_seq(seq, "deadline")

    def _cancel_seq(self, seq: SequenceState, reason: str) -> None:
        self.scheduler.cancel(seq)
        out = self._outputs[seq.seq_id]
        out.finished = True
        out.finish_reason = reason
        if reason == "deadline":
            out.deadline_missed = True
            self._deadline_miss_total += 1
            self._m_deadline_misses.inc()
        # offered work the cancellation turned away, symmetric with the
        # submit-time rejection accounting: a cancel that produced NOTHING
        # (expired in the queue / mid-initial-prefill) sheds prompt +
        # requested generation exactly like a reject; one that already
        # emitted tokens sheds only the un-generated remainder (the
        # delivered tokens stay on the output and were counted generated)
        if seq.generated:
            shed = seq.request.sampling.max_new_tokens - len(seq.generated)
        else:
            shed = (len(seq.request.prompt_ids)
                    + seq.request.sampling.max_new_tokens)
        if shed > 0:
            self._shed_tokens_total += shed
            self._m_shed_tokens.inc(shed)
        tl = self.tracer.on_finished(seq.seq_id, reason, len(seq.generated))
        if tl is not None:
            out.queue_wait_s = tl.queue_wait_s
            out.tpot_s = tl.tpot_s
            out.preemptions = tl.preemptions
        self._m_queue.set(self.scheduler.queue_depth)

    # --------------------------------------------------------------- internals
    def _start_prefill(self, seq: SequenceState) -> None:
        """Per-admission bookkeeping: prefix-cache accounting and the
        copy-on-write device copy for a fully-cached prompt's divergence
        block (the copy MUST land before any chunk writes into it)."""
        p = len(seq.recompute_prompt)
        self._prompt_tokens_total += p
        if seq.cached_tokens:
            self._cached_tokens_total += seq.cached_tokens
            self._m_cached_tokens.inc(seq.cached_tokens)
            self._m_hit_admissions.inc()
        self._m_hit_rate.set(
            self._cached_tokens_total / max(1, self._prompt_tokens_total)
        )
        out = self._outputs.get(seq.seq_id)
        if out is not None:
            out.cached_tokens = seq.cached_tokens
        if self.layer_state is not None:
            # the slot's state: the snapshot of the last cached block of the
            # prompt, or a sequence's start. The cached positions are not
            # computed again; a request preempted and recomputed comes back
            # through here too, behind the blocks it left in the cache
            bs = self.config.block_size
            hit = seq.cached_tokens > 0
            assert seq.cow_src is None and seq.cached_tokens % bs == 0
            src = (self.blocks.table(seq.seq_id)[seq.cached_tokens // bs - 1]
                   if hit else KVBlockManager.NULL_BLOCK)
            self.layer_state = self._restore_state(
                self.layer_state, jnp.int32(seq.slot), jnp.int32(src),
                jnp.bool_(hit),
            )
            self._prefill_rows[seq.slot] = 0
        if seq.cow_src is not None:
            dst = self.blocks.table(seq.seq_id)[-1]
            self.k_pool, self.v_pool = self._cow(
                self.k_pool, self.v_pool,
                jnp.int32(seq.cow_src), jnp.int32(dst),
            )
            # the source was pinned at admission so claiming fresh blocks
            # could not evict it before this copy; release it now
            self.blocks.release_block(seq.cow_src)
            seq.cow_src = None

    def _prefill_tick(self, seq: SequenceState) -> List[StreamEvent]:
        """Advance one sequence's prefill by one chunk. The legacy
        monolithic path (cache miss + chunking off) is kept verbatim so a
        cache-off engine is byte-identical to the pre-cache one."""
        fault_point("serve.prefill")
        if (seq.cached_tokens == 0 and self.config.prefill_chunk <= 0
                and self.layer_state is None):
            return self._prefill_monolithic(seq)
        return self._prefill_chunk(seq)

    def _prefill_monolithic(self, seq: SequenceState) -> List[StreamEvent]:
        bs = self.config.block_size
        prompt = seq.recompute_prompt
        pt = len(prompt)
        pb = decode_mod._bucket_pow2(pt, floor=max(16, bs))
        # padded on the host: an eager `zeros(pb).at[:pt].set(ids)` is one
        # more compiled program for every new prompt length, inside TTFT
        tokens = np.zeros((1, pb), np.int32)
        tokens[0, :pt] = prompt
        logits, caches = self._prefill(
            self.params, jnp.asarray(tokens), jnp.int32(pt), pb, pb
        )
        # scatter the contiguous prompt cache into this sequence's blocks;
        # tail entries past the real allocation point at the null block
        ids = self.blocks.table(seq.seq_id)
        ids = ids + [KVBlockManager.NULL_BLOCK] * (pb // bs - len(ids))
        self.k_pool, self.v_pool = self._scatter(
            (self.k_pool, self.v_pool), caches,
            jnp.asarray(ids, jnp.int32),
        )
        self._prefill_chunks_total += 1
        self._m_chunks.inc()
        return self._finish_prefill(seq, logits)

    def _prefill_chunk(self, seq: SequenceState) -> List[StreamEvent]:
        bs = self.config.block_size
        prompt = seq.recompute_prompt
        p = len(prompt)
        start = seq.prefill_pos
        budget = self.config.prefill_chunk or (p - start)
        clen = min(budget, p - start)
        cb = decode_mod._bucket_pow2(clen, floor=max(16, bs))
        tokens = np.zeros(cb, np.int32)  # on the host, as the table below
        tokens[:clen] = prompt[start:start + clen]
        ids = self.blocks.table(seq.seq_id)
        nbb = decode_mod._bucket_pow2(len(ids), floor=1)
        table = np.zeros(nbb, np.int32)  # null-block padded
        table[: len(ids)] = ids
        extra = (() if self.layer_state is None
                 else (self.layer_state, jnp.int32(seq.slot)))
        logits, (self.k_pool, self.v_pool, *state) = self._prefill_chunk_step(
            self.params, self.k_pool, self.v_pool, jnp.asarray(table),
            jnp.int32(start), jnp.asarray(tokens), jnp.int32(clen), cb,
            *extra,
        )
        if state:
            self.layer_state = state[0]
            self._prefill_rows[seq.slot] += clen
        seq.prefill_pos = start + clen
        self._prefill_chunks_total += 1
        self._m_chunks.inc()
        if seq.prefill_pos < p:
            return []  # more chunks next tick; decode interleaves meanwhile
        return self._finish_prefill(seq, logits)

    def _finish_prefill(self, seq: SequenceState,
                        logits) -> List[StreamEvent]:
        """Shared prefill tail: sample the first token from the last prompt
        row's logits, publish the full prompt blocks to the prefix cache,
        and flip the sequence into the decode batch."""
        sp = seq.request.sampling
        rng, sub = jax.random.split(seq.rng)
        seq.rng = np.asarray(rng)
        first = int(self._sample(
            logits.astype(jnp.float32), sub[None],
            jnp.full((1,), sp.temperature, jnp.float32),
            jnp.full((1,), sp.top_k, jnp.int32),
            jnp.full((1,), sp.top_p, jnp.float32),
        )[0])
        pt = len(seq.recompute_prompt)
        if (self.layer_state is not None and seq.cached_tokens
                and self._prefill_rows[seq.slot] == pt - seq.cached_tokens):
            # the hit was kept: no cached position went to the device again
            self._m_taps_restored.inc()
        seq.prefill_len = pt
        seq.pos = pt  # the pending token's write position
        seq.prefill_pos = pt
        seq.prefilling = False
        # prompt blocks become shareable the moment they hold real KV: a
        # staggered arrival with the same system prompt hits immediately
        self.scheduler.cache_insert(seq)
        self.tracer.on_prefill_done(seq.seq_id,
                                    cached_tokens=seq.cached_tokens)
        if seq.first_token_time is None:
            seq.first_token_time = time.perf_counter()
            ttft = seq.first_token_time - seq.submit_time
            self._outputs[seq.seq_id].ttft_s = ttft
            self._ttft_sum += ttft
            self._ttft_n += 1
            with self._metrics_lock:
                self._win_ttft_sum += ttft
                self._win_ttft_n += 1
            self._m_ttft.observe(ttft)
            self.tracer.on_first_token(seq.seq_id)
        else:
            # post-preemption re-admission: this prefill's resume token is
            # part of the DECODE phase (it lands after first_token), so it
            # counts toward the tracer's per-tick decode-token tally —
            # serve.tpot_s divides by exactly the tokens inside its wall
            self.tracer.on_decode_tokens(seq.seq_id, 1)
        return [self._emit(seq, first)]

    def _decode_tick(
        self, running: List[Tuple[int, SequenceState]]
    ) -> List[StreamEvent]:
        fault_point("serve.decode_tick")
        if self._spec_enabled:
            return self._spec_decode_tick(running)
        return self._plain_decode_tick(running)

    def _fill_slot_arrays(self, running: List[Tuple[int, SequenceState]]):
        """Per-slot batch rows shared by the plain and verify decode
        ticks: null-padded block tables (width = the power-of-two bucket
        of the widest running table — the step's only varying table
        shape), positions, PRNG keys and per-slot sampling params. Keeping
        ONE assembly path is what keeps the two ticks' batches — and
        therefore their token streams — in lockstep."""
        nbb = decode_mod._bucket_pow2(
            max(self.blocks.num_allocated(s.seq_id) for _, s in running),
            floor=1,
        )
        S = self.config.num_slots
        tables = np.zeros((S, nbb), np.int32)  # null-block padded
        positions = np.zeros(S, np.int32)
        keys = np.zeros((S, 2), np.uint32)
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.ones(S, np.float32)
        for slot, seq in running:
            tbl = self.blocks.table(seq.seq_id)
            tables[slot, : len(tbl)] = tbl
            positions[slot] = seq.pos
            keys[slot] = seq.rng
            sp = seq.request.sampling
            temps[slot] = sp.temperature
            top_ks[slot] = sp.top_k
            top_ps[slot] = sp.top_p
        # an idle slot attends position 0 of the null block: one page
        bs = self.config.block_size
        self._m_live_pages.set(float((positions // bs + 1).sum()) / tables.size)
        self._m_sampler_ticks[decode_mod.sampler_path(
            temps, top_ks, top_ps, self._head_width)].inc()
        return tables, positions, keys, temps, top_ks, top_ps

    def _plain_decode_tick(
        self, running: List[Tuple[int, SequenceState]]
    ) -> List[StreamEvent]:
        tables, positions, keys, temps, top_ks, top_ps = (
            self._fill_slot_arrays(running)
        )
        tokens = np.zeros(self.config.num_slots, np.int32)
        for slot, seq in running:
            tokens[slot] = seq.last_token

        extra = () if self.layer_state is None else (self.layer_state,)
        nxt, new_keys, self.k_pool, self.v_pool, *state = self._decode_step(
            self.params, self.k_pool, self.v_pool,
            jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(tokens),
            jnp.asarray(keys), jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), *extra,
        )
        nxt = np.asarray(nxt)
        new_keys = np.asarray(new_keys)
        if state:
            self.layer_state = state[0]
            self._note_routing(np.asarray(state[0]["moe_counts"]))

        events = []
        for slot, seq in running:
            seq.rng = new_keys[slot]
            seq.pos += 1  # the freshly sampled token's write position
            # per-tick emitted-token count: keeps serve.tpot_s honest for
            # any multi-token tick (the verify path lands several)
            self.tracer.on_decode_tokens(seq.seq_id, 1)
            events.append(self._emit(seq, int(nxt[slot])))
        return events

    def _note_routing(self, counts: np.ndarray) -> None:
        """The decode tick's routing census, ``counts [expert layers,
        experts]``: every slot's row is routed and multiplied, idle or not."""
        if not counts.size:
            return
        touched = int((counts > 0).sum())
        self._m_moe_assignments.inc(int(counts.sum()))
        self._m_moe_touched.inc(touched)
        self._routing_window.append(touched)
        self._m_moe_touched_share.set(
            sum(self._routing_window) / (len(self._routing_window) * counts.size))
        mean = counts.mean(axis=1)
        self._m_moe_load.set(float((counts.max(axis=1) / np.maximum(mean, 1e-9)).max()))

    def _spec_decode_tick(
        self, running: List[Tuple[int, SequenceState]]
    ) -> List[StreamEvent]:
        """Draft-then-verify decode tick: host-side drafting per slot,
        best-effort speculative block claims, ONE batched verify step, then
        per-slot accept/rollback. Token-exact with the one-token path: the
        verify step replays the same logits contexts and the same per-token
        PRNG key schedule, and only emits tokens the target model would
        have emitted anyway."""
        ec = self.config
        bs = ec.block_size
        # 1) draft (host, cheap) + claim blocks for the drafted positions.
        # Per-slot k: a slot whose drafter proposes nothing — or whose
        # remaining token budget is 0 — degrades to k=0 (pure decode for
        # that slot) instead of widening everyone's verify step.
        drafts: Dict[int, List[int]] = {}
        pre_lens: Dict[int, int] = {}
        for slot, seq in running:
            sp = seq.request.sampling
            # a verify tick emits up to k+1 tokens; never draft past the
            # request's remaining budget (parity: the one-token path would
            # have stopped at max_new_tokens too)
            budget = sp.max_new_tokens - len(seq.generated) - 1
            k = min(ec.spec_k, max(0, budget))
            d = list(self._draft_fn(seq.recompute_prompt, k))[:k] if k else []
            if d:
                pre = self.blocks.num_allocated(seq.seq_id)
                k_granted, claimed = self.scheduler.claim_speculative(
                    seq, len(d)
                )
                d = d[:max(0, k_granted)]
                if not d and claimed:
                    # pool too dry to cover even one draft: roll the claim
                    # back immediately, this slot decodes plainly
                    self.blocks.shrink(seq.seq_id, pre)
                else:
                    pre_lens[slot] = pre
            drafts[slot] = d
        if not any(drafts.values()):
            # nothing to verify anywhere: the plain decode step (same
            # compiled program as the non-speculative engine) is strictly
            # cheaper than a kb=2 verify
            return self._plain_decode_tick(running)

        # 2) ONE batched verify step over all slots. kb (committed token +
        # widest draft, power-of-two) and the table-width bucket are the
        # only varying shapes — compile count stays O(log2 k x log2 width).
        kb = decode_mod._bucket_pow2(
            1 + max(len(d) for d in drafts.values()), floor=2
        )
        tables, positions, keys, temps, top_ks, top_ps = (
            self._fill_slot_arrays(running)
        )
        S = ec.num_slots
        tokens = np.zeros((S, kb), np.int32)
        n_input = np.ones(S, np.int32)
        for slot, seq in running:
            d = drafts[slot]
            tokens[slot, 0] = seq.last_token
            if d:
                tokens[slot, 1:1 + len(d)] = d
            n_input[slot] = 1 + len(d)

        targets, n_emit, new_keys, self.k_pool, self.v_pool = (
            self._verify_step(
                self.params, self.k_pool, self.v_pool, jnp.asarray(tables),
                jnp.asarray(positions), jnp.asarray(tokens),
                jnp.asarray(n_input), jnp.asarray(keys), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
            )
        )
        targets = np.asarray(targets)
        n_emit = np.asarray(n_emit)
        new_keys = np.asarray(new_keys)

        # 3) per-slot accept + emit + rollback
        events: List[StreamEvent] = []
        for slot, seq in running:
            seq.rng = new_keys[slot]
            m = int(n_emit[slot])
            proposed = len(drafts[slot])
            accepted = m - 1  # drafts matching target sampling, in order
            # truncate at eos / budget BEFORE emitting so the tick's token
            # count (and the accepted rollup) reflect what actually lands
            sp = seq.request.sampling
            emit: List[int] = []
            for j in range(m):
                t = int(targets[slot, j])
                emit.append(t)
                if sp.eos_id >= 0 and t == sp.eos_id:
                    break
                if len(seq.generated) + len(emit) >= sp.max_new_tokens:
                    break
            # accepted drafts that actually LANDED as extra tokens: a tick
            # emitting L tokens saves L-1 decode steps, so an eos/budget
            # truncation caps the rollup at len(emit) - 1 (counting the
            # truncated tick's first token too would overstate the win)
            accepted_emitted = min(accepted, len(emit) - 1)
            if proposed:
                self._spec_proposed_total += proposed
                self._m_spec_proposed.inc(proposed)
                self._spec_accepted_total += accepted_emitted
                self._m_spec_accepted.inc(accepted_emitted)
                with self._metrics_lock:
                    self._win_spec_proposed += proposed
                    self._win_spec_accepted += accepted_emitted
                self._outputs[seq.seq_id].spec_accepted_tokens += (
                    accepted_emitted
                )
            self.tracer.on_decode_tokens(seq.seq_id, len(emit),
                                         spec_accepted=accepted_emitted)
            finished = False
            for t in emit:
                seq.pos += 1  # this token's write position
                ev = self._emit(seq, t)
                events.append(ev)
                if ev.finished:
                    finished = True
                    break
            if finished or slot not in pre_lens:
                continue  # finish freed every block / nothing was claimed
            # rollback: release claimed blocks past what the ACCEPTED
            # extent (plus the pending token's write position) needs — a
            # rejected draft's block goes back to the pool this tick, and
            # the refcounted release can never strand a shared/cached block
            keep = max(pre_lens[slot], seq.pos // bs + 1)
            self.blocks.shrink(seq.seq_id, keep)
        return events

    def _emit(self, seq: SequenceState, token: int) -> StreamEvent:
        """Record a sampled token, finishing the request on eos/length."""
        seq.generated.append(token)
        with self._metrics_lock:
            self._window_tokens += 1
        self._total_generated += 1
        self._m_tokens.inc()
        sp = seq.request.sampling
        out = self._outputs[seq.seq_id]
        out.token_ids.append(token)
        finished = False
        reason = ""
        if sp.eos_id >= 0 and token == sp.eos_id:
            finished, reason = True, "eos"
        elif len(seq.generated) >= sp.max_new_tokens:
            finished, reason = True, "length"
        if finished:
            self.scheduler.finish(seq)
            out.finished = True
            out.finish_reason = reason
            # goodput: every token of a request that finished WITHIN its
            # deadline counts (no deadline = trivially met); a late finish
            # keeps its tokens but is a deadline miss and contributes none
            if seq.deadline_expired(time.perf_counter()):
                out.deadline_missed = True
                self._deadline_miss_total += 1
                self._m_deadline_misses.inc()
            else:
                self._goodput_tokens_total += len(seq.generated)
                with self._metrics_lock:
                    self._win_goodput_tokens += len(seq.generated)
            tl = self.tracer.on_finished(seq.seq_id, reason,
                                         len(seq.generated))
            if tl is not None:
                # surface the lifecycle rollup on the output the caller
                # already holds (SLO tooling reads these, not the tracer)
                out.queue_wait_s = tl.queue_wait_s
                out.tpot_s = tl.tpot_s
                out.preemptions = tl.preemptions
        return StreamEvent(
            request_id=seq.seq_id, token=token,
            index=len(seq.generated) - 1, finished=finished,
            finish_reason=reason,
        )

    # ---------------------------------------------------------------- metrics
    def revoke_metrics(self) -> None:
        """Fence off this engine's labelled metric writes (no-op for an
        unlabelled engine). The router calls this when it abandons a
        WEDGED replica's pump thread: that zombie may still be inside XLA
        and will eventually return and try to bump its ``serve.<rid>.*``
        instruments — after revocation those writes are dropped, so the
        respawned successor (a fresh engine, fresh labelled view, same
        rid) never has its window double-counted by its predecessor."""
        revoke = getattr(self._registry, "revoke", None)
        if revoke is not None:
            revoke()

    def kv_capacity(self) -> Dict[str, float]:
        """Block-pool capacity in operator units (pool bytes + estimated
        max-concurrent max-length sequences); the `/debug/memory` pool
        document (``scripts/serve.py`` wires it to the exporter)."""
        from veomni_tpu.observability.devmem import kv_capacity_stats

        return kv_capacity_stats(
            self.blocks, self.k_pool, self.v_pool,
            max_model_len=self.config.max_model_len,
        )

    def metrics(self, reset_window: bool = True) -> Dict[str, float]:
        """Host-float engine metrics; feed them straight into any
        logger/meter sink. ``decode_tokens_per_sec`` and ``ttft_avg_s`` are
        measured over the window since the last resetting call (pass
        ``reset_window=False`` for a peek that leaves another consumer's
        window intact); ``ttft_avg_lifetime_s`` never resets.

        Window snapshot and reset are ATOMIC under ``_metrics_lock``: two
        concurrent resetting scrapers (router poll + exporter) each claim
        a disjoint window instead of racing the reset and losing one
        window's tokens from both readings."""
        now = time.perf_counter()
        with self._metrics_lock:
            dt = max(now - self._window_t0, 1e-9)
            m = {
                "queue_depth": float(self.scheduler.queue_depth),
                "num_running": float(self.scheduler.num_running),
                "block_utilization": self.blocks.utilization(),
                "preemptions": float(self.scheduler.preemption_count),
                "generated_tokens": float(self._total_generated),
                "decode_tokens_per_sec": self._window_tokens / dt,
                "prefix_hit_rate": (
                    self._cached_tokens_total
                    / max(1, self._prompt_tokens_total)
                ),
                "cached_tokens": float(self._cached_tokens_total),
                "prompt_tokens": float(self._prompt_tokens_total),
                "prefill_chunks": float(self._prefill_chunks_total),
                # speculative decoding: lifetime totals (callers take deltas) +
                # the window acceptance rate (drafts the verify step kept)
                "spec_proposed": float(self._spec_proposed_total),
                "spec_accepted": float(self._spec_accepted_total),
                "spec_acceptance_rate": (
                    self._win_spec_accepted
                    / max(1, self._win_spec_proposed)
                ),
                # QoS / overload outcomes (lifetime totals; the storm drill
                # takes deltas) + the window goodput rate — tokens from
                # requests that met their deadline
                "rejected": float(self._rejected_total),
                "shed_tokens": float(self._shed_tokens_total),
                "deadline_misses": float(self._deadline_miss_total),
                "goodput_tokens": float(self._goodput_tokens_total),
                "goodput_tokens_per_sec": self._win_goodput_tokens / dt,
            }
            if self._win_ttft_n:
                m["ttft_avg_s"] = self._win_ttft_sum / self._win_ttft_n
            if self._ttft_n:
                m["ttft_avg_lifetime_s"] = self._ttft_sum / self._ttft_n
            if reset_window:
                # the resetting caller owns the throughput window; mirror
                # its reading to the exporter gauge
                self._m_tps.set(m["decode_tokens_per_sec"])
                self._m_spec_rate.set(m["spec_acceptance_rate"])
                self._m_goodput.set(m["goodput_tokens_per_sec"])
                self._window_tokens = 0
                self._win_goodput_tokens = 0
                self._window_t0 = now
                self._win_ttft_sum = 0.0
                self._win_ttft_n = 0
                self._win_spec_proposed = 0
                self._win_spec_accepted = 0
        return host_floats(m)
