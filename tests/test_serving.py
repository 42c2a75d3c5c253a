"""Continuous-batching engine: paging, scheduling, and token parity.

The load-bearing guarantee is **greedy parity**: the engine serving N
staggered requests over the paged cache must emit exactly the tokens
``models/decode.py::greedy_generate`` produces for each request in
isolation — including across recompute preemption — while the jitted
decode step compiles a bounded (bucket-count) number of times regardless
of how many requests flow through.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.models import decode as decode_mod
from veomni_tpu.models.decode import greedy_generate
from veomni_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    KVBlockManager,
    PrefixCache,
    Request,
    SamplingParams,
    Scheduler,
    SequenceState,
)

QWEN3 = dict(
    model_type="qwen3", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True,
)
# sinks + alternating sliding windows: covers the paged attend's window
# masking and sink softmax-denominator math
GPT_OSS_ISH = dict(
    model_type="gpt_oss", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, attention_sinks=True,
    attention_bias=True, o_bias=True, sliding_window=8,
    layer_types=["sliding_attention", "full_attention"] * 2,
    hidden_act="gpt_oss_glu",
)
QWEN3_MOE = dict(
    model_type="qwen3_moe", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True, num_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=32,
)


@pytest.fixture(scope="module")
def qwen3():
    cfg = TransformerConfig(dtype=jnp.float32, **QWEN3)
    model = build_foundation_model(config=cfg)
    return model.family.init_params(jax.random.PRNGKey(0), cfg), cfg


def _prompts(lengths, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


# --------------------------------------------------------------- block manager
def test_block_manager_alloc_grow_free():
    bm = KVBlockManager(num_blocks=6, block_size=4)
    assert bm.num_free == 5  # block 0 reserved as the null block
    assert bm.blocks_for(1) == 1 and bm.blocks_for(4) == 1
    assert bm.blocks_for(5) == 2
    t = bm.allocate("a", 2)
    assert len(t) == 2 and KVBlockManager.NULL_BLOCK not in t
    assert bm.num_allocated("a") == 2 and bm.num_free == 3
    bm.grow("a")
    assert bm.num_allocated("a") == 3
    assert bm.utilization() == pytest.approx(3 / 5)
    with pytest.raises(ValueError):
        bm.allocate("a", 1)  # double-allocate
    assert bm.free_seq("a") == 3
    assert bm.num_free == 5 and bm.free_seq("a") == 0  # idempotent
    with pytest.raises(ValueError):
        KVBlockManager(num_blocks=8, block_size=6)  # not a power of two


def test_block_manager_exhaustion():
    bm = KVBlockManager(num_blocks=4, block_size=4)
    bm.allocate("a", 3)
    assert not bm.can_allocate(1)
    with pytest.raises(RuntimeError):
        bm.grow("a")
    with pytest.raises(RuntimeError):
        bm.allocate("b", 1)
    bm.free_seq("a")
    assert bm.can_allocate(3)


def test_block_manager_unknown_seq_errors_are_actionable():
    """grow()/table() on an unknown sequence name the sequence and the
    valid transition instead of a bare KeyError (satellite bugfix)."""
    bm = KVBlockManager(num_blocks=6, block_size=4)
    bm.allocate("a", 1)
    with pytest.raises(KeyError, match=r"ghost.*grow\(\) is only valid"):
        bm.grow("ghost")
    with pytest.raises(KeyError, match=r"ghost.*table\(\) is only valid"):
        bm.table("ghost")
    # the message lists what IS allocated, so the operator can see the typo
    with pytest.raises(KeyError, match=r"'a'"):
        bm.table("ghost")


def test_block_manager_refcounts_shared_and_cow():
    """Shared allocation refcounts blocks; free_seq releases references,
    not blocks; the copy-on-write source is pinned through allocation."""
    bm = KVBlockManager(num_blocks=8, block_size=4)
    t_a, fresh_a = bm.allocate_shared("a", [], 3)
    assert t_a == fresh_a and all(bm.refcount(b) == 1 for b in t_a)
    # b shares a's first two blocks and adds one of its own
    t_b, fresh_b = bm.allocate_shared("b", t_a[:2], 1)
    assert t_b[:2] == t_a[:2] and len(fresh_b) == 1
    assert bm.refcount(t_a[0]) == 2 and bm.refcount(t_a[2]) == 1
    assert bm.num_used == 4  # 3 + 1 shared-suffix block
    bm.free_seq("a")
    # shared blocks survive a's release (b still references them); a's
    # exclusive third block is back on the free list (no cache attached)
    assert bm.refcount(t_a[0]) == 1 and bm.refcount(t_a[2]) == 0
    assert bm.num_used == 3
    # CoW: the pinned source keeps a reference until released
    t_c, fresh_c = bm.allocate_shared("c", t_b[:1], 1, cow_src=t_b[1])
    assert bm.cow_count == 1 and bm.refcount(t_b[1]) == 2
    bm.release_block(t_b[1])
    assert bm.refcount(t_b[1]) == 1  # b's own reference remains
    bm.free_seq("b")
    bm.free_seq("c")
    assert bm.num_used == 0 and bm.num_free == 7


def test_prefix_cache_match_insert_refcount_gated_eviction():
    bm = KVBlockManager(num_blocks=10, block_size=2)
    cache = PrefixCache(bm)
    toks = [1, 2, 3, 4, 5, 6, 7]  # 3 full blocks + 1 partial token
    table, _ = bm.allocate_shared("a", [], 4)
    assert cache.match(toks) == []  # cold
    assert cache.insert(toks[:6], table[:3]) == 3  # full blocks only
    assert cache.match(toks) == table[:3]
    assert cache.match([1, 2, 3, 99]) == table[:1]  # divergence mid-stream
    assert cache.match([9, 9, 9, 9]) == []
    # a still references everything -> nothing evictable
    assert cache.num_evictable() == 0 and bm.num_free == 5
    bm.free_seq("a")
    # refcounts dropped to 0: cached blocks are warm AND count as free
    assert cache.num_evictable() == 3 and bm.num_free == 9
    assert bm.num_used == 0
    # eviction is leaf-first (deepest block goes first), LRU-ordered
    assert cache.evict_lru() == table[2]
    assert cache.match(toks) == table[:2]
    # a match bumps LRU recency but refcount-0 blocks stay evictable
    assert cache.num_evictable() == 2
    # re-referencing a cached block removes it from the evictable set
    bm.allocate_shared("b", table[:1], 0)
    assert cache.num_evictable() == 1
    assert cache.evict_lru() == table[1]  # only the unreferenced leaf
    assert cache.evict_lru() is None  # table[0] is referenced by b
    bm.free_seq("b")
    assert cache.evict_lru() == table[0]
    assert len(cache) == 0


def test_block_manager_pool_pressure_evicts_before_exhaustion():
    """free ∪ evictable: allocation reclaims refcount-0 cached blocks LRU
    instead of failing (the engine-level counterpart: eviction before any
    preemption fires)."""
    bm = KVBlockManager(num_blocks=6, block_size=2)
    cache = PrefixCache(bm)
    table, _ = bm.allocate_shared("a", [], 3)
    cache.insert([1, 2, 3, 4, 5, 6], table)
    bm.free_seq("a")
    assert bm.num_free == 5 and bm.num_free_uncached == 2
    # needs 4 blocks: 2 free + 2 evicted from the cache (leaf-first)
    t_b, _ = bm.allocate_shared("b", [], 4)
    assert len(t_b) == 4 and bm.evictions == 2
    assert cache.match([1, 2, 3, 4, 5, 6]) == table[:1]  # root survived
    with pytest.raises(RuntimeError, match="out of KV blocks"):
        bm.grow("b", 2)  # 1 evictable + 0 free < 2


# ------------------------------------------------------------------- scheduler
def _seq(rid, n_prompt):
    return SequenceState(
        request=Request(prompt_ids=list(range(1, n_prompt + 1)),
                        request_id=rid)
    )


def test_scheduler_fifo_head_of_line_and_self_preempt():
    bm = KVBlockManager(num_blocks=4, block_size=4)  # 3 usable
    sched = Scheduler(2, bm)
    a, b = _seq("a", 8), _seq("b", 4)
    sched.add(a)
    sched.add(b)
    assert [s.seq_id for s in sched.admit()] == ["a"]  # idle: no headroom
    # b needs 1+1 (headroom) but only 1 block is free -> head-of-line blocked
    assert sched.admit() == []
    a.prefilling = False  # engine contract: prefill completed
    a.pos = 8  # crosses into block 3
    assert sched.ensure_decode_capacity() == []
    assert bm.num_allocated("a") == 3
    a.pos = 12  # needs a 4th block: pool dry, a is the only victim
    preempted = sched.ensure_decode_capacity()
    assert preempted == [a] and a.slot == -1 and a.preemptions == 1
    # recompute requeue lands at the FRONT (FIFO order preserved)
    assert [s.seq_id for s in sched.waiting] == ["a", "b"]
    assert bm.num_free == 3


def test_scheduler_lifo_preemption():
    bm = KVBlockManager(num_blocks=5, block_size=4)  # 4 usable
    sched = Scheduler(2, bm)
    a, b = _seq("a", 4), _seq("b", 4)
    sched.add(a)
    sched.add(b)
    assert len(sched.admit()) == 2
    a.prefilling = b.prefilling = False  # engine contract: prefill completed
    a.pos, b.pos = 4, 4
    sched.ensure_decode_capacity()  # both grow; pool now dry
    a.pos = 8
    preempted = sched.ensure_decode_capacity()
    # a needed a block; the LATEST admission (b) is the victim
    assert preempted == [b] and b.slot == -1
    assert bm.num_allocated("a") == 3
    assert sched.waiting[0] is b


# ---------------------------------------------------------------- engine parity
def test_engine_greedy_parity_staggered(qwen3):
    """The acceptance gate: staggered arrivals through 2 slots, outputs
    token-identical to isolated generation; TTFT + finish metadata set."""
    params, cfg = qwen3
    prompts = _prompts((5, 9, 17, 12), seed=0)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts[:2]]
    events = []
    for _ in range(2):  # let the first wave start decoding, then add load
        events += eng.step()
    ids += [eng.submit(Request(prompt_ids=p,
                               sampling=SamplingParams(max_new_tokens=6)))
            for p in prompts[2:]]
    for ev in eng.generate():
        events.append(ev)
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want, (rid, outs[rid].token_ids, want)
        assert outs[rid].finished and outs[rid].finish_reason == "length"
        assert outs[rid].ttft_s is not None and outs[rid].ttft_s >= 0
    # the event stream carries every token exactly once, in order
    for rid in ids:
        stream = [ev.token for ev in events if ev.request_id == rid]
        assert stream == outs[rid].token_ids
        assert [ev for ev in events if ev.request_id == rid][-1].finished


def test_engine_decode_trace_count_bounded(qwen3):
    """Compile count of the batched decode step is bounded by the
    block-table-width buckets (<= log2), NOT by the number of requests in a
    mixed-length stream."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    base = dict(decode_mod.TRACE_COUNTS)
    first = _prompts((5, 9, 17, 21, 33, 7), seed=3)
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=5))
             for p in first])
    delta = decode_mod.TRACE_COUNTS["paged_decode"] - base["paged_decode"]
    # max_model_len 64 / block 8 -> table-width buckets {1,2,4,8}
    assert 1 <= delta <= 4, delta
    # doubling the request count with lengths inside the same buckets must
    # not add a single compile
    mid = dict(decode_mod.TRACE_COUNTS)
    more = _prompts((6, 10, 18, 22, 34, 8, 12, 30), seed=4)
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=5))
             for p in more])
    assert decode_mod.TRACE_COUNTS["paged_decode"] == mid["paged_decode"]


def test_engine_preemption_recompute_parity(qwen3):
    """A pool too small for the full load forces preemption; recompute must
    resume every greedy stream exactly."""
    params, cfg = qwen3
    prompts = _prompts((9, 11, 7), seed=1)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=10)))
           for p in prompts]
    outs = eng.run()
    assert eng.scheduler.preemption_count > 0
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=10)[len(p):]
        assert outs[rid].token_ids == want
    # every block returned to the pool at the end
    assert eng.blocks.num_used == 0


def test_engine_per_slot_sampling(qwen3):
    """One batch mixing greedy and sampled requests: the greedy stream is
    unaffected by its batch-mates; the sampled stream is reproducible per
    seed and changes with the seed."""
    params, cfg = qwen3
    prompts = _prompts((9, 11), seed=2)

    def run(seed):
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=2, block_size=8, max_model_len=64,
        ))
        g = eng.submit(Request(prompt_ids=prompts[0],
                               sampling=SamplingParams(max_new_tokens=8)))
        s = eng.submit(Request(
            prompt_ids=prompts[1],
            sampling=SamplingParams(temperature=0.8, top_k=10, top_p=0.9,
                                    max_new_tokens=8, seed=seed),
        ))
        outs = eng.run()
        return outs[g].token_ids, outs[s].token_ids

    g1, s1 = run(7)
    g2, s2 = run(7)
    _, s3 = run(8)
    want = greedy_generate(params, cfg, prompts[0],
                           max_new_tokens=8)[len(prompts[0]):]
    assert g1 == g2 == want
    assert s1 == s2  # per-seed reproducible
    assert s1 != s3  # seed actually threads through
    assert all(0 <= t < cfg.vocab_size for t in s1)


def test_engine_eos_and_validation(qwen3):
    params, cfg = qwen3
    prompt = _prompts((9,), seed=5)[0]
    full = greedy_generate(params, cfg, prompt, max_new_tokens=8)[len(prompt):]
    eos = full[3]  # force an early stop on a token greedy actually emits
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    rid = eng.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
        max_new_tokens=8, eos_id=eos,
    )))
    out = eng.run()[rid]
    assert out.finish_reason == "eos"
    assert out.token_ids == full[: full.index(eos) + 1]
    with pytest.raises(ValueError):
        eng.submit(Request(prompt_ids=[], sampling=SamplingParams()))
    with pytest.raises(ValueError):  # prompt + max_new over max_model_len
        eng.submit(Request(prompt_ids=prompt,
                           sampling=SamplingParams(max_new_tokens=64)))
    with pytest.raises(ValueError):  # unsupported dialect fails fast
        InferenceEngine(params, TransformerConfig(
            model_type="deepseek_v3", vocab_size=64, hidden_size=64,
            num_hidden_layers=1, num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        ))


@pytest.mark.parametrize("spec", ["gpt_oss_ish", "qwen3_moe"])
def test_engine_dialect_parity(spec):
    """Paged decode matches isolated decode on the dialect extremes: learned
    sinks + alternating sliding windows, and MoE MLP segments."""
    conf = {"gpt_oss_ish": GPT_OSS_ISH, "qwen3_moe": QWEN3_MOE}[spec]
    cfg = TransformerConfig(dtype=jnp.float32, **conf)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts((9, 13), seed=6)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want


def test_scheduler_admission_headroom_excludes_matched_blocks():
    """Regression: matched cached blocks leave the evictable set the moment
    admission references them, so they must not double-count as claimable
    headroom — a fully-cached tight pool head-of-line waits cleanly instead
    of exploding inside allocate_shared."""
    bm = KVBlockManager(num_blocks=6, block_size=4)  # 5 usable
    cache = PrefixCache(bm)
    sched = Scheduler(2, bm, prefix_cache=cache)
    r = _seq("r", 8)  # running seq holds 2 blocks
    sched.add(r)
    assert sched.admit() == [r]
    toks = list(range(100, 112))  # 12 tokens = 3 full blocks
    table, _ = bm.allocate_shared("x", [], 3)
    cache.insert(toks, table)
    bm.free_seq("x")  # 3 cached evictable, free list empty
    y = SequenceState(request=Request(prompt_ids=toks, request_id="y"))
    sched.add(y)
    # full-match CoW admission needs 1 fresh block + 1 headroom, but every
    # "free" block is a matched block about to be pinned -> must WAIT
    assert sched.admit() == []
    assert sched.waiting[0] is y and bm.cow_count == 0
    sched.finish(r)  # releases 2 uncached blocks to the free list
    admitted = sched.admit()
    assert admitted == [y] and bm.cow_count == 1
    assert y.cow_src == table[2] and y.cached_tokens == 11  # P-1
    assert bm.refcount(y.cow_src) == 1  # pinned until the engine's copy


# ----------------------------------------------------- prefix cache + chunking
def test_engine_shared_prefix_parity_and_hit_rate(qwen3):
    """Staggered arrivals sharing a common system prompt, cache ON +
    chunked prefill ON: token-exact greedy parity, and later arrivals are
    admitted against cached prompt blocks (charged only the suffix)."""
    params, cfg = qwen3
    rng = np.random.default_rng(11)
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, 19)]
    prompts = [system + [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (5, 9, 2, 13)]
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=96,
        prefix_cache=True, prefill_chunk=8,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts[:2]]
    for _ in range(3):  # let the first wave cache its prompt blocks
        eng.step()
    ids += [eng.submit(Request(prompt_ids=p,
                               sampling=SamplingParams(max_new_tokens=6)))
            for p in prompts[2:]]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want, (rid, outs[rid].token_ids, want)
    # the late arrivals hit the cached 19-token system prompt: two full
    # 8-token blocks of it are shared, never recomputed
    assert all(outs[r].cached_tokens >= 16 for r in ids[2:]), [
        outs[r].cached_tokens for r in ids
    ]
    m = eng.metrics()
    assert m["prefix_hit_rate"] > 0 and m["cached_tokens"] >= 32
    assert m["prefill_chunks"] > 0


def test_engine_cow_divergence_mid_block_parity(qwen3):
    """Copy-on-write matrix: an exact block-aligned replay of a cached
    prompt (full match -> CoW the divergence block, recompute only the last
    token) and a prompt diverging mid-block both stay token-exact, and the
    shared cached block is never corrupted for a third replay."""
    params, cfg = qwen3
    rng = np.random.default_rng(12)
    base = [int(t) for t in rng.integers(1, cfg.vocab_size, 16)]  # 2 blocks
    diverged = base[:12] + [int(t) for t in rng.integers(1, 128, 4)]
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, prefix_cache=True,
    ))
    r1 = eng.submit(Request(prompt_ids=base,
                            sampling=SamplingParams(max_new_tokens=5)))
    eng.run()
    assert eng.blocks.cow_count == 0
    # exact replay: both blocks cached -> CoW on block 2, 1-token prefill
    r2 = eng.submit(Request(prompt_ids=base,
                            sampling=SamplingParams(max_new_tokens=5)))
    # mid-block divergence: block 1 shared, block 2 recomputed fresh
    r3 = eng.submit(Request(prompt_ids=diverged,
                            sampling=SamplingParams(max_new_tokens=5)))
    outs = eng.run()
    assert eng.blocks.cow_count == 1
    assert outs[r2].cached_tokens == 15  # P-1: everything but the last token
    assert outs[r3].cached_tokens == 8  # the shared first block only
    # a third replay still matches the ORIGINAL cached blocks (the CoW
    # write landed in a private copy, not the shared block)
    r4 = eng.submit(Request(prompt_ids=base,
                            sampling=SamplingParams(max_new_tokens=5)))
    outs4 = eng.run()
    for rid, p, o in ((r2, base, outs[r2]), (r3, diverged, outs[r3]),
                      (r4, base, outs4[r4])):
        want = greedy_generate(params, cfg, p, max_new_tokens=5)[len(p):]
        assert o.token_ids == want, (rid, o.token_ids, want)


def test_engine_preemption_cached_readmission(qwen3):
    """A preempted sequence's blocks stay cached: re-admission matches them
    and recomputes only the tail instead of the whole recompute prompt —
    while parity holds exactly."""
    params, cfg = qwen3
    prompts = _prompts((9, 11, 7), seed=13)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
        prefix_cache=True,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=10)))
           for p in prompts]
    outs = eng.run()
    assert eng.scheduler.preemption_count > 0
    # at least one re-admission was a cache hit (the preempted sequence's
    # own blocks) — the LIFO-recompute cost collapsed to the uncached tail
    assert eng._cached_tokens_total > 0
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=10)[len(p):]
        assert outs[rid].token_ids == want
    assert eng.blocks.num_used == 0


def test_engine_eviction_reclaims_cache_before_preemption(qwen3):
    """Pool pressure: refcount-0 cached blocks are evicted LRU to satisfy
    admissions/growth BEFORE any running sequence is preempted."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=1, block_size=8, max_model_len=40, num_blocks=6,
        prefix_cache=True,
    ))
    prompts = _prompts((17, 19, 18), seed=14)
    for p in prompts:  # sequential: each run leaves its blocks cached
        eng.run([Request(prompt_ids=p,
                         sampling=SamplingParams(max_new_tokens=8))])
    assert eng.blocks.evictions > 0  # dry free list was refilled by LRU
    assert eng.scheduler.preemption_count == 0  # ... never by preemption
    assert eng.blocks.num_used == 0


def test_engine_cache_off_matches_seed_behavior(qwen3):
    """prefix_cache=False restores the pre-cache engine: exclusive blocks,
    monolithic prefill, zero cache accounting, all blocks truly freed."""
    params, cfg = qwen3
    prompts = _prompts((9, 9), seed=15)  # identical prompts: maximal overlap
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, prefix_cache=False,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts]
    outs = eng.run()
    assert eng.prefix_cache is None
    m = eng.metrics()
    assert m["prefix_hit_rate"] == 0 and m["cached_tokens"] == 0
    assert all(outs[r].cached_tokens == 0 for r in ids)
    assert eng.blocks.num_cached == 0
    assert eng.blocks.num_free_uncached == eng.config.num_blocks - 1
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want


def test_engine_chunked_prefill_interleaves_decode(qwen3):
    """A long prompt arriving mid-stream no longer stalls a running
    request: with prefill_chunk set, the running sequence keeps emitting a
    token on ticks where the new arrival is still prefilling chunks."""
    params, cfg = qwen3
    short, long = _prompts((5, 60), seed=16)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=128,
        prefix_cache=True, prefill_chunk=16,
    ))
    a = eng.submit(Request(prompt_ids=short,
                           sampling=SamplingParams(max_new_tokens=20)))
    eng.step()  # a prefilled + first token
    b = eng.submit(Request(prompt_ids=long,
                           sampling=SamplingParams(max_new_tokens=4)))
    # 60 tokens / 16-chunk = 4 chunk ticks; a must produce a token on each
    interleaved = 0
    while not eng._outputs[b].token_ids:
        got_a = any(ev.request_id == a for ev in eng.step())
        if not eng._outputs[b].token_ids:
            interleaved += got_a
    assert interleaved >= 3, interleaved
    outs = eng.run()
    for rid, p, n in ((a, short, 20), (b, long, 4)):
        want = greedy_generate(params, cfg, p, max_new_tokens=n)[len(p):]
        assert outs[rid].token_ids == want


def test_engine_prefill_trace_count_bounded(qwen3):
    """Compile-count gate for the chunked-prefill path: TRACE_COUNTS
    ["paged_prefill"] is bounded by (chunk bucket x table-width bucket),
    never per-request or per-chunk-position, across staggered arrivals and
    a preemption storm."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=128,
        prefix_cache=True, prefill_chunk=16,
    ))
    base = dict(decode_mod.TRACE_COUNTS)
    first = _prompts((5, 21, 40, 60, 33, 9), seed=17)
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=4))
             for p in first])
    delta = decode_mod.TRACE_COUNTS["paged_prefill"] - base["paged_prefill"]
    # chunk buckets {16} + final-chunk remainders {16} x table-width
    # buckets {1,2,4,8,16}: comfortably O(log2 x log2), never O(requests)
    assert 1 <= delta <= 10, delta
    # doubling the request count inside the same buckets adds ZERO compiles
    mid = dict(decode_mod.TRACE_COUNTS)
    more = _prompts((6, 22, 41, 61, 34, 10, 50, 13), seed=18)
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=4))
             for p in more])
    assert decode_mod.TRACE_COUNTS["paged_prefill"] == mid["paged_prefill"]
    # a preemption storm (tiny pool) re-admits through the SAME buckets
    eng2 = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
        prefix_cache=True, prefill_chunk=16,
    ))
    pre = dict(decode_mod.TRACE_COUNTS)
    eng2.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=10))
              for p in _prompts((9, 11, 7), seed=19)])
    assert eng2.scheduler.preemption_count > 0
    storm = decode_mod.TRACE_COUNTS["paged_prefill"] - pre["paged_prefill"]
    assert storm <= 6, storm  # bucket-bounded, not per-(re)admission


def test_engine_no_block_leaks_after_drain(qwen3):
    """After run() drains: every non-cached block is on the free list,
    every cached block's refcount is 0, and the accounting identity
    free + cached == pool holds."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefix_cache=True, prefill_chunk=8,
    ))
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=6))
             for p in _prompts((5, 9, 17, 12), seed=20)])
    bm = eng.blocks
    assert bm.num_used == 0
    assert bm.num_free_uncached + bm.num_cached == bm.num_blocks - 1
    cache = eng.prefix_cache
    assert all(bm.refcount(b) == 0 for b in cache._by_block)
    assert cache.num_evictable() == len(cache)


@pytest.mark.parametrize("spec", ["gpt_oss_ish", "qwen3_moe"])
def test_engine_dialect_parity_cached_chunked(spec):
    """The dialect extremes (sinks + alternating sliding windows, MoE MLP
    segments) through the chunked-prefill + prefix-cache path: shared
    prompts, cache hits, still token-exact."""
    conf = {"gpt_oss_ish": GPT_OSS_ISH, "qwen3_moe": QWEN3_MOE}[spec]
    cfg = TransformerConfig(dtype=jnp.float32, **conf)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(21)
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, 17)]
    prompts = [system + [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (5, 9)]
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefix_cache=True, prefill_chunk=8,
    ))
    ids, outs = [], {}
    for p in prompts:  # sequential drains so the second hits the cache
        ids.append(eng.submit(Request(
            prompt_ids=p, sampling=SamplingParams(max_new_tokens=6))))
        outs.update(eng.run())
    assert outs[ids[1]].cached_tokens >= 16
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want


# --------------------------------------------------------------------- metrics
def test_engine_metrics_are_host_floats(qwen3):
    from veomni_tpu.trainer.callbacks import WandbCallback
    from veomni_tpu.utils.helper import host_floats

    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    eng.run([Request(prompt_ids=_prompts((9,), seed=7)[0],
                     sampling=SamplingParams(max_new_tokens=4))])
    m = eng.metrics()
    assert m and all(isinstance(v, (int, float)) for v in m.values())
    assert 0.0 <= m["block_utilization"] <= 1.0
    assert m["generated_tokens"] == 4.0
    assert m["ttft_avg_s"] > 0 and m["queue_depth"] == 0.0
    # the last decode tick wrote position 11 of the one running request (two
    # pages of 8), the idle slot attends the null block's one page, and the
    # table was 2 blocks wide for 2 slots: 3 live pages of 4 entries
    from veomni_tpu.observability.metrics import get_registry
    assert get_registry().gauge("serve.paged_live_page_share").value == 0.75
    # the filter is the SHARED util (WandbCallback delegates to it): device
    # futures are dropped, host scalars pass
    mixed = dict(m, device_val=jnp.ones(()))
    assert "device_val" not in host_floats(mixed)
    assert WandbCallback._host_floats(mixed) == host_floats(mixed)


def test_engine_counts_its_decode_ticks_by_what_the_sampler_had_to_do(qwen3):
    """``serve.sampler_ticks.<path>``: one count a decode tick, by the most
    work any running slot asked of the sampler (PR 49). The expected path of
    every decode step is reckoned here from the arrays the step was handed,
    slot by slot in plain Python."""
    from veomni_tpu.observability.metrics import get_registry

    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(num_slots=2, block_size=8, max_model_len=64))
    counters = {path: get_registry().counter(f"serve.sampler_ticks.{path}")
                for path in decode_mod.SAMPLER_PATHS}
    before = {path: c.value for path, c in counters.items()}
    # the host counts over the vocabulary the program's switch sees: the logits' width
    assert eng._head_width == decode_mod.lm_head_kernel(params, cfg).shape[-1]
    step, seen = eng._decode_step, []

    def noting(*args):
        temps, top_ks, top_ps = (np.asarray(a) for a in args[7:10])
        samples = [t > 0 for t in temps]
        filters = [s and ((0 < k < cfg.vocab_size) or p < 1.0)
                   for s, k, p in zip(samples, top_ks, top_ps)]
        seen.append("filtered" if any(filters) else "unfiltered" if any(samples) else "greedy")
        return step(*args)

    eng._decode_step = noting
    a, b = _prompts((9, 12), seed=11)

    def run(*samplings):
        eng.run([Request(prompt_ids=p, sampling=sp) for p, sp in zip((a, b), samplings)])

    run(SamplingParams(max_new_tokens=4), SamplingParams(max_new_tokens=5, top_k=3, top_p=0.5))
    assert set(seen) == {"greedy"}  # a filter on a greedy request asks for nothing
    run(SamplingParams(max_new_tokens=3), SamplingParams(max_new_tokens=6, temperature=1.0))
    assert set(seen) == {"greedy", "unfiltered"}
    run(SamplingParams(max_new_tokens=7, temperature=1.0, top_k=cfg.vocab_size),
        SamplingParams(max_new_tokens=4, temperature=0.7, top_k=5, top_p=0.9))
    assert set(seen) == set(decode_mod.SAMPLER_PATHS) and seen[-1] == "unfiltered"
    for path, c in counters.items():
        assert c.value - before[path] == seen.count(path), (path, seen)


def test_engine_ttft_is_window_scoped(qwen3):
    """Satellite bugfix: ttft_avg_s resets with the metrics window like
    decode_tokens_per_sec; the lifetime average lives under its own key."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    prompt = _prompts((9,), seed=22)[0]
    eng.run([Request(prompt_ids=prompt,
                     sampling=SamplingParams(max_new_tokens=4))])
    m1 = eng.metrics()  # resets the window
    assert m1["ttft_avg_s"] > 0
    assert m1["ttft_avg_lifetime_s"] == pytest.approx(m1["ttft_avg_s"])
    m2 = eng.metrics()  # fresh window: no TTFT observed since the reset
    assert "ttft_avg_s" not in m2
    assert m2["ttft_avg_lifetime_s"] == pytest.approx(
        m1["ttft_avg_lifetime_s"])
    # a peek must not clobber the window another consumer owns
    eng.run([Request(prompt_ids=_prompts((5,), seed=23)[0],
                     sampling=SamplingParams(max_new_tokens=4))])
    peek = eng.metrics(reset_window=False)
    assert peek["ttft_avg_s"] > 0
    again = eng.metrics()
    assert again["ttft_avg_s"] == pytest.approx(peek["ttft_avg_s"])


def test_engine_new_prompt_length_in_a_warm_bucket_builds_no_program(qwen3):
    """Prompts are padded to their bucket on the host: once a bucket's
    programs exist, a prompt of another length in it (whole, or chunked
    behind a cached prefix) compiles nothing, eager or jitted."""
    import jax.monitoring as monitoring

    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, prefix_cache=True,
    ))
    head_a, head_b = _prompts((16, 16), seed=11)

    def run(head, n, seed):
        eng.run([Request(prompt_ids=head + _prompts((n,), seed=seed)[0],
                         sampling=SamplingParams(max_new_tokens=3))])

    # every request is 4 blocks of 8 to its end: one prefill bucket (32), one
    # chunk bucket (16 behind the cached 16-token head), one table width
    run(head_a, 9, seed=1)    # whole
    run(head_a, 12, seed=2)   # the suffix behind the cached head
    built = []

    def listener(name, *_, **__):
        if name == "/jax/core/compile/backend_compile_duration":
            built.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        run(head_b, 10, seed=3)
        run(head_a, 11, seed=4)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert built == []
