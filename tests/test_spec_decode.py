"""Speculative decoding (draft-then-verify) on the paged serving engine.

The load-bearing guarantee is unchanged from the non-speculative engine:
**token-exact parity** — greedy AND seeded sampling — with isolated
``greedy_generate`` and with the one-token engine, across staggered
arrivals, preemption/recompute, prefix-cache hits, and the dialect
extremes. Speculation may only change *when* tokens land (several per
verify tick), never *which* tokens. On top of that: the verify program's
compile count is bucket-bounded, speculative block claims roll back without
leaking (or freeing anything shared), and the request tracer's TPOT stays
correct when one tick emits many tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.models import decode as decode_mod
from veomni_tpu.models.decode import greedy_generate
from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
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
from veomni_tpu.serving.spec_decode import (
    draft_ngram,
    draft_off,
    resolve_draft_fn,
)

QWEN3 = dict(
    model_type="qwen3", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True,
)
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


def _loopy_prompts(lengths, seed=0, vocab=128, period=8):
    """Prompts whose tail n-grams recur (a repeated block), so the ngram
    prompt-lookup drafter actually proposes continuations."""
    rng = np.random.default_rng(seed)
    base = [int(t) for t in rng.integers(1, vocab, period)]
    out = []
    for n in lengths:
        reps = base * (n // period + 2)
        uniq = [int(t) for t in rng.integers(1, vocab, 2)]
        out.append((reps[: max(0, n - 2)] + uniq)[:n])
    return out


class _registered_draft:
    """Register a throwaway spec_draft impl for one test, cleanly removed
    afterwards (the registry is process-global)."""

    def __init__(self, name, fn):
        self.name, self.fn = name, fn

    def __enter__(self):
        KERNEL_REGISTRY.register("spec_draft", self.name)(self.fn)
        return self.name

    def __exit__(self, *exc):
        KERNEL_REGISTRY._ops["spec_draft"].pop(self.name, None)
        KERNEL_REGISTRY.resolve.cache_clear()


# ------------------------------------------------------------------ drafting
def test_draft_ngram_prompt_lookup():
    # tail [7,8] recurred earlier; the most recent occurrence is followed
    # by [9, 1] — that continuation is the proposal
    ctx = [1, 2, 7, 8, 3, 4, 7, 8, 9, 1, 5, 7, 8]
    assert draft_ngram(ctx, 4) == [9, 1, 5, 7]
    assert draft_ngram(ctx, 2) == [9, 1]  # k caps the proposal
    # no recurrence of any tail n-gram -> no proposal (slot degrades to 0)
    assert draft_ngram([1, 2, 3, 4, 5], 4) == []
    assert draft_ngram([1, 2], 0) == []
    assert draft_ngram([], 4) == []
    # the trivial strategy never proposes
    assert draft_off(ctx, 4) == []
    # a 1-token context can't have an earlier occurrence
    assert draft_ngram([5], 4) == []


def test_draft_ngram_prefers_longest_match():
    # the 2-gram tail [7, 8] matches at position 1 (-> 9 follows); the
    # 1-gram tail [8] ALSO matches at position 5 (-> 6 follows): the longer
    # n-gram wins because it is more specific
    ctx = [1, 7, 8, 9, 2, 8, 6, 7, 8]
    assert draft_ngram(ctx, 1) == [9]


def test_resolve_draft_fn_validates_and_honors_pin():
    assert resolve_draft_fn("ngram") is draft_ngram
    assert resolve_draft_fn("off") is draft_off
    with pytest.raises(ValueError, match="unknown spec_draft"):
        resolve_draft_fn("nope")
    # an ops-config pin outranks the engine knob (ulysses-dispatch rules)
    KERNEL_REGISTRY.pin("spec_draft", "off")
    try:
        assert resolve_draft_fn("ngram") is draft_off
    finally:
        KERNEL_REGISTRY.clear_pins()


# ------------------------------------------------- block manager / scheduler
def test_block_manager_shrink_rollback():
    bm = KVBlockManager(num_blocks=8, block_size=4)
    t = bm.allocate("a", 2)
    grown = bm.grow("a", 3)  # returns the full 5-entry table
    released = bm.shrink("a", 2)
    assert released == list(reversed(grown[2:]))  # tail first
    assert bm.table("a") == t
    assert bm.num_free == 5
    assert bm.shrink("a", 2) == []  # idempotent at the target
    assert bm.shrink("a", 99) == []  # never grows
    with pytest.raises(ValueError):
        bm.shrink("a", 0)  # a live sequence keeps >= 1 block
    with pytest.raises(KeyError, match="ghost"):
        bm.shrink("ghost", 1)


def test_block_manager_shrink_never_strands_shared_blocks():
    """A trailing block shared with another sequence (or cached) survives
    one sequence's rollback: shrink drops a REFERENCE, not the block."""
    bm = KVBlockManager(num_blocks=8, block_size=4)
    cache = PrefixCache(bm)
    t_a, _ = bm.allocate_shared("a", [], 3)
    t_b, _ = bm.allocate_shared("b", t_a, 0)  # b shares all of a's blocks
    released = bm.shrink("a", 1)
    assert released == [t_a[2], t_a[1]]
    # b still references them: NOT freed, refcount simply dropped to 1
    assert bm.refcount(t_a[1]) == 1 and bm.refcount(t_a[2]) == 1
    assert bm.num_free == 4  # nothing actually returned to the pool
    bm.free_seq("b")
    assert bm.num_free == 6  # now they are
    # cached (refcount-0-bound) trailing block: rollback re-enters it into
    # the evictable set via the cache, not the raw free list
    t_c, _ = bm.allocate_shared("c", [], 2)
    cache.insert(list(range(100, 108)), t_c)
    bm.shrink("c", 1)
    assert cache.has_block(t_c[1]) and cache.num_evictable() == 1
    assert bm.num_free_uncached + bm.num_cached == bm.num_free


def test_scheduler_claim_speculative_degrades_never_preempts():
    bm = KVBlockManager(num_blocks=6, block_size=4)  # 5 usable
    sched = Scheduler(2, bm)
    a = SequenceState(request=Request(prompt_ids=list(range(1, 9)),
                                      request_id="a"))
    b = SequenceState(request=Request(prompt_ids=list(range(1, 9)),
                                      request_id="b"))
    sched.add(a)
    sched.add(b)
    assert len(sched.admit()) == 2  # 2 blocks each, 1 free
    a.prefilling = b.prefilling = False
    a.pos = b.pos = 8
    # a wants 4 drafted positions = cover position 12 -> needs block 4, but
    # only ONE block is free: k degrades to what the claimed coverage holds
    k, claimed = sched.claim_speculative(a, 4)
    assert len(claimed) == 1 and k == 3  # coverage [0,12): pos 8 + 3 drafts
    assert bm.num_free == 0
    # the pool is dry: b's claim degrades all the way to 0 — NO preemption
    k_b, claimed_b = sched.claim_speculative(b, 4)
    assert (k_b, claimed_b) == (0, []) and sched.preemption_count == 0
    # rollback returns a's claim; b can then claim it
    bm.shrink("a", 2)
    assert sched.claim_speculative(b, 2)[0] > 0


def test_spec_admission_headroom_accounts_for_k_growth():
    """With speculation on, admission keeps ceil(spec_k/bs) extra blocks
    free per tick so a fresh admission doesn't starve every claim."""

    def build(spec_headroom):
        bm = KVBlockManager(num_blocks=8, block_size=4)  # 7 usable
        bm.allocate("x", 1)  # 6 free
        sched = Scheduler(2, bm, spec_headroom_blocks=spec_headroom)
        a = SequenceState(request=Request(prompt_ids=list(range(1, 9)),
                                          request_id="a"))
        b = SequenceState(request=Request(prompt_ids=list(range(1, 13)),
                                          request_id="b"))
        sched.add(a)
        sched.add(b)
        return bm, sched, a, b

    # WITHOUT spec headroom both admit in one pass: a (idle, no headroom,
    # 2 blocks), then b (3 blocks + 1 base headroom = 4 <= 4 free)
    _, sched0, a0, b0 = build(0)
    assert sched0.admit() == [a0, b0]
    # WITH one spec-headroom block b must wait: 3 + (1 + 1) = 5 > 4 free
    bm, sched, a, b = build(1)
    assert sched.admit() == [a]
    assert sched.admit() == []  # still head-of-line blocked on headroom
    bm.free_seq("x")  # one more free block covers the spec headroom
    assert sched.admit() == [b]


def test_spec_enabled_honors_registry_pin(qwen3):
    """The ops-config pin outranks the engine knob for the ON/OFF decision
    too: a pinned `off` releases the admission headroom and the per-tick
    draft calls, a pinned strategy enables speculation over spec_draft=
    'off' (spec_k still gates)."""
    params, cfg = qwen3
    ec = dict(num_slots=1, block_size=8, max_model_len=64)
    KERNEL_REGISTRY.pin("spec_draft", "off")
    try:
        eng = InferenceEngine(params, cfg, EngineConfig(spec_k=4, **ec))
        assert not eng._spec_enabled
        assert eng.scheduler.spec_headroom_blocks == 0
        assert eng._verify_step is None
    finally:
        KERNEL_REGISTRY.clear_pins()
    KERNEL_REGISTRY.pin("spec_draft", "ngram")
    try:
        eng = InferenceEngine(params, cfg, EngineConfig(
            spec_k=4, spec_draft="off", **ec))
        assert eng._spec_enabled and eng._draft_fn is draft_ngram
    finally:
        KERNEL_REGISTRY.clear_pins()


# ------------------------------------------------------------- engine parity
# ------------------------------------------------- verify_accept, by name
def _one_token_chain(logits, tokens, n_input, keys, temperature, top_k, top_p):
    """What the one-token path emits over a verify step's columns: a split
    and a ``sample_tokens`` call a token, going on while the draft matched."""
    s, kb, _ = logits.shape
    targets = np.zeros((s, kb), np.int32)
    n_emit = np.zeros(s, np.int32)
    new_keys = np.zeros((s, 2), np.uint32)
    for i in range(s):
        carry, emitting = jnp.asarray(keys[i], jnp.uint32), True
        for j in range(kb):
            carry, sub = jax.random.split(carry)
            tok = int(decode_mod.sample_tokens(
                logits[i:i + 1, j], sub[None], temperature[i:i + 1], top_k[i:i + 1],
                top_p[i:i + 1])[0])
            targets[i, j] = tok
            if emitting:  # every draft before this column was accepted
                n_emit[i], new_keys[i] = j + 1, np.asarray(carry)
                emitting = j + 1 < n_input[i] and tokens[i, j + 1] == tok
    return targets, n_emit, new_keys


VERIFY_ROWS = {
    # temperature, top_k, top_p of four slots
    "greedy": ([0.0] * 4, [0] * 4, [1.0] * 4),
    "a_filtered_row_in_the_batch": ([0.0, 0.8, 0.0, 1.0], [0, 5, 0, 0], [1.0, 0.9, 1.0, 1.0]),
}


@pytest.mark.parametrize("rows", sorted(VERIFY_ROWS))
def test_verify_accept_is_the_one_token_paths_chain_bit_for_bit(rows):
    s, kb, v = 4, 4, 96
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(s, kb, v)) * 3, jnp.float32)
    keys = np.asarray(jax.vmap(jax.random.PRNGKey)(jnp.arange(40, 40 + s)))
    temperature, top_k, top_p = (np.asarray(x, dt) for x, dt in
                                 zip(VERIFY_ROWS[rows], (np.float32, np.int32, np.float32)))
    n_input = np.asarray([4, 3, 1, 4], np.int32)
    # drafts: the chain's own tokens (accepted) with one planted miss a slot
    free, _, _ = _one_token_chain(logits, np.zeros((s, kb), np.int32), np.ones(s, np.int32),
                                  keys, temperature, top_k, top_p)
    tokens = np.zeros((s, kb), np.int32)
    tokens[:, 1:] = free[:, :-1]
    tokens[0, 3] = (tokens[0, 3] + 1) % v  # slot 0: the third draft misses
    tokens[1, 1] = (tokens[1, 1] + 1) % v  # slot 1: the first draft misses
    want = _one_token_chain(logits, tokens, n_input, keys, temperature, top_k, top_p)
    got = jax.jit(decode_mod.verify_accept)(logits, tokens, n_input, keys, temperature, top_k, top_p)
    targets, n_emit, new_keys = (np.asarray(x) for x in got)
    assert n_emit.tolist() == want[1].tolist() == [3, 1, 1, 4]
    for i in range(s):
        assert targets[i, :n_emit[i]].tolist() == want[0][i, :n_emit[i]].tolist()
    assert (new_keys == want[2]).all()
    greedy = temperature <= 0
    assert (targets[greedy] == np.asarray(jnp.argmax(logits, -1))[greedy]).all()


def test_spec_engine_greedy_parity_staggered(qwen3):
    """The acceptance gate: staggered arrivals through a spec_k=4 engine
    emit exactly the tokens isolated generation produces — and on a
    loopy-prompt workload the drafter actually gets tokens accepted."""
    params, cfg = qwen3
    prompts = _loopy_prompts((21, 17, 26, 19), seed=0)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=96, spec_k=4,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=8)))
           for p in prompts[:2]]
    events = []
    for _ in range(2):
        events += eng.step()
    ids += [eng.submit(Request(prompt_ids=p,
                               sampling=SamplingParams(max_new_tokens=8)))
            for p in prompts[2:]]
    for ev in eng.generate():
        events.append(ev)
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=8)[len(p):]
        assert outs[rid].token_ids == want, (rid, outs[rid].token_ids, want)
        assert outs[rid].finished
    # the event stream carries every token exactly once, in order, even
    # when one verify tick emitted several
    for rid in ids:
        stream = [ev.token for ev in events if ev.request_id == rid]
        assert stream == outs[rid].token_ids
        idxs = [ev.index for ev in events if ev.request_id == rid]
        assert idxs == list(range(len(stream)))
    # speculation did something: drafts were proposed AND accepted
    m = eng.metrics()
    assert m["spec_proposed"] > 0 and m["spec_accepted"] > 0
    assert sum(outs[r].spec_accepted_tokens for r in ids) == int(
        m["spec_accepted"]
    )


def test_spec_engine_sampled_parity_vs_nonspec(qwen3):
    """Seeded sampling through forced verify steps is token-identical to
    the one-token engine: the verify path replays the exact per-token PRNG
    key schedule, so even 100%-rejected drafts change nothing."""
    params, cfg = qwen3

    def junk(context, k):
        # deterministic junk: forces real verify steps with ~zero
        # acceptance, the worst case for parity
        return [(int(context[-1]) + 37 + i) % 127 + 1 for i in range(k)]

    prompts = _prompts((9, 13, 7), seed=1)
    sampling = SamplingParams(temperature=0.8, top_k=20, top_p=0.9,
                              max_new_tokens=9, seed=5)

    def run(spec_k, draft="ngram"):
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=2, block_size=8, max_model_len=64,
            spec_k=spec_k, spec_draft=draft,
        ))
        ids = [eng.submit(Request(prompt_ids=list(p), sampling=sampling))
               for p in prompts]
        outs = eng.run()
        return [outs[r].token_ids for r in ids], eng

    base, _ = run(0)
    with _registered_draft("__test_junk", junk) as name:
        spec, eng = run(3, name)
        assert eng.metrics()["spec_proposed"] > 0  # verify really ran
    assert spec == base


@pytest.mark.parametrize("spec", ["gpt_oss_ish", "qwen3_moe"])
def test_spec_dialect_parity(spec):
    """Verify-step parity on the dialect extremes: learned sinks +
    alternating sliding windows (the verify rows must window-mask per
    position exactly like single-token decode), and MoE MLP segments."""
    conf = {"gpt_oss_ish": GPT_OSS_ISH, "qwen3_moe": QWEN3_MOE}[spec]
    cfg = TransformerConfig(dtype=jnp.float32, **conf)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _loopy_prompts((17, 21), seed=6)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, spec_k=3,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want, (rid, outs[rid].token_ids, want)


def test_spec_preemption_recompute_parity(qwen3):
    """A pool too small for the full load forces preemption mid-speculation;
    recompute must resume every greedy stream exactly, and drafted-block
    rollback must leave no block behind."""
    params, cfg = qwen3
    prompts = _loopy_prompts((9, 11, 7), seed=7)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
        spec_k=3,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=10)))
           for p in prompts]
    outs = eng.run()
    assert eng.scheduler.preemption_count > 0
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=10)[len(p):]
        assert outs[rid].token_ids == want
    assert eng.blocks.num_used == 0


def test_spec_prefix_cache_parity_and_hits(qwen3):
    """Speculation composes with the prefix cache + chunked prefill: shared
    system prompts still hit, and the combined path stays token-exact."""
    params, cfg = qwen3
    rng = np.random.default_rng(11)
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, 19)]
    prompts = [system + [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (5, 9, 2, 13)]
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=96,
        prefix_cache=True, prefill_chunk=8, spec_k=4,
    ))
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=6)))
           for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    ids += [eng.submit(Request(prompt_ids=p,
                               sampling=SamplingParams(max_new_tokens=6)))
            for p in prompts[2:]]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert outs[rid].token_ids == want, (rid, outs[rid].token_ids, want)
    assert all(outs[r].cached_tokens >= 16 for r in ids[2:])


def test_spec_cow_replay_parity(qwen3):
    """Exact block-aligned replay of a cached prompt: the full-match CoW
    admission (recompute only the last token into a copied divergence
    block) composes with speculative decode ticks, token-exact, and the
    shared cached blocks survive rollback untouched."""
    params, cfg = qwen3
    rng = np.random.default_rng(14)
    base = [int(t) for t in rng.integers(1, cfg.vocab_size, 8)] * 2
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefix_cache=True, spec_k=4,
    ))
    r1 = eng.submit(Request(prompt_ids=list(base),
                            sampling=SamplingParams(max_new_tokens=5)))
    eng.run()
    r2 = eng.submit(Request(prompt_ids=list(base),
                            sampling=SamplingParams(max_new_tokens=5)))
    outs = eng.run()
    assert eng.blocks.cow_count == 1
    assert outs[r2].cached_tokens == 15  # P-1: all but the last token
    want = greedy_generate(params, cfg, base, max_new_tokens=5)[len(base):]
    assert outs[r2].token_ids == want
    # a third replay still matches the ORIGINAL cached blocks
    r3 = eng.submit(Request(prompt_ids=list(base),
                            sampling=SamplingParams(max_new_tokens=5)))
    assert eng.run()[r3].token_ids == want
    bm = eng.blocks
    assert bm.num_used == 0
    assert bm.num_free_uncached + bm.num_cached == bm.num_blocks - 1


def test_spec_k0_path_byte_identical(qwen3):
    """spec_k=0 (the default) IS the PR 9 engine: the verify program is
    never built, never traced, and outputs are identical — same for an
    explicit spec_draft='off' with k > 0."""
    params, cfg = qwen3
    prompts = _loopy_prompts((9, 13), seed=8)

    def run(**kw):
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=2, block_size=8, max_model_len=64, **kw,
        ))
        ids = [eng.submit(Request(prompt_ids=list(p),
                                  sampling=SamplingParams(max_new_tokens=6)))
               for p in prompts]
        outs = eng.run()
        return [outs[r].token_ids for r in ids], eng

    before = decode_mod.TRACE_COUNTS["paged_verify"]
    base, eng0 = run()
    off, eng_off = run(spec_k=4, spec_draft="off")
    assert decode_mod.TRACE_COUNTS["paged_verify"] == before
    assert eng0._verify_step is None and eng_off._verify_step is None
    assert not eng0._spec_enabled and not eng_off._spec_enabled
    assert base == off
    assert eng0.metrics()["spec_proposed"] == 0.0
    spec, _ = run(spec_k=4)
    assert spec == base  # and the speculative path agrees token-for-token


def test_spec_verify_trace_count_bounded(qwen3):
    """Compile-count gate: TRACE_COUNTS["paged_verify"] is bounded by
    (verify-width bucket x table-width bucket), never per-request — across
    staggered arrivals, and a same-bucket re-run adds ZERO compiles, and a
    preemption storm re-admits through the SAME buckets."""
    params, cfg = qwen3
    # cache OFF so a re-run of the identical batch replays the exact same
    # tick/draft trajectory (with the cache on, warm prompt blocks change
    # admissions — and bucket SEQUENCES — between runs)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, spec_k=4,
        prefix_cache=False,
    ))
    base = dict(decode_mod.TRACE_COUNTS)
    first = _loopy_prompts((5, 21, 40, 33, 9, 14), seed=17)
    batch = lambda: [Request(prompt_ids=p,
                             sampling=SamplingParams(max_new_tokens=6))
                     for p in first]
    eng.run(batch())
    delta = decode_mod.TRACE_COUNTS["paged_verify"] - base["paged_verify"]
    # verify-width buckets {2,4,8} x table-width buckets {1,2,4,8}:
    # O(log2 k x log2 width), never O(requests)
    assert 1 <= delta <= 12, delta
    # the SAME request set again: same buckets, ZERO new compiles
    mid = dict(decode_mod.TRACE_COUNTS)
    eng.run(batch())
    assert decode_mod.TRACE_COUNTS["paged_verify"] == mid["paged_verify"]
    assert decode_mod.TRACE_COUNTS["paged_decode"] == mid["paged_decode"]
    # more requests with lengths inside the same prompt buckets: the
    # verify-bucket PRODUCT space stays the cumulative bound — compile
    # count tracks buckets, never request count
    more = _loopy_prompts((6, 22, 41, 34, 10, 15, 28, 13), seed=18)
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=6))
             for p in more])
    total = decode_mod.TRACE_COUNTS["paged_verify"] - base["paged_verify"]
    assert total <= 12, total
    # preemption storm (tiny pool): rollback/recompute stays in-bucket
    eng2 = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
        spec_k=3,
    ))
    pre = dict(decode_mod.TRACE_COUNTS)
    # per-prompt repetition (drafting stays active) but NO cross-request
    # sharing: the prefix cache must not absorb the pool pressure the
    # storm needs
    storm = [_loopy_prompts((n,), seed=40 + n)[0] for n in (9, 11, 7)]
    eng2.run([Request(prompt_ids=p,
                      sampling=SamplingParams(max_new_tokens=10))
              for p in storm])
    assert eng2.scheduler.preemption_count > 0
    storm = decode_mod.TRACE_COUNTS["paged_verify"] - pre["paged_verify"]
    assert storm <= 8, storm


def test_spec_no_block_leak_after_rollback(qwen3):
    """The accounting identity free_uncached + cached == pool holds after
    a run whose every verify tick rejected drafts (maximal rollback),
    including rejection mid-shared-block — and at no point does a block
    referenced by one sequence sit on the free list."""
    params, cfg = qwen3

    def junk(context, k):
        return [(int(context[-1]) + 53 + i) % 127 + 1 for i in range(k)]

    rng = np.random.default_rng(21)
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, 16)]
    prompts = [system + [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (3, 5, 9)]
    with _registered_draft("__test_junk_leak", junk) as name:
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=2, block_size=8, max_model_len=96,
            prefix_cache=True, prefill_chunk=8, spec_k=4, spec_draft=name,
        ))
        for p in prompts:
            eng.submit(Request(prompt_ids=p,
                               sampling=SamplingParams(max_new_tokens=6)))
        bm = eng.blocks
        while eng.has_work:
            eng.step()
            free = set(bm._free)
            for sid in list(bm._tables):
                for b in bm._tables[sid]:
                    assert b not in free, (sid, b)
                    assert bm.refcount(b) >= 1
        assert eng.metrics()["spec_proposed"] > 0
    assert bm.num_used == 0
    assert bm.num_free_uncached + bm.num_cached == bm.num_blocks - 1
    cache = eng.prefix_cache
    assert all(bm.refcount(b) == 0 for b in cache._by_block)
    assert cache.num_evictable() == len(cache)


def test_spec_eos_mid_verify_stops_exactly(qwen3):
    """When an accepted draft IS the eos token, emission stops there: no
    post-eos tokens leak out of a multi-token verify tick."""
    params, cfg = qwen3
    prompt = _loopy_prompts((17,), seed=9)[0]
    full = greedy_generate(params, cfg, prompt,
                           max_new_tokens=8)[len(prompt):]
    eos = full[4]
    want = full[: full.index(eos) + 1]

    def oracle(context, k):
        g = len(context) - len(prompt)
        return full[g:g + k]  # the true greedy continuation: full accept

    with _registered_draft("__test_oracle_eos", oracle) as name:
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=2, block_size=8, max_model_len=64,
            spec_k=4, spec_draft=name,
        ))
        rid = eng.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
            max_new_tokens=8, eos_id=eos,
        )))
        out = eng.run()[rid]
    assert out.finish_reason == "eos"
    assert out.token_ids == want
    assert eng.blocks.num_used == 0
    # accepted-token rollup counts SAVED decode steps: the truncated tick
    # emitted len(want)-1 tokens (prefill gave the first), one of which is
    # the tick's own step — not inflated by post-eos accepted drafts
    assert out.spec_accepted_tokens == len(want) - 2


# ---------------------------------------------------------- tracer / metrics
def test_spec_tpot_counts_multi_token_ticks(qwen3):
    """Satellite regression: with forced k-acceptance (oracle drafter) a
    request finishes in a handful of verify ticks; serve.tpot_s must
    divide by the per-tick RECORDED token counts, and the timeline must
    carry the verify_emit marks + the spec_accepted_tokens rollup."""
    params, cfg = qwen3
    prompt = _prompts((9,), seed=10)[0]
    n_new = 12
    full = greedy_generate(params, cfg, prompt,
                           max_new_tokens=n_new)[len(prompt):]

    def oracle(context, k):
        g = len(context) - len(prompt)
        return full[g:g + k]

    with _registered_draft("__test_oracle", oracle) as name:
        eng = InferenceEngine(params, cfg, EngineConfig(
            num_slots=1, block_size=8, max_model_len=64,
            spec_k=4, spec_draft=name,
        ))
        rid = eng.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
            max_new_tokens=n_new,
        )))
        out = eng.run()[rid]
    assert out.token_ids == full
    # full acceptance: every verify tick emitted k+1 tokens
    assert out.spec_accepted_tokens > 0
    tl = eng.tracer.get(rid)
    assert tl is not None and tl.decode_tokens == n_new - 1
    assert tl.spec_accepted_tokens == out.spec_accepted_tokens
    marks = [(s, d) for _, s, d in tl.marks if s == "verify_emit"]
    assert marks and all(d["tokens"] > 1 for _, d in marks)
    assert sum(d["tokens"] for _, d in marks) >= tl.spec_accepted_tokens
    assert out.tpot_s is not None and out.tpot_s >= 0
    doc = tl.to_doc()
    assert doc["spec_accepted_tokens"] == out.spec_accepted_tokens


def test_tracer_tpot_uses_recorded_tick_counts():
    """Direct unit pin of the bugfix: when the per-tick counts disagree
    with ``tokens - 1`` (the old assumption of one token per decode tick),
    the recorded counts win."""
    from veomni_tpu.observability.metrics import MetricsRegistry
    from veomni_tpu.observability.request_trace import RequestTracer

    tracer = RequestTracer(1, registry=MetricsRegistry())
    tracer.on_queued("r")
    tracer.on_admitted("r", 0)
    tracer.on_first_token("r")
    # one verify tick emitted 4 tokens (3 accepted drafts + bonus)
    tracer.on_decode_tokens("r", 4, spec_accepted=3)
    tl = tracer.on_finished("r", "length", tokens=5)
    assert tl is not None and tl.tpot_s is not None
    wall = tl.finished_t - tl.first_token_t
    assert tl.tpot_s == pytest.approx(wall / 4)
    assert tl.spec_accepted_tokens == 3
    # fallback: an engine that never reports tick counts keeps the old
    # (tokens - 1) denominator
    tracer.on_queued("s")
    tracer.on_admitted("s", 0)
    tracer.on_first_token("s")
    tl2 = tracer.on_finished("s", "length", tokens=3)
    wall2 = tl2.finished_t - tl2.first_token_t
    assert tl2.tpot_s == pytest.approx(wall2 / 2)


def test_spec_metrics_and_acceptance_window(qwen3):
    """serve.spec_* counters/gauge: lifetime totals monotone, the
    acceptance-rate gauge is window-scoped like decode_tokens_per_sec."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=96, spec_k=4,
    ))
    eng.run([Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=8))
             for p in _loopy_prompts((21, 17), seed=12)])
    m1 = eng.metrics()  # resets the window
    assert m1["spec_proposed"] > 0
    assert 0.0 < m1["spec_acceptance_rate"] <= 1.0
    assert m1["spec_accepted"] <= m1["spec_proposed"]
    m2 = eng.metrics()  # fresh window: rate zeroed, totals persist
    assert m2["spec_acceptance_rate"] == 0.0
    assert m2["spec_proposed"] == m1["spec_proposed"]
    from veomni_tpu.observability.metrics import get_registry

    names = {name for name, _ in get_registry().items_snapshot()}
    assert {"serve.spec_proposed", "serve.spec_accepted",
            "serve.spec_acceptance_rate"} <= names
