"""QoS serving layer: SLO classes, tenant fairness, load-shedding, deadlines.

The heavy-traffic hardening guarantees (docs/serving.md "QoS, fairness &
overload"):

* single-class / no-deadline config is **behavior-identical to the seed
  FIFO scheduler** (the whole of tests/test_serving.py runs on the default
  config and pins that);
* interactive requests cannot starve behind a batch backlog, and batch is
  preempted before interactive;
* one tenant cannot starve another inside a class (bounded share);
* past the queue bound, ``submit()`` load-sheds with a terminal
  ``rejected`` status instead of growing the queue — and a shed storm
  (including mid-chunked-prefill cancellations) leaks zero KV blocks;
* deadline-expired waiting/prefilling requests are cancelled; survivors
  stay token-exact vs an unloaded run;
* under open-loop overload at ~2x capacity the bounded-queue QoS engine
  rejects (never grows past the bound) and interactive p99 TTFT beats the
  FIFO baseline on the same workload.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.models.decode import greedy_generate
from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.resilience.faults import (
    InjectedFault,
    configure_faults,
    disarm_faults,
    fired_faults,
)
from veomni_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    KVBlockManager,
    Request,
    SamplingParams,
    Scheduler,
    SequenceState,
    parse_classes,
)

QWEN3 = dict(
    model_type="qwen3", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True,
)


@pytest.fixture(scope="module")
def qwen3():
    cfg = TransformerConfig(dtype=jnp.float32, **QWEN3)
    model = build_foundation_model(config=cfg)
    return model.family.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture(autouse=True)
def _disarm():
    yield
    disarm_faults()


def _prompts(lengths, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


def _seq(rid, n_prompt, priority="interactive", tenant="", deadline_s=None):
    return SequenceState(request=Request(
        prompt_ids=list(range(1, n_prompt + 1)), request_id=rid,
        priority=priority, tenant=tenant, deadline_s=deadline_s,
    ))


def _pool_identity(eng):
    """The no-leak identity: every non-cached block on the free list, every
    cached block refcount-0, nothing still attributed to a sequence."""
    bm = eng.blocks
    assert bm.num_used == 0
    assert bm.num_free_uncached + bm.num_cached == bm.num_blocks - 1
    if eng.prefix_cache is not None:
        assert all(bm.refcount(b) == 0 for b in eng.prefix_cache._by_block)


# ------------------------------------------------------------- class parsing
def test_parse_classes():
    assert parse_classes("interactive:4,batch:1") == [
        ("interactive", 4), ("batch", 1)
    ]
    assert parse_classes(None) == [("interactive", 4), ("batch", 1)]
    assert parse_classes("rt:8, bulk:2 ,best_effort") == [
        ("rt", 8), ("bulk", 2), ("best_effort", 1)
    ]
    assert parse_classes([("a", 2)]) == [("a", 2)]
    with pytest.raises(ValueError, match="weight"):
        parse_classes("a:x")
    with pytest.raises(ValueError, match="weight"):
        parse_classes("a:0")
    with pytest.raises(ValueError, match="duplicate"):
        parse_classes("a:1,a:2")
    with pytest.raises(ValueError, match="no classes"):
        parse_classes(",")
    with pytest.raises(ValueError):  # malformed spec fails at construction
        EngineConfig(classes="a:-1")


def test_scheduler_unknown_priority():
    bm = KVBlockManager(num_blocks=8, block_size=4)
    multi = Scheduler(2, bm, classes=parse_classes(None))
    with pytest.raises(ValueError, match="unknown priority class 'vip'"):
        multi.add(_seq("a", 4, priority="vip"))
    # a single-class scheduler is the seed FIFO and accepts ANY label
    single = Scheduler(2, KVBlockManager(num_blocks=8, block_size=4),
                       classes=[("default", 1)])
    assert single.add(_seq("a", 4, priority="vip"))
    assert single.add(_seq("b", 4, priority="batch"))
    assert [s.seq_id for s in single.admit()] == ["a", "b"]  # plain FIFO


# -------------------------------------------------------- weighted admission
def test_scheduler_interactive_jumps_batch_backlog():
    """A batch backlog arrives first; interactive requests still get the
    weighted share of admissions (4:1 default) instead of queueing behind
    the entire backlog — and batch is NOT starved."""
    bm = KVBlockManager(num_blocks=64, block_size=4)
    sched = Scheduler(4, bm, classes=parse_classes(None))
    for i in range(4):
        sched.add(_seq(f"b{i}", 4, priority="batch"))
    for i in range(2):
        sched.add(_seq(f"i{i}", 4, priority="interactive"))
    # stride pick: interactive first (priority tie-break), then batch's
    # 1-in-5 turn, then interactive again
    assert [s.seq_id for s in sched.admit()] == ["i0", "b0", "i1", "b1"]


def test_scheduler_admission_order_weighted_share():
    """Drain a long mixed backlog through one slot: interactive ends up
    with ~4/5 of admissions while batch keeps progressing."""
    bm = KVBlockManager(num_blocks=64, block_size=4)
    sched = Scheduler(1, bm, classes=parse_classes(None))
    for i in range(10):
        sched.add(_seq(f"b{i}", 4, priority="batch"))
    for i in range(10):
        sched.add(_seq(f"i{i}", 4, priority="interactive"))
    order = []
    while sched.waiting and len(order) < 10:
        (adm,) = sched.admit()
        order.append(adm.seq_id)
        sched.finish(adm)
    n_inter = sum(1 for x in order if x.startswith("i"))
    assert n_inter == 8, order  # 4:1 stride over the first 10 picks
    assert any(x.startswith("b") for x in order)  # batch not starved


def test_scheduler_class_aware_preemption_order():
    """Pool pressure preempts BATCH before interactive even when the
    interactive sequence was admitted later (seed LIFO would evict it)."""
    bm = KVBlockManager(num_blocks=5, block_size=4)  # 4 usable
    sched = Scheduler(2, bm, classes=parse_classes(None))
    b = _seq("b", 4, priority="batch")
    sched.add(b)
    assert sched.admit() == [b]
    i = _seq("i", 4, priority="interactive")
    sched.add(i)
    assert sched.admit() == [i]
    assert b.admit_order < i.admit_order  # i is the newest admission
    b.prefilling = i.prefilling = False  # engine contract
    b.pos, i.pos = 4, 4
    sched.ensure_decode_capacity()  # both grow; pool dry
    i.pos = 8  # interactive needs another block
    preempted = sched.ensure_decode_capacity()
    # victim = newest admission of the LOWEST-priority class: batch
    assert preempted == [b] and b.slot == -1 and i.slot >= 0
    # within one class the choice stays LIFO (the seed test still passes
    # via test_serving.py; pin the class tie-break here too)
    assert sched._preempt_victim() is i  # only interactive left running


def test_scheduler_tenant_fairness_bounded_share():
    """A greedy tenant floods the queue; a trickle tenant arriving later
    still gets every other admission inside the class (unit-quantum DRR) —
    bounded share, no starvation."""
    bm = KVBlockManager(num_blocks=64, block_size=4)
    sched = Scheduler(1, bm, classes=parse_classes(None))
    for i in range(8):
        sched.add(_seq(f"greedy{i}", 4, tenant="greedy"))
    for i in range(3):
        sched.add(_seq(f"small{i}", 4, tenant="small"))
    order = []
    for _ in range(6):
        (adm,) = sched.admit()
        order.append(adm.seq_id)
        sched.finish(adm)
    # alternating shares while both are backlogged; FIFO within each tenant
    assert order == ["greedy0", "small0", "greedy1", "small1",
                     "greedy2", "small2"], order
    # a tenant joining late starts at the current credit level — it cannot
    # burst to "catch up" on rounds it never waited through
    sched.add(_seq("late0", 4, tenant="late"))
    sched.add(_seq("late1", 4, tenant="late"))
    (adm,) = sched.admit()
    assert adm.tenant == "late"  # fair share from now on...
    sched.finish(adm)
    (adm2,) = sched.admit()
    assert adm2.tenant == "greedy"  # ...but not two in a row


def test_scheduler_queue_bound_and_requeue_exempt():
    bm = KVBlockManager(num_blocks=8, block_size=4)
    sched = Scheduler(1, bm, classes=parse_classes(None), queue_bound=2)
    a = _seq("a", 4)
    sched.add(a)
    assert sched.admit() == [a]
    assert sched.add(_seq("w1", 4))
    assert sched.add(_seq("w2", 4))
    assert not sched.add(_seq("w3", 4))  # bound reached: shed
    assert len(sched.waiting) == 2
    # preemption requeue is EXEMPT: admitted work is never shed by its own
    # recompute — the queue may transiently exceed the bound
    a.prefilling = False
    a.pos = 40  # needs more blocks than the whole pool holds
    assert sched.ensure_decode_capacity() == [a]
    assert len(sched.waiting) == 3 and sched.waiting[0] is a


def test_scheduler_tenant_inflight_cap():
    bm = KVBlockManager(num_blocks=16, block_size=4)
    sched = Scheduler(2, bm, classes=parse_classes(None),
                      tenant_max_inflight=2)
    assert sched.add(_seq("a1", 4, tenant="a"))
    assert sched.add(_seq("a2", 4, tenant="a"))
    assert not sched.add(_seq("a3", 4, tenant="a"))  # cap: waiting counts
    assert sched.add(_seq("b1", 4, tenant="b"))  # other tenants unaffected
    sched.admit()  # a1, a2 admitted (b1 waits: 2 slots)
    assert not sched.add(_seq("a4", 4, tenant="a"))  # running counts too
    for _, s in sched.running():
        sched.finish(s)
    assert sched.add(_seq("a5", 4, tenant="a"))  # capacity released


# --------------------------------------------------------- engine: shedding
def test_engine_rejects_past_queue_bound(qwen3):
    params, cfg = qwen3
    reg = get_registry()
    rej0 = reg.counter("serve.rejected").value
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, queue_bound=2,
    ))
    prompts = _prompts((5, 7, 9, 6, 8), seed=30)
    ids = [eng.submit(Request(prompt_ids=p,
                              sampling=SamplingParams(max_new_tokens=4)))
           for p in prompts]
    # the queue never grew past the bound; the overflow is terminal NOW
    assert eng.scheduler.queue_depth == 2
    shed = [rid for rid in ids if eng._outputs[rid].finished]
    assert len(shed) == 3
    for rid in shed:
        o = eng._outputs[rid]
        assert o.finish_reason == "rejected" and o.token_ids == []
    outs = eng.run()
    # run() hands back terminal outputs (rejected included) — a driver
    # never hangs waiting for tokens a shed request will not produce
    assert set(outs) == set(ids)
    m = eng.metrics()
    assert m["rejected"] == 3
    assert m["shed_tokens"] == sum(
        len(eng._outputs.get(rid, outs[rid]).prompt_ids) + 4 for rid in shed
    )
    assert reg.counter("serve.rejected").value - rej0 == 3
    # survivors are token-exact: shedding changed WHO ran, never WHAT the
    # survivors computed
    for rid, p in zip(ids[:2], prompts[:2]):
        want = greedy_generate(params, cfg, p, max_new_tokens=4)[len(p):]
        assert outs[rid].token_ids == want
    # the tracer carries the rejections as terminal timelines
    snap = eng.tracer.snapshot()
    rej_rows = [r for r in snap["finished"]
                if r.get("finish_reason") == "rejected"]
    assert len(rej_rows) == 3
    _pool_identity(eng)


def test_engine_tenant_inflight_cap(qwen3):
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, tenant_max_inflight=1,
    ))
    p1, p2, p3 = _prompts((5, 7, 6), seed=31)
    r1 = eng.submit(Request(prompt_ids=p1, tenant="t0",
                            sampling=SamplingParams(max_new_tokens=4)))
    r2 = eng.submit(Request(prompt_ids=p2, tenant="t0",
                            sampling=SamplingParams(max_new_tokens=4)))
    r3 = eng.submit(Request(prompt_ids=p3, tenant="t1",
                            sampling=SamplingParams(max_new_tokens=4)))
    assert eng._outputs[r2].finish_reason == "rejected"  # t0 at cap
    outs = eng.run()
    assert outs[r1].finish_reason == "length"
    assert outs[r3].finish_reason == "length"  # other tenant unaffected
    _pool_identity(eng)


# --------------------------------------------------------- engine: deadlines
def test_engine_deadline_expiry_cancellation_and_parity(qwen3):
    """Expired-while-waiting requests are cancelled (blocks released,
    terminal 'deadline' status) and the survivors stay token-exact vs an
    unloaded run."""
    params, cfg = qwen3
    reg = get_registry()
    miss0 = reg.counter("serve.deadline_misses").value
    prompts = _prompts((9, 11, 7, 8), seed=32)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=1, block_size=8, max_model_len=64,
    ))
    # slot width 1: the later arrivals genuinely WAIT; the deadline=0 ones
    # expire in the queue before a slot ever frees for them
    ids, deadlines = [], [None, 0.0, None, 0.0]
    for p, dl in zip(prompts, deadlines):
        ids.append(eng.submit(Request(
            prompt_ids=p, deadline_s=dl,
            sampling=SamplingParams(max_new_tokens=6),
        )))
    outs = eng.run()
    for rid, p, dl in zip(ids, prompts, deadlines):
        if dl is None:
            want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
            assert outs[rid].token_ids == want  # survivor parity
            assert not outs[rid].deadline_missed
        else:
            assert outs[rid].finish_reason == "deadline"
            assert outs[rid].deadline_missed and outs[rid].token_ids == []
    assert reg.counter("serve.deadline_misses").value - miss0 == 2
    assert eng.metrics()["deadline_misses"] == 2
    _pool_identity(eng)


def test_engine_late_finish_counts_deadline_miss_not_goodput(qwen3):
    """A request that is already DECODING when its deadline passes runs to
    completion (the tokens exist; cancelling wastes them) but is marked
    deadline_missed and contributes nothing to goodput."""
    params, cfg = qwen3
    p1, p2 = _prompts((9, 7), seed=33)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    r1 = eng.submit(Request(prompt_ids=p1, deadline_s=30.0,
                            sampling=SamplingParams(max_new_tokens=5)))
    r2 = eng.submit(Request(prompt_ids=p2,
                            sampling=SamplingParams(max_new_tokens=5)))
    eng.metrics()  # reset the window
    eng.step()  # r1 admitted + first token: now decoding
    # make the deadline ALREADY passed without wall-clock sleeps: shift the
    # submit time back (deterministic — no timing races in tier-1)
    seq = eng._find_seq(r1)
    assert seq is not None and not seq.prefilling
    seq.submit_time -= 60.0
    outs = eng.run()
    assert outs[r1].finish_reason == "length"  # ran to completion
    assert outs[r1].deadline_missed
    want = greedy_generate(params, cfg, p1, max_new_tokens=5)[len(p1):]
    assert outs[r1].token_ids == want  # tokens kept, and still exact
    m = eng.metrics()
    assert m["deadline_misses"] == 1
    # goodput counted ONLY the in-deadline request's tokens
    assert m["goodput_tokens"] == 5
    m2 = eng.metrics()  # window reset: rate returns to 0
    assert m2["goodput_tokens_per_sec"] == 0.0
    assert m2["goodput_tokens"] == 5  # lifetime total survives


def test_preempted_streaming_request_not_cancelled_by_deadline(qwen3):
    """Review-pinned: deadline expiry only cancels requests that produced
    NOTHING. A request that already streamed tokens and then got preempted
    (requeued, waiting past its deadline) is re-admitted and runs to
    completion — cancelling it mid-stream would waste delivered tokens and
    make the client-visible outcome depend on pool pressure. It finishes
    late: deadline_missed, excluded from goodput, tokens exact."""
    params, cfg = qwen3
    prompts = _prompts((9, 11, 7), seed=44)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=40, num_blocks=8,
    ))
    ids = [eng.submit(Request(prompt_ids=p, deadline_s=30.0,
                              sampling=SamplingParams(max_new_tokens=10)))
           for p in prompts]
    victim = None
    while eng.has_work:
        eng.step()
        if victim is None:
            streaming_waiters = [s for s in eng.scheduler.waiting
                                 if s.generated]
            if streaming_waiters:
                victim = streaming_waiters[0]
                victim.submit_time -= 60.0  # deadline now LONG past
    assert victim is not None  # preemption really hit a streaming request
    outs = eng.run()
    out = outs[victim.seq_id]
    assert out.finish_reason == "length"  # finished, not "deadline"
    assert out.deadline_missed
    idx = ids.index(victim.seq_id)
    want = greedy_generate(params, cfg, prompts[idx],
                           max_new_tokens=10)[len(prompts[idx]):]
    assert out.token_ids == want
    _pool_identity(eng)


def test_engine_cancel_mid_prefill_releases_blocks(qwen3):
    """The satellite bugfix pin: cancelling a request mid-chunked-prefill
    releases its partially-claimed blocks (and any cow pin) — the pool
    identity holds immediately, not just after a drain."""
    params, cfg = qwen3
    long_prompt = _prompts((60,), seed=34)[0]
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=128,
        prefix_cache=True, prefill_chunk=8,
    ))
    rid = eng.submit(Request(prompt_ids=long_prompt,
                             sampling=SamplingParams(max_new_tokens=4)))
    eng.step()  # admitted + first chunk
    seq = eng._find_seq(rid)
    assert seq is not None and seq.prefilling  # genuinely mid-prefill
    assert eng.blocks.num_used > 0
    assert eng.cancel(rid)
    out = eng._outputs[rid]
    assert out.finished and out.finish_reason == "cancelled"
    _pool_identity(eng)
    assert not eng.cancel(rid)  # idempotent: already terminal
    assert not eng.has_work


def test_engine_shed_storm_no_block_leaks(qwen3):
    """Shed-under-pressure storm over a TIGHT pool with chunked prefill:
    rejections, deadline expirations (waiting AND mid-prefill), explicit
    cancels, preemptions and completions all interleave — afterwards the
    block accounting identity holds exactly (free_uncached + cached ==
    pool) and survivors are token-exact."""
    params, cfg = qwen3
    rng = np.random.default_rng(35)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, block_size=8, max_model_len=48, num_blocks=10,
        prefix_cache=True, prefill_chunk=8, queue_bound=4,
    ))
    prompts = _prompts((20, 30, 9, 25, 11, 28, 7, 18, 26, 13), seed=35)
    ids, survivors = [], {}
    for i, p in enumerate(prompts):
        dl = 0.0 if i % 3 == 1 else None  # a third expire in the queue
        ids.append(eng.submit(Request(
            prompt_ids=p, deadline_s=dl,
            sampling=SamplingParams(max_new_tokens=6),
        )))
        # churn: a couple of ticks between arrivals, with a mid-prefill
        # cancel thrown in whenever something is prefilling
        for _ in range(int(rng.integers(0, 3))):
            if eng.has_work:
                eng.step()
        if i == 4:
            prefilling = [s for _, s in eng.scheduler.running()
                          if s.prefilling]
            if prefilling:
                assert eng.cancel(prefilling[0].seq_id)
    outs = eng.run()
    statuses = {rid: eng._outputs.get(rid, outs.get(rid)).finish_reason
                for rid in ids}
    assert any(v == "deadline" for v in statuses.values())
    for rid, p in zip(ids, prompts):
        o = outs.get(rid) or eng._outputs.get(rid)
        if o.finish_reason in ("eos", "length"):
            survivors[rid] = (p, o)
    assert survivors  # the storm didn't shed literally everything
    for rid, (p, o) in survivors.items():
        want = greedy_generate(params, cfg, p, max_new_tokens=6)[len(p):]
        assert o.token_ids == want, (rid, o.token_ids, want)
    _pool_identity(eng)


# ------------------------------------------------------- engine: fault drills
def test_serve_admit_fault_point(qwen3):
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    configure_faults([{"point": "serve.admit", "mode": "exception",
                       "hit": 2}])
    p1, p2 = _prompts((5, 7), seed=36)
    eng.submit(Request(prompt_ids=p1,
                       sampling=SamplingParams(max_new_tokens=3)))
    with pytest.raises(InjectedFault):
        eng.submit(Request(prompt_ids=p2,
                           sampling=SamplingParams(max_new_tokens=3)))
    disarm_faults()
    outs = eng.run()  # the accepted request is unaffected by the drill
    assert len(outs) == 1
    _pool_identity(eng)


def test_serve_prefill_delay_fault(qwen3):
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    configure_faults([{"point": "serve.prefill", "mode": "delay", "ms": 1,
                       "times": 2}])
    eng.run([Request(prompt_ids=_prompts((9,), seed=37)[0],
                     sampling=SamplingParams(max_new_tokens=3))])
    fired = [a for a in fired_faults() if a.point == "serve.prefill"]
    assert fired and all(a.mode == "delay" for a in fired)


def test_serve_decode_tick_delay_drill_postmortem_names_tick(qwen3, tmp_path):
    """The serving stall drill: a delay fault on serve.decode_tick outlives
    the watchdog deadline; the dog's flight-recorder post-mortem carries
    the injected-fault event naming the stalled tick (and thread stacks) —
    exactly the artifact an operator gets from a real decode stall."""
    from veomni_tpu.observability.flight_recorder import (
        configure_flight_recorder,
    )
    from veomni_tpu.utils.helper import Watchdog

    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
    ))
    # warm the jit caches first: a compile wall would also trip a 0.3s dog
    eng.run([Request(prompt_ids=_prompts((5,), seed=38)[0],
                     sampling=SamplingParams(max_new_tokens=2))])
    configure_flight_recorder(dump_dir=str(tmp_path), fresh=True)
    configure_faults([{"point": "serve.decode_tick", "mode": "delay",
                       "hit": 2, "ms": 900}])
    wd = Watchdog(0.25, exit_code=None, description="serve drill").start()
    try:
        outs = eng.run([Request(prompt_ids=_prompts((7,), seed=39)[0],
                                sampling=SamplingParams(max_new_tokens=4))])
    finally:
        wd.stop()
        disarm_faults()
    assert wd.stall_count >= 1  # the dog fired DURING the stalled tick
    assert wd.last_postmortem_path
    with open(wd.last_postmortem_path) as f:
        pm = json.load(f)
    faults = [e for e in pm["events"]
              if e["kind"] == "fault.injected"
              and e["cid"] == "serve.decode_tick"]
    assert faults, [e["kind"] for e in pm["events"]]
    assert faults[0]["payload"]["mode"] == "delay"
    assert pm["thread_stacks"]  # where every thread was, mid-stall
    # the run itself survived the drill (delay, not a wedge): tokens exact
    (out,) = outs.values()
    assert out.finish_reason == "length"


# ------------------------------------------------------------ overload drill
def _drive_overload(params, cfg, classes, batch_prompts, inter_prompts,
                    queue_bound=0):
    """Staged overload: a batch backlog saturates the engine, interactive
    requests arrive after the first wave is already running. Returns
    (outputs-by-id, interactive ids, batch ids, max observed queue depth,
    engine)."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64,
        classes=classes, queue_bound=queue_bound,
    ))
    # warm EVERY bucket the timed run can hit (one length class at a time,
    # full allocation trajectory): a cold compile landing on an interactive request in one engine but a
    # batch request in the other would swamp the scheduling signal the
    # TTFT comparison measures
    for p in _prompts((6, 9, 12), seed=99):
        eng.run([Request(prompt_ids=p,
                         sampling=SamplingParams(max_new_tokens=8))])
    ids_b = [eng.submit(Request(prompt_ids=p, priority="batch",
                                sampling=SamplingParams(max_new_tokens=8)))
             for p in batch_prompts]
    max_q = eng.scheduler.queue_depth
    for _ in range(2):  # first batch wave starts decoding
        eng.step()
        max_q = max(max_q, eng.scheduler.queue_depth)
    ids_i = [eng.submit(Request(prompt_ids=p, priority="interactive",
                                sampling=SamplingParams(max_new_tokens=8)))
             for p in inter_prompts]
    max_q = max(max_q, eng.scheduler.queue_depth)
    outs = {}
    while eng.has_work:
        eng.step()
        max_q = max(max_q, eng.scheduler.queue_depth)
    outs.update(eng.run())
    for rid in ids_b + ids_i:  # rejected outputs stay in _outputs until run
        if rid not in outs:
            outs[rid] = eng._outputs[rid]
    return outs, ids_i, ids_b, max_q, eng


def test_overload_interactive_p99_beats_fifo_and_parity(qwen3):
    """The acceptance drill: same overload workload through (1) a
    single-class FIFO engine and (2) the QoS engine with a bounded queue.
    The QoS side must (a) shed — nonzero rejected, queue never past the
    bound, (b) give interactive strictly better p99 TTFT than FIFO, (c)
    keep every non-shed output token-exact, (d) leak zero blocks."""
    params, cfg = qwen3
    batch_prompts = _prompts((9, 11, 7, 10, 8, 12), seed=40)
    inter_prompts = _prompts((6, 9, 7, 8), seed=41)

    fifo_outs, fifo_i, _, _, fifo_eng = _drive_overload(
        params, cfg, "default:1", batch_prompts, inter_prompts,
        queue_bound=0,
    )
    qos_outs, qos_i, qos_b, max_q, qos_eng = _drive_overload(
        params, cfg, "interactive:4,batch:1", batch_prompts, inter_prompts,
        queue_bound=5,
    )
    # (a) load was actually shed, and the queue respected its bound
    n_rej = sum(1 for rid, o in qos_outs.items()
                if o.finish_reason == "rejected")
    assert n_rej > 0
    assert max_q <= 5
    assert qos_eng.metrics()["rejected"] == n_rej

    # (b) interactive p99 TTFT strictly better than the FIFO baseline
    def p99(outs, ids):
        vals = [outs[r].ttft_s for r in ids
                if outs[r].ttft_s is not None]
        assert vals
        return float(np.percentile(np.asarray(vals), 99))

    assert p99(qos_outs, qos_i) < p99(fifo_outs, fifo_i), (
        p99(qos_outs, qos_i), p99(fifo_outs, fifo_i)
    )
    # (c) token parity for every non-shed request, both engines
    for outs, prompts_by_id in (
        (fifo_outs, dict(zip(fifo_i, inter_prompts))),
        (qos_outs, dict(zip(qos_i, inter_prompts))),
        (qos_outs, dict(zip(qos_b, batch_prompts))),
    ):
        for rid, p in prompts_by_id.items():
            o = outs[rid]
            if o.finish_reason == "rejected":
                continue
            want = greedy_generate(params, cfg, p,
                                   max_new_tokens=8)[len(p):]
            assert o.token_ids == want, (rid, o.token_ids, want)
    # (d) zero leaked blocks on both engines
    _pool_identity(fifo_eng)
    _pool_identity(qos_eng)


def test_open_loop_bench_smoke(qwen3):
    """The open-loop storm drill end to end on CPU: Poisson arrivals at 3x
    measured capacity against a bounded queue produce a well-formed sweep
    entry with nonzero rejects, a respected bound, and the fields its
    callers read (reject_rate / p99 TTFT / goodput)."""
    from veomni_tpu.resilience.storm import run_open_loop_storm

    params, cfg = qwen3
    r = run_open_loop_storm(
        params, cfg, num_slots=2, block_size=8, n_requests=16, prompt_lens=(12, 20),
        max_new_tokens=6, arrival_rate_mults=(3.0,), queue_bound=3,
        deadline_s=2.0, interactive_frac=0.5, seed=42,
    )
    assert r["capacity_rps"] > 0
    (entry,) = r["sweep"]
    assert entry["rate_vs_capacity"] == pytest.approx(3.0)
    for key in ("reject_rate", "deadline_miss_rate", "ttft_p50_s",
                "ttft_p99_s", "ttft_p99_interactive_s", "tpot_p99_s",
                "goodput_tok_s", "decode_tok_s", "max_queue_depth",
                "shed_tokens", "completed"):
        assert key in entry, key
    assert entry["reject_rate"] > 0  # 3x capacity vs a 3-deep queue
    assert entry["max_queue_depth"] <= 3
    assert entry["completed"] > 0 and entry["goodput_tok_s"] >= 0
    json.dumps(r)  # the whole result is JSON-serializable
