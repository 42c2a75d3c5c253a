"""The open-loop storm drill: seeded Poisson arrivals against a bounded-queue
engine, and a chaos leg over a self-healing fleet.

A closed loop (submit, wait, submit) can never overload an engine; a storm
fires its arrivals on a fixed schedule whether or not the engine keeps up,
which is what shows queue growth, shedding, deadline misses and the tail of
TTFT. :func:`run_open_loop_storm` draws ONE workload from ``seed`` (an
interactive/batch mix over a cycle of prompt lengths), measures the engine's
closed-loop completion capacity, and replays that workload at each arrival
rate against a fresh QoS engine with a bounded queue. With ``chaos_seed`` it
also hands a router factory to ``chaos.py::run_chaos_soak`` twice (fault-free,
then under the seed's plan), which owns the plan and the fleet invariants.

Who calls it: ``scripts/chaos_smoke.py`` (tier-1's fixed-seed drill) and
``tests/test_serve_qos.py::test_open_loop_bench_smoke``. It is a drill of
behaviour on whatever device JAX has, not a measurement: its times are the
host's, and no number of it is a speed (PERF.md section 7 lists the serving
cells the benchmark still waits for). It reads no environment.

Like ``chaos.py`` it imports the serving layer only inside its functions.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

#: the first class plays "interactive" and the last "batch"
STORM_CLASSES = "interactive:4,batch:1"
#: fleet size of the chaos leg
CHAOS_REPLICAS = 3
#: chaos goodput must stay above this share of the fault-free replay's
CHAOS_GOODPUT_FLOOR = 0.7


def _pctl(vals: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(vals), q)) if vals else 0.0


def _clone(protos: List[Any], deadlines: bool = True) -> List[Any]:
    """Fresh Request objects over the same prompts, classes and deadlines:
    every replay sees the IDENTICAL workload while each engine assigns its
    own request ids."""
    from veomni_tpu.serving import Request

    return [Request(prompt_ids=list(r.prompt_ids), sampling=r.sampling,
                    priority=r.priority,
                    deadline_s=r.deadline_s if deadlines else None)
            for r in protos]


def run_open_loop_storm(
    params,
    cfg,
    *,
    num_slots: int,
    block_size: int,
    n_requests: int,
    prompt_lens: Sequence[int],
    max_new_tokens: int,
    arrival_rate_mults: Sequence[float] = (),
    arrival_rates: Sequence[float] = (),
    queue_bound: int = 0,
    deadline_s: float = 0.0,
    interactive_frac: float = 0.5,
    seed: int = 0,
    chaos_seed: Optional[int] = None,
    chaos_stall_s: float = 2.0,
    chaos_publishes: int = 0,
) -> Dict[str, Any]:
    """Replay one seeded workload open-loop at each arrival rate.

    ``arrival_rates`` are requests/s; ``arrival_rate_mults`` scale the
    measured closed-loop capacity instead (2.0 is past capacity). Interactive
    requests (``interactive_frac`` of the mix) carry ``deadline_s`` when it
    is set. ``queue_bound`` 0 means ``4 * num_slots``. Each ``sweep`` entry
    reports the reject and deadline-miss rates, p50/p99 TTFT (overall and
    interactive only), p99 TPOT, decode tokens/s, the largest queue depth
    seen and **goodput**: tokens of requests that finished inside their
    deadline per second of wall time.

    ``chaos_seed`` adds ``result["chaos"]``: the storm at the highest rate
    through a :data:`CHAOS_REPLICAS`-replica self-healing router (wedge
    deadline ``chaos_stall_s``), once fault-free and once under
    ``build_chaos_plan(chaos_seed)`` with ``chaos_publishes`` mid-storm
    weight publications; ``ok`` needs both soaks' invariants and a goodput
    ratio of at least :data:`CHAOS_GOODPUT_FLOOR`.
    """
    from veomni_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        Request,
        SamplingParams,
        parse_classes,
    )

    max_len = max(prompt_lens) + max_new_tokens
    queue_bound = queue_bound or 4 * num_slots
    rng = np.random.default_rng(seed)
    class_names = [n for n, _ in parse_classes(STORM_CLASSES)]
    hi_class, lo_class = class_names[0], class_names[-1]

    def make_requests(n):
        reqs = []
        for i in range(n):
            want = prompt_lens[i % len(prompt_lens)]
            prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, want)]
            interactive = bool(rng.random() < interactive_frac)
            reqs.append(Request(
                prompt_ids=prompt,
                sampling=SamplingParams(max_new_tokens=max_new_tokens),
                priority=hi_class if interactive else lo_class,
                deadline_s=(deadline_s if interactive and deadline_s > 0
                            else None),
            ))
        return reqs

    def engine_cfg(**kw):
        return EngineConfig(num_slots=num_slots, block_size=block_size,
                            max_model_len=max_len, classes=STORM_CLASSES,
                            **kw)

    # closed-loop calibration: completion capacity with every slot busy.
    # The jit caches are per engine, so each engine below is warmed with the
    # same ``warm`` set before its window opens.
    cal = InferenceEngine(params, cfg, engine_cfg())
    warm = make_requests(len(prompt_lens))
    for r in warm:
        cal.run([r])
    proto = make_requests(n_requests)  # ONE workload, replayed per rate
    # no deadlines here: an expiry "completing" a request early would
    # inflate the capacity the multiples scale from
    t0 = time.perf_counter()
    cal.run(_clone(proto, deadlines=False))
    capacity_rps = n_requests / max(time.perf_counter() - t0, 1e-9)

    rates = [float(r) for r in arrival_rates] or [
        m * capacity_rps for m in arrival_rate_mults]

    def run_rate(rate):
        eng = InferenceEngine(params, cfg, engine_cfg(queue_bound=queue_bound))
        for r in _clone(warm, deadlines=False):
            eng.run([r])
        reqs = _clone(proto)
        # the Poisson pattern is a function of (seed, rate) alone
        arng = np.random.default_rng((seed, int(rate * 1e6)))
        arrivals = np.cumsum(arng.exponential(1.0 / rate, size=n_requests))
        m0 = eng.metrics()  # resets the goodput/throughput window
        ids = []
        max_queue = 0
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs) or eng.has_work:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now:
                ids.append(eng.submit(reqs[i]))  # open loop: never blocks
                i += 1
            max_queue = max(max_queue, eng.scheduler.queue_depth)
            if eng.has_work:
                eng.step()
            elif i < len(reqs):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        dt = time.perf_counter() - t0
        m1 = eng.metrics(reset_window=False)
        outs = {rid: eng._outputs[rid] for rid in ids}
        done = [o for o in outs.values()
                if o.finish_reason in ("eos", "length")]
        ttfts = [o.ttft_s for o in done if o.ttft_s is not None]
        inter_ttfts = [outs[rid].ttft_s for rid, r in zip(ids, reqs)
                       if r.priority == hi_class
                       and outs[rid].ttft_s is not None]
        tpots = [o.tpot_s for o in done if o.tpot_s is not None]
        return {
            "arrival_rate_rps": rate,
            "rate_vs_capacity": rate / max(capacity_rps, 1e-9),
            "reject_rate": sum(
                1 for o in outs.values() if o.finish_reason == "rejected"
            ) / max(1, n_requests),
            "deadline_miss_rate": sum(
                1 for o in outs.values() if o.deadline_missed
            ) / max(1, n_requests),
            "completed": len(done),
            "max_queue_depth": max_queue,
            "ttft_p50_s": _pctl(ttfts, 50),
            "ttft_p99_s": _pctl(ttfts, 99),
            "ttft_p99_interactive_s": _pctl(inter_ttfts, 99),
            "tpot_p99_s": _pctl(tpots, 99),
            "decode_tok_s": sum(len(o.token_ids) for o in done) / dt,
            # goodput over the open-loop wall, not the window's own clock
            "goodput_tok_s": (m1["goodput_tokens"] - m0["goodput_tokens"])
            / dt,
            "shed_tokens": m1["shed_tokens"] - m0["shed_tokens"],
            "prefix_hit_rate": m1["prefix_hit_rate"],
        }

    result = {
        "capacity_rps": capacity_rps,
        "queue_bound": queue_bound,
        "sweep": [run_rate(rate) for rate in rates],
    }
    if chaos_seed is not None:
        result["chaos"] = _chaos_leg(
            params, cfg, engine_cfg(queue_bound=queue_bound * CHAOS_REPLICAS),
            warm=warm, proto=proto, rate=max(rates), seed=seed,
            chaos_seed=chaos_seed, stall_s=chaos_stall_s,
            publishes=chaos_publishes,
            expected_ticks=max(50, (n_requests * max_new_tokens) // 8),
        )
    return result


def _chaos_leg(params, cfg, engine_config, *, warm, proto, rate, seed,
               chaos_seed, stall_s, publishes, expected_ticks):
    """The same storm fault-free and under ``chaos_seed``'s plan, each
    through a fresh self-healing router; the seed in the report replays a
    failure bit for bit."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.resilience.chaos import build_chaos_plan, run_chaos_soak
    from veomni_tpu.serving import Router, RouterConfig

    arng = np.random.default_rng((seed, 777))
    arrivals = [float(t) for t in np.cumsum(
        arng.exponential(1.0 / rate, size=len(proto)))]

    def factory():
        router = Router(params, cfg, engine_config, RouterConfig(
            replicas=CHAOS_REPLICAS, replica_stall_ticks=2, max_respawns=4,
            respawn_backoff_s=0.05, respawn_backoff_max_s=0.5,
            probation_requests=2))
        # warm under the default forgiving stall deadline (a compile must
        # not read as a wedge), and twice: the second pass's prefix-cache
        # hits go through the chunked-prefill program, which would
        # otherwise first compile mid-storm and trip the deadline below
        for _ in range(2):
            router.run(_clone(warm, deadlines=False))
        router.config.replica_stall_s = stall_s
        return router

    def publish(router, idx):
        # same shapes and dtypes, so the hot swap traces nothing
        scale = 1.0 + 1e-3 * (idx + 1)
        payload = jax.tree_util.tree_map(
            lambda x: x * scale
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)
            else x, params)
        return router.publish_weights(payload, f"storm-v{idx + 1}")

    plan = build_chaos_plan(
        chaos_seed, duration_s=arrivals[-1],
        hang_seconds=2.0 * stall_s + 1.0, expected_ticks=expected_ticks,
        publishes=publishes,
    )
    fault_free = run_chaos_soak(
        router_factory=factory, requests=_clone(proto), arrivals=arrivals,
        plan=None, restore_timeout_s=60.0)
    chaos = run_chaos_soak(
        router_factory=factory, requests=_clone(proto), arrivals=arrivals,
        plan=plan, publish_fn=publish if publishes > 0 else None,
        restore_timeout_s=60.0)
    ratio = chaos["goodput_tok_s"] / max(fault_free["goodput_tok_s"], 1e-9)

    def slim(report):
        return {k: v for k, v in report.items()
                if k not in ("outputs", "router")}

    return {
        "seed": chaos_seed,
        "replicas": CHAOS_REPLICAS,
        "arrival_rate_rps": rate,
        "plan": plan.to_doc(),
        "fault_free": slim(fault_free),
        "chaos": slim(chaos),
        "goodput_ratio": ratio,
        "ok": bool(fault_free["invariants_ok"] and chaos["invariants_ok"]
                   and ratio >= CHAOS_GOODPUT_FLOOR),
    }
