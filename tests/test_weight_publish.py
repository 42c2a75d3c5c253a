"""Crash-safe live weight publication drills.

The rolling publish (docs/serving.md "Versioned weight publication")
turns the router's version tag from a label on FUTURE replicas into a
live control surface over the RUNNING fleet. These tests pin its whole
contract:

* ``WeightStore``: monotonic sequence numbers, immutable version tags;
* the engine swap: drain-fenced (never mid-stream), structurally
  validated (an incongruent payload would silently retrace — refused),
  prefix cache flushed under a bumped ``cache_epoch`` with the
  block-manager no-leak identity conserved, and ZERO new jit traces;
* **token parity**: a published engine must produce byte-identical
  streams to a FRESH engine built on the new weights — any divergence
  means stale KV (or stale buffers) survived the swap;
* the roll: one replica at a time, ``min_live`` respected, nobody
  starved, respawns and late arrivals attach at the LATEST version even
  when the publish itself is what killed a replica (``serve.publish``
  fault drill);
* the checkpoint gate: corrupt or uncommitted generations are refused
  BEFORE any buffer is touched;
* chaos composition: publish events extend seeded plans without moving
  a single fault/kill draw of existing seeds, and a soak that schedules
  publishes without a ``publish_fn`` refuses to silently skip them.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.models import decode as decode_mod
from veomni_tpu.resilience.faults import configure_faults, disarm_faults
from veomni_tpu.resilience.integrity import (
    CheckpointCorruptError,
    write_manifest,
)
from veomni_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    Request,
    SamplingParams,
    WeightStore,
    load_published_params,
)
from veomni_tpu.serving.replica import STATE_PROBATION
from veomni_tpu.serving.router import Router, RouterConfig

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
def _clean_faults():
    yield
    disarm_faults()


def _perturb(params, seed=7, scale=0.1):
    """A payload that is congruent but decisively DIFFERENT: per-leaf
    additive noise big enough to move greedy argmaxes, proving a swap is
    live rather than a no-op."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    rng = np.random.default_rng(seed)
    out = []
    for x in leaves:
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            noise = rng.standard_normal(x.shape).astype(np.float32) * scale
            out.append(x + jnp.asarray(noise, dtype=x.dtype))
        else:
            out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _prompts(n, seed=0, length=8, prefix=()):
    rng = np.random.default_rng(seed)
    return [list(prefix) + [int(t) for t in rng.integers(1, 128, length)]
            for _ in range(n)]


def _reqs(prompts, n_new=6):
    return [Request(prompt_ids=list(p),
                    sampling=SamplingParams(max_new_tokens=n_new))
            for p in prompts]


def _pool_identity(eng):
    bm = eng.blocks
    assert bm.num_used == 0
    assert bm.num_free_uncached + bm.num_cached == bm.num_blocks - 1


def _engine_cfg(**kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_model_len", 128)
    return EngineConfig(**kw)


def _drain(router, timeout_s=60.0):
    deadline = time.perf_counter() + timeout_s
    while router.has_work and time.perf_counter() < deadline:
        router.step()
    assert not router.has_work, "router failed to drain"


def _restore_fleet(router, probe_prompt, timeout_s=60.0):
    """Drive respawns to landing and probation replicas to parole (same
    idiom as test_self_healing.py). Returns probe request ids."""
    probes = []
    deadline = time.perf_counter() + timeout_s
    n_cfg = router.config.replicas
    while time.perf_counter() < deadline:
        probation = [h for h in router.replicas.values()
                     if h.state == STATE_PROBATION]
        if (len(router.live_replicas()) >= n_cfg
                and not router._pending_respawns and not probation
                and not router.has_work):
            return probes
        if router.has_work or router._pending_respawns:
            router.step()
            continue
        burst = router.config.spill_queue_depth + 1 + sum(
            router.config.probation_requests for _ in probation)
        for req in _reqs([probe_prompt] * burst, n_new=4):
            probes.append(router.submit(req))
    raise AssertionError("fleet did not restore in time")


# --------------------------------------------------------------- WeightStore
def test_weight_store_monotonic_seq_and_immutable_tags(qwen3):
    params, _ = qwen3
    store = WeightStore(params, "v0")
    assert store.latest.version == "v0" and store.latest.seq == 0
    rec = store.put("step-100", params)
    assert rec.seq == 1 and store.latest.version == "step-100"
    assert store.seq("v0") == 0 and store.seq("step-100") == 1
    assert store.seq("never-published") == -1
    assert store.versions() == ["v0", "step-100"]
    assert "v0" in store and "nope" not in store and len(store) == 2
    with pytest.raises(ValueError, match="immutable"):
        store.put("v0", params)  # retagging is a caught operator error
    with pytest.raises(ValueError, match="non-empty"):
        store.put("", params)
    assert store.get("v0").params is params


# ----------------------------------------------------------- the engine swap
def test_swap_refuses_busy_engine_and_incongruent_payloads(qwen3):
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, _engine_cfg())
    eng.submit(_reqs(_prompts(1), n_new=4)[0])
    with pytest.raises(RuntimeError, match="busy engine"):
        eng.swap_weights(_perturb(params))
    eng.run()  # drain; swaps are legal again
    # dtype change on every float leaf: congruence check must refuse it
    # BEFORE any state changes (it would silently retrace every program)
    half = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)
        else x, params)
    epoch_before = eng.cache_epoch
    with pytest.raises(ValueError, match="incongruent"):
        eng.swap_weights(half)
    assert eng.cache_epoch == epoch_before  # refusal changed nothing


def test_swap_flushes_prefix_cache_no_leak_identity(qwen3):
    """The cache-epoch invalidation: a swap flushes EVERY cached block
    back to the free pool (the no-leak identity holds across the flush),
    bumps the epoch, and the cache repopulates cleanly afterwards."""
    params, cfg = qwen3
    eng = InferenceEngine(params, cfg, _engine_cfg())
    shared = tuple(_prompts(1, seed=3, length=16)[0])
    eng.run(_reqs(_prompts(4, seed=4, prefix=shared), n_new=4))
    bm = eng.blocks
    cached_before = bm.num_cached
    assert cached_before > 0  # the swap has real cached KV to invalidate
    _pool_identity(eng)
    assert eng.cache_epoch == 0 and eng.prefix_cache.epoch == 0
    info = eng.swap_weights(_perturb(params))
    assert info["flushed_blocks"] == cached_before
    assert info["cache_epoch"] == 1
    assert eng.cache_epoch == 1 and eng.prefix_cache.epoch == 1
    assert bm.num_cached == 0  # stale KV is unreachable, not leaked
    _pool_identity(eng)
    # the flushed cache repopulates under the new weights
    eng.run(_reqs(_prompts(4, seed=5, prefix=shared), n_new=4))
    assert bm.num_cached > 0
    _pool_identity(eng)


def test_swap_token_parity_vs_fresh_engine_zero_traces(qwen3):
    """THE acceptance gate: after swapping perturbed weights into an
    engine with a hot prefix cache, its outputs must be token-identical
    to a FRESH engine built on the new weights (zero stale KV anywhere),
    must DIFFER from the old weights' streams (the swap is live), and
    the swap + post-swap serving must add zero jit traces."""
    params, cfg = qwen3
    new_params = _perturb(params)
    ecfg = _engine_cfg(num_slots=2)
    shared = tuple(_prompts(1, seed=9, length=16)[0])
    prompts = _prompts(4, seed=10, prefix=shared)
    eng = InferenceEngine(params, cfg, ecfg)
    old_outs = eng.run(_reqs(prompts))  # warm: cache hot, buckets traced
    _pool_identity(eng)
    base = dict(decode_mod.TRACE_COUNTS)
    eng.swap_weights(new_params)
    outs = eng.run(_reqs(prompts))  # same shapes -> same buckets
    assert decode_mod.TRACE_COUNTS == base, "weight swap must not retrace"
    fresh = InferenceEngine(new_params, cfg, ecfg)
    fresh_outs = fresh.run(_reqs(prompts))
    by_tokens = lambda outs: sorted(o.token_ids for o in outs.values())
    assert by_tokens(outs) == by_tokens(fresh_outs), \
        "published engine diverged from fresh engine on the same weights"
    assert by_tokens(outs) != by_tokens(old_outs), \
        "outputs unchanged after swap: the perturbed publish was a no-op"


# ------------------------------------------------------------ the rolling roll
def test_rolling_publish_respects_min_live_and_starves_nobody(qwen3):
    """A publish under load rolls ONE replica at a time, never drops the
    live fleet below min_live, and every request — submitted before,
    during and after the roll — reaches a clean terminal output."""
    params, cfg = qwen3
    r = Router(params, cfg, _engine_cfg(num_slots=2), RouterConfig(
        replicas=3, min_live=2))
    ids = [r.submit(q) for q in _reqs(_prompts(6, seed=20), n_new=5)]
    for _ in range(2):
        r.step()
    assert r.publish_weights(_perturb(params), "v1") == "v1"
    min_live_seen = len(r.live_replicas())
    max_publishing = 0
    ids += [r.submit(q) for q in _reqs(_prompts(4, seed=21), n_new=5)]
    deadline = time.perf_counter() + 60.0
    while r.has_work and time.perf_counter() < deadline:
        r.step()
        min_live_seen = min(min_live_seen, len(r.live_replicas()))
        max_publishing = max(max_publishing, sum(
            1 for h in r.replicas.values() if h.state == "publishing"))
    assert not r.has_work
    assert min_live_seen >= 2, "publish took the fleet below min_live"
    assert max_publishing <= 1, "roll must fence one replica at a time"
    assert not r.publish_in_progress
    assert all(h.weights_version == "v1" for h in r.live_replicas())
    outs = {i: r.pop_output(i) for i in ids}
    assert all(o is not None and o.finish_reason == "length"
               for o in outs.values()), "a request starved during the roll"


def test_kill_mid_publish_respawn_attaches_at_latest_version(qwen3):
    """The crash drill: ``serve.publish`` kills the first victim inside
    its swap window. Failure triage must run (no lost ids), the respawn
    must attach at the LATEST version (the satellite-1 bugfix pin — an
    ancestor-version respawn would freeze the fleet mixed forever), and
    the fleet still converges to one version with zero leaked blocks."""
    params, cfg = qwen3
    r = Router(params, cfg, _engine_cfg(), RouterConfig(
        replicas=3, min_live=1, probation_requests=1,
        respawn_backoff_s=0.05))
    probe = _prompts(1, seed=30)[0]
    ids = [r.submit(q) for q in _reqs(_prompts(3, seed=31), n_new=4)]
    _drain(r)
    configure_faults([{"point": "serve.publish", "mode": "exception",
                       "hit": 1, "times": 1}])
    r.publish_weights(_perturb(params), "v1")
    probes = _restore_fleet(r, probe)
    disarm_faults()
    _drain(r)
    assert not r.publish_in_progress
    replicas = list(r.replicas.values())
    assert len(r.live_replicas()) == 3
    assert all(h.weights_version == "v1" for h in replicas)
    died = [h for h in replicas if h.generation > 0]
    assert len(died) == 1, "exactly one replica dies in this drill"
    for i in ids + probes:  # nobody lost, nobody duplicated
        assert r.pop_output(i) is not None
        assert r.pop_output(i) is None
    for h in replicas:
        _pool_identity(h.engine)


def test_publish_then_respawn_parity_with_add_replica(qwen3):
    """Respawns and freshly-added replicas agree: both attach at the
    latest published version, not at the fleet's founding version."""
    params, cfg = qwen3
    r = Router(params, cfg, _engine_cfg(), RouterConfig(
        replicas=2, min_live=1, probation_requests=0,
        respawn_backoff_s=0.05))
    r.publish_weights(_perturb(params), "v1")
    _drain(r)  # converge the publish first
    victim = next(iter(r.live_replicas()))
    r.kill_replica(victim.rid)
    probe = _prompts(1, seed=40)[0]
    _restore_fleet(r, probe)
    _drain(r)
    assert r.replicas[victim.rid].generation == 1
    assert r.replicas[victim.rid].weights_version == "v1"
    added = r.add_replica()
    assert added.weights_version == "v1"
    assert all(h.weights_version == "v1" for h in r.replicas.values())


# ------------------------------------------------------- the checkpoint gate
def _fake_generation(tmp_path, name="global_step_7", payload=b"x" * 512):
    step_dir = tmp_path / name
    (step_dir / "train_state").mkdir(parents=True)
    (step_dir / "train_state" / "arrays.bin").write_bytes(payload)
    return str(step_dir)


def test_publish_from_checkpoint_integrity_gate(qwen3, tmp_path):
    """Corrupt and uncommitted generations are refused BEFORE the loader
    runs — the fleet's buffers and version history stay untouched."""
    params, cfg = qwen3
    new_params = _perturb(params)
    loads = []

    def loader(step_dir):
        loads.append(step_dir)
        return new_params

    r = Router(params, cfg, _engine_cfg(), RouterConfig(
        replicas=2))
    # clean generation: manifest written, loader runs, fleet converges
    good = _fake_generation(tmp_path, "global_step_7")
    write_manifest(good, subtrees=("train_state",))
    assert r.publish_from_checkpoint(good, loader) == "global_step_7"
    _drain(r)
    assert all(h.weights_version == "global_step_7"
               for h in r.live_replicas())
    assert loads == [good]
    # truncated payload: CORRUPT — refused, loader never called
    bad = _fake_generation(tmp_path, "global_step_8")
    write_manifest(bad, subtrees=("train_state",))
    os.truncate(os.path.join(bad, "train_state", "arrays.bin"), 1)
    with pytest.raises(CheckpointCorruptError, match="verification failed"):
        r.publish_from_checkpoint(bad, loader)
    # uncommitted dir (no train_state payload): refused, loader never ran
    empty = tmp_path / "global_step_9"
    empty.mkdir()
    with pytest.raises(CheckpointCorruptError, match="not a committed"):
        r.publish_from_checkpoint(str(empty), loader)
    assert loads == [good], "a refused generation must never be loaded"
    assert r.weights_version == "global_step_7"  # history untouched
    # verify_mode="off" still refuses uncommitted dirs
    with pytest.raises(CheckpointCorruptError):
        load_published_params(str(empty), loader, verify_mode="off")


# --------------------------------------------------------- chaos composition
def test_chaos_plan_publish_draws_deterministic_and_prefix_stable():
    """Adding publish events to a seeded plan must not move a single
    fault/kill draw (existing seeds stay repros), and the publish draws
    themselves are deterministic."""
    from veomni_tpu.resilience.chaos import build_chaos_plan

    base = build_chaos_plan(11, duration_s=10.0).to_doc()
    withpub = build_chaos_plan(11, duration_s=10.0, publishes=2).to_doc()
    assert withpub["faults"] == base["faults"]
    assert withpub["kills"] == base["kills"]
    assert base["publishes"] == [] and len(withpub["publishes"]) == 2
    again = build_chaos_plan(11, duration_s=10.0, publishes=2).to_doc()
    assert again == withpub
    for p in withpub["publishes"]:
        assert 0.15 * 10.0 <= p["at_s"] <= 0.70 * 10.0


def test_chaos_soak_publish_only_converges_and_requires_publish_fn(qwen3):
    """A publish-only storm (no faults, no kills) through the soak
    harness: every invariant incl. version convergence holds, nothing is
    traced from the moment of the publish on (drain -> swap -> rotation
    under load is a buffer swap, never a recompile), and a plan that
    schedules publishes without a publish_fn is refused loudly."""
    from veomni_tpu.resilience.chaos import build_chaos_plan, run_chaos_soak

    params, cfg = qwen3
    plan = build_chaos_plan(5, duration_s=2.0, kills=0, hangs=0, delays=0,
                            exceptions=0, publishes=1)
    reqs = _reqs(_prompts(8, seed=50), n_new=4)
    arrivals = [0.2 * i for i in range(len(reqs))]

    def factory():
        r = Router(params, cfg, _engine_cfg(num_slots=2), RouterConfig(
            replicas=3))
        # warm with the storm's own prompts, twice: the second pass's
        # prefix-cache hits reach the chunked-prefill program too
        for _ in range(2):
            r.run(_reqs(_prompts(8, seed=50), n_new=4))
        return r

    traces_at_publish = {}

    def publish(router, idx):
        traces_at_publish.update(decode_mod.TRACE_COUNTS)
        return router.publish_weights(_perturb(params), f"storm-v{idx + 1}")

    with pytest.raises(ValueError, match="publish_fn"):
        run_chaos_soak(router_factory=factory, requests=reqs,
                       arrivals=arrivals, plan=plan)
    report = run_chaos_soak(
        router_factory=factory, requests=reqs, arrivals=arrivals, plan=plan,
        publish_fn=publish)
    assert dict(decode_mod.TRACE_COUNTS) == traces_at_publish
    assert report["publishes"] == 1
    assert report["published_versions"] == ["storm-v1"]
    assert report["version_converged"], report
    assert report["serving_versions"] == ["storm-v1"]
    assert report["publish_wall_s"] >= 0
    assert report["invariants_ok"], report
