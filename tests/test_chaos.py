"""Chaos schedule generation: determinism, grammar validity, soak driver.

The chaos plan is the replay token for every soak failure — the whole
harness is worthless unless the same seed produces the identical schedule
on every machine, every run. These tests pin that, check the generated
specs actually parse under the ``faults.py`` grammar (a plan the fault
layer rejects at arm time would turn every chaos drill into a no-op), and
drive :func:`run_chaos_soak` once fault-free over a real (tiny) fleet so
the driver's storm/restore/invariant plumbing is covered without paying
for a full chaos drill here — ``scripts/chaos_smoke.py`` owns that as its
own tier-1 stage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.resilience.chaos import (
    CHAOS_POINTS,
    ChaosPlan,
    KillEvent,
    build_chaos_plan,
    run_chaos_soak,
)
from veomni_tpu.resilience.faults import KNOWN_POINTS, _parse_specs
from veomni_tpu.serving import EngineConfig, Request, SamplingParams
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


def test_chaos_plan_same_seed_identical():
    """Same seed -> field-for-field identical schedule; the to_doc() form
    is the canonical comparison (and what soak reports embed)."""
    kw = dict(duration_s=7.5, kills=2, hangs=2, delays=3, exceptions=2,
              hang_seconds=1.5, delay_ms=10.0, expected_ticks=200)
    a = build_chaos_plan(123, **kw)
    b = build_chaos_plan(123, **kw)
    assert a.to_doc() == b.to_doc()
    # and the doc is JSON-shaped: plain dicts/lists/numbers only
    import json

    json.dumps(a.to_doc())


def test_chaos_plan_different_seed_differs():
    kw = dict(duration_s=7.5, kills=1, hangs=1, delays=2, exceptions=1)
    docs = [build_chaos_plan(s, **kw).to_doc() for s in (1, 2, 3)]
    assert docs[0] != docs[1] or docs[1] != docs[2]


def test_chaos_plan_specs_parse_and_target_known_points():
    """Every generated fault spec must survive ``_parse_specs`` (the arm
    gate) and target a registered serving point; hangs must land only at
    pump-side points where the wedge detector can see them."""
    plan = build_chaos_plan(99, duration_s=10.0, kills=3, hangs=3,
                            delays=3, exceptions=3, hang_seconds=2.0)
    specs = _parse_specs(plan.fault_plan())
    assert len(specs) == 9
    for spec in specs:
        assert spec.point in CHAOS_POINTS
        assert spec.point in KNOWN_POINTS
        if spec.mode == "hang":
            # a hang at serve.admit would hang the ROUTER thread, not a
            # pump worker — a failure mode resurrection cannot fix
            assert spec.point in ("serve.prefill", "serve.decode_tick")
            assert spec.seconds == 2.0
    # kills: sorted ascending, inside the middle of the storm window
    kills = plan.kill_events()
    assert kills == sorted(kills, key=lambda k: k.at_s)
    for k in kills:
        assert 0.15 * 10.0 <= k.at_s <= 0.70 * 10.0
        assert k.pick >= 0


def test_chaos_plan_rejects_nonpositive_duration():
    with pytest.raises(ValueError):
        build_chaos_plan(1, duration_s=0.0)


def test_kill_event_resolution_is_modular():
    """The seeded pick resolves against the live set at fire time — any
    fleet size maps it onto a valid victim."""
    ev = KillEvent(at_s=1.0, pick=7)
    for n in (1, 2, 3, 5):
        assert 0 <= ev.pick % n < n


def test_chaos_plan_fault_plan_is_a_copy():
    plan = ChaosPlan(seed=1, duration_s=1.0,
                     faults=[{"point": "serve.admit", "mode": "delay",
                              "hit": 1, "ms": 5.0}])
    got = plan.fault_plan()
    got[0]["mode"] = "exception"
    assert plan.faults[0]["mode"] == "delay"


def test_run_chaos_soak_fault_free_reports_clean(qwen3):
    """The soak driver end to end with ``plan=None``: every id reaches a
    terminal output, no pool leaks, fleet stays at size, report flags
    read clean — the baseline every chaos verdict divides by."""
    params, cfg = qwen3
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 128, 8)] for _ in range(6)]
    arrivals = [0.0, 0.01, 0.02, 0.05, 0.08, 0.1]

    def factory():
        r = Router(params, cfg,
                   EngineConfig(num_blocks=64, block_size=8, num_slots=2,
                                max_model_len=64),
                   RouterConfig(replicas=2))
        return r

    report = run_chaos_soak(
        router_factory=factory,
        requests=[Request(prompt_ids=list(p),
                          sampling=SamplingParams(max_new_tokens=4))
                  for p in prompts],
        arrivals=arrivals, plan=None, restore_timeout_s=10.0)
    assert report["seed"] is None
    assert report["submitted"] == 6 and report["completed"] == 6
    assert not report["lost_ids"] and not report["duplicated"]
    assert not report["leaked_blocks"]
    assert report["restored"] and not report["stalled"]
    assert report["wedged"] == 0 and report["respawns"] == 0
    assert report["goodput_tok"] > 0
    assert report["invariants_ok"]
