"""Numerics & training-health observatory (ISSUE 14).

* ``tree_health`` unit semantics: RMS/absmax/non-finite counts, per-layer
  vectors for scan-stacked subtrees, update/weight ratio, overflow-margin
  bits, deterministic group-cardinality capping;
* the instrumented sibling step: its own ``numerics_step`` cost-census
  site + ``TRACE_COUNTS`` key, provenance ordering (param beats grad);
* cost-census hygiene: ``CostWindow`` excludes the numerics bucket from
  the MFU math;
* knob-off byte-identical trajectory drill + trace-count gate (exactly one
  extra compiled program when the tier is on, zero steady-state retraces);
* the ``step.params`` nan-fault drill: the supervisor's anomaly re-run
  produces a post-mortem whose provenance doc NAMES the injected group;
* the step_ok window-accumulation gate on the channel-loss accumulators
  (regression for the PR 3 ``step.loss`` nan fault polluting averages);
* ``/debug/numerics`` exporter endpoint.
"""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from veomni_tpu.arguments import VeOmniArguments


@pytest.fixture(autouse=True)
def _disarm_and_clear():
    yield
    from veomni_tpu.observability.numerics import set_active_monitor
    from veomni_tpu.resilience.faults import disarm_faults

    disarm_faults()
    set_active_monitor(None)
    os.environ.pop("VEOMNI_FAULT_PLAN", None)


# ---------------------------------------------------------------------------
# tree_health unit semantics
# ---------------------------------------------------------------------------

def test_tree_health_stats_and_stacked_groups():
    from veomni_tpu.observability.numerics import NumericsMonitor, tree_health

    L = 3
    params = {
        "layers": {"w": jnp.full((L, 2, 2), 2.0, jnp.float32)},
        "embed": jnp.full((4,), 1.0, jnp.float32),
    }
    grads = {
        # layer 1's grads carry one inf; magnitudes are per-layer distinct
        "layers": {"w": jnp.stack([
            jnp.full((2, 2), 0.5), jnp.full((2, 2), jnp.inf),
            jnp.full((2, 2), 4.0),
        ])},
        "embed": jnp.full((4,), 3.0, jnp.float32),
    }
    updates = {
        "layers": {"w": jnp.full((L, 2, 2), 0.2, jnp.float32)},
        "embed": jnp.full((4,), 0.1, jnp.float32),
    }
    health = tree_health(params, grads, updates)
    assert sorted(health) == ["embed", "layers.w"]

    emb = {k: float(v) for k, v in health["embed"].items()}
    assert emb["grad_rms"] == pytest.approx(3.0)
    assert emb["grad_absmax"] == pytest.approx(3.0)
    assert emb["param_rms"] == pytest.approx(1.0)
    assert emb["update_ratio"] == pytest.approx(0.1, rel=1e-5)
    assert emb["grad_nonfinite"] == 0.0
    # f32 leaf: log2(f32max) - log2(3) = 128 - log2(3)
    assert emb["overflow_margin_bits"] == pytest.approx(
        128 - np.log2(3.0), abs=0.01)

    lw = {k: np.asarray(v) for k, v in health["layers.w"].items()}
    # stacked subtree -> per-layer vectors
    assert lw["grad_rms"].shape == (L,)
    np.testing.assert_allclose(lw["grad_rms"], [0.5, 0.0, 4.0])  # inf masked
    np.testing.assert_allclose(lw["grad_nonfinite"], [0.0, 4.0, 0.0])
    np.testing.assert_allclose(lw["param_rms"], [2.0, 2.0, 2.0])
    np.testing.assert_allclose(lw["update_ratio"], [0.1] * L, rtol=1e-5)

    # host-side provenance ordering: grads bad in layers.w only -> grad kind
    doc = NumericsMonitor._to_doc(health)
    first = NumericsMonitor.first_nonfinite(doc)
    assert first == {"group": "layers.w", "kind": "grad",
                     "nonfinite_count": 4.0, "layer": 1}

    # param beats grad: poison a param too, in a group that sorts EARLIER
    params2 = dict(params)
    params2["embed"] = params["embed"].at[0].set(jnp.nan)
    doc2 = NumericsMonitor._to_doc(tree_health(params2, grads, updates))
    first2 = NumericsMonitor.first_nonfinite(doc2)
    assert first2["group"] == "embed" and first2["kind"] == "param"


def test_build_groups_cap_is_deterministic():
    from veomni_tpu.observability.numerics import REST_GROUP, build_groups

    tree = {f"mod{i:03d}": {"a": 0.0, "b": 1.0} for i in range(40)}
    paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
    # uncapped: full leaf-path granularity
    full = build_groups(paths, max_groups=100)
    assert len(full) == 80 and full[0][0] == "mod000.a"
    # capped below the leaf count but above the subtree count: coarsens to
    # the 40 subtree roots (deterministic, no rest bucket)
    coarse = build_groups(paths, max_groups=50)
    assert [n for n, _ in coarse][:2] == ["mod000", "mod001"]
    assert len(coarse) == 40
    assert all(len(m) == 2 for _, m in coarse)
    # capped below even that: sorted head survives, tail merges into rest
    capped = build_groups(paths, max_groups=8)
    names = [n for n, _ in capped]
    assert len(names) == 8 and REST_GROUP in names
    assert names[:3] == [REST_GROUP, "mod000", "mod001"]
    # deterministic across calls
    assert capped == build_groups(paths, max_groups=8)
    # degenerate caps hold EXACTLY: 1 (everything in the rest bucket) and
    # 0 (clamped to 1) — a keep-head of max(1, cap-1) would emit 2 groups
    for cap in (1, 0):
        tiny = build_groups(paths, max_groups=cap)
        assert [n for n, _ in tiny] == [REST_GROUP]
        assert sorted(i for _, m in tiny for i in m) == list(range(80))


# ---------------------------------------------------------------------------
# instrumented sibling step: own census site, own trace counter
# ---------------------------------------------------------------------------

def test_numerics_sibling_step_site_and_counts(monkeypatch):
    from veomni_tpu.observability.cost import get_cost_census
    from veomni_tpu.observability.numerics import NumericsSpec
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import TRACE_COUNTS

    monkeypatch.setenv("VEOMNI_DONATE_STATE", "1")  # sibling must ignore it

    def loss_fn(params, micro):
        loss = (params["w"] * micro["x"]).sum() * micro["scale"][0]
        return loss, {"ntokens": jnp.int32(micro["x"].size)}

    opt = optax.adam(0.1)
    state = build_train_state({"w": jnp.ones((4,), jnp.float32)}, opt)
    step = build_train_step(loss_fn, opt, None, skip_nonfinite=True,
                            numerics_spec=NumericsSpec())

    def batch(scale):
        return {"x": jnp.ones((1, 4), jnp.float32),
                "scale": jnp.full((1, 1), scale, jnp.float32)}

    t0 = TRACE_COUNTS["numerics_step"]
    # the census is process-global: other tests may already have a
    # train_step/1x4 record — the sibling must not bump ITS call count
    hot = get_cost_census().get("train_step", "1x4")
    hot_calls = hot.calls if hot is not None else 0
    st2, metrics, health = step(state, batch(1.0))
    assert bool(metrics["step_ok"]) and "w" in health
    # no donation: the input state must still be alive and re-steppable
    st3, m3, h3 = step(state, batch(float("nan")))
    assert not bool(m3["step_ok"])
    assert float(h3["w"]["grad_nonfinite"]) > 0
    assert TRACE_COUNTS["numerics_step"] == t0 + 1  # one program, two calls
    rec = get_cost_census().get("numerics_step", "1x4")
    assert rec is not None and rec.calls >= 2
    # the hot site is untouched by the sibling's compiles and calls
    hot = get_cost_census().get("train_step", "1x4")
    assert (hot.calls if hot is not None else 0) == hot_calls


def test_costwindow_excludes_numerics_site():
    from veomni_tpu.observability.cost import CostCensus, CostWindow
    from veomni_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    census = CostCensus(registry=reg)
    census.record("train_step", "b", flops=100.0, bytes_accessed=10.0)
    census.record("numerics_step", "b", flops=1e9, bytes_accessed=1e9)
    window = CostWindow(census)
    window.begin()
    for _ in range(4):
        census.note_call("train_step", "b")
        census.note_call("numerics_step", "b")
    out = window.end()
    # achieved FLOPs counted the train-step program only: the diagnostic
    # site's 1e9-FLOPs program must not inflate the window
    assert out["census_tflops_s"] * 1e12 * out["census_window_s"] == \
        pytest.approx(400.0, rel=1e-6)
    # an explicit allowlist overrides the exclusion
    w2 = CostWindow(census, sites=("numerics_step",))
    w2.begin()
    census.note_call("numerics_step", "b")
    out2 = w2.end()
    assert out2["census_tflops_s"] > 0


# ---------------------------------------------------------------------------
# e2e: knob-off byte-identical trajectory + trace-count gate
# ---------------------------------------------------------------------------

DENSE_TOY = {
    "model_type": "qwen3", "vocab_size": 256, "hidden_size": 32,
    "intermediate_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "qk_norm": True,
}


def _write_data(path, n=96, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            f.write(json.dumps({
                "input_ids": rng.integers(
                    0, vocab, int(rng.integers(16, 80))).tolist(),
            }) + "\n")


def _dense_args(tmp_path, out_name="out", **train_overrides):
    args = VeOmniArguments()
    args.model.config_overrides = dict(DENSE_TOY)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.train.output_dir = str(tmp_path / out_name)
    args.train.micro_batch_size = 2
    args.train.train_steps = 6
    args.train.lr = 1e-3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 1
    for k, v in train_overrides.items():
        setattr(args.train, k, v)
    return args


def _run(args):
    from veomni_tpu.trainer import TextTrainer
    from veomni_tpu.trainer.callbacks import Callback

    trainer = TextTrainer(args)
    losses = {}

    class Rec(Callback):
        def on_step_end(self, t, state):
            if state.synced:
                losses[state.global_step] = float(state.metrics["loss"]).hex()

    trainer.callbacks.append(Rec())
    ctl = trainer.train()
    params = jax.tree.map(np.asarray, trainer.train_state.params)
    trainer.checkpointer.close()
    return ctl, losses, params, trainer


def test_knob_off_byte_identical_and_trace_count_gate(tmp_path):
    from veomni_tpu.observability.cost import get_cost_census
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.train.train_step import TRACE_COUNTS

    _write_data(tmp_path / "data.jsonl")

    n0, t0 = TRACE_COUNTS["numerics_step"], TRACE_COUNTS["train_step"]
    _ctl_off, losses_off, params_off, _ = _run(_dense_args(tmp_path, "off"))
    destroy_parallel_state()
    # knob off (the default): the tier contributes NOTHING — no sibling
    # program exists, the hot step compiled exactly once
    assert TRACE_COUNTS["numerics_step"] == n0
    assert TRACE_COUNTS["train_step"] == t0 + 1

    _ctl_on, losses_on, params_on, trainer_on = _run(
        _dense_args(tmp_path, "on", observability_numerics_interval=2)
    )
    # trace-count gate: the tier costs exactly ONE extra compiled program
    # (the sibling), zero steady-state retraces of either site
    assert TRACE_COUNTS["numerics_step"] == n0 + 1
    assert TRACE_COUNTS["train_step"] == t0 + 2
    # with interval=2 over 6 steps the sibling ran on steps 2/4/6
    rec = get_cost_census().latest("numerics_step")
    assert rec is not None and rec.calls >= 3

    # the instrumented sibling computes the SAME update math: trajectory
    # and final params are bit-identical to the knob-off run
    assert losses_on == losses_off
    la, lb = jax.tree.leaves(params_off), jax.tree.leaves(params_on)
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))

    # the interval cadence published numerics gauges + filled the history
    from veomni_tpu.observability.metrics import get_registry

    names = [n for n, _ in get_registry().items_snapshot()
             if n.startswith("numerics.")]
    assert any(".grad_rms" in n for n in names)
    assert any(".update_ratio" in n for n in names)
    assert trainer_on._numerics is not None
    assert len(trainer_on._numerics.snapshot()["history"]) == 3


# ---------------------------------------------------------------------------
# e2e: step.params nan drill -> post-mortem names the injected group
# ---------------------------------------------------------------------------

def test_step_params_drill_postmortem_names_injected_group(tmp_path):
    from veomni_tpu.resilience import AnomalyBudgetExceeded
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(
        tmp_path, train_steps=8,
        observability_numerics_interval=100,  # tier armed; cadence unused
        resilience_anomaly_budget=1, resilience_rollback_after=10,
    )
    configure_faults([{"point": "step.params", "mode": "nan", "hit": 3,
                       "group": "layers.q_proj"}])
    with pytest.raises(AnomalyBudgetExceeded):
        _run(args)

    pm = json.load(open(os.path.join(args.train.output_dir,
                                     "postmortem-0.json")))
    assert pm["reason"] == "exception:AnomalyBudgetExceeded"
    prov = pm["numerics"]["provenance"]
    first = prov["first_nonfinite"]
    # the provenance doc NAMES the injected group — and classifies it as a
    # PARAM problem (upstream of the NaN grads it caused everywhere else)
    assert first["group"] == "layers.q_proj"
    assert first["kind"] == "param"
    assert first["layer"] == 0
    assert prov["groups"]["layers.q_proj"]["param_nonfinite"][0] > 0
    # flight recorder carries the same attribution
    evs = [e for e in pm["events"] if e.get("kind") == "numerics.nonfinite"]
    assert evs and evs[0]["payload"]["group"] == "layers.q_proj"


def test_fault_plan_step_params_grammar():
    from veomni_tpu.resilience import faults

    # nan mode now covers step.params, carrying the group on the action
    faults.configure_faults([{"point": "step.params", "mode": "nan",
                              "group": "layers.mlp"}])
    act = faults.fault_point("step.params")
    assert act is not None and act.mode == "nan"
    assert act.target == "layers.mlp"
    faults.disarm_faults()
    # ...but stays rejected anywhere else
    with pytest.raises(ValueError, match="step.params"):
        faults.configure_faults([{"point": "ckpt.save", "mode": "nan"}])


def test_poison_param_group_targets_match():
    from veomni_tpu.observability.numerics import poison_param_group

    params = {
        "embed": jnp.ones((4,), jnp.float32),
        "layers": {"q_proj": jnp.ones((2, 3), jnp.float32),
                   "tid": jnp.ones((2,), jnp.int32)},
    }
    poisoned, target = poison_param_group(params, "q_proj")
    assert target == "layers.q_proj"
    assert not np.isfinite(np.asarray(poisoned["layers"]["q_proj"])).all()
    assert np.isfinite(np.asarray(poisoned["embed"])).all()
    # empty pattern: first float leaf in sorted-path order; int leaves are
    # never poisoned
    _, t2 = poison_param_group(params, "")
    assert t2 == "embed"
    same, t3 = poison_param_group(params, "tid")
    assert t3 == "" and same is params


# ---------------------------------------------------------------------------
# step_ok window-accumulation gate (satellite bugfix)
# ---------------------------------------------------------------------------

def _channel_cb_step(cb, metrics, step=1):
    from veomni_tpu.trainer.callbacks import TrainerControlState

    state = TrainerControlState(global_step=step)
    state.metrics = metrics
    cb.on_step_end(None, state)


def test_channel_loss_accumulation_gated_on_step_ok():
    from veomni_tpu.train.channel_loss import ChannelLossCallback

    cb = ChannelLossCallback(["a", "b"], log_steps=100)
    sums = jnp.asarray([2.0, 4.0])
    counts = jnp.asarray([1.0, 2.0])

    # host-flag False (sync step / injected drill): contribution dropped
    _channel_cb_step(cb, {"channel_loss_sums": sums,
                          "channel_token_counts": counts,
                          "step_ok": False})
    assert cb._acc_sums is None

    # device-array False (async step): masked lazily to zeros, loop stays
    # async (no fetch happened here)
    _channel_cb_step(cb, {"channel_loss_sums": sums * jnp.nan,
                          "channel_token_counts": counts,
                          "step_ok": jnp.asarray(False)})
    np.testing.assert_allclose(np.asarray(cb._acc_sums), [0.0, 0.0])

    # ok steps accumulate as before
    _channel_cb_step(cb, {"channel_loss_sums": sums,
                          "channel_token_counts": counts,
                          "step_ok": jnp.asarray(True)})
    _channel_cb_step(cb, {"channel_loss_sums": sums,
                          "channel_token_counts": counts,
                          "step_ok": 1.0})
    cb._fold()
    np.testing.assert_allclose(cb._sums, [4.0, 8.0])
    np.testing.assert_allclose(cb._counts, [2.0, 4.0])


def test_channel_loss_e2e_excludes_injected_nan_step(tmp_path):
    """PR 3 ``step.loss`` nan-fault regression: the injected anomalous
    step's per-channel sums/counts must NOT pollute the window
    accumulators — lifetime channel token counts equal the sum over the
    OK steps only."""
    from veomni_tpu.resilience.faults import configure_faults
    from veomni_tpu.trainer import TextTrainer
    from veomni_tpu.trainer.callbacks import Callback

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for i in range(96):
            f.write(json.dumps({
                "input_ids": rng.integers(
                    0, 256, int(rng.integers(16, 60))).tolist(),
                "channel": "web" if i % 2 else "code",
            }) + "\n")
    args = _dense_args(tmp_path, train_steps=4)
    args.data.channel_list = ["code", "web"]
    configure_faults([{"point": "step.loss", "mode": "nan", "hit": 2}])

    trainer = TextTrainer(args)
    per_step_tokens = {}

    class Rec(Callback):
        def on_step_end(self, t, state):
            if state.synced:
                per_step_tokens[state.global_step] = float(
                    state.metrics["ntokens"])

    # BEFORE ChannelLossCallback in hook order: it pops the channel metrics
    trainer.callbacks.insert(0, Rec())
    ctl = trainer.train()
    trainer.checkpointer.close()
    assert ctl.resilience["anomaly_steps"] == [2]
    cb = [c for c in trainer.callbacks
          if type(c).__name__ == "ChannelLossCallback"][0]
    cb._fold()
    expected = sum(v for s, v in per_step_tokens.items() if s != 2)
    assert sum(cb._counts) == pytest.approx(expected)
    assert all(np.isfinite(s) for s in cb._sums)


# ---------------------------------------------------------------------------
# exporter endpoint + post-mortem attach
# ---------------------------------------------------------------------------

def test_debug_numerics_endpoint():
    from veomni_tpu.observability.exporter import MetricsExporter
    from veomni_tpu.observability.numerics import (
        NumericsMonitor,
        set_active_monitor,
        tree_health,
    )

    exp = MetricsExporter(port=0, host="127.0.0.1")
    port = exp.start()
    try:
        def get():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/numerics", timeout=10) as r:
                return json.loads(r.read())

        set_active_monitor(None)
        doc = get()
        assert doc["enabled"] is False and "interval" in doc["hint"]

        mon = NumericsMonitor()
        set_active_monitor(mon)
        params = {"w": jnp.ones((2,), jnp.float32)}
        grads = {"w": jnp.asarray([jnp.nan, 1.0])}
        health = tree_health(params, grads, params)
        mon.observe(7, health)
        mon.diagnose(7, health)
        doc = get()
        assert doc["enabled"] is True
        assert doc["latest"]["step"] == 7
        assert doc["provenance"]["first_nonfinite"]["group"] == "w"
        assert doc["provenance"]["first_nonfinite"]["kind"] == "grad"
    finally:
        exp.stop()
        set_active_monitor(None)


def test_attach_numerics_extra_tolerates_no_monitor():
    from veomni_tpu.observability.numerics import (
        attach_numerics_extra,
        set_active_monitor,
    )

    set_active_monitor(None)
    extra = {}
    attach_numerics_extra(extra)
    assert "numerics" not in extra
