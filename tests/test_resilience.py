"""Resilience subsystem tests.

Adversarial training behavior (capacity overflow, topology-change resume)
plus the ``veomni_tpu/resilience`` recovery paths, each driven by the
deterministic fault-injection plan (``VEOMNI_FAULT_PLAN`` /
``configure_faults``) under ``JAX_PLATFORMS=cpu``:

* fault-plan grammar + hit-window arming;
* device-side non-finite skip inside the jitted train step;
* NaN-skip accounting, checkpoint rollback + bit-exact replay, abort budget;
* checkpoint save/restore I/O faults survived within the retry budget, and
  retry-exhaustion aborting the run;
* async-save error surfacing/eviction at step boundaries;
* streaming data-fetch faults absorbed by the retry layer;
* hang watchdog firing on a stalled loop (bounded — no unbounded hang);
* SIGTERM graceful final checkpoint + exit 0 + exact resume (subprocess);
* SIGKILL mid-async-save crash consistency: resumed loss trajectory is
  bit-exact vs an uninterrupted run (subprocess).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from veomni_tpu.arguments import VeOmniArguments


@pytest.fixture(autouse=True)
def _disarm_fault_plan():
    yield
    from veomni_tpu.resilience.faults import disarm_faults

    disarm_faults()
    os.environ.pop("VEOMNI_FAULT_PLAN", None)


def _write_data(path, n=96, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            f.write(json.dumps({
                "input_ids": rng.integers(0, vocab, int(rng.integers(16, 80))).tolist(),
            }) + "\n")


def _args(tmp_path, **overrides):
    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "qwen3_moe", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "qk_norm": True, "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, **overrides,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 2
    args.train.train_steps = 4
    args.train.lr = 1e-3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 1
    return args


def test_capacity_overflow_training_stays_finite(tmp_path):
    """A drastically undersized expert capacity (most tokens dropped) must
    degrade throughput, not stability: finite loss/grad at every step."""
    from veomni_tpu.trainer import TextTrainer

    _write_data(tmp_path / "data.jsonl")
    args = _args(tmp_path, moe_capacity_factor=0.25)
    trainer = TextTrainer(args)
    losses = []

    from veomni_tpu.trainer.callbacks import Callback

    class Rec(Callback):
        def on_step_end(self, t, state):
            if state.synced:
                losses.append(float(state.metrics["loss"]))
                assert np.isfinite(state.metrics["grad_norm"])

    trainer.callbacks.append(Rec())
    ctl = trainer.train()
    assert ctl.global_step == 4
    assert all(np.isfinite(l) for l in losses) and len(losses) == 4
    trainer.checkpointer.close()


def test_resume_after_torn_sidecar_set_falls_back_and_continues(tmp_path):
    """A generation whose per-rank extra state doesn't cover this rank
    (here: rank 0's sidecar renamed to rank 7 — a torn set no world size
    explains) must NOT silently restore with an empty dataloader cursor
    (the pre-elastic behavior, which repeats/skips that rank's samples).
    With a digest manifest the integrity gate already quarantines the
    missing-file generation; this test removes the manifest (an off-mode /
    pre-integrity checkpoint) so the TOPOLOGY gate is the layer that
    refuses: a pinned-step load raises `ElasticRestoreError`, and the
    restore walk falls back to the previous intact generation."""
    from veomni_tpu.resilience import ElasticRestoreError
    from veomni_tpu.trainer import TextTrainer

    _write_data(tmp_path / "data.jsonl")
    args = _args(tmp_path)
    args.train.save_steps = 2
    trainer = TextTrainer(args)
    trainer.train()
    trainer.checkpointer.close()

    # simulate "saved by a different topology": this rank's extra-state file
    # is absent, another rank's is present — and no digest manifest exists
    # to catch the missing file first
    step_dir = os.path.join(args.train.output_dir, "checkpoints", "global_step_4")
    os.rename(
        os.path.join(step_dir, "extra_state_rank0.json"),
        os.path.join(step_dir, "extra_state_rank7.json"),
    )
    os.remove(os.path.join(step_dir, "manifest.json"))

    args2 = _args(tmp_path)
    args2.train.train_steps = 6
    trainer2 = TextTrainer(args2)
    # a pinned-step load of the torn generation surfaces the error directly
    import jax

    abstract = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        trainer2.abstract_state, trainer2.state_shardings,
    )
    # without topology metadata a lone rank-7 sidecar reads as a world-8
    # save missing ranks 0-6 — either way, unmergeable and refused
    with pytest.raises(ElasticRestoreError, match="sidecar"):
        trainer2.checkpointer.load(abstract, step=4)

    import logging

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    target = logging.getLogger("veomni_tpu.checkpoint.checkpointer")
    target.addHandler(handler)
    try:
        restored, extra = trainer2.try_resume()
    finally:
        target.removeHandler(handler)
    assert restored
    assert any("onto this topology" in r.getMessage() for r in records)
    # the torn step-4 generation was refused; the walk landed on step 2
    assert int(extra["global_step"]) == 2
    # training continues from the restored params
    ctl = trainer2.train()
    assert ctl.global_step == 6
    assert np.isfinite(ctl.metrics["loss"])
    trainer2.checkpointer.close()


# ---------------------------------------------------------------------------
# shared helpers for the resilience-path tests (tiny DENSE model: these tests
# run several full trains; the MoE toy above stays with its capacity test)
# ---------------------------------------------------------------------------

DENSE_TOY = {
    "model_type": "qwen3", "vocab_size": 256, "hidden_size": 32,
    "intermediate_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "qk_norm": True,
}


def _dense_args(tmp_path, out_name="out", **train_overrides):
    args = VeOmniArguments()
    args.model.config_overrides = dict(DENSE_TOY)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.train.output_dir = str(tmp_path / out_name)
    args.train.micro_batch_size = 2
    args.train.train_steps = 4
    args.train.lr = 1e-3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 1
    args.train.resilience_retry_base_s = 0.001
    for k, v in train_overrides.items():
        setattr(args.train, k, v)
    return args


def _train_with_loss_log(args, data_path_writer=None):
    """Run a TextTrainer recording the bit pattern of every synced loss;
    returns (ctl, {step: loss_hex}, trainer)."""
    from veomni_tpu.trainer import TextTrainer
    from veomni_tpu.trainer.callbacks import Callback

    trainer = TextTrainer(args)
    losses = {}

    class Rec(Callback):
        def on_step_end(self, t, state):
            if state.synced:
                # replayed (post-rollback) steps overwrite: last wins
                losses[state.global_step] = float(state.metrics["loss"]).hex()

    trainer.callbacks.append(Rec())
    ctl = trainer.train()
    return ctl, losses, trainer


def _tree_bits_equal(a, b):
    import jax

    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# fault plan grammar + retry unit behavior
# ---------------------------------------------------------------------------

def test_fault_plan_grammar_env_and_file(tmp_path):
    from veomni_tpu.resilience import faults

    os.environ["VEOMNI_FAULT_PLAN"] = json.dumps(
        [{"point": "ckpt.save", "mode": "exception", "hit": 2, "times": 2,
          "message": "boom"}]
    )
    assert faults.arm_from_env()
    assert faults.fault_point("ckpt.save") is None          # hit 1: unarmed
    for _ in range(2):                                       # hits 2-3 fire
        with pytest.raises(faults.InjectedFault, match="boom"):
            faults.fault_point("ckpt.save")
    assert faults.fault_point("ckpt.save") is None           # hit 4: window past
    assert [a.hit for a in faults.fired_faults()] == [2, 3]
    # injected faults are OSErrors: the retry layer's default classification
    assert issubclass(faults.InjectedFault, OSError)

    # @file indirection + nan mode returns an action instead of raising
    plan_file = tmp_path / "plan.json"
    plan_file.write_text(json.dumps([{"point": "step.loss", "mode": "nan"}]))
    os.environ["VEOMNI_FAULT_PLAN"] = "@" + str(plan_file)
    assert faults.arm_from_env()
    act = faults.fault_point("step.loss")
    assert act is not None and act.mode == "nan" and act.hit == 1
    assert faults.fault_point("step.loss") is None

    with pytest.raises(ValueError, match="unknown fault mode"):
        faults.configure_faults([{"point": "x", "mode": "explode"}])
    with pytest.raises(ValueError, match="missing 'point'"):
        faults.configure_faults([{"mode": "nan"}])
    faults.disarm_faults()
    assert faults.fault_point("ckpt.save") is None
    assert faults.fired_faults() == []


def test_retry_deterministic_backoff_and_exhaustion():
    from veomni_tpu.resilience.retry import RetryPolicy, retry_call

    delays, calls = [], []

    def flaky(fail_times):
        calls.append(1)
        if len(calls) <= fail_times:
            raise OSError(f"transient {len(calls)}")
        return "ok"

    policy = RetryPolicy(retries=3, base_delay_s=0.5, max_delay_s=1.5)
    assert retry_call(flaky, 2, policy=policy, sleep=delays.append) == "ok"
    assert delays == [0.5, 1.0]  # deterministic: base * 2**attempt, no jitter
    assert policy.delay(5) == 1.5  # capped

    calls.clear()
    with pytest.raises(OSError, match="transient 4"):  # original, not laundered
        retry_call(flaky, 99, policy=policy, sleep=lambda _: None)
    assert len(calls) == 4  # 1 + 3 retries

    # non-I/O errors are NOT retried
    def bug():
        calls.append(1)
        raise ValueError("schema mismatch")

    calls.clear()
    with pytest.raises(ValueError):
        retry_call(bug, policy=policy, sleep=lambda _: None)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# device-side non-finite skip in the jitted train step
# ---------------------------------------------------------------------------

def test_train_step_device_side_skip(monkeypatch):
    import jax.numpy as jnp
    import optax

    from veomni_tpu.train import build_train_state, build_train_step

    # the test re-steps from the SAME state object; donation would delete it
    monkeypatch.setenv("VEOMNI_DONATE_STATE", "0")

    def loss_fn(params, micro):
        loss = (params["w"] * micro["x"]).sum() * micro["scale"][0]
        return loss, {"ntokens": jnp.int32(micro["x"].size)}

    opt = optax.adam(0.1)
    state0 = build_train_state({"w": jnp.ones((4,), jnp.float32)}, opt)
    step = build_train_step(loss_fn, opt, None, skip_nonfinite=True)

    def batch(scale):
        return {"x": jnp.ones((1, 4), jnp.float32),
                "scale": jnp.full((1, 1), scale, jnp.float32)}

    bad_state, bad_metrics = step(state0, batch(float("nan")))
    assert not bool(bad_metrics["step_ok"])
    assert not np.isfinite(float(bad_metrics["loss"]))
    # params AND optimizer state untouched by the non-finite update
    assert _tree_bits_equal(bad_state.params, state0.params)
    assert _tree_bits_equal(bad_state.opt_state, state0.opt_state)

    good_state, good_metrics = step(state0, batch(1.0))
    assert bool(good_metrics["step_ok"])
    assert not _tree_bits_equal(good_state.params, state0.params)

    # ungated build: the same bad batch poisons params (documents the knob)
    step_raw = build_train_step(loss_fn, opt, None, skip_nonfinite=False)
    raw_state, raw_metrics = step_raw(state0, batch(float("nan")))
    assert not bool(raw_metrics["step_ok"])  # flag still reported
    assert not np.isfinite(np.asarray(raw_state.params["w"])).all()


# ---------------------------------------------------------------------------
# supervisor escalation: NaN-skip, rollback + bit-exact replay, abort
# ---------------------------------------------------------------------------

def test_nan_skip_counts_anomaly_and_completes(tmp_path):
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, resilience_rollback_after=10)
    configure_faults([{"point": "step.loss", "mode": "nan", "hit": 2}])
    ctl, losses, trainer = _train_with_loss_log(args)
    trainer.checkpointer.close()
    assert ctl.global_step == 4
    assert ctl.resilience["anomalies"] == 1
    assert ctl.resilience["anomaly_steps"] == [2]
    assert ctl.resilience["rollbacks"] == 0
    assert all(np.isfinite(float.fromhex(h)) for h in losses.values())


def test_rollback_replays_bit_exact(tmp_path):
    """Two consecutive anomalies at steps 4-5 -> rollback to the step-4
    checkpoint, cursor-exact iterator replay; the final params and the
    replayed per-step losses must be BIT-identical to an uninterrupted run."""
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")

    ctl_a, losses_a, trainer_a = _train_with_loss_log(
        _dense_args(tmp_path, "clean", train_steps=6, save_steps=2)
    )
    import jax

    ref_params = jax.tree.map(np.asarray, trainer_a.train_state.params)
    trainer_a.checkpointer.close()
    destroy_parallel_state()

    configure_faults([{"point": "step.loss", "mode": "nan", "hit": 4, "times": 2}])
    ctl_b, losses_b, trainer_b = _train_with_loss_log(
        _dense_args(tmp_path, "faulty", train_steps=6, save_steps=2,
                    resilience_rollback_after=2)
    )
    assert ctl_b.global_step == 6
    assert ctl_b.resilience["rollbacks"] == 1
    assert ctl_b.resilience["anomalies"] == 2
    assert ctl_b.resilience["anomaly_steps"] == [4, 5]
    assert _tree_bits_equal(
        ref_params, jax.tree.map(np.asarray, trainer_b.train_state.params)
    )
    assert losses_a == losses_b  # incl. replayed steps 5-6 (last-wins)
    trainer_b.checkpointer.close()


def test_rollback_without_checkpoint_is_impossible(tmp_path):
    from veomni_tpu.resilience import RollbackImpossible
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, save_steps=0, resilience_rollback_after=2)
    configure_faults([{"point": "step.loss", "mode": "nan", "hit": 2, "times": 2}])
    with pytest.raises(RollbackImpossible):
        _train_with_loss_log(args)


def test_anomaly_budget_aborts(tmp_path):
    from veomni_tpu.resilience import AnomalyBudgetExceeded
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, train_steps=8, resilience_anomaly_budget=2,
                       resilience_rollback_after=10)
    configure_faults([{"point": "step.loss", "mode": "nan", "hit": 2, "times": 6}])
    with pytest.raises(AnomalyBudgetExceeded):
        _train_with_loss_log(args)


# ---------------------------------------------------------------------------
# checkpoint I/O faults: retried saves/restores, exhaustion, async eviction
# ---------------------------------------------------------------------------

def test_ckpt_save_fault_survived_within_retry_budget(tmp_path):
    from veomni_tpu.resilience.faults import configure_faults, fired_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, save_steps=2, resilience_io_retries=3)
    configure_faults([{"point": "ckpt.save", "mode": "exception", "hit": 1,
                       "times": 2}])
    ctl, losses, trainer = _train_with_loss_log(args)
    trainer.checkpointer.close()
    assert ctl.global_step == 4
    assert len(fired_faults()) == 2  # two failed attempts, third succeeded
    ckpts = trainer.checkpointer.list_steps()
    assert ckpts == [2, 4]


def test_ckpt_save_retry_exhaustion_aborts_run(tmp_path):
    from veomni_tpu.resilience.faults import InjectedFault, configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, save_steps=2, resilience_io_retries=1)
    configure_faults([{"point": "ckpt.save", "mode": "exception", "times": 20}])
    with pytest.raises(InjectedFault):
        _train_with_loss_log(args)


def test_ckpt_restore_fault_survived_within_retry_budget(tmp_path):
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.resilience.faults import configure_faults, fired_faults
    from veomni_tpu.trainer import TextTrainer

    _write_data(tmp_path / "data.jsonl")
    ctl, _, trainer = _train_with_loss_log(_dense_args(tmp_path, save_steps=2))
    trainer.checkpointer.close()
    destroy_parallel_state()

    configure_faults([{"point": "ckpt.restore", "mode": "exception", "hit": 1}])
    trainer2 = TextTrainer(_dense_args(tmp_path))
    restored, extra = trainer2.try_resume()
    assert restored and int(extra["global_step"]) == 4
    assert len(fired_faults()) == 1
    trainer2.checkpointer.close()


def test_async_save_error_surfaced_and_evicted(tmp_path):
    """check_for_errors-style probe at the step boundary: a failed async
    commit raises at wait(), and the step leaves the dedupe set so a later
    save() re-dispatches instead of silently skipping."""
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=True)
    state = {"w": jnp.arange(4, dtype=jnp.float32)}
    ck.save(1, state, extra_state={"global_step": 1})
    ck.wait()
    assert ck.list_steps() == [1]

    # simulate an async commit failure of a dispatched step 2
    ck._saved_steps.add(2)
    ck._inflight_step = 2
    ck._ckptr.check_for_errors = lambda: (_ for _ in ()).throw(IOError("commit failed"))
    with pytest.raises(IOError, match="commit failed"):
        ck.wait()
    assert 2 not in ck._saved_steps  # evicted: not silently lost

    del ck._ckptr.check_for_errors  # commit thread healthy again
    ck.save(2, state, extra_state={"global_step": 2})  # NOT dedupe-skipped
    ck.wait()
    assert ck.list_steps() == [1, 2]
    ck.close()


def test_extra_state_precedes_payload_commit(tmp_path):
    """The train_state dir rename is the commit marker; the JSON sidecars a
    committed checkpoint needs must already be on disk when it appears —
    a crash can never yield a committed step missing its cursor metadata."""
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import Checkpointer
    from veomni_tpu.resilience.faults import InjectedFault, configure_faults

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False, io_retries=0)
    configure_faults([{"point": "ckpt.save", "mode": "exception"}])
    with pytest.raises(InjectedFault):
        ck.save(3, {"w": jnp.zeros(2)}, extra_state={"global_step": 3},
                rank_state={"dataloader": {"cursor": 7}})
    step_dir = tmp_path / "ck" / "global_step_3"
    assert (step_dir / "extra_state.json").exists()
    assert (step_dir / "extra_state_rank0.json").exists()
    assert not (step_dir / "train_state").exists()
    assert ck.list_steps() == []  # uncommitted: invisible to resume
    ck.close()


# ---------------------------------------------------------------------------
# data-fetch faults: streaming retry + watchdog on a stalled loop
# ---------------------------------------------------------------------------

def test_streaming_fetch_fault_survived_by_retry(tmp_path):
    from veomni_tpu.data.streaming import StreamingShardDataset
    from veomni_tpu.resilience.faults import configure_faults, fired_faults

    shard_dir = tmp_path / "shards"
    shard_dir.mkdir()
    rows = [{"i": i} for i in range(10)]
    with open(shard_dir / "00.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    configure_faults([{"point": "data.fetch", "mode": "exception", "hit": 3,
                       "times": 2}])
    ds = StreamingShardDataset(str(shard_dir), shuffle=False, retry_base_s=0.001)
    got = [r["i"] for r in ds]
    assert got == list(range(10))  # nothing dropped, order preserved
    assert len(fired_faults()) == 2


def test_watchdog_fires_on_stalled_loop_and_run_completes(tmp_path):
    """A bounded hang at data.fetch stalls the loop past the watchdog
    deadline: stacks are dumped (stall counted) but the run still finishes —
    no unbounded hang, no spurious kill."""
    from veomni_tpu.resilience.faults import configure_faults

    _write_data(tmp_path / "data.jsonl")
    args = _dense_args(tmp_path, resilience_watchdog_s=0.25, prefetch_depth=1)
    # the LAST fetch of the run: nothing queued behind it hides the stall
    configure_faults([{"point": "data.fetch", "mode": "hang", "hit": 4,
                       "seconds": 1.5}])
    ctl, losses, trainer = _train_with_loss_log(args)
    trainer.checkpointer.close()
    assert ctl.global_step == 4
    assert ctl.resilience["watchdog_stalls"] >= 1


def test_watchdog_unit_dump_names_threads():
    from veomni_tpu.utils.helper import Watchdog

    dumps = []
    wd = Watchdog(0.1, on_stall=dumps.append, description="unit").start()
    try:
        time.sleep(0.35)
    finally:
        wd.stop()
    assert wd.stall_count >= 1 and dumps
    assert "MainThread" in dumps[0] and "test_watchdog_unit" in dumps[0]
    # petting resets the deadline
    wd2 = Watchdog(0.25, on_stall=dumps.append).start()
    try:
        for _ in range(4):
            time.sleep(0.1)
            wd2.pet()
        assert wd2.stall_count == 0
    finally:
        wd2.stop()


# ---------------------------------------------------------------------------
# real-process preemption/crash tests (subprocess: signals need a process)
# ---------------------------------------------------------------------------

_DRIVER = """\
import json, os, sys, time

cfg = json.load(open(sys.argv[1]))
sys.path.insert(0, cfg["repo"])

from veomni_tpu.arguments import VeOmniArguments
from veomni_tpu.trainer import TextTrainer
from veomni_tpu.trainer.callbacks import Callback

args = VeOmniArguments()
args.model.config_overrides = cfg["toy"]
args.data.train_path = cfg["data"]
args.data.data_type = "pretokenized"
args.data.max_seq_len = 64
t = args.train
t.output_dir = cfg["out"]
t.micro_batch_size = 2
t.train_steps = cfg["train_steps"]
t.save_steps = cfg.get("save_steps", 0)
t.async_save = cfg.get("async_save", False)
t.ckpt_verify = cfg.get("ckpt_verify", "size")
t.data_skip_budget = cfg.get("data_skip_budget", 0)
t.lr_decay_style = cfg.get("lr_decay_style", "cosine")
if cfg.get("dataset_type"):
    args.data.dataset_type = cfg["dataset_type"]
t.lr = 1e-3
t.bf16 = False
t.save_hf_weights = False
t.log_steps = 1

trainer = TextTrainer(args)


class Rec(Callback):
    def on_step_end(self, tr, state):
        if state.synced:
            with open(cfg["loss_log"], "a") as f:
                f.write(json.dumps({
                    "step": state.global_step,
                    "loss_hex": float(state.metrics["loss"]).hex(),
                }) + "\\n")
        # AFTER CheckpointCallback in the list: by marker time the step's
        # save has been dispatched
        if cfg.get("marker_at") and state.global_step == cfg["marker_at"]:
            with open(cfg["marker"], "w") as f:
                f.write(str(state.global_step))
        if cfg.get("step_sleep"):
            time.sleep(cfg["step_sleep"])


trainer.callbacks.append(Rec())
ctl = trainer.train()
trainer.checkpointer.close()
res = {"global_step": ctl.global_step, "preempted": ctl.preempted,
       "resilience": ctl.resilience}
if hasattr(trainer.dataset, "state_dict"):
    res["dataset_state"] = trainer.dataset.state_dict()
with open(cfg["result"], "w") as f:
    json.dump(res, f)
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_driver(tmp_path, cfg, extra_env=None):
    driver = tmp_path / "driver.py"
    driver.write_text(_DRIVER)
    cfg_path = tmp_path / f"cfg_{os.path.basename(cfg['loss_log'])}.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu", VEOMNI_LOG_LEVEL="WARNING")
    env.pop("VEOMNI_FAULT_PLAN", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, str(driver), str(cfg_path)],
        env=env, cwd=_REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def _base_cfg(tmp_path, out_name, loss_log, **over):
    cfg = {
        "repo": _REPO,
        "toy": DENSE_TOY,
        "data": str(tmp_path / "data.jsonl"),
        "out": str(tmp_path / out_name),
        "loss_log": str(tmp_path / loss_log),
        "result": str(tmp_path / (loss_log + ".result.json")),
        "marker": str(tmp_path / (loss_log + ".marker")),
        "train_steps": 8,
    }
    cfg.update(over)
    return cfg


def _wait_for(path, proc, timeout=180.0):
    t0 = time.monotonic()
    while not os.path.exists(path):
        if proc.poll() is not None:
            out, err = proc.communicate()
            raise AssertionError(
                f"driver exited rc={proc.returncode} before {path}:\n{err[-2000:]}"
            )
        if time.monotonic() - t0 > timeout:
            proc.kill()
            raise AssertionError(f"timed out waiting for {path}")
        time.sleep(0.05)


def _read_losses(path):
    out = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            out[rec["step"]] = rec["loss_hex"]  # replayed steps: last wins
    return out


def test_sigterm_graceful_checkpoint_exit0_and_resume(tmp_path):
    """SIGTERM mid-run: the loop finishes the in-flight step, takes one
    final synchronous checkpoint, and exits 0; a restart resumes from
    exactly that step."""
    _write_data(tmp_path / "data.jsonl")
    cfg = _base_cfg(tmp_path, "out", "leg1.jsonl",
                    train_steps=60, step_sleep=0.15, marker_at=2)
    proc = _spawn_driver(tmp_path, cfg)
    _wait_for(cfg["marker"], proc)
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=180)
    assert proc.returncode == 0, f"expected clean exit, rc={proc.returncode}:\n{err[-2000:]}"

    result = json.load(open(cfg["result"]))
    stopped_at = result["global_step"]
    assert result["preempted"] and 2 <= stopped_at < 60
    step_dir = os.path.join(cfg["out"], "checkpoints", f"global_step_{stopped_at}")
    assert os.path.isdir(os.path.join(step_dir, "train_state"))  # committed
    assert os.path.exists(os.path.join(step_dir, "extra_state.json"))

    # restart: auto-resume picks up at stopped_at and continues
    cfg2 = _base_cfg(tmp_path, "out", "leg2.jsonl", train_steps=stopped_at + 2)
    proc2 = _spawn_driver(tmp_path, cfg2)
    out, err = proc2.communicate(timeout=300)
    assert proc2.returncode == 0, err[-2000:]
    result2 = json.load(open(cfg2["result"]))
    assert not result2["preempted"] and result2["global_step"] == stopped_at + 2
    leg2 = _read_losses(cfg2["loss_log"])
    assert min(leg2) == stopped_at + 1  # no step re-run, none skipped


def test_sigkill_mid_async_save_resume_bit_exact(tmp_path):
    """Crash consistency: SIGKILL the trainer right as the step-4 async save
    is in flight, restart, and the resumed loss trajectory must be BIT-exact
    vs an uninterrupted run — whether the kill landed before or after the
    async commit (uncommitted debris is cleaned, committed state resumes)."""
    _write_data(tmp_path / "data.jsonl")

    ref_cfg = _base_cfg(tmp_path, "ref_out", "ref.jsonl",
                        save_steps=4, async_save=True)
    proc = _spawn_driver(tmp_path, ref_cfg)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    ref = _read_losses(ref_cfg["loss_log"])
    assert sorted(ref) == list(range(1, 9))

    kill_cfg = _base_cfg(tmp_path, "kill_out", "kill1.jsonl",
                         save_steps=4, async_save=True, marker_at=4)
    proc = _spawn_driver(tmp_path, kill_cfg)
    _wait_for(kill_cfg["marker"], proc)
    proc.kill()  # SIGKILL: no handlers, no cleanup — a real crash
    proc.communicate(timeout=60)
    assert proc.returncode == -signal.SIGKILL
    assert not os.path.exists(kill_cfg["result"])

    resume_cfg = _base_cfg(tmp_path, "kill_out", "kill2.jsonl",
                           save_steps=4, async_save=True)
    proc = _spawn_driver(tmp_path, resume_cfg)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    result = json.load(open(resume_cfg["result"]))
    assert result["global_step"] == 8
    leg2 = _read_losses(resume_cfg["loss_log"])
    assert max(leg2) == 8
    for step, hexloss in leg2.items():
        assert ref[step] == hexloss, (
            f"step {step}: resumed loss {hexloss} != uninterrupted {ref[step]}"
        )


# ---------------------------------------------------------------------------
# integrity: manifest roundtrip + verify-mode matrix (resilience/integrity.py)
# ---------------------------------------------------------------------------

def _make_ckpt_tree(root):
    ts = root / "train_state"
    ts.mkdir(parents=True)
    (ts / "arr0.bin").write_bytes(bytes(range(256)) * 8)  # largest file
    (ts / "nested").mkdir()
    (ts / "nested" / "arr1.bin").write_bytes(b"hello world" * 10)
    (root / "extra_state.json").write_text('{"global_step": 3}')
    (root / "extra_state_rank0.json").write_text('{"dataloader": {}}')


def test_manifest_roundtrip_and_verify_matrix(tmp_path):
    from veomni_tpu.resilience import integrity

    step_dir = tmp_path / "global_step_3"
    _make_ckpt_tree(step_dir)
    integrity.write_manifest(str(step_dir))
    doc = integrity.read_manifest(str(step_dir))
    assert doc["version"] == integrity.MANIFEST_VERSION
    # payload subtree (incl. nested dirs) + both extra-state sidecars
    assert set(doc["files"]) == {
        os.path.join("train_state", "arr0.bin"),
        os.path.join("train_state", "nested", "arr1.bin"),
        "extra_state.json", "extra_state_rank0.json",
    }
    # off -> no report (unverified, not verified-clean); size/full pass
    assert integrity.verify_manifest(str(step_dir), mode="off") is None
    for mode in ("size", "full"):
        rep = integrity.verify_manifest(str(step_dir), mode=mode)
        assert rep.passed and rep.total == 4 and rep.problems == []
        assert "OK" in rep.summary()
    with pytest.raises(ValueError, match="unknown verify mode"):
        integrity.verify_manifest(str(step_dir), mode="paranoid")

    # BITFLIP keeps the size: invisible to "size", caught only by "full"
    payload = step_dir / "train_state" / "arr0.bin"
    raw = bytearray(payload.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    payload.write_bytes(bytes(raw))
    assert integrity.verify_manifest(str(step_dir), mode="size").passed
    rep = integrity.verify_manifest(str(step_dir), mode="full")
    assert [(p.path, p.kind) for p in rep.problems] == [
        (os.path.join("train_state", "arr0.bin"), "mismatch")]
    assert "CORRUPT" in rep.summary()

    # TRUNCATION: already caught by "size", classified as truncated
    raw2 = payload.read_bytes()
    payload.write_bytes(raw2[: len(raw2) // 2])
    rep = integrity.verify_manifest(str(step_dir), mode="size")
    assert [(p.path, p.kind) for p in rep.problems] == [
        (os.path.join("train_state", "arr0.bin"), "truncated")]

    # MISSING file
    payload.unlink()
    rep = integrity.verify_manifest(str(step_dir), mode="size")
    assert [(p.path, p.kind) for p in rep.problems] == [
        (os.path.join("train_state", "arr0.bin"), "missing")]

    # an unreadable or absent manifest is UNVERIFIABLE (None), never corrupt
    (step_dir / integrity.MANIFEST_NAME).write_text("{not json")
    assert integrity.verify_manifest(str(step_dir), mode="full") is None
    (step_dir / integrity.MANIFEST_NAME).unlink()
    assert integrity.verify_manifest(str(step_dir), mode="full") is None


def test_corrupt_fault_mode_truncate_and_bitflip(tmp_path):
    from veomni_tpu.resilience import faults

    d = tmp_path / "gen"
    d.mkdir()
    (d / "a.bin").write_bytes(b"x" * 10)
    (d / "b.bin").write_bytes(bytes(range(100)))

    # default target = LARGEST file under the context dir; bitflip keeps size
    faults.configure_faults([{"point": "ckpt.manifest", "mode": "corrupt"}])
    act = faults.fault_point("ckpt.manifest", context={"dir": str(d)})
    assert act is not None and act.mode == "corrupt"
    assert act.target == str(d / "b.bin")
    assert (d / "b.bin").stat().st_size == 100
    assert (d / "b.bin").read_bytes()[50] == 50 ^ 0xFF  # middle byte flipped
    assert (d / "a.bin").read_bytes() == b"x" * 10      # untouched

    # truncate op; context names the file directly
    faults.configure_faults([{"point": "data.record", "mode": "corrupt",
                              "op": "truncate"}])
    shard = tmp_path / "shard.jsonl"
    shard.write_bytes(b"y" * 64)
    act = faults.fault_point("data.record", context={"file": str(shard)})
    assert act.target == str(shard) and shard.stat().st_size == 32

    # glob-resolved explicit target + pinned offset
    faults.configure_faults([{"point": "ckpt.manifest", "mode": "corrupt",
                              "file": "*.bin", "offset": 0}])
    act = faults.fault_point("ckpt.manifest", context={"dir": str(d)})
    assert act.target == str(d / "a.bin")  # first sorted match
    assert (d / "a.bin").read_bytes()[0] == ord("x") ^ 0xFF

    with pytest.raises(ValueError, match="unknown corrupt op"):
        faults.configure_faults([{"point": "ckpt.manifest", "mode": "corrupt",
                                  "op": "melt"}])


# ---------------------------------------------------------------------------
# integrity: checkpointer quarantine + multi-generation restore fallback
# ---------------------------------------------------------------------------

def _corrupt_payload(step_dir, op="truncate"):
    """Damage the largest payload file of a committed generation in place."""
    best, best_size = None, -1
    for dirpath, _dirs, files in os.walk(os.path.join(step_dir, "train_state")):
        for f in files:
            full = os.path.join(dirpath, f)
            size = os.path.getsize(full)
            if size > best_size:
                best, best_size = full, size
    with open(best, "r+b") as f:
        if op == "truncate":
            f.truncate(best_size // 2)
        else:
            f.seek(best_size // 2)
            b = f.read(1)
            f.seek(best_size // 2)
            f.write(bytes([b[0] ^ 0xFF]))
    return best


def test_ckpt_quarantine_and_multi_generation_fallback(tmp_path):
    import jax
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.observability.metrics import get_registry
    from veomni_tpu.resilience import CheckpointCorruptError

    reg = get_registry()
    q0 = reg.counter("integrity.ckpt_quarantined").value
    f0 = reg.counter("integrity.ckpt_fallbacks").value

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size")
    state = None
    for step in (1, 2, 3):
        state = {"w": jnp.full((128,), float(step), jnp.float32)}
        ck.save(step, state, extra_state={"global_step": step})
    assert ck.list_steps() == [1, 2, 3]
    for step in (1, 2, 3):  # sync saves wrote their manifests immediately
        assert os.path.exists(os.path.join(
            ck.ckpt_dir, f"global_step_{step}", "manifest.json"))

    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)

    # newest TWO generations rot: restore quarantines both, lands on step 1
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_3"))
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_2"))
    restored, extra = ck.load(abstract)
    assert int(extra["global_step"]) == 1
    assert float(np.asarray(restored["w"])[0]) == 1.0
    assert ck.list_steps() == [1] and ck.latest_step() == 1
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_3.corrupt"))
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_2.corrupt"))
    assert not os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_3"))
    assert reg.counter("integrity.ckpt_quarantined").value - q0 == 2
    assert reg.counter("integrity.ckpt_fallbacks").value - f0 == 2

    # the last generation rots too: clean abort with actionable guidance
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_1"))
    with pytest.raises(CheckpointCorruptError, match="no trustworthy state"):
        ck.load(abstract)
    assert ck.list_steps() == []
    ck.close()


def test_resave_supersedes_quarantined_step_same_process(tmp_path):
    """A quarantine must not block a later legitimate save() of the same
    step IN THE SAME PROCESS (the supervisor-rollback timeline: quarantine
    step N, restore older, train forward past N again): the re-save must
    dispatch a fresh generation — not be deduped as "already dispatched" —
    and that generation must be offered by list_steps/latest_step again."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience import CheckpointCorruptError

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size")
    for step in (1, 2):
        ck.save(step, {"w": jnp.full((64,), float(step), jnp.float32)},
                extra_state={"global_step": step})
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_2"))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        {"w": jnp.zeros((64,), jnp.float32)})
    restored, extra = ck.load(abstract)  # quarantines 2, falls back to 1
    assert int(extra["global_step"]) == 1 and ck.latest_step() == 1

    # the run trains forward and saves step 2 again: fresh healthy bytes
    ck.save(2, {"w": jnp.full((64,), 2.0, jnp.float32)},
            extra_state={"global_step": 2})
    assert ck.list_steps() == [1, 2] and ck.latest_step() == 2
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_2"))
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_2.corrupt"))
    restored2, extra2 = ck.load(abstract)  # the new generation verifies
    assert int(extra2["global_step"]) == 2
    assert float(np.asarray(restored2["w"])[0]) == 2.0
    ck.close()


def test_resave_after_failed_quarantine_rename_clears_corpse(tmp_path, monkeypatch):
    """If the quarantine rename itself fails (EBUSY/ESTALE on the flaky
    shared fs this layer targets), the corrupt dir stays at the live path.
    A later superseding save() of that step must clear the corpse (rename
    retry, then deletion) instead of dispatching Orbax into the existing
    dir and dying on an unretried 'destination already exists'."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size")
    for step in (1, 2):
        ck.save(step, {"w": jnp.full((64,), float(step), jnp.float32)},
                extra_state={"global_step": step})
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_2"))

    # every .corrupt rename fails; Orbax's own commit renames stay live
    real_rename = os.rename

    def flaky_rename(src, dst, *a, **kw):
        if ".corrupt" in str(dst):
            raise OSError("ESTALE: simulated shared-fs rename failure")
        return real_rename(src, dst, *a, **kw)

    monkeypatch.setattr(os, "rename", flaky_rename)

    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        {"w": jnp.zeros((64,), jnp.float32)})
    restored, extra = ck.load(abstract)  # quarantine rename fails in-flight
    assert int(extra["global_step"]) == 1
    # the corpse still occupies the live path, excluded only in-memory
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_2"))
    assert ck.latest_step() == 1

    # superseding save: rename retry fails again -> deletion fallback
    ck.save(2, {"w": jnp.full((64,), 2.0, jnp.float32)},
            extra_state={"global_step": 2})
    assert ck.list_steps() == [1, 2]
    restored2, extra2 = ck.load(abstract)
    assert int(extra2["global_step"]) == 2
    assert float(np.asarray(restored2["w"])[0]) == 2.0
    ck.close()


def test_ckpt_verify_mode_gates_bitflip_detection(tmp_path):
    import jax
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="full")
    state = {"w": jnp.arange(1024, dtype=jnp.float32)}
    ck.save(1, state, extra_state={"global_step": 1})
    ck.save(2, state, extra_state={"global_step": 2})
    _corrupt_payload(os.path.join(ck.ckpt_dir, "global_step_2"), op="bitflip")

    # a size-mode verify misses the same-size bitflip...
    ck_size = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                                 verify_mode="size")
    rep = ck_size.verify_step(2)
    assert rep is not None and rep.passed

    # ...the full-mode gate catches it, quarantines, falls back to step 1
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    restored, extra = ck.load(abstract)
    assert int(extra["global_step"]) == 1
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_2.corrupt"))

    # off-mode never verifies; bogus mode rejected at construction
    ck_off = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                                verify_mode="off")
    assert ck_off.verify_step(1) is None
    with pytest.raises(ValueError, match="unknown ckpt verify mode"):
        build_checkpointer(str(tmp_path / "x"), verify_mode="paranoid")
    for c in (ck, ck_size, ck_off):
        c.close()


def test_quarantined_dirs_age_out_beyond_max_to_keep(tmp_path):
    import jax.numpy as jnp

    from veomni_tpu.checkpoint import build_checkpointer

    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            max_to_keep=2)
    # three pre-existing corpses (incl. a rename-collision suffix)
    for name in ("global_step_1.corrupt", "global_step_2.corrupt",
                 "global_step_3.corrupt.1"):
        d = tmp_path / "ck" / name / "train_state"
        d.mkdir(parents=True)
        (d / "junk.bin").write_bytes(b"z" * 8)
    ck.save(10, {"w": jnp.zeros(4)}, extra_state={"global_step": 10})
    corpses = sorted(d for d in os.listdir(tmp_path / "ck")
                     if ".corrupt" in d)
    # newest max_to_keep corpses stay for post-mortem, the oldest is reaped
    assert corpses == ["global_step_2.corrupt", "global_step_3.corrupt.1"]
    assert ck.list_steps() == [10]
    ck.close()


# ---------------------------------------------------------------------------
# integrity: streaming shard provenance + poison-record skip budget
# ---------------------------------------------------------------------------

def test_shard_decode_errors_carry_provenance(tmp_path):
    from veomni_tpu.data.streaming import _open_shard
    from veomni_tpu.resilience import ShardRecordError

    shard = tmp_path / "00.jsonl"
    shard.write_text('{"i": 0}\n{oops not json\n{"i": 2}\n')
    reader = _open_shard(str(shard))
    assert reader.read(0) == {"i": 0}
    with pytest.raises(ShardRecordError) as ei:
        reader.read(1)
    assert ei.value.shard == str(shard) and ei.value.record == 1
    assert "00.jsonl" in str(ei.value) and "record 1" in str(ei.value)
    assert reader.read(2) == {"i": 2}  # neighbors unaffected

    # tar member rot: same provenance contract, member named in the detail
    import io
    import tarfile

    tar_path = tmp_path / "01.tar"
    with tarfile.open(tar_path, "w") as tf:
        for name, payload in (("s0.json", b'{"i": 0}'), ("s1.json", b"{rot")):
            info = tarfile.TarInfo(name)
            info.size = len(payload)
            tf.addfile(info, io.BytesIO(payload))
    treader = _open_shard(str(tar_path))
    assert treader.read(0) == {"i": 0}
    with pytest.raises(ShardRecordError) as ei:
        treader.read(1)
    assert ei.value.record == 1 and "member .json" in str(ei.value)


def _poison_shard_dir(tmp_path, n=10, poison=(4,), name="shards"):
    shard_dir = tmp_path / name
    shard_dir.mkdir(exist_ok=True)
    lines = ["{rot}" if i in poison else json.dumps({"i": i})
             for i in range(n)]
    (shard_dir / "00.jsonl").write_text("\n".join(lines) + "\n")
    return shard_dir


def test_poison_skip_budget_sequential_and_fail_fast(tmp_path):
    from veomni_tpu.data.streaming import StreamingShardDataset
    from veomni_tpu.resilience import ShardRecordError

    shard_dir = _poison_shard_dir(tmp_path, n=10, poison=(4,))

    # budget 0 (the default): fail FAST with shard+record provenance
    ds0 = StreamingShardDataset(str(shard_dir), shuffle=False,
                                retry_base_s=0.001)
    with pytest.raises(ShardRecordError) as ei:
        list(ds0)
    assert ei.value.record == 4 and "00.jsonl" in str(ei.value)
    assert "skip budget exhausted" in str(ei.value)

    # budget 1: the poisoned record is dropped, order otherwise preserved
    ds = StreamingShardDataset(str(shard_dir), shuffle=False,
                               retry_base_s=0.001, skip_budget=1)
    got = [r["i"] for r in ds]
    assert got == [i for i in range(10) if i != 4]
    assert ds.state_dict()["skipped"] == [["00.jsonl", 4]]

    # epoch 2 re-skips the same record WITHOUT consuming fresh budget
    got2 = [r["i"] for r in ds]
    assert got2 == got and len(ds.state_dict()["skipped"]) == 1

    # two poisons against a budget of one: exhaustion carries the history
    shard_dir2 = _poison_shard_dir(tmp_path, n=10, poison=(2, 7), name="s2")
    ds2 = StreamingShardDataset(str(shard_dir2), shuffle=False,
                                retry_base_s=0.001, skip_budget=1)
    with pytest.raises(ShardRecordError) as ei:
        list(ds2)
    assert ei.value.record == 7 and "already skipped" in str(ei.value)


def test_poison_skip_replay_across_state_roundtrip(tmp_path):
    from veomni_tpu.data.streaming import StreamingShardDataset

    shard_dir = _poison_shard_dir(tmp_path, n=12, poison=(2, 7))

    def build():
        return StreamingShardDataset(str(shard_dir), shuffle=True, seed=5,
                                     retry_base_s=0.001, skip_budget=2)

    ref = build()
    ref_rows = [r["i"] for r in ref]
    assert len(ref_rows) == 10 and len(ref.state_dict()["skipped"]) == 2

    # consume part of the epoch, snapshot mid-stream, resume in a FRESH
    # dataset: the combined row sequence and the final skip history must be
    # identical to the uninterrupted epoch
    a = build()
    it = iter(a)
    first = [next(it)["i"] for _ in range(4)]
    snap = a.state_dict()
    b = build()
    b.load_state_dict(snap)
    rest = [r["i"] for r in b]
    assert first + rest == ref_rows
    assert b.state_dict()["skipped"] == ref.state_dict()["skipped"]


def test_poison_getitem_substitutes_deterministically(tmp_path):
    from veomni_tpu.data.streaming import StreamingShardDataset
    from veomni_tpu.resilience import ShardRecordError

    shard_dir = _poison_shard_dir(tmp_path, n=6, poison=(3,))
    ds = StreamingShardDataset(str(shard_dir), shuffle=False,
                               retry_base_s=0.001, skip_budget=1)
    assert len(ds) == 6
    # linear access substitutes the NEXT healthy record for the poisoned one
    # (batch shapes must stay full), stable across repeated access
    assert ds[3]["i"] == 4 and ds[3]["i"] == 4
    assert ds[2]["i"] == 2 and ds[4]["i"] == 4
    assert ds.state_dict()["skipped"] == [["00.jsonl", 3]]

    # the substitution survives a state roundtrip
    ds2 = StreamingShardDataset(str(shard_dir), shuffle=False,
                                retry_base_s=0.001, skip_budget=1)
    ds2.load_state_dict(ds.state_dict())
    assert ds2[3]["i"] == 4

    # budget 0: the same access fails fast instead of substituting
    ds3 = StreamingShardDataset(str(shard_dir), shuffle=False,
                                retry_base_s=0.001)
    with pytest.raises(ShardRecordError):
        ds3[3]


def test_validate_hook_feeds_skip_budget(tmp_path):
    from veomni_tpu.data.streaming import StreamingShardDataset
    from veomni_tpu.resilience import ShardRecordError

    shard_dir = tmp_path / "vshards"
    shard_dir.mkdir()
    with open(shard_dir / "00.jsonl", "w") as f:
        for i in range(6):
            f.write(json.dumps({"i": i}) + "\n")

    def validate(row):
        return row["i"] != 2

    ds = StreamingShardDataset(str(shard_dir), shuffle=False,
                               retry_base_s=0.001, skip_budget=1,
                               validate=validate)
    assert [r["i"] for r in ds] == [0, 1, 3, 4, 5]
    ds0 = StreamingShardDataset(str(shard_dir), shuffle=False,
                                retry_base_s=0.001, validate=validate)
    with pytest.raises(ShardRecordError, match="validation hook"):
        list(ds0)


def test_retry_counters_and_exhaustion_log():
    import logging

    from veomni_tpu.observability.metrics import get_registry
    from veomni_tpu.resilience.retry import RetryPolicy, retry_call

    reg = get_registry()
    a0 = reg.counter("retry.attempts").value
    e0 = reg.counter("retry.exhausted").value

    def doomed():
        raise OSError("disk on fire")

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    target = logging.getLogger("veomni_tpu.resilience.retry")
    target.addHandler(handler)
    try:
        with pytest.raises(OSError):
            retry_call(doomed, policy=RetryPolicy(retries=2, base_delay_s=0.5),
                       sleep=lambda _: None, description="probe")
    finally:
        target.removeHandler(handler)
    assert reg.counter("retry.attempts").value - a0 == 2
    assert reg.counter("retry.exhausted").value - e0 == 1
    final = [r.getMessage() for r in records
             if "exhausted" in r.getMessage()]
    # evidence the retries happened rides the final-failure line
    assert final and "3 attempt(s)" in final[0]
    assert "total backoff" in final[0]


# ---------------------------------------------------------------------------
# integrity: real-process drills (acceptance criteria)
# ---------------------------------------------------------------------------

def test_subprocess_corrupt_ckpt_quarantine_fallback_bit_exact(tmp_path):
    """A corrupt-mode fault plan flips bytes in the newest committed
    generation right after its manifest is written; the resumed run must
    quarantine it, restore the previous generation, and replay to the end
    with a loss trajectory BIT-exact vs an uncorrupted control run."""
    _write_data(tmp_path / "data.jsonl")

    # constant LR: the cosine default bakes train_steps into every update,
    # and the three legs train different horizons
    # control: uninterrupted 8-step run over the same data/seed
    ctl_cfg = _base_cfg(tmp_path, "ictl_out", "ictl.jsonl", save_steps=2,
                        lr_decay_style="constant")
    proc = _spawn_driver(tmp_path, ctl_cfg)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    ref = _read_losses(ctl_cfg["loss_log"])
    assert sorted(ref) == list(range(1, 9))

    # leg 1: checkpoints at steps 2 and 4; the ckpt.manifest corrupt fault
    # (hit 2 = the step-4 manifest) bitflips the step-4 payload AFTER its
    # digests were recorded — the storage-rot timeline
    leg1_cfg = _base_cfg(tmp_path, "ivic_out", "ivic1.jsonl",
                         train_steps=4, save_steps=2,
                         lr_decay_style="constant")
    plan = [{"point": "ckpt.manifest", "mode": "corrupt", "hit": 2,
             "op": "bitflip"}]
    proc = _spawn_driver(tmp_path, leg1_cfg,
                         extra_env={"VEOMNI_FAULT_PLAN": json.dumps(plan)})
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    ck_dir = os.path.join(leg1_cfg["out"], "checkpoints")
    assert os.path.isdir(os.path.join(ck_dir, "global_step_4"))  # committed

    # leg 2: resume under full verification — step 4 quarantined, step 2
    # restored, steps 3-8 replayed
    leg2_cfg = _base_cfg(tmp_path, "ivic_out", "ivic2.jsonl",
                         save_steps=0, ckpt_verify="full",
                         lr_decay_style="constant")
    proc = _spawn_driver(tmp_path, leg2_cfg)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    result = json.load(open(leg2_cfg["result"]))
    assert result["global_step"] == 8
    assert os.path.isdir(os.path.join(ck_dir, "global_step_4.corrupt"))
    assert not os.path.isdir(os.path.join(ck_dir, "global_step_4"))
    leg2 = _read_losses(leg2_cfg["loss_log"])
    assert sorted(leg2) == list(range(3, 9))  # resumed from step 2
    for step, hexloss in leg2.items():
        assert ref[step] == hexloss, (
            f"step {step}: post-fallback loss {hexloss} != control {ref[step]}"
        )


def test_subprocess_data_skip_budget_across_resume_and_exhaustion(tmp_path):
    """With ``train.data_skip_budget=1`` a poisoned streaming record is
    skipped deterministically across a save/restore boundary (trajectory
    bit-exact vs an uninterrupted run over the same poisoned corpus, skip
    recorded in the restored rank state); with the default budget of 0 the
    same corpus fails fast with shard+record provenance."""
    # sized to the packing collator's demand-driven offer: with the pinned
    # 4-device topology below it requests samples_per_micro_batch*local_mb
    # = 64 raw samples per batch, so 64 records = every record (incl. the
    # poison) is offered from step 1 on — and a smaller corpus would starve
    # the offer loop outright
    shard_dir = tmp_path / "stream_shards"
    shard_dir.mkdir()
    rng = np.random.default_rng(0)
    poison_idx = 7
    with open(shard_dir / "00.jsonl", "w") as f:
        for i in range(64):
            if i == poison_idx:
                f.write("{this is not json\n")
                continue
            f.write(json.dumps({
                "input_ids": rng.integers(
                    0, 256, int(rng.integers(16, 80))).tolist(),
            }) + "\n")

    # constant LR: the cosine default bakes train_steps into every update,
    # and the legs train different horizons. The device topology is pinned
    # (not inherited from the pytest process) so batch assembly — and with
    # it which records each step consumes — is identical across legs
    # however the suite is invoked.
    xla4 = {"XLA_FLAGS": f"{os.environ.get('XLA_FLAGS', '')} --xla_force_host_platform_device_count=4"}
    common = dict(dataset_type="streaming", data_skip_budget=1,
                  lr_decay_style="constant")

    ctl = _base_cfg(tmp_path, "sctl_out", "sctl.jsonl", save_steps=2, **common)
    ctl["data"] = str(shard_dir)
    proc = _spawn_driver(tmp_path, ctl, extra_env=xla4)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    ref = _read_losses(ctl["loss_log"])
    assert sorted(ref) == list(range(1, 9))
    ctl_result = json.load(open(ctl["result"]))
    assert ctl_result["dataset_state"]["skipped"] == [["00.jsonl", poison_idx]]

    leg1 = _base_cfg(tmp_path, "svic_out", "svic1.jsonl",
                     train_steps=4, save_steps=2, **common)
    leg1["data"] = str(shard_dir)
    proc = _spawn_driver(tmp_path, leg1, extra_env=xla4)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]

    leg2 = _base_cfg(tmp_path, "svic_out", "svic2.jsonl", save_steps=0,
                     **common)
    leg2["data"] = str(shard_dir)
    proc = _spawn_driver(tmp_path, leg2, extra_env=xla4)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-2000:]
    result = json.load(open(leg2["result"]))
    assert result["global_step"] == 8
    # the restored run carries the identical skip record
    assert result["dataset_state"]["skipped"] == [["00.jsonl", poison_idx]]
    leg2_losses = _read_losses(leg2["loss_log"])
    assert sorted(leg2_losses) == list(range(5, 9))  # resumed from step 4
    for step, hexloss in leg2_losses.items():
        assert ref[step] == hexloss, (
            f"step {step}: post-resume loss {hexloss} != control {ref[step]}"
        )

    # budget exhaustion: same corpus, budget 0 -> fast failure w/ provenance
    fail = _base_cfg(tmp_path, "sfail_out", "sfail.jsonl",
                     dataset_type="streaming", data_skip_budget=0)
    fail["data"] = str(shard_dir)
    proc = _spawn_driver(tmp_path, fail, extra_env=xla4)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode != 0
    assert "00.jsonl" in err and f"record {poison_idx}" in err
    assert "skip budget exhausted" in err
