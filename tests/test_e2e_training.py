"""End-to-end training smoke: toy model, packed data, FSDP+SP mesh, resume.

Ports the reference's e2e strategy (``tests/e2e/test_e2e_training*.py`` +
``tests/checkpoints/test_trainer_saveload.py``): run real trainer steps on a
toy config and assert loss decreases and resume reproduces state.
"""

import json
import os

import numpy as np
import pytest

from veomni_tpu.arguments import VeOmniArguments


def _write_dummy_data(path, n=512, vocab=256, seed=0, channels=None):
    rng = np.random.default_rng(seed)
    # zipf-skewed tokens: unigram stats are learnable, so the smoke test's
    # "loss decreases" check measures optimization, not noise (uniform data
    # has optimal loss == ln(vocab) == the init loss)
    weights = 1.0 / (np.arange(vocab) + 5.0)
    weights /= weights.sum()
    rows = []
    for _ in range(n):
        ln = int(rng.integers(16, 100))
        row = {"input_ids": rng.choice(vocab, size=ln, p=weights).tolist()}
        if channels:
            row["channel"] = channels[int(rng.integers(0, len(channels)))]
        rows.append(row)
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


TOY = {
    "model_type": "qwen3",
    "vocab_size": 256,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "qk_norm": True,
}


def _make_args(tmp_path, **train_overrides):
    args = VeOmniArguments()
    args.model.config_overrides = dict(TOY)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 128
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 8
    args.train.lr = 1e-3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 100
    for k, v in train_overrides.items():
        setattr(args.train, k, v)
    return args


def test_e2e_training_fsdp_sp(tmp_path):
    from veomni_tpu.trainer import TextTrainer

    _write_dummy_data(tmp_path / "data.jsonl")
    args = _make_args(tmp_path, ulysses_parallel_size=2, train_steps=12, lr=5e-3)
    trainer = TextTrainer(args)
    orig_step = trainer.train_step

    losses = []

    def wrapped(state, batch):
        out = orig_step(state, batch)
        losses.append(float(out[1]["loss"]))
        return out

    trainer.train_step = wrapped
    ctl = trainer.train()
    assert ctl.global_step == 12
    head = np.mean(losses[:2])
    tail = np.mean(losses[-4:])
    assert tail < head, f"loss did not decrease: {losses}"
    trainer.checkpointer.close()


def _host_tree(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x), tree)


def _assert_trees_identical(a, b, what):
    import jax

    leaves_a, treedef_a = jax.tree.flatten(a)
    leaves_b, treedef_b = jax.tree.flatten(b)
    assert treedef_a == treedef_b, f"{what}: tree structure differs"
    for i, (la, lb) in enumerate(zip(leaves_a, leaves_b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: leaf {i} ({treedef_a}) not bit-identical; "
            f"max abs diff {np.abs(np.asarray(la, np.float64) - np.asarray(lb, np.float64)).max()}"
        )


def _run_resume_case(tmp_path, *, data_kwargs=None, data_overrides=None,
                     **train_overrides):
    """8 straight steps vs (4 steps, save, restart, 4 steps) must produce
    bit-identical params, opt_state, and dataloader cursor (reference
    CheckpointerCallback exact-resume contract, checkpoint_callback.py:60-115)."""
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import TextTrainer

    _write_dummy_data(tmp_path / "data.jsonl", **(data_kwargs or {}))

    def make(out_name, **over):
        args = _make_args(tmp_path, **{**train_overrides, **over})
        args.train.output_dir = str(tmp_path / out_name)
        for k, v in (data_overrides or {}).items():
            setattr(args.data, k, v)
        return args

    # ---- run A: 8 straight steps, one trainer
    trainer_a = TextTrainer(make("a", train_steps=8, save_steps=0))
    ctl_a = trainer_a.train()
    assert ctl_a.global_step == 8
    ref_state = _host_tree(
        {"params": trainer_a.train_state.params,
         "opt_state": trainer_a.train_state.opt_state}
    )
    def _consumed_cursor(trainer):
        # with background prefetch the raw loader runs ahead by a
        # timing-dependent amount; the consumed-batch cursor (what a
        # checkpoint would record) is the deterministic quantity
        src = getattr(trainer, "_prefetcher", None) or trainer.dataloader
        return src.state_dict() if hasattr(src, "state_dict") else None

    ref_loader = _consumed_cursor(trainer_a)
    trainer_a.checkpointer.close()
    destroy_parallel_state()

    # ---- run B: 4 steps, save, fresh process-equivalent restart, 4 more.
    # train_steps stays 8 (the lr-schedule horizon must match run A); a
    # callback stops the first leg after step 4, like a preempted job.
    from veomni_tpu.trainer.callbacks import Callback

    class StopAt(Callback):
        def __init__(self, at):
            self.at = at

        def on_step_end(self, trainer, state):
            if state.global_step >= self.at:
                state.should_stop = True

    trainer_b1 = TextTrainer(make("b", train_steps=8, save_steps=4))
    trainer_b1.callbacks.append(StopAt(4))
    trainer_b1.train()
    trainer_b1.checkpointer.close()
    destroy_parallel_state()

    trainer_b2 = TextTrainer(make("b", train_steps=8, save_steps=4))
    ctl_b = trainer_b2.train()
    assert ctl_b.global_step == 8

    got_state = _host_tree(
        {"params": trainer_b2.train_state.params,
         "opt_state": trainer_b2.train_state.opt_state}
    )
    _assert_trees_identical(ref_state, got_state, "resumed train_state")
    if ref_loader is not None:
        assert ref_loader == _consumed_cursor(trainer_b2), (
            "dataloader cursor state diverged after resume"
        )
    trainer_b2.checkpointer.close()
    destroy_parallel_state()


def test_e2e_resume_exact(tmp_path):
    _run_resume_case(tmp_path)


def test_e2e_resume_exact_dynbsz_channels(tmp_path):
    _run_resume_case(
        tmp_path,
        data_kwargs={"channels": ["code", "web"]},
        data_overrides={"dyn_bsz": True, "channel_list": ["code", "web"]},
    )


def test_e2e_eval_loop(tmp_path):
    """Periodic evaluation: eval_loss computed from data.eval_path every
    eval_steps and at train end (the reference's EvaluateCallback is an
    empty TODO — ours runs)."""
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import TextTrainer

    _write_dummy_data(tmp_path / "data.jsonl")
    _write_dummy_data(tmp_path / "eval.jsonl")
    args = _make_args(tmp_path, train_steps=4)
    args.data.eval_path = str(tmp_path / "eval.jsonl")
    args.train.eval_steps = 2
    args.train.eval_batches = 2
    destroy_parallel_state()
    try:
        trainer = TextTrainer(args)
        seen = []
        orig = trainer.evaluate

        def spy():
            loss = orig()
            seen.append(loss)
            return loss

        trainer.evaluate = spy
        ctl = trainer.train()
        trainer.checkpointer.close()
        assert len(seen) == 2  # steps 2 and 4 (train-end skips: 4 % 2 == 0)
        assert all(np.isfinite(l) for l in seen)
        assert "eval_loss" in ctl.metrics
    finally:
        destroy_parallel_state()


def test_e2e_training_ctx_remat_policy(tmp_path):
    """The "ctx" remat policy (save only the named attention context) must
    train end-to-end through the CLI argument plumbing
    (train.gradient_checkpointing_policy -> cfg.remat_policy) with losses
    matching the nothing-policy run exactly (same seeds, pure remat change)."""
    from veomni_tpu.trainer import TextTrainer

    _write_dummy_data(tmp_path / "data.jsonl")
    losses = {}
    for policy in ("ctx", "nothing"):
        args = _make_args(
            tmp_path, train_steps=4,
            gradient_checkpointing_policy=policy,
        )
        args.model.config_overrides = {**TOY, "remat": True}
        args.train.output_dir = str(tmp_path / f"out_{policy}")
        trainer = TextTrainer(args)
        orig_step = trainer.train_step
        seen = []

        def wrapped(state, batch, _s=seen, _o=orig_step):
            out = _o(state, batch)
            _s.append(float(out[1]["loss"]))
            return out

        trainer.train_step = wrapped
        trainer.train()
        losses[policy] = seen
    assert len(losses["ctx"]) == 4
    np.testing.assert_allclose(losses["ctx"], losses["nothing"], rtol=1e-6)
