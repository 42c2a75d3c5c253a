"""LoRA: adapter init/merge/train/save-load (reference ``tests/lora/``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.lora import LoraConfig, init_lora_params, merge_lora_params
from veomni_tpu.lora.lora import load_adapter, save_adapter
from veomni_tpu.models import TransformerConfig, build_foundation_model


def _cfg(moe=False):
    kw = dict(
        model_type="qwen3_moe" if moe else "qwen3",
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, qk_norm=True, dtype=jnp.float32,
    )
    if moe:
        kw.update(num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32)
    return TransformerConfig(**kw)


def test_lora_init_zero_delta_and_gradients():
    model = build_foundation_model(config=_cfg())
    base = model.init(jax.random.PRNGKey(0))
    lcfg = LoraConfig(rank=4, alpha=8)
    lora = init_lora_params(jax.random.PRNGKey(1), base, lcfg)

    # B=0 init => merged == base exactly
    merged = merge_lora_params(base, lora)
    np.testing.assert_allclose(
        np.asarray(merged["layers"]["q_proj"]), np.asarray(base["layers"]["q_proj"])
    )

    batch = {
        "input_ids": jnp.ones((1, 16), jnp.int32),
        "labels": jnp.ones((1, 16), jnp.int32),
        "position_ids": jnp.broadcast_to(jnp.arange(16), (1, 16)),
        "segment_ids": jnp.ones((1, 16), jnp.int32),
    }

    def loss(lora_tree):
        return model.loss_fn(merge_lora_params(base, lora_tree), batch)[0]

    g = jax.jit(jax.grad(loss))(lora)
    ga = g["layers"]["q_proj"]["lora_a"]
    gb = g["layers"]["q_proj"]["lora_b"]
    # dB nonzero (dA is 0 at init because B=0 — standard LoRA property)
    assert float(jnp.abs(gb).sum()) > 0


def test_lora_moe_experts_adapted():
    model = build_foundation_model(config=_cfg(moe=True))
    base = model.init(jax.random.PRNGKey(0))
    lora = init_lora_params(jax.random.PRNGKey(1), base, LoraConfig(rank=2))
    exp = lora["layers"]["experts"]["gate_proj"]
    # batched adapters over [L, E, ...]
    assert exp["lora_a"].shape[:2] == base["layers"]["experts"]["gate_proj"].shape[:2]


# ---------------------------------------------------------- trainer matrix
# Reference composes LoRA with every trainer (``lora/model.py:101``,
# ``trainer/base.py:411-457``); these exercise the merged-forward wiring.

TOY_ARGS = {
    "model_type": "qwen2", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": True,
}


def _base_args(tmp_path):
    from veomni_tpu.arguments import VeOmniArguments

    args = VeOmniArguments()
    args.model.config_overrides = dict(TOY_ARGS)
    args.model.lora = {"rank": 4, "alpha": 8}
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 2
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 100
    return args


def test_dpo_lora_e2e(tmp_path):
    from veomni_tpu.trainer.dpo_trainer import TextDPOTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "dpo.jsonl", "w") as f:
        for _ in range(32):
            f.write(json.dumps({
                "prompt": rng.integers(0, 256, int(rng.integers(4, 16))).tolist(),
                "chosen": rng.integers(0, 256, int(rng.integers(4, 24))).tolist(),
                "rejected": rng.integers(0, 256, int(rng.integers(4, 24))).tolist(),
            }) + "\n")
    args = _base_args(tmp_path)
    args.data.train_path = str(tmp_path / "dpo.jsonl")
    args.data.data_type = "dpo"
    args.data.max_seq_len = 64
    trainer = TextDPOTrainer(args)
    base_before = jax.tree.map(np.asarray, trainer.base_params)
    ctl = trainer.train()
    assert ctl.global_step == 3
    assert np.isfinite(ctl.metrics["loss"])
    # adapter-off reference policy IS the frozen base (no copy)
    assert trainer.ref_params is trainer.base_params
    # trainable surface is the adapter tree only; base stays bit-frozen
    np.testing.assert_array_equal(
        np.asarray(trainer.base_params["layers"]["q_proj"]),
        base_before["layers"]["q_proj"],
    )
    # the adapter actually moved (B leaves get nonzero grads)
    assert float(
        jnp.abs(trainer.train_state.params["layers"]["q_proj"]["lora_b"]).sum()
    ) > 0
    trainer.checkpointer.close()


def test_rl_lora_e2e(tmp_path):
    from veomni_tpu.trainer.rl_trainer import BaseRLTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "rl.jsonl", "w") as f:
        for _ in range(32):
            f.write(json.dumps({
                "prompt": rng.integers(0, 256, 8).tolist(),
                "response": rng.integers(0, 256, int(rng.integers(4, 16))).tolist(),
                "advantage": float(rng.normal()),
            }) + "\n")
    args = _base_args(tmp_path)
    args.data.train_path = str(tmp_path / "rl.jsonl")
    args.data.data_type = "rl"
    args.data.max_seq_len = 32
    trainer = BaseRLTrainer(args)
    ctl = trainer.train()
    assert ctl.global_step == 3
    assert np.isfinite(ctl.metrics["loss"])
    assert "ratio_mean" in ctl.metrics
    trainer.checkpointer.close()


def test_lora_channel_list_e2e(tmp_path):
    from veomni_tpu.trainer.text_trainer import TextTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for _ in range(64):
            f.write(json.dumps({
                "input_ids": rng.integers(0, 256, int(rng.integers(16, 80))).tolist(),
                "channel": ["code", "web"][int(rng.integers(0, 2))],
            }) + "\n")
    args = _base_args(tmp_path)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.data.channel_list = ["code", "web"]
    trainer = TextTrainer(args)
    ctl = trainer.train()
    assert ctl.global_step == 3
    assert np.isfinite(ctl.metrics["loss"])
    trainer.checkpointer.close()


def test_lora_hf_export_roundtrip(tmp_path):
    """Trainer HF export under LoRA writes BOTH a merged full model and the
    adapter; reloading them reproduces merge(base, adapter) exactly."""
    from veomni_tpu.trainer.text_trainer import TextTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for _ in range(64):
            f.write(json.dumps({
                "input_ids": rng.integers(0, 256, int(rng.integers(16, 80))).tolist(),
            }) + "\n")
    args = _base_args(tmp_path)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.train.save_hf_weights = True
    trainer = TextTrainer(args)
    trainer.train()
    out = str(tmp_path / "out")

    # adapter reload matches the live adapter tree
    restored = load_adapter(
        os.path.join(out, "lora_adapter"),
        jax.eval_shape(lambda: trainer.train_state.params),
    )
    np.testing.assert_allclose(
        np.asarray(restored["layers"]["q_proj"]["lora_b"]),
        np.asarray(trainer.train_state.params["layers"]["q_proj"]["lora_b"]),
    )

    # merged HF export loads back == merge(base, adapter)
    merged_live = merge_lora_params(trainer.base_params, trainer.train_state.params)
    reloaded = build_foundation_model(config_path=os.path.join(out, "hf_ckpt"))
    hf_params = reloaded.load_hf(os.path.join(out, "hf_ckpt"))
    np.testing.assert_allclose(
        np.asarray(hf_params["layers"]["q_proj"]),
        np.asarray(merged_live["layers"]["q_proj"]),
        atol=1e-6,
    )
    trainer.checkpointer.close()


def test_lora_adapter_roundtrip(tmp_path):
    model = build_foundation_model(config=_cfg())
    base = model.init(jax.random.PRNGKey(0))
    lcfg = LoraConfig(rank=4)
    lora = init_lora_params(jax.random.PRNGKey(1), base, lcfg)
    # perturb B so the roundtrip is nontrivial
    lora["layers"]["q_proj"]["lora_b"] = jnp.ones_like(lora["layers"]["q_proj"]["lora_b"])
    save_adapter(lora, lcfg, str(tmp_path / "adapter"))
    restored = load_adapter(str(tmp_path / "adapter"), jax.eval_shape(lambda: lora))
    np.testing.assert_allclose(
        np.asarray(restored["layers"]["q_proj"]["lora_b"]),
        np.asarray(lora["layers"]["q_proj"]["lora_b"]),
    )
