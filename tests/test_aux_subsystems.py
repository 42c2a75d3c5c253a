"""Aux subsystems: channel loss accounting, MoE router monitor, determinism
shim, remat policies."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.arguments import VeOmniArguments

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

TOY_MOE = {
    **TOY,
    "model_type": "qwen3_moe",
    "num_experts": 4,
    "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
}


def test_channel_loss_e2e(tmp_path):
    from veomni_tpu.trainer import TextTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for i in range(128):
            f.write(json.dumps({
                "input_ids": rng.integers(0, 256, int(rng.integers(16, 60))).tolist(),
                "channel": "web" if i % 2 else "code",
            }) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = dict(TOY)
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 128
    args.data.channel_list = ["code", "web"]
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = False
    args.train.log_steps = 100
    trainer = TextTrainer(args)
    cb = [c for c in trainer.callbacks if type(c).__name__ == "ChannelLossCallback"][0]
    trainer.train()
    assert sum(cb._counts) > 0, "no channel tokens accounted"
    assert all(c > 0 for c in cb._counts), f"channel counts {cb._counts}"
    trainer.checkpointer.close()


def test_moe_router_capture():
    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.utils.moe_monitor import capture_router_stats

    cfg = TransformerConfig(
        **{**TOY, "model_type": "qwen3_moe"},
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        dtype=jnp.float32,
    )
    model = build_foundation_model(config=cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {
        "input_ids": jnp.ones((1, 32), jnp.int32),
        "position_ids": jnp.broadcast_to(jnp.arange(32), (1, 32)),
        "segment_ids": jnp.ones((1, 32), jnp.int32),
    }
    stats = capture_router_stats(model, params, batch)
    assert stats["expert_load"].shape == (2, 4)  # 2 moe layers, 4 experts
    np.testing.assert_allclose(stats["expert_load"].sum(1), 1.0, rtol=1e-6)


def test_remat_policies_run():
    from veomni_tpu.models import TransformerConfig, build_foundation_model

    for policy in ("nothing", "dots"):
        cfg = TransformerConfig(**TOY, dtype=jnp.float32, remat_policy=policy)
        model = build_foundation_model(config=cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {
            "input_ids": jnp.ones((1, 16), jnp.int32),
            "labels": jnp.ones((1, 16), jnp.int32),
            "position_ids": jnp.broadcast_to(jnp.arange(16), (1, 16)),
            "segment_ids": jnp.ones((1, 16), jnp.int32),
        }
        g = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
        assert np.isfinite(float(jax.tree.leaves(g)[0].sum()))


def test_batch_invariant_shim():
    from veomni_tpu.utils.determinism import set_batch_invariant_mode

    with set_batch_invariant_mode(True):
        pass


def test_checkpointer_skips_uncommitted_step(tmp_path):
    """A crash mid-async-save leaves only the orbax tmp payload; resume must
    fall back to the last committed step (ADVICE r1: checkpointer.py:87)."""
    import os

    from veomni_tpu.checkpoint import build_checkpointer

    ckptr = build_checkpointer(str(tmp_path), async_save=False)
    state = {"w": jnp.arange(4, dtype=jnp.float32)}
    ckptr.save(2, state, {"global_step": 2})
    ckptr.wait()
    # fake a crashed save of step 4: tmp dir + eager extra_state, no payload
    crashed = tmp_path / "global_step_4"
    os.makedirs(crashed / "train_state.orbax-checkpoint-tmp-123")
    (crashed / "extra_state.json").write_text('{"global_step": 4}')
    assert ckptr.list_steps() == [2]
    assert ckptr.latest_step() == 2
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    restored, extra = ckptr.load(abstract)
    assert extra["global_step"] == 2
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4))

    # re-saving step 4 after the crash must commit and become visible even
    # though stale tmp debris existed (review r2: stale sibling must not
    # permanently mask a later successful save)
    ckptr.save(4, state, {"global_step": 4})
    ckptr.wait()
    assert ckptr.latest_step() == 4
    restored, extra = ckptr.load(abstract)
    assert extra["global_step"] == 4
    ckptr.close()


def test_hf_config_roundtrip_moe_keys():
    """to_hf_config must emit the expert-count key + activation spelling HF
    transformers expects for each MoE dialect (ADVICE r1: config.py:202)."""
    from veomni_tpu.models.config import TransformerConfig

    for mt, hf_key in [
        ("qwen3_moe", "num_experts"),
        ("deepseek_v3", "n_routed_experts"),
        ("gpt_oss", "num_local_experts"),
    ]:
        cfg = TransformerConfig(
            model_type=mt, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32,
            hidden_act="gpt_oss_glu" if mt == "gpt_oss" else "silu",
        )
        hf = cfg.to_hf_config()
        assert hf.get(hf_key) == 8, (mt, hf)
        assert hf["hidden_act"] in ("silu",), (mt, hf["hidden_act"])
        back = TransformerConfig.from_hf_config(hf)
        assert back.num_experts == 8, mt
        if mt == "gpt_oss":
            assert back.hidden_act == "gpt_oss_glu"


def test_ep_capacity_drop_metric():
    """Capacity-mode EP surfaces a nonzero dropped-assignment fraction while
    dropless reports exactly zero (ADVICE r1: moe.py:65)."""
    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    from veomni_tpu.models import TransformerConfig

    destroy_parallel_state()
    try:
        ps = init_parallel_state(ep_size=2)  # 4 devices: ep2 x fsdp2
        batch = {
            "input_ids": jnp.ones((4, 32), jnp.int32),
            "labels": jnp.ones((4, 32), jnp.int32),
            "position_ids": jnp.broadcast_to(jnp.arange(32), (4, 32)),
            "segment_ids": jnp.ones((4, 32), jnp.int32),
        }
        # identical tokens route identically -> tight capacity guarantees drops
        cfg = TransformerConfig(
            **dict(TOY_MOE, dtype=jnp.float32, moe_capacity_factor=0.25)
        )
        model = build_foundation_model(config=cfg)
        params = model.init(jax.random.PRNGKey(0))
        with use_parallel_state(ps):
            _, metrics = model.loss_fn(params, batch)
        assert float(metrics["moe_dropped_frac"]) > 0.0

        cfg2 = TransformerConfig(**dict(TOY_MOE, dtype=jnp.float32))
        model2 = build_foundation_model(config=cfg2)
        params2 = model2.init(jax.random.PRNGKey(0))
        with use_parallel_state(ps):
            _, metrics2 = model2.loss_fn(params2, batch)
        assert float(metrics2["moe_dropped_frac"]) == 0.0
    finally:
        destroy_parallel_state()


def test_trim_safetensor_layers(tmp_path):
    """scripts/trim_safetensor_layers.py: layer filter + index + config patch."""
    import json
    import subprocess
    import sys

    import numpy as np
    from safetensors.numpy import save_file

    src = tmp_path / "full"
    src.mkdir()
    tensors = {"model.embed_tokens.weight": np.ones((8, 4), np.float32)}
    for i in range(4):
        tensors[f"model.layers.{i}.mlp.w"] = np.full((2, 2), float(i), np.float32)
    save_file(tensors, str(src / "model.safetensors"))
    with open(src / "config.json", "w") as f:
        json.dump({"num_hidden_layers": 4, "text_config": {"num_hidden_layers": 4}}, f)

    out = tmp_path / "trim"
    r = subprocess.run(
        [sys.executable, "scripts/trim_safetensor_layers.py",
         "--model_dir", str(src), "--out_dir", str(out), "--num_layers", "2"],
        capture_output=True, text=True, cwd="/root/repo", timeout=300,
    )
    assert r.returncode == 0, r.stderr
    from safetensors import safe_open

    with open(out / "model.safetensors.index.json") as f:
        wm = json.load(f)["weight_map"]
    assert "model.layers.1.mlp.w" in wm and "model.layers.2.mlp.w" not in wm
    with safe_open(str(out / next(iter(set(wm.values())))), framework="np") as f:
        assert set(f.keys()) == set(wm)
    with open(out / "config.json") as f:
        cfg = json.load(f)
    assert cfg["num_hidden_layers"] == 2
    assert cfg["text_config"]["num_hidden_layers"] == 2


def test_merge_chrome_trace(tmp_path):
    import json
    import subprocess
    import sys

    for i in range(2):
        with open(tmp_path / f"t{i}.json", "w") as f:
            json.dump({"traceEvents": [
                {"pid": 1, "tid": 1, "name": "process_name", "ph": "M",
                 "args": {"name": "dev"}},
                {"pid": 1, "tid": 1, "name": "op", "ph": "X", "ts": i, "dur": 1},
            ]}, f)
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, "scripts/merge_chrome_trace.py", str(out),
         str(tmp_path / "t0.json"), str(tmp_path / "t1.json")],
        capture_output=True, text=True, cwd="/root/repo", timeout=300,
    )
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        ev = json.load(f)["traceEvents"]
    assert len(ev) == 4
    assert {e["pid"] for e in ev} == {1, 3}  # hosts offset apart


def test_channel_loss_omni_family():
    """Per-channel CE hooks the omni thinkers' merged-hidden preamble (was
    a NotImplementedError scope guard through r4): channel sums must add up
    to the total loss on a text-only batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.train.channel_loss import (
        make_channel_loss_fn,
        supports_channel_loss,
    )

    cfg = build_config(
        "qwen2_5_omni",
        text=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
                  dtype="float32", param_dtype="float32"),
        vision=None, audio=None,
        image_token_id=9, video_token_id=10, vision_start_token_id=8,
        audio_token_id=11,
    )
    model = build_foundation_model(config=cfg)
    assert supports_channel_loss(model)
    model.init(jax.random.PRNGKey(0))
    loss_fn = make_channel_loss_fn(model, num_channels=2)

    rng = np.random.default_rng(0)
    b, s = 2, 16
    ids = rng.integers(12, 256, (b, s))
    pos = np.broadcast_to(np.arange(s), (3, b, s)).transpose(1, 0, 2)
    batch = {
        "input_ids": jnp.asarray(ids, jnp.int32),
        "labels": jnp.asarray(ids, jnp.int32),
        "position_ids": jnp.asarray(pos.copy(), jnp.int32),
        "segment_ids": jnp.ones((b, s), jnp.int32),
        "channel_ids": jnp.asarray(
            np.where(np.arange(s)[None] < s // 2, 0, 1), jnp.int32
        ).repeat(b, 0).reshape(b, s),
    }
    loss_sum, metrics = loss_fn(model.params, batch)
    ch = np.asarray(metrics["channel_loss_sums"])
    counts = np.asarray(metrics["channel_token_counts"])
    assert ch.shape == (2,) and np.all(ch > 0)
    assert counts.sum() == b * s
    assert float(ch.sum()) == pytest.approx(float(loss_sum), rel=1e-5)
