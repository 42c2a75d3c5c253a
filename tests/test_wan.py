"""Wan T2V DiT: structural self-tests.

No diffusers oracle is available in this environment (the reference wraps
``diffusers.WanTransformer3DModel``), so these tests pin the architecture's
own contract: shape/adaLN/rope behavior, checkpoint round-trip through the
diffusers-format key layout, and a full DiTTrainer drive.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import wan
from veomni_tpu.models.wan import WanConfig, hf_to_params, params_to_hf, rope_3d
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, wan_forward = under_jit(wan.init_params), under_jit(wan.wan_forward)

TINY = dict(
    patch_size=(1, 2, 2),
    num_attention_heads=2,
    attention_head_dim=24,  # t/h/w rope split 8/8/8
    in_channels=4,
    out_channels=4,
    text_dim=32,
    freq_dim=32,
    ffn_dim=96,
    num_layers=2,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model():
    cfg = WanConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_forward_shape_and_determinism(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    lat = jnp.asarray(rng.standard_normal((2, 4, 2, 8, 8)), jnp.float32)
    t = jnp.asarray([100.0, 700.0], jnp.float32)
    text = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    out = wan_forward(params, cfg, lat, t, text)
    assert out.shape == lat.shape
    out2 = wan_forward(params, cfg, lat, t, text)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # timestep conditioning changes the output (adaLN path live)
    out3 = wan_forward(params, cfg, lat, t * 0.1, text)
    assert np.abs(np.asarray(out) - np.asarray(out3)).max() > 1e-6
    # text conditioning changes the output (cross-attention live)
    out4 = wan_forward(params, cfg, lat, t, text * -1.0)
    assert np.abs(np.asarray(out) - np.asarray(out4)).max() > 1e-6


def test_rope_split():
    cfg = WanConfig(**TINY)
    cos, sin = rope_3d(cfg, 2, 4, 4)
    assert cos.shape == (1, 32, 24)
    # temporal-axis angles identical across (h, w) within a frame
    c = np.asarray(cos).reshape(2, 4, 4, 24)
    np.testing.assert_array_equal(
        c[1, :, :, :8], np.broadcast_to(c[1, 0, 0, :8], (4, 4, 8))
    )
    # height-axis angles identical across w
    np.testing.assert_array_equal(
        c[0, 1, :, 8:16], np.broadcast_to(c[0, 1, 0, 8:16], (4, 8))
    )
    # width-axis angles identical across h
    np.testing.assert_array_equal(
        c[0, :, 1, 16:24], np.broadcast_to(c[0, 0, 1, 16:24], (4, 8))
    )


def test_loss_and_grads_finite(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    batch = {
        "latents": jnp.asarray(rng.standard_normal((2, 4, 2, 8, 8)), jnp.float32),
        "timestep": jnp.asarray([10.0, 500.0], jnp.float32),
        "text_states": jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32),
        "target": jnp.asarray(rng.standard_normal((2, 4, 2, 8, 8)), jnp.float32),
    }

    loss, grads = jax.jit(jax.value_and_grad(lambda p: wan.loss_fn(p, cfg, batch)[0]))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # every parameter receives gradient (cross-attn, adaLN tables, rope paths)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in flat)


def test_checkpoint_roundtrip(model, tmp_path):
    from safetensors.flax import save_file

    cfg, params = model
    tensors = params_to_hf(params, cfg)
    save_file({k: jnp.asarray(v) for k, v in tensors.items()},
              str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"_class_name": "WanTransformer3DModel"}, f)
    reloaded = hf_to_params(str(tmp_path), cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, reloaded,
    )


def test_wan_trainer_e2e(tmp_path):
    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer.dit_trainer import DiTTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for _ in range(16):
            f.write(json.dumps({
                "latents": rng.standard_normal((4, 2, 8, 8)).tolist(),
                "text_states": rng.standard_normal((5, 32)).tolist(),
            }) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "wan_t2v", **{k: v for k, v in TINY.items() if k != "dtype"},
        "latent_shape": (4, 2, 8, 8), "text_len": 5,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 2
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = True
    args.train.log_steps = 100
    destroy_parallel_state()
    try:
        trainer = DiTTrainer(args)
        ctl = trainer.train()
        assert ctl.global_step == 3
        assert np.isfinite(ctl.metrics["loss"])
        trainer.checkpointer.close()
        import os

        hf_dir = os.path.join(args.train.output_dir, "hf_ckpt")
        assert os.path.exists(
            os.path.join(hf_dir, "diffusion_pytorch_model.safetensors")
        )
        # diffusers-format reload
        from veomni_tpu.models import build_foundation_model

        m2 = build_foundation_model(hf_dir, dtype="float32")
        m2.load_hf(hf_dir)
    finally:
        destroy_parallel_state()
