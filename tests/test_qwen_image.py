"""Qwen-Image MMDiT: structural self-tests (no diffusers oracle available;
same approach as test_wan.py — architecture contract, checkpoint
round-trip through the diffusers key layout, DiTTrainer drive)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import qwen_image
from veomni_tpu.models.qwen_image import QwenImageConfig, hf_to_params, params_to_hf, rope_plan
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, qwen_image_forward = (
    under_jit(f) for f in (qwen_image.init_params, qwen_image.qwen_image_forward))

TINY = dict(
    patch_size=2,
    in_channels=16,    # latent C=4, p=2
    out_channels=4,
    num_layers=2,
    attention_head_dim=24,  # rope axes (8, 8, 8)
    num_attention_heads=2,
    joint_attention_dim=32,
    axes_dims_rope=(8, 8, 8),
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model():
    cfg = QwenImageConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_forward_shape_and_conditioning(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    lat = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)  # 4x4 grid
    t = jnp.asarray([100.0, 700.0], jnp.float32)
    text = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], jnp.int32)
    out = qwen_image_forward(params, cfg, lat, t, text, mask)
    assert out.shape == (2, 16, cfg.proj_dim)
    # masked text tokens must not influence the prediction
    text2 = text.at[0, 3:].set(123.0)
    out2 = qwen_image_forward(params, cfg, lat, t, text2, mask)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out2[0]),
                               rtol=1e-5, atol=1e-6)
    # unmasked text changes it (joint attention live)
    text3 = text.at[0, 0].set(7.0)
    out3 = qwen_image_forward(params, cfg, lat, t, text3, mask)
    assert np.abs(np.asarray(out[0]) - np.asarray(out3[0])).max() > 1e-6
    # timestep conditioning live (dual-stream modulation)
    out4 = qwen_image_forward(params, cfg, lat, t * 0.1, text, mask)
    assert np.abs(np.asarray(out) - np.asarray(out4)).max() > 1e-6


def test_rope_joint_layout():
    """QwenEmbedRope scale_rope layout: centered image rows/cols, text
    range starting at max(h//2, w//2)."""
    cfg = QwenImageConfig(**TINY)
    cos, sin = rope_plan(cfg, (1, 4, 4), txt_len=3)
    assert cos.shape == (1, 19, 24)
    c = np.asarray(cos)[0]
    s = np.asarray(sin)[0]
    inv = 1.0 / (10000.0 ** (np.arange(0, 8, 2) / 8))
    # image grid rows span [-2, 2): token (0, row=-2, col=-2) is the first
    img0 = 3  # after the 3 text tokens
    np.testing.assert_allclose(
        s[img0, 8:16], np.sin(np.repeat(-2 * inv, 2)), rtol=1e-6, atol=1e-7
    )
    # the (row=0, col=0) token sits at grid index (2, 2)
    np.testing.assert_allclose(c[img0 + 2 * 4 + 2, 8:], 1.0)
    # text tokens start at max(h//2, w//2) = 2 on every axis
    np.testing.assert_allclose(
        c[0, :8], np.cos(np.repeat(2 * inv, 2)), rtol=1e-6
    )


def test_loss_and_grads_finite(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    batch = {
        "latents": jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32),
        "timestep": jnp.asarray([10.0, 500.0], jnp.float32),
        "text_states": jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32),
        "target": jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32),
    }

    loss, grads = jax.jit(jax.value_and_grad(lambda p: qwen_image.loss_fn(p, cfg, batch)[0]))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in flat)


def test_checkpoint_roundtrip(model, tmp_path):
    from safetensors.flax import save_file

    cfg, params = model
    tensors = params_to_hf(params, cfg)
    save_file({k: jnp.asarray(v) for k, v in tensors.items()},
              str(tmp_path / "model.safetensors"))
    reloaded = hf_to_params(str(tmp_path), cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, reloaded,
    )


def test_qwen_image_trainer_e2e(tmp_path):
    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer.dit_trainer import DiTTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for _ in range(16):
            f.write(json.dumps({
                "latents": rng.standard_normal((16, 16)).tolist(),
                "text_states": rng.standard_normal((5, 32)).tolist(),
            }) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "qwen_image",
        **{k: v for k, v in TINY.items() if k != "dtype"},
        "latent_shape": (16, 16), "text_len": 5,
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
        from veomni_tpu.models import build_foundation_model

        m2 = build_foundation_model(hf_dir, dtype="float32")
        m2.load_hf(hf_dir)
    finally:
        destroy_parallel_state()
