"""VLM: ViT forward, feature merge, e2e VLM training on a CPU mesh.

Reference tests: vlm model-patch tests + ``tests/train_scripts/train_vlm_test.py``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest


VISION = dict(image_size=28, patch_size=7, hidden_size=32, intermediate_size=64,
              num_hidden_layers=2, num_attention_heads=2, spatial_merge_size=2)
TEXT = dict(model_type="qwen2", vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, attention_bias=True)


def _vlm_config():
    from veomni_tpu.models.auto import build_config

    return build_config("slot_vlm", text=dict(TEXT, dtype=jnp.float32),
                        vision=VISION, image_token_id=500)


def test_vit_shapes():
    from veomni_tpu.models.vision import ViTConfig, init_vit_params, vit_forward

    cfg = ViTConfig(**VISION, out_hidden_size=64)
    params = init_vit_params(jax.random.PRNGKey(0), cfg)
    patches = jnp.ones((3, cfg.grid ** 2, cfg.num_channels * cfg.patch_size ** 2))
    feats = vit_forward(params, cfg, patches)
    assert feats.shape == (3, cfg.tokens_per_image, 64)


def test_feature_merge_positions():
    from veomni_tpu.models.vlm import merge_image_features

    b, s, h, t_img = 1, 10, 4, 2
    embeds = jnp.zeros((b, s, h))
    ids = jnp.array([[1, 500, 500, 2, 500, 500, 3, 4, 5, 6]])
    feats = jnp.arange(b * 2 * t_img * h, dtype=jnp.float32).reshape(b, 2, t_img, h)
    mask = jnp.array([[True, True]])
    out = merge_image_features(embeds, ids, feats, mask, 500)
    np.testing.assert_allclose(np.asarray(out[0, 1]), np.asarray(feats[0, 0, 0]))
    np.testing.assert_allclose(np.asarray(out[0, 5]), np.asarray(feats[0, 1, 1]))
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.zeros(h))  # text untouched


def test_vlm_loss_and_grads():
    from veomni_tpu.models import build_foundation_model

    cfg = _vlm_config()
    model = build_foundation_model(config=cfg)
    params = model.init(jax.random.PRNGKey(0))
    vcfg = cfg.vision
    t_img = vcfg.tokens_per_image
    s = 32
    ids = np.full((2, s), 7, np.int32)
    ids[:, :t_img] = 500  # one image leading each row
    batch = {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(ids),
        "position_ids": jnp.broadcast_to(jnp.arange(s), (2, s)),
        "segment_ids": jnp.ones((2, s), jnp.int32),
        "pixel_patches": jnp.ones(
            (2, 1, vcfg.grid ** 2, vcfg.num_channels * vcfg.patch_size ** 2), jnp.float32
        ),
        "image_mask": jnp.ones((2, 1), bool),
    }
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    g = jax.jit(jax.grad(lambda p: model.loss_fn(p, batch)[0]))(params)
    assert float(jnp.abs(g["vision_tower"]["patch_embed"]).sum()) > 0


def test_vlm_trainer_e2e(tmp_path):
    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.trainer.vlm_trainer import VLMTrainer

    rng = np.random.default_rng(0)
    rows = []
    for _ in range(64):
        n_img = int(rng.integers(0, 3))
        rows.append({
            "input_ids": rng.integers(0, 499, int(rng.integers(10, 40))).tolist(),
            "images": [rng.random((28, 28, 3)).tolist() for _ in range(n_img)],
        })
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "slot_vlm", "text": dict(TEXT), "vision": dict(VISION),
        "image_token_id": 500,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.max_seq_len = 128
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = True
    args.train.log_steps = 100
    trainer = VLMTrainer(args)
    ctl = trainer.train()
    assert ctl.global_step == 3
    assert (tmp_path / "out" / "hf_ckpt" / "model.safetensors").exists()
    trainer.checkpointer.close()
