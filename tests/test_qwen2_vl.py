"""Qwen2-VL parity vs HF transformers (tiny config, random weights).

Same oracle strategy as test_qwen2_5_vl.py: build a tiny
``Qwen2VLForConditionalGeneration``, save HF safetensors, import into our
model, assert identical vision features / mrope walk / loss on text + two
differently-sized images (full per-frame attention, LayerNorm blocks,
quick-GELU MLP, merger)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

IMG_ID, VID_ID, VSTART_ID = 9, 10, 8


def _tiny_hf_model(tmp_path):
    import torch
    from transformers import Qwen2VLConfig, Qwen2VLForConditionalGeneration

    cfg = Qwen2VLConfig(
        text_config=dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=512,
            rope_theta=10000.0,
            rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
            tie_word_embeddings=False,
        ),
        vision_config=dict(
            depth=3,
            embed_dim=32,
            hidden_size=64,   # LM width (merger out)
            mlp_ratio=2,
            num_heads=2,
            in_channels=3,
            patch_size=2,
            temporal_patch_size=2,
            spatial_merge_size=2,
        ),
        image_token_id=IMG_ID,
        video_token_id=VID_ID,
        vision_start_token_id=VSTART_ID,
    )
    torch.manual_seed(0)
    model = Qwen2VLForConditionalGeneration(cfg).eval()
    out = tmp_path / "hf_ckpt"
    model.save_pretrained(out, safe_serialization=True)
    return model, cfg, str(out)


def _vision_inputs(rng, grids, patch_dim):
    n = sum(t * h * w for t, h, w in grids)
    pixel_values = rng.standard_normal((n, patch_dim)).astype(np.float32)
    return pixel_values, np.asarray(grids, np.int64)


@pytest.fixture(scope="module")
def hf_and_ours(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("q2vl")
    hf_model, hf_cfg, ckpt = _tiny_hf_model(tmp_path)

    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(ckpt, dtype="float32")
    assert model.config.model_type == "qwen2_vl"
    params = model.load_hf(ckpt)
    return hf_model, hf_cfg, model, params


def test_vision_tower_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    # multi-frame grid exercises the per-frame attention segments
    grids = [(1, 4, 6), (2, 4, 4)]
    rng = np.random.default_rng(0)
    pixel_values, grid_thw = _vision_inputs(rng, grids, cfg.vision.patch_dim)

    with torch.no_grad():
        ref = hf_model.model.visual(
            torch.from_numpy(pixel_values), torch.from_numpy(grid_thw)
        ).numpy()

    from veomni_tpu.models.qwen2_vl import vision_forward, vision_metadata

    meta = vision_metadata(grids, cfg.vision, n_pad_patches=pixel_values.shape[0] + 8)
    px = np.zeros((pixel_values.shape[0] + 8, pixel_values.shape[1]), np.float32)
    px[: pixel_values.shape[0]] = pixel_values
    got = jax.jit(lambda tower, *a: vision_forward(tower, cfg.vision, *a, dtype=jnp.float32))(
        params["vision_tower"],
        jnp.asarray(px), jnp.asarray(meta["pos_hw"]), jnp.asarray(meta["seg"]),
    )
    got = np.asarray(got)[np.asarray(meta["merged_mask"])]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_mrope_position_ids_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids = [(1, 4, 6), (2, 4, 4)]
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in grids]
    rng = np.random.default_rng(1)

    ids = []
    for nm in n_merged:
        ids += [VSTART_ID] + [IMG_ID] * nm
    ids += list(rng.integers(11, 256, 7))
    input_ids = np.asarray([ids], np.int64)

    ref_pos, _ = hf_model.model.get_rope_index(
        torch.from_numpy(input_ids), torch.as_tensor(grids)
    )
    from veomni_tpu.models.qwen2_vl import mrope_position_ids

    got = mrope_position_ids(input_ids, grids, cfg)  # [B,3,S]
    np.testing.assert_array_equal(got[0], ref_pos[:, 0].numpy())


def test_full_loss_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids = [(1, 4, 6), (2, 4, 4)]
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in grids]
    rng = np.random.default_rng(2)
    pixel_values, grid_thw = _vision_inputs(rng, grids, cfg.vision.patch_dim)

    ids = [VSTART_ID] + [IMG_ID] * n_merged[0] + list(rng.integers(11, 256, 5))
    ids += [VSTART_ID] + [IMG_ID] * n_merged[1] + list(rng.integers(11, 256, 6))
    input_ids = np.asarray([ids], np.int64)
    labels = input_ids.copy()
    labels[:, : n_merged[0] + 1] = -100  # mask the first image span

    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.from_numpy(input_ids),
            labels=torch.from_numpy(labels),
            pixel_values=torch.from_numpy(pixel_values),
            image_grid_thw=torch.from_numpy(grid_thw),
        )
    ref_loss = float(ref.loss)

    from veomni_tpu.models.qwen2_vl import mrope_position_ids, vision_metadata

    meta = vision_metadata(grids, cfg.vision, n_pad_patches=pixel_values.shape[0])
    pos = mrope_position_ids(input_ids, grids, cfg)
    shifted = np.full_like(labels, -100)
    shifted[:, :-1] = labels[:, 1:]
    batch = {
        "input_ids": jnp.asarray(input_ids, jnp.int32),
        "labels": jnp.asarray(shifted, jnp.int32),
        "position_ids": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.ones_like(jnp.asarray(input_ids, jnp.int32)),
        "pixel_values": jnp.asarray(pixel_values),
        "vis_pos_hw": jnp.asarray(meta["pos_hw"]),
        "vis_seg": jnp.asarray(meta["seg"]),
        "vis_merged_mask": jnp.asarray(meta["merged_mask"]),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=2e-4)


def test_hf_export_roundtrip(hf_and_ours, tmp_path):
    hf_model, hf_cfg, model, params = hf_and_ours
    out = tmp_path / "exported"
    model.family.save_hf_checkpoint(params, model.config, str(out))

    from veomni_tpu.models import build_foundation_model

    m2 = build_foundation_model(str(out), dtype="float32")
    p2 = m2.load_hf(str(out))
    flat_a = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(params)}
    flat_b = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(p2)}
    assert flat_a.keys() == flat_b.keys()
    for k in flat_a:
        np.testing.assert_array_equal(
            np.asarray(flat_a[k]), np.asarray(flat_b[k]), err_msg=k
        )


def test_qwen2_vl_trainer_e2e(tmp_path):
    """Trainer drive: images -> patches/metadata -> mrope -> train steps."""
    import json

    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import VLMTrainer

    rng = np.random.default_rng(0)
    rows = []
    for i in range(24):
        rows.append({
            "input_ids": rng.integers(11, 256, int(rng.integers(8, 24))).tolist(),
            "images": [rng.random((8 + 4 * (i % 2), 8, 3)).tolist()],
        })
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "qwen2_vl",
        "vocab_size": 256,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "rope_scaling": {"type": "mrope", "mrope_section": [2, 3, 3]},
        "vision": {
            "depth": 2, "embed_dim": 32, "hidden_size": 64, "mlp_ratio": 2,
            "num_heads": 2, "patch_size": 2, "spatial_merge_size": 2,
        },
        "image_token_id": 9, "video_token_id": 10,
        "vision_start_token_id": 8,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.data.max_patches = 256
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 2
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = True
    args.train.log_steps = 100
    destroy_parallel_state()
    try:
        trainer = VLMTrainer(args)
        ctl = trainer.train()
        assert ctl.global_step == 3
        assert np.isfinite(ctl.metrics["loss"])
        trainer.checkpointer.close()
        import os

        hf_dir = os.path.join(args.train.output_dir, "hf_ckpt")
        assert os.path.exists(os.path.join(hf_dir, "model.safetensors"))
        from veomni_tpu.models import build_foundation_model

        m2 = build_foundation_model(hf_dir, dtype="float32")
        m2.load_hf(hf_dir)
    finally:
        destroy_parallel_state()
