"""Qwen3-VL parity vs HF transformers (tiny config, random weights).

Same oracle pattern as test_qwen2_5_vl.py: build a tiny
``Qwen3VLForConditionalGeneration``, save HF-format safetensors, import into
our model, and assert identical vision features (main + deepstack taps) and
loss on inputs with text + two differently-sized images — exercising the
learnable pos-embed bilinear interpolation, interleaved mrope, per-frame
attention segmentation, and the deepstack residual injection into the first
K decoder layers.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

IMG_ID, VID_ID, VSTART_ID = 9, 10, 8


def _tiny_hf_model(tmp_path):
    import torch
    from transformers.models.qwen3_vl import (
        Qwen3VLConfig, Qwen3VLForConditionalGeneration,
    )

    cfg = Qwen3VLConfig(
        text_config=dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=4,
            num_attention_heads=4,
            num_key_value_heads=2,
            head_dim=16,
            max_position_embeddings=512,
            rope_theta=10000.0,
            rope_scaling={"rope_type": "default", "mrope_section": [2, 3, 3],
                          "mrope_interleaved": True},
            tie_word_embeddings=False,
        ),
        vision_config=dict(
            depth=4,
            hidden_size=32,
            intermediate_size=64,
            num_heads=2,
            in_channels=3,
            patch_size=2,
            temporal_patch_size=2,
            spatial_merge_size=2,
            out_hidden_size=64,
            num_position_embeddings=16,  # 4x4 grid -> real interpolation
            deepstack_visual_indexes=[0, 2],
        ),
        image_token_id=IMG_ID,
        video_token_id=VID_ID,
        vision_start_token_id=VSTART_ID,
    )
    torch.manual_seed(0)
    model = Qwen3VLForConditionalGeneration(cfg).eval()
    out = tmp_path / "hf_ckpt"
    model.save_pretrained(out, safe_serialization=True)
    return model, cfg, str(out)


def _vision_inputs(rng, grids, patch_dim):
    n = sum(t * h * w for t, h, w in grids)
    pixel_values = rng.standard_normal((n, patch_dim)).astype(np.float32)
    return pixel_values, np.asarray(grids, np.int64)


@pytest.fixture(scope="module")
def hf_and_ours(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("q3vl")
    hf_model, hf_cfg, ckpt = _tiny_hf_model(tmp_path)

    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(ckpt, dtype="float32")
    params = model.load_hf(ckpt)
    return hf_model, hf_cfg, model, params


GRIDS = [(1, 4, 6), (2, 6, 4)]  # image + 2-frame video (per-frame segments)


def _metadata_and_px(cfg, pixel_values, pad=8):
    from veomni_tpu.models.qwen3_vl import vision_metadata

    meta = vision_metadata(GRIDS, cfg.vision,
                           n_pad_patches=pixel_values.shape[0] + pad)
    px = np.zeros((pixel_values.shape[0] + pad, pixel_values.shape[1]),
                  np.float32)
    px[: pixel_values.shape[0]] = pixel_values
    return meta, px


def test_vision_tower_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    rng = np.random.default_rng(0)
    pixel_values, grid_thw = _vision_inputs(rng, GRIDS, cfg.vision.patch_dim)

    with torch.no_grad():
        ref, ref_deepstack = hf_model.model.visual(
            torch.from_numpy(pixel_values), torch.from_numpy(grid_thw)
        )

    from veomni_tpu.models.qwen3_vl import vision_forward

    meta, px = _metadata_and_px(cfg, pixel_values)
    got, got_deep = jax.jit(lambda tower, *a: vision_forward(tower, cfg.vision, *a, dtype=jnp.float32))(
        params["vision_tower"], jnp.asarray(px),
        jnp.asarray(meta["pos_hw"]), jnp.asarray(meta["pos_interp_idx"]),
        jnp.asarray(meta["pos_interp_w"]), jnp.asarray(meta["seg_full"]),
    )
    mask = np.asarray(meta["merged_mask"])
    np.testing.assert_allclose(
        np.asarray(got)[mask], ref.numpy(), rtol=2e-4, atol=2e-4
    )
    assert got_deep.shape[0] == len(ref_deepstack)
    for k, rd in enumerate(ref_deepstack):
        np.testing.assert_allclose(
            np.asarray(got_deep[k])[mask], rd.numpy(), rtol=2e-4, atol=2e-4
        )


def test_mrope_position_ids_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    rng = np.random.default_rng(1)

    from veomni_tpu.models.qwen3_vl import (
        mrope_position_ids, split_video_grids,
    )

    image_grid = [GRIDS[0]]
    video_grid = [GRIDS[1]]
    split = split_video_grids(video_grid)
    n_img = [t * (h // 2) * (w // 2) for t, h, w in image_grid]
    n_vid = [t * (h // 2) * (w // 2) for t, h, w in split]

    ids = [VSTART_ID] + [IMG_ID] * n_img[0] + list(rng.integers(11, 256, 5))
    for nm in n_vid:  # timestamp-text then frame, per HF chat format
        ids += list(rng.integers(11, 256, 2)) + [VSTART_ID] + [VID_ID] * nm
    ids += list(rng.integers(11, 256, 7))
    input_ids = np.asarray([ids], np.int64)

    ref_pos, _ = hf_model.model.get_rope_index(
        torch.from_numpy(input_ids),
        image_grid_thw=torch.as_tensor(image_grid),
        video_grid_thw=torch.as_tensor(video_grid),
    )
    got = mrope_position_ids(input_ids, image_grid + split, cfg)  # [B,3,S]
    np.testing.assert_array_equal(got[0], ref_pos[:, 0].numpy())


def test_full_loss_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in GRIDS]
    rng = np.random.default_rng(2)
    pixel_values, grid_thw = _vision_inputs(rng, GRIDS, cfg.vision.patch_dim)

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

    from veomni_tpu.models.qwen3_vl import mrope_position_ids

    meta, px = _metadata_and_px(cfg, pixel_values, pad=0)
    pos = mrope_position_ids(input_ids, GRIDS, cfg)
    shifted = np.full_like(labels, -100)
    shifted[:, :-1] = labels[:, 1:]
    batch = {
        "input_ids": jnp.asarray(input_ids, jnp.int32),
        "labels": jnp.asarray(shifted, jnp.int32),
        "position_ids": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.ones_like(jnp.asarray(input_ids, jnp.int32)),
        "pixel_values": jnp.asarray(px),
        "vis_pos_hw": jnp.asarray(meta["pos_hw"]),
        "vis_pos_interp_idx": jnp.asarray(meta["pos_interp_idx"]),
        "vis_pos_interp_w": jnp.asarray(meta["pos_interp_w"]),
        "vis_seg_full": jnp.asarray(meta["seg_full"]),
        "vis_merged_mask": jnp.asarray(meta["merged_mask"]),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=2e-4)


def test_hf_export_roundtrip(hf_and_ours, tmp_path):
    """Our params -> HF safetensors -> reload into a fresh HF model: the
    exported checkpoint must produce the identical loss."""
    import torch
    from transformers.models.qwen3_vl import Qwen3VLForConditionalGeneration

    hf_model, hf_cfg, model, params = hf_and_ours
    out = tmp_path / "export"
    model.family.save_hf_checkpoint(params, model.config, str(out))

    reloaded = Qwen3VLForConditionalGeneration.from_pretrained(
        str(out), config=hf_cfg, torch_dtype=torch.float32
    ).eval()
    with torch.no_grad():
        for (n1, p1), (n2, p2) in zip(
            sorted(hf_model.named_parameters()),
            sorted(reloaded.named_parameters()),
        ):
            assert n1 == n2
            np.testing.assert_allclose(
                p1.numpy(), p2.numpy(), rtol=1e-6, atol=1e-6,
            )


def test_qwen3_vl_moe_loss_parity(tmp_path):
    """MoE variant: fused-chunked expert import + loss parity vs HF."""
    import torch
    from transformers.models.qwen3_vl_moe import (
        Qwen3VLMoeConfig, Qwen3VLMoeForConditionalGeneration,
    )

    cfg_hf = Qwen3VLMoeConfig(
        text_config=dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            moe_intermediate_size=32,
            num_experts=4,
            num_experts_per_tok=2,
            norm_topk_prob=True,
            router_aux_loss_coef=0.0,
            output_router_logits=False,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            head_dim=16,
            max_position_embeddings=512,
            rope_theta=10000.0,
            rope_scaling={"rope_type": "default", "mrope_section": [2, 3, 3],
                          "mrope_interleaved": True},
            tie_word_embeddings=False,
        ),
        vision_config=dict(
            depth=2,
            hidden_size=32,
            intermediate_size=64,
            num_heads=2,
            in_channels=3,
            patch_size=2,
            temporal_patch_size=2,
            spatial_merge_size=2,
            out_hidden_size=64,
            num_position_embeddings=16,
            deepstack_visual_indexes=[0],
        ),
        image_token_id=IMG_ID,
        video_token_id=VID_ID,
        vision_start_token_id=VSTART_ID,
    )
    torch.manual_seed(0)
    hf_model = Qwen3VLMoeForConditionalGeneration(cfg_hf).eval()
    ckpt = tmp_path / "hf_moe"
    hf_model.save_pretrained(ckpt, safe_serialization=True)

    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(str(ckpt), dtype="float32")
    assert model.config.model_type == "qwen3_vl_moe"
    assert model.config.text.num_experts == 4
    params = model.load_hf(str(ckpt))

    grids = [(1, 4, 4)]
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in grids]
    rng = np.random.default_rng(3)
    cfg = model.config
    pixel_values, grid_thw = _vision_inputs(rng, grids, cfg.vision.patch_dim)
    ids = [VSTART_ID] + [IMG_ID] * n_merged[0] + list(rng.integers(11, 256, 9))
    input_ids = np.asarray([ids], np.int64)
    labels = input_ids.copy()

    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.from_numpy(input_ids),
            labels=torch.from_numpy(labels),
            pixel_values=torch.from_numpy(pixel_values),
            image_grid_thw=torch.from_numpy(grid_thw),
        )
    ref_loss = float(ref.loss)

    from veomni_tpu.models.qwen3_vl import mrope_position_ids, vision_metadata

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
        "vis_pos_interp_idx": jnp.asarray(meta["pos_interp_idx"]),
        "vis_pos_interp_w": jnp.asarray(meta["pos_interp_w"]),
        "vis_seg_full": jnp.asarray(meta["seg_full"]),
        "vis_merged_mask": jnp.asarray(meta["merged_mask"]),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=3e-4)

    # export round-trip: fused-chunked gate_up reassembled correctly
    out = tmp_path / "export_moe"
    model.family.save_hf_checkpoint(params, cfg, str(out))
    reloaded = Qwen3VLMoeForConditionalGeneration.from_pretrained(
        str(out), config=cfg_hf, torch_dtype=torch.float32
    ).eval()
    with torch.no_grad():
        for (n1, p1), (n2, p2) in zip(
            sorted(hf_model.named_parameters()),
            sorted(reloaded.named_parameters()),
        ):
            assert n1 == n2
            np.testing.assert_allclose(p1.numpy(), p2.numpy(), rtol=1e-6, atol=1e-6)


def test_qwen3_vl_trainer_e2e(tmp_path):
    """Full trainer drive through the qwen3_vl data path: images ->
    merge-block patches + interp plan -> interleaved mrope -> deepstack
    train steps (loss finite, checkpoint written, HF export reimports)."""
    import json

    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import VLMTrainer

    rng = np.random.default_rng(0)
    rows = []
    for i in range(24):
        rows.append({
            "input_ids": rng.integers(11, 256, int(rng.integers(8, 24))).tolist(),
            # 8x8 or 12x8 pixels -> 4x4 / 6x4 patch grids (patch 2, merge 2)
            "images": [rng.random((8 + 4 * (i % 2), 8, 3)).tolist()],
        })
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "qwen3_vl",
        "vocab_size": 256,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 16,
        "rope_scaling": {"rope_type": "default", "mrope_section": [2, 3, 3]},
        "vision": {
            "depth": 2, "hidden_size": 32, "intermediate_size": 64,
            "num_heads": 2, "patch_size": 2, "spatial_merge_size": 2,
            "out_hidden_size": 64, "num_position_embeddings": 16,
            "deepstack_visual_indexes": [0],
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
