"""Qwen2.5-VL parity vs HF transformers (tiny config, random weights).

The reference's headline VLM capability — training real Qwen-VL checkpoints —
oracle-tested the same way as text families in test_hf_parity.py: build a tiny
``Qwen2_5_VLForConditionalGeneration``, export HF-format safetensors, import
into our model, and assert identical vision features / loss on inputs with
text + two differently-sized images (exercising window attention, mrope, and
the patch merger).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

IMG_ID, VID_ID, VSTART_ID = 9, 10, 8


def _tiny_hf_model(tmp_path):
    import torch
    from transformers import Qwen2_5_VLConfig, Qwen2_5_VLForConditionalGeneration

    cfg = Qwen2_5_VLConfig(
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
            hidden_size=32,
            intermediate_size=64,
            num_heads=2,
            in_channels=3,
            patch_size=2,
            temporal_patch_size=2,
            spatial_merge_size=2,
            window_size=8,  # 2 merged patches per window side
            fullatt_block_indexes=[1],
            out_hidden_size=64,
            tokens_per_second=2,
        ),
        image_token_id=IMG_ID,
        video_token_id=VID_ID,
        vision_start_token_id=VSTART_ID,
    )
    torch.manual_seed(0)
    model = Qwen2_5_VLForConditionalGeneration(cfg).eval()
    out = tmp_path / "hf_ckpt"
    model.save_pretrained(out, safe_serialization=True)
    return model, cfg, str(out)


def _vision_inputs(rng, grids, patch_dim):
    n = sum(t * h * w for t, h, w in grids)
    pixel_values = rng.standard_normal((n, patch_dim)).astype(np.float32)
    return pixel_values, np.asarray(grids, np.int64)


@pytest.fixture(scope="module")
def hf_and_ours(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("q25vl")
    hf_model, hf_cfg, ckpt = _tiny_hf_model(tmp_path)

    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(ckpt, dtype="float32")
    params = model.load_hf(ckpt)
    return hf_model, hf_cfg, model, params


def test_vision_tower_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids = [(1, 4, 6), (1, 8, 4)]  # uneven grids: window padding paths
    rng = np.random.default_rng(0)
    pixel_values, grid_thw = _vision_inputs(rng, grids, cfg.vision.patch_dim)

    with torch.no_grad():
        ref = hf_model.model.visual(
            torch.from_numpy(pixel_values), torch.from_numpy(grid_thw)
        ).numpy()

    from veomni_tpu.models.qwen2_5_vl import vision_forward, vision_metadata

    meta = vision_metadata(grids, cfg.vision, n_pad_patches=pixel_values.shape[0] + 8)
    px = np.zeros((pixel_values.shape[0] + 8, pixel_values.shape[1]), np.float32)
    px[: pixel_values.shape[0]] = pixel_values
    got = jax.jit(lambda tower, *a: vision_forward(tower, cfg.vision, *a, dtype=jnp.float32))(
        params["vision_tower"],
        jnp.asarray(px)[jnp.asarray(meta["patch_gather"])],
        jnp.asarray(meta["pos_hw"]), jnp.asarray(meta["seg_window"]),
        jnp.asarray(meta["seg_full"]), jnp.asarray(meta["reverse"]),
    )
    got = np.asarray(got)[np.asarray(meta["merged_mask"])]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_mrope_position_ids_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids = [(1, 4, 6), (1, 8, 4)]
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
    from veomni_tpu.models.qwen2_5_vl import mrope_position_ids

    got = mrope_position_ids(input_ids, grids, cfg)  # [B,3,S]
    np.testing.assert_array_equal(got[0], ref_pos[:, 0].numpy())


def test_full_loss_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids = [(1, 4, 6), (1, 8, 4)]
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

    from veomni_tpu.models.qwen2_5_vl import mrope_position_ids, vision_metadata

    meta = vision_metadata(grids, cfg.vision, n_pad_patches=pixel_values.shape[0])
    pos = mrope_position_ids(input_ids, grids, cfg)
    # pre-shift labels to our collator contract (labels[t] = next token)
    shifted = np.full_like(labels, -100)
    shifted[:, :-1] = labels[:, 1:]
    batch = {
        "input_ids": jnp.asarray(input_ids, jnp.int32),
        "labels": jnp.asarray(shifted, jnp.int32),
        "position_ids": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.ones_like(jnp.asarray(input_ids, jnp.int32)),
        "pixel_values": jnp.asarray(pixel_values)[jnp.asarray(meta["patch_gather"])],
        "vis_pos_hw": jnp.asarray(meta["pos_hw"]),
        "vis_seg_window": jnp.asarray(meta["seg_window"]),
        "vis_seg_full": jnp.asarray(meta["seg_full"]),
        "vis_reverse": jnp.asarray(meta["reverse"]),
        "vis_merged_mask": jnp.asarray(meta["merged_mask"]),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=2e-4)


def test_qwen25_vl_trainer_e2e(tmp_path):
    """Full trainer drive: images -> patches/metadata -> mrope -> train steps
    (loss finite and decreasing-ish, checkpoint written)."""
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
        "model_type": "qwen2_5_vl",
        "vocab_size": 256,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "rope_scaling": {"type": "mrope", "mrope_section": [2, 3, 3]},
        "vision": {
            "depth": 2, "hidden_size": 32, "intermediate_size": 64,
            "num_heads": 2, "patch_size": 2, "spatial_merge_size": 2,
            "window_size": 8, "fullatt_block_indexes": [1],
            "out_hidden_size": 64,
        },
        "image_token_id": 9, "video_token_id": 10,
        "vision_start_token_id": 8,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.data.max_patches = 256  # 8 global rows (mb 2 x dp 4) x <=24 patches
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
        # HF export exists and reimports
        import os

        hf_dir = os.path.join(args.train.output_dir, "hf_ckpt")
        assert os.path.exists(os.path.join(hf_dir, "model.safetensors"))
        from veomni_tpu.models import build_foundation_model

        m2 = build_foundation_model(hf_dir, dtype="float32")
        m2.load_hf(hf_dir)
    finally:
        destroy_parallel_state()


def test_qwen25_vl_sp_equivalence(hf_and_ours):
    """Heterogeneous SP: vision tower at sp=1 (scoped no-SP state) + LM at
    ulysses=2 must reproduce the unsharded loss exactly (fp32)."""
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.models.qwen2_5_vl import mrope_position_ids, vision_metadata

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    grids_row = [(1, 4, 6), (1, 8, 4)]
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in grids_row]
    rng = np.random.default_rng(3)
    pixel_row, _ = _vision_inputs(rng, grids_row, cfg.vision.patch_dim)
    # two rows (batch divisible by the dp axes), images packed in row order
    grids = grids_row * 2
    pixel_values = np.concatenate([pixel_row, pixel_row])

    ids = [VSTART_ID] + [IMG_ID] * n_merged[0] + list(rng.integers(11, 256, 5))
    ids += [VSTART_ID] + [IMG_ID] * n_merged[1] + list(rng.integers(11, 256, 6))
    ids += [0] * (64 - len(ids))  # pad to an sp-divisible length
    input_ids = np.asarray([ids, ids], np.int64)
    labels = np.full_like(input_ids, -100)
    labels[:, n_merged[0] + 1: -1] = input_ids[:, n_merged[0] + 2:]

    meta = vision_metadata(grids, cfg.vision, n_pad_patches=pixel_values.shape[0])
    pos = mrope_position_ids(input_ids, grids, cfg)
    batch = {
        "input_ids": jnp.asarray(input_ids, jnp.int32),
        "labels": jnp.asarray(labels, jnp.int32),
        "position_ids": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.asarray((input_ids != 0).astype(np.int32)),
        "pixel_values": jnp.asarray(pixel_values)[jnp.asarray(meta["patch_gather"])],
        "vis_pos_hw": jnp.asarray(meta["pos_hw"]),
        "vis_seg_window": jnp.asarray(meta["seg_window"]),
        "vis_seg_full": jnp.asarray(meta["seg_full"]),
        "vis_reverse": jnp.asarray(meta["reverse"]),
        "vis_merged_mask": jnp.asarray(meta["merged_mask"]),
    }
    destroy_parallel_state()
    ref_loss, ref_metrics = jax.jit(model.loss_fn)(params, batch)
    ref = float(ref_loss) / float(ref_metrics["ntokens"])
    try:
        ps = init_parallel_state(ulysses_size=2, dp_shard_size=2)
        with use_parallel_state(ps):
            got_loss, got_metrics = jax.jit(model.loss_fn)(params, batch)
            got = float(got_loss) / float(got_metrics["ntokens"])
    finally:
        destroy_parallel_state()
    np.testing.assert_allclose(got, ref, rtol=1e-5)
