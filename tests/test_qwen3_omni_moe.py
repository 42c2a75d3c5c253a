"""Qwen3-Omni-MoE thinker parity vs HF transformers (tiny config).

Oracle pattern as test_qwen3_vl.py: tiny
``Qwen3OmniMoeThinkerForConditionalGeneration``, HF-format export, import,
and identical audio-tower features / full loss on text + audio + image —
exercising the chunked conv downsampling, per-chunk sinusoid positions,
windowed audio attention, deepstack vision reuse, MoE text, and the omni
3-stream rope index.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

IMG_ID, VID_ID, AUD_ID = 9, 10, 11
VSTART_ID, ASTART_ID = 8, 7


def _tiny_hf_model(tmp_path):
    import torch
    from transformers.models.qwen3_omni_moe.configuration_qwen3_omni_moe import (
        Qwen3OmniMoeThinkerConfig,
    )
    from transformers.models.qwen3_omni_moe.modeling_qwen3_omni_moe import (
        Qwen3OmniMoeThinkerForConditionalGeneration,
    )

    cfg = Qwen3OmniMoeThinkerConfig(
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
        audio_config=dict(
            d_model=32,
            encoder_layers=2,
            encoder_attention_heads=2,
            encoder_ffn_dim=64,
            num_mel_bins=32,
            max_source_positions=200,
            n_window=50,          # chunks of 100 mel frames -> 13 conv frames
            n_window_infer=200,   # 2 chunks per attention window
            downsample_hidden_size=16,
            output_dim=64,
            conv_chunksize=500,
        ),
        image_token_id=IMG_ID,
        video_token_id=VID_ID,
        audio_token_id=AUD_ID,
        vision_start_token_id=VSTART_ID,
        audio_start_token_id=ASTART_ID,
        position_id_per_seconds=13,
    )
    torch.manual_seed(0)
    model = Qwen3OmniMoeThinkerForConditionalGeneration(cfg).eval()
    out = tmp_path / "hf_ckpt"
    model.save_pretrained(out, safe_serialization=True)
    return model, cfg, str(out)


@pytest.fixture(scope="module")
def hf_and_ours(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("q3omni")
    hf_model, hf_cfg, ckpt = _tiny_hf_model(tmp_path)

    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(ckpt, dtype="float32")
    params = model.load_hf(ckpt)
    return hf_model, hf_cfg, model, params


AUDIO_LENS = [130, 97]  # multi-chunk (100+30) + single-chunk audios


def test_audio_tower_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    rng = np.random.default_rng(0)
    mels = [rng.standard_normal((cfg.audio.num_mel_bins, L)).astype(np.float32)
            for L in AUDIO_LENS]

    with torch.no_grad():
        ref = hf_model.audio_tower(
            torch.from_numpy(np.concatenate(mels, axis=1)),
            feature_lens=torch.tensor(AUDIO_LENS),
        ).last_hidden_state.numpy()

    from veomni_tpu.models.qwen3_omni_moe import (
        audio_forward, audio_metadata, pack_audio_chunks,
    )

    n_chunk_pad, n_frame_pad = 4, 64
    meta = audio_metadata(AUDIO_LENS, cfg.audio, n_chunk_pad, n_frame_pad)
    chunks = pack_audio_chunks(mels, cfg.audio, n_chunk_pad)
    got = jax.jit(lambda tower, *a: audio_forward(tower, cfg.audio, *a, dtype=jnp.float32))(
        params["audio_tower"], jnp.asarray(chunks),
        jnp.asarray(meta["frame_gather"]),
        jnp.asarray(meta["seg"]),
    )
    got = np.asarray(got)[meta["frame_mask"]]
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


def test_full_loss_parity(hf_and_ours):
    import torch

    hf_model, hf_cfg, model, params = hf_and_ours
    cfg = model.config
    rng = np.random.default_rng(1)

    from veomni_tpu.models.qwen3_omni_moe import (
        audio_metadata, audio_output_lengths, omni_position_ids,
        pack_audio_chunks,
    )
    from veomni_tpu.models.qwen3_vl import vision_metadata

    grids = [(1, 4, 4)]
    n_merged = [t * (h // 2) * (w // 2) for t, h, w in grids]
    n_img_patches = sum(t * h * w for t, h, w in grids)
    pixel_values = rng.standard_normal(
        (n_img_patches, cfg.vision.patch_dim)).astype(np.float32)
    mels = [rng.standard_normal((cfg.audio.num_mel_bins, L)).astype(np.float32)
            for L in AUDIO_LENS]
    aud_tokens = [audio_output_lengths(L) for L in AUDIO_LENS]

    ids = [ASTART_ID] + [AUD_ID] * aud_tokens[0]
    ids += list(rng.integers(12, 256, 5))
    ids += [VSTART_ID] + [IMG_ID] * n_merged[0]
    ids += list(rng.integers(12, 256, 4))
    ids += [ASTART_ID] + [AUD_ID] * aud_tokens[1]
    ids += list(rng.integers(12, 256, 6))
    input_ids = np.asarray([ids], np.int64)
    labels = input_ids.copy()

    max_mel = max(AUDIO_LENS)
    feat_padded = np.zeros((len(mels), cfg.audio.num_mel_bins, max_mel), np.float32)
    feat_mask = np.zeros((len(mels), max_mel), np.int64)
    for i, m in enumerate(mels):
        feat_padded[i, :, : m.shape[1]] = m
        feat_mask[i, : m.shape[1]] = 1
    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.from_numpy(input_ids),
            labels=torch.from_numpy(labels),
            pixel_values=torch.from_numpy(pixel_values),
            image_grid_thw=torch.as_tensor(grids),
            input_features=torch.from_numpy(feat_padded),
            feature_attention_mask=torch.from_numpy(feat_mask),
        )
    ref_loss = float(ref.loss)

    n_chunk_pad, n_frame_pad = 4, 64
    ameta = audio_metadata(AUDIO_LENS, cfg.audio, n_chunk_pad, n_frame_pad)
    chunks = pack_audio_chunks(mels, cfg.audio, n_chunk_pad)
    vmeta = vision_metadata(grids, cfg.vision, n_pad_patches=n_img_patches)

    # reference position ids (our numpy port must match HF's)
    ref_pos, _ = hf_model.get_rope_index(
        torch.from_numpy(input_ids),
        image_grid_thw=torch.as_tensor(grids),
        audio_seqlens=torch.tensor(AUDIO_LENS),
        attention_mask=torch.ones_like(torch.from_numpy(input_ids)),
    )
    pos = omni_position_ids(
        input_ids, cfg, image_grid_thw=grids, audio_lens=AUDIO_LENS
    )
    np.testing.assert_array_equal(pos[0], ref_pos[:, 0].numpy())

    shifted = np.full_like(labels, -100)
    shifted[:, :-1] = labels[:, 1:]
    batch = {
        "input_ids": jnp.asarray(input_ids, jnp.int32),
        "labels": jnp.asarray(shifted, jnp.int32),
        "position_ids": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.ones_like(jnp.asarray(input_ids, jnp.int32)),
        "pixel_values": jnp.asarray(pixel_values),
        "vis_pos_hw": jnp.asarray(vmeta["pos_hw"]),
        "vis_pos_interp_idx": jnp.asarray(vmeta["pos_interp_idx"]),
        "vis_pos_interp_w": jnp.asarray(vmeta["pos_interp_w"]),
        "vis_seg_full": jnp.asarray(vmeta["seg_full"]),
        "vis_merged_mask": jnp.asarray(vmeta["merged_mask"]),
        "audio_chunks": jnp.asarray(chunks),
        "aud_frame_gather": jnp.asarray(ameta["frame_gather"]),
        "aud_seg": jnp.asarray(ameta["seg"]),
        "aud_frame_mask": jnp.asarray(ameta["frame_mask"]),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=3e-4)


def test_hf_export_roundtrip(hf_and_ours, tmp_path):
    import torch
    from transformers.models.qwen3_omni_moe.modeling_qwen3_omni_moe import (
        Qwen3OmniMoeThinkerForConditionalGeneration,
    )

    hf_model, hf_cfg, model, params = hf_and_ours
    out = tmp_path / "export"
    model.family.save_hf_checkpoint(params, model.config, str(out))

    reloaded = Qwen3OmniMoeThinkerForConditionalGeneration.from_pretrained(
        str(out), config=hf_cfg, torch_dtype=torch.float32
    ).eval()
    with torch.no_grad():
        for (n1, p1), (n2, p2) in zip(
            sorted(hf_model.named_parameters()),
            sorted(reloaded.named_parameters()),
        ):
            assert n1 == n2
            np.testing.assert_allclose(p1.numpy(), p2.numpy(), rtol=1e-6, atol=1e-6)


def test_qwen3_omni_trainer_e2e(tmp_path):
    """Full OmniTrainer drive: raw audio + images -> mel/patch plans ->
    omni rope -> deepstack MoE train steps; checkpoint + HF export."""
    import json

    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import OmniTrainer

    rng = np.random.default_rng(0)
    rows = []
    for i in range(16):
        row = {
            "input_ids": rng.integers(12, 256, int(rng.integers(8, 20))).tolist(),
        }
        if i % 2 == 0:  # 8x8 pixels -> 4x4 patch grid (patch 2)
            row["images"] = [rng.random((8, 8, 3)).tolist()]
        if i % 3 == 0:  # precomputed mel [n_mels, T]
            row["audios"] = [rng.standard_normal((32, 60)).tolist()]
        rows.append(row)
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "qwen3_omni_moe",
        "vocab_size": 256,
        "hidden_size": 64,
        "intermediate_size": 128,
        "moe_intermediate_size": 32,
        "num_experts": 4,
        "num_experts_per_tok": 2,
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
        "audio": {
            "d_model": 32, "encoder_layers": 2, "encoder_attention_heads": 2,
            "encoder_ffn_dim": 64, "num_mel_bins": 32,
            "max_source_positions": 64, "n_window": 50, "n_window_infer": 200,
            "downsample_hidden_size": 16, "output_dim": 64,
        },
        "image_token_id": 9, "video_token_id": 10, "audio_token_id": 11,
        "vision_start_token_id": 8, "audio_start_token_id": 7,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.data_type = "pretokenized"
    args.data.max_seq_len = 64
    args.data.max_patches = 256
    args.data.max_audio_chunks = 8
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 2
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = True
    args.train.log_steps = 100
    destroy_parallel_state()
    try:
        trainer = OmniTrainer(args)
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
