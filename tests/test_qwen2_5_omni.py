"""Qwen2.5-Omni thinker parity vs HF transformers (tiny config).

Reference capability: veomni/models/transformers/qwen2_5_omni/ (training the
thinker: audio encoder + vision tower + LM). Oracle style of
test_qwen2_5_vl.py: build a tiny HF thinker, export, import, compare.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

IMG_ID, VID_ID, VSTART_ID = 9, 10, 8
AUD_ID, ASTART_ID, AEND_ID = 5, 6, 7


def _tiny_hf_thinker(tmp_path):
    import torch
    from transformers import (
        Qwen2_5OmniThinkerConfig, Qwen2_5OmniThinkerForConditionalGeneration,
    )

    cfg = Qwen2_5OmniThinkerConfig(
        audio_config=dict(
            num_mel_bins=16, d_model=32, encoder_layers=2,
            encoder_attention_heads=2, encoder_ffn_dim=64, n_window=8,
            max_source_positions=64, output_dim=64,
        ),
        vision_config=dict(
            depth=2, hidden_size=32, intermediate_size=64, num_heads=2,
            in_channels=3, patch_size=2, temporal_patch_size=2,
            spatial_merge_size=2, window_size=8, fullatt_block_indexes=[1],
            out_hidden_size=64,
        ),
        text_config=dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            rope_theta=10000.0, tie_word_embeddings=False,
            rope_scaling={"type": "default", "mrope_section": [2, 3, 3]},
        ),
        audio_token_index=AUD_ID, image_token_index=IMG_ID,
        video_token_index=VID_ID, vision_start_token_id=VSTART_ID,
        audio_start_token_id=ASTART_ID, audio_end_token_id=AEND_ID,
        position_id_per_seconds=25,
    )
    torch.manual_seed(0)
    model = Qwen2_5OmniThinkerForConditionalGeneration(cfg).eval()
    out = tmp_path / "hf_thinker"
    model.save_pretrained(out, safe_serialization=True)
    return model, str(out)


@pytest.fixture(scope="module")
def hf_and_ours(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("q25omni")
    hf_model, ckpt = _tiny_hf_thinker(tmp_path)

    from veomni_tpu.models import build_foundation_model

    # audio static slot: 32 mel frames (= 2 chunks of 2*n_window=16)
    model = build_foundation_model(ckpt, dtype="float32", audio_max_frames=32)
    params = model.load_hf(ckpt)
    return hf_model, model, params


def test_audio_encoder_parity(hf_and_ours):
    import torch

    hf_model, model, params = hf_and_ours
    acfg = model.config.audio
    t_mel = acfg.max_frames
    rng = np.random.default_rng(0)
    mel = rng.standard_normal((1, acfg.num_mel_bins, t_mel)).astype(np.float32)

    with torch.no_grad():
        ref = hf_model.audio_tower(
            torch.from_numpy(mel[0]),
            feature_lens=torch.tensor([t_mel]),
            aftercnn_lens=torch.tensor([t_mel // 2]),
        ).last_hidden_state.numpy()

    from veomni_tpu.models.qwen2_5_omni import audio_encoder_forward

    got = jax.jit(lambda tower, mel: audio_encoder_forward(tower, acfg, mel, dtype=jnp.float32))(
        params["audio_tower"], jnp.asarray(mel.transpose(0, 2, 1)))
    np.testing.assert_allclose(np.asarray(got)[0], ref, rtol=2e-4, atol=2e-4)


def test_thinker_loss_parity(hf_and_ours):
    import torch

    hf_model, model, params = hf_and_ours
    cfg = model.config
    acfg, vcfg = cfg.audio, cfg.vision
    rng = np.random.default_rng(1)

    # one audio (32 mel frames -> 8 tokens) + one image (4x4 grid -> 4 merged)
    t_mel = acfg.max_frames
    n_audio_tok = acfg.tokens_per_audio
    grids = [(1, 4, 4)]
    n_merged = 4
    mel = rng.standard_normal((1, acfg.num_mel_bins, t_mel)).astype(np.float32)
    patch_dim = vcfg.patch_dim
    pixel_values = rng.standard_normal((16, patch_dim)).astype(np.float32)

    ids = (
        [ASTART_ID] + [AUD_ID] * n_audio_tok + [AEND_ID]
        + list(rng.integers(11, 256, 4))
        + [VSTART_ID] + [IMG_ID] * n_merged
        + list(rng.integers(11, 256, 6))
    )
    input_ids = np.asarray([ids], np.int64)
    labels = input_ids.copy()
    labels[:, : n_audio_tok + 2] = -100

    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.from_numpy(input_ids),
            labels=torch.from_numpy(labels),
            input_features=torch.from_numpy(mel),
            feature_attention_mask=torch.ones(1, t_mel, dtype=torch.bool),
            pixel_values=torch.from_numpy(pixel_values),
            image_grid_thw=torch.tensor(grids),
        )
    ref_loss = float(ref.loss)

    from veomni_tpu.models.qwen2_5_vl import mrope_position_ids, vision_metadata

    meta = vision_metadata(grids, vcfg, n_pad_patches=pixel_values.shape[0])
    pos = mrope_position_ids(input_ids, grids, cfg)
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
        "audio_features": jnp.asarray(mel.transpose(0, 2, 1)),
        "audio_mask": jnp.ones((1,), bool),
    }
    loss_sum, metrics = jax.jit(model.loss_fn)(params, batch)
    got_loss = float(loss_sum) / float(metrics["ntokens"])
    np.testing.assert_allclose(got_loss, ref_loss, rtol=2e-4)


def test_hf_export_roundtrip(hf_and_ours, tmp_path):
    hf_model, model, params = hf_and_ours
    out = str(tmp_path / "export")
    model.save_hf(out, params)

    from veomni_tpu.models import build_foundation_model

    cfg = model.config
    model2 = build_foundation_model(
        config=cfg,
    )
    params2 = model2.family.hf_to_params(out, cfg)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves_with_path(params2),
    ):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(pa))
