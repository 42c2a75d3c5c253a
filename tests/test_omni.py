"""Omni composite model: audio encoder, multi-modality merge, e2e training."""

import json

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.arguments import VeOmniArguments
from veomni_tpu.models import omni
from veomni_tpu.models.omni import init_omni_params
from veomni_tpu.utils.testing import under_jit

# the whole model as one program a shape, not op by op (init_omni_params stays
# eager: the MoVQ tower's draws compile slower as one program than they run)
omni_loss_fn = under_jit(omni.omni_loss_fn)

TEXT = dict(model_type="qwen2", vocab_size=600, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, attention_bias=True)
VISION = dict(image_size=28, patch_size=7, hidden_size=32, intermediate_size=64,
              num_hidden_layers=2, num_attention_heads=2, spatial_merge_size=2)
AUDIO = dict(n_mels=16, max_frames=32, subsample=4, hidden_size=32,
             intermediate_size=64, num_hidden_layers=2, num_attention_heads=2)


def test_audio_encoder_shapes():
    from veomni_tpu.models.omni import AudioEncoderConfig, audio_forward, init_audio_params

    cfg = AudioEncoderConfig(**AUDIO, out_hidden_size=64)
    params = init_audio_params(jax.random.PRNGKey(0), cfg)
    feats = audio_forward(params, cfg, jnp.ones((3, 32, 16)))
    assert feats.shape == (3, cfg.tokens_per_audio, 64)


MOVQ = dict(resolution=8, ch=8, ch_mult=(1, 2), num_res_blocks=1,
            attn_resolutions=(4,), z_channels=4, embed_dim=6, n_embed=32,
            num_groups=4)  # token_grid 4 -> 16 tokens/image


def _gen_cfg():
    from veomni_tpu.models.omni import OmniConfig

    return OmniConfig(
        text=dict(TEXT), image_gen={"movq": dict(MOVQ)}, image_gen_token_id=512,
        max_gen_images=1,
    )


def _gen_batch(cfg, with_gen: bool):
    from veomni_tpu.data.data_collator import IGNORE_INDEX

    rng = np.random.default_rng(1)
    s = 48
    t_gen = cfg.image_gen.tokens_per_image
    ids = rng.integers(1, 500, (2, s)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    labels[:, -1] = IGNORE_INDEX
    gen_mask = np.zeros((2, 1), bool)
    pixels = np.zeros((2, 1, 8, 8, 3), np.float32)
    if with_gen:
        # row 0 carries one generated image after 16 text tokens
        ids[0, 16:16 + t_gen] = cfg.image_gen_token_id
        labels[0, 15:15 + t_gen] = IGNORE_INDEX
        gen_mask[0, 0] = True
        pixels[0, 0] = rng.random((8, 8, 3), np.float32) * 2 - 1
    return {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(labels),
        "position_ids": jnp.broadcast_to(jnp.arange(s), (2, s)).astype(jnp.int32),
        "segment_ids": jnp.ones((2, s), jnp.int32),
        "gen_pixels": jnp.asarray(pixels),
        "gen_image_mask": jnp.asarray(gen_mask),
    }


def test_image_gen_loss_trains_and_text_invariant():
    from veomni_tpu.models.omni import OmniConfig

    cfg = _gen_cfg()
    params = init_omni_params(jax.random.PRNGKey(0), cfg)
    batch = _gen_batch(cfg, with_gen=True)

    @jax.jit
    def step(p):
        (total, metrics), grads = jax.value_and_grad(
            lambda q: omni.omni_loss_fn(q, cfg, batch), has_aux=True
        )(p)
        # train only aligner + gen head (freeze_tokenizer semantics keep the
        # movq grads zero; LM drift would also move gen loss, so isolate)
        new_ig = {
            k: jax.tree.map(lambda a, g: a - 0.5 * g, p["image_gen"][k],
                            grads["image_gen"][k])
            for k in ("aligner", "gen_head")
        }
        new_p = dict(p)
        new_p["image_gen"] = dict(p["image_gen"], **new_ig)
        return new_p, metrics

    _, m0 = step(params)
    assert int(m0["gen_ntokens"]) == cfg.image_gen.tokens_per_image
    p1 = params
    for _ in range(6):
        p1, m = step(p1)
    gl0 = float(m0["gen_loss_sum"]) / float(m0["gen_ntokens"])
    gl1 = float(m["gen_loss_sum"]) / float(m["gen_ntokens"])
    assert gl1 < gl0 - 0.05, (gl0, gl1)

    # movq tokenizer stays frozen: its grads are exactly zero
    grads = jax.jit(jax.grad(lambda q: omni.omni_loss_fn(q, cfg, batch)[0]))(params)
    assert all(
        float(jnp.abs(g).max()) == 0.0
        for g in jax.tree.leaves(grads["image_gen"]["movq"])
    )

    # no gen tokens in the batch -> text loss identical to a plain text model
    nb = _gen_batch(cfg, with_gen=False)
    total_gen, m_gen = omni_loss_fn(params, cfg, nb)
    plain = OmniConfig(text=dict(TEXT))
    p_plain = dict(params)
    p_plain.pop("image_gen")
    total_plain, m_plain = omni_loss_fn(p_plain, plain, nb)
    assert float(m_gen["gen_loss_sum"]) == 0.0
    np.testing.assert_allclose(
        float(m_gen["loss_sum"]), float(m_plain["loss_sum"]), rtol=1e-6
    )


def test_image_gen_janus_vq_decoder():
    """The seed_omni decoder registry: the same composite machinery drives
    the llamagen/janus VQ decoder (reference decoder/janusvq16) via
    ImageGenConfig.decoder_type."""
    from veomni_tpu.models.omni import OmniConfig

    cfg = OmniConfig(
        text=dict(TEXT),
        image_gen={
            "decoder_type": "janus_vq",
            "movq": dict(codebook_size=32, codebook_embed_dim=6, ch=8,
                         encoder_ch_mult=(1, 2), decoder_ch_mult=(1, 2),
                         num_res_blocks=1, z_channels=4, image_size=8,
                         num_groups=4),
        },
        image_gen_token_id=512,
        max_gen_images=1,
    )
    assert cfg.image_gen.tokens_per_image == 16
    assert cfg.image_gen.image_size == 8
    params = init_omni_params(jax.random.PRNGKey(0), cfg)
    batch = _gen_batch(cfg, with_gen=True)
    total, metrics = omni_loss_fn(params, cfg, batch)
    assert np.isfinite(float(total))
    assert int(metrics["gen_ntokens"]) == 16
    # frozen VQ; aligner/head trainable
    grads = jax.jit(jax.grad(lambda p: omni.omni_loss_fn(p, cfg, batch)[0]))(params)
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(grads["image_gen"]["movq"]))
    assert float(jnp.abs(grads["image_gen"]["gen_head"]["fc2"]).sum()) > 0.0


def test_generate_image():
    """lm_generate contract: autoregressive code sampling + VQ decode
    produce a correctly-shaped image; greedy determinism at temperature~0."""
    from veomni_tpu.models.omni import generate_image

    cfg = _gen_cfg()
    params = init_omni_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(np.random.default_rng(0).integers(1, 500, (1, 6)),
                         jnp.int32)
    pixels, codes = generate_image(params, cfg, prompt, jax.random.PRNGKey(1))
    r = cfg.image_gen.image_size
    assert pixels.shape == (1, r, r, 3)
    assert codes.shape == (1, cfg.image_gen.tokens_per_image)
    assert np.all(np.asarray(codes) >= 0)
    assert np.all(np.asarray(codes) < cfg.image_gen.movq.n_embed)
    # sampling is a pure function of the key (an untrained head has logit
    # ties, so near-greedy runs are NOT key-invariant — compare same-key)
    _, c1 = generate_image(params, cfg, prompt, jax.random.PRNGKey(2))
    _, c2 = generate_image(params, cfg, prompt, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_movqgan_hf_roundtrip(tmp_path):
    from safetensors.numpy import save_file

    from veomni_tpu.models import movqgan

    cfg = movqgan.MoVQGANConfig(**MOVQ)
    params = movqgan.init_params(jax.random.PRNGKey(3), cfg)

    # emit the torch-layout (OIHW, reference module names) state dict by
    # walking the same structure hf_to_params expects
    sd = {}

    def put_conv(name, w, b):
        # ascontiguousarray: safetensors serializes the raw buffer, silently
        # ignoring the transpose's strides
        sd[name + ".weight"] = np.ascontiguousarray(np.transpose(np.asarray(w), (3, 2, 0, 1)))
        sd[name + ".bias"] = np.asarray(b)

    def put_norm(prefix, p, spatial):
        if spatial:
            sd[prefix + ".norm_layer.weight"] = np.asarray(p["gn_w"])
            sd[prefix + ".norm_layer.bias"] = np.asarray(p["gn_b"])
            put_conv(prefix + ".conv_y", p["conv_y_w"], p["conv_y_b"])
            put_conv(prefix + ".conv_b", p["conv_b_w"], p["conv_b_b"])
        else:
            sd[prefix + ".weight"] = np.asarray(p["gn_w"])
            sd[prefix + ".bias"] = np.asarray(p["gn_b"])

    def put_res(prefix, p, spatial):
        put_norm(prefix + ".norm1", p["norm1"], spatial)
        put_conv(prefix + ".conv1", p["conv1_w"], p["conv1_b"])
        put_norm(prefix + ".norm2", p["norm2"], spatial)
        put_conv(prefix + ".conv2", p["conv2_w"], p["conv2_b"])
        if "shortcut_w" in p:
            put_conv(prefix + ".nin_shortcut", p["shortcut_w"], p["shortcut_b"])

    def put_attn(prefix, p, spatial):
        put_norm(prefix + ".norm", p["norm"], spatial)
        for mine, theirs in (("q", "q"), ("k", "k"), ("v", "v"), ("proj", "proj_out")):
            put_conv(f"{prefix}.{theirs}", p[f"{mine}_w"], p[f"{mine}_b"])

    enc = params["encoder"]
    put_conv("encoder.conv_in", enc["conv_in_w"], enc["conv_in_b"])
    for i, level in enumerate(enc["down"]):
        for j, rp in enumerate(level["res"]):
            put_res(f"encoder.down.{i}.block.{j}", rp, False)
        for j, ap in enumerate(level["attn"]):
            put_attn(f"encoder.down.{i}.attn.{j}", ap, False)
        if "down_w" in level:
            put_conv(f"encoder.down.{i}.downsample.conv", level["down_w"], level["down_b"])
    put_res("encoder.mid.block_1", enc["mid_res1"], False)
    put_attn("encoder.mid.attn_1", enc["mid_attn"], False)
    put_res("encoder.mid.block_2", enc["mid_res2"], False)
    put_norm("encoder.norm_out", enc["norm_out"], False)
    put_conv("encoder.conv_out", enc["conv_out_w"], enc["conv_out_b"])

    dec = params["decoder"]
    levels = len(cfg.ch_mult)
    put_conv("decoder.conv_in", dec["conv_in_w"], dec["conv_in_b"])
    put_res("decoder.mid.block_1", dec["mid_res1"], True)
    put_attn("decoder.mid.attn_1", dec["mid_attn"], True)
    put_res("decoder.mid.block_2", dec["mid_res2"], True)
    for j, level in enumerate(dec["up"]):
        i = levels - 1 - j
        for k, rp in enumerate(level["res"]):
            put_res(f"decoder.up.{i}.block.{k}", rp, True)
        for k, ap in enumerate(level["attn"]):
            put_attn(f"decoder.up.{i}.attn.{k}", ap, True)
        if "up_w" in level:
            put_conv(f"decoder.up.{i}.upsample.conv", level["up_w"], level["up_b"])
    put_norm("decoder.norm_out", dec["norm_out"], True)
    put_conv("decoder.conv_out", dec["conv_out_w"], dec["conv_out_b"])

    sd["quantize.embedding.weight"] = np.asarray(params["codebook"])
    put_conv("quant_conv", params["quant_conv_w"], params["quant_conv_b"])
    put_conv("post_quant_conv", params["post_quant_conv_w"], params["post_quant_conv_b"])

    save_file(sd, str(tmp_path / "model.safetensors"))
    loaded = movqgan.hf_to_params(str(tmp_path), cfg)

    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_leaves_with_path(loaded)}
    assert len(flat_a) == len(flat_b)
    for path, v in flat_a:
        np.testing.assert_array_equal(np.asarray(v), np.asarray(flat_b[jax.tree_util.keystr(path)]), err_msg=jax.tree_util.keystr(path))

    # decode path with embed_dim != z_channels (regression: decoder conv_in
    # consumes post_quant_conv output, which has z_channels channels)
    pixels = jnp.asarray(np.random.default_rng(0).random((1, 8, 8, 3), np.float32))
    z_q, idx, _ = movqgan.encode(loaded, cfg, pixels)
    rec = movqgan.decode(loaded, cfg, z_q)
    assert rec.shape == (1, 8, 8, 3)
    assert idx.shape == (1, 4, 4)
    rec2 = movqgan.decode_code(loaded, cfg, idx.reshape(1, -1))
    np.testing.assert_allclose(np.asarray(rec), np.asarray(rec2), atol=1e-5)


def test_omni_trainer_e2e(tmp_path):
    from veomni_tpu.trainer.omni_trainer import OmniTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "omni.jsonl", "w") as f:
        for i in range(48):
            row = {"input_ids": rng.integers(0, 500, int(rng.integers(10, 30))).tolist()}
            if i % 2:
                row["images"] = [rng.random((28, 28, 3)).tolist()]
            if i % 3:
                row["audio"] = [rng.random((32, 16)).tolist()]
            if i % 5 == 0:
                row["gen_images"] = [rng.random((8, 8, 3)).tolist()]
            f.write(json.dumps(row) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "text": dict(TEXT), "vision": dict(VISION), "audio": dict(AUDIO),
        "image_gen": {"movq": dict(MOVQ)},
        "image_token_id": 510, "audio_token_id": 511, "image_gen_token_id": 512,
        "freeze_audio": False,
    }
    args.data.train_path = str(tmp_path / "omni.jsonl")
    args.data.max_seq_len = 96
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.save_hf_weights = True
    args.train.log_steps = 100
    trainer = OmniTrainer(args)
    ctl = trainer.train()
    assert ctl.global_step == 3
    assert np.isfinite(ctl.metrics["loss"])
    assert (tmp_path / "out" / "hf_ckpt" / "language_model" / "model.safetensors").exists()
    trainer.checkpointer.close()
