"""Janus composite: understanding + generation pathways, VQ invariants,
HF io round-trip (reference ``janus/modeling_janus.py``; no torch oracle —
the family isn't in transformers)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import janus
from veomni_tpu.models.janus import JanusConfig, decode_code, gen_vision_encode, init_params
from veomni_tpu.utils.testing import under_jit

# the whole model as one program a shape, not op by op (init_params stays
# eager: the VQ tower's draws compile slower as one program than they run)
loss_fn = under_jit(janus.loss_fn)

TEXT = dict(model_type="llama", vocab_size=600, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False)
VISION = dict(width=32, layers=2, heads=2, patch_size=8, image_size=32,
              mlp_ratio=2.0)
GEN = dict(codebook_size=32, codebook_embed_dim=6, ch=8,
           encoder_ch_mult=(1, 2), decoder_ch_mult=(1, 2), num_res_blocks=1,
           z_channels=4, image_size=8, num_groups=4)
IMG_ID, GEN_ID = 510, 512


@pytest.fixture(scope="module")
def model():
    cfg = JanusConfig(text=dict(TEXT), vision=dict(VISION), gen_vision=dict(GEN),
                      image_token_id=IMG_ID, image_gen_token_id=GEN_ID,
                      gen_head_embed=48)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batch(cfg, with_images=True, with_gen=True):
    rng = np.random.default_rng(0)
    s = 64
    t_img = cfg.vision.tokens_per_image       # 16
    t_gen = cfg.gen_vision.tokens_per_image   # 16
    ids = rng.integers(1, 500, (2, s)).astype(np.int32)
    if with_images:
        ids[0, :t_img] = IMG_ID
    if with_gen:
        ids[0, 24:24 + t_gen] = GEN_ID
    labels = np.roll(ids, -1, 1).astype(np.int32)
    labels[:, -1] = -100
    labels[np.roll(ids, -1, 1) >= 500] = -100  # no text CE on placeholders
    batch = {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(labels),
        "position_ids": jnp.broadcast_to(jnp.arange(s), (2, s)).astype(jnp.int32),
        "segment_ids": jnp.ones((2, s), jnp.int32),
    }
    if with_images:
        px = rng.random((2, 1, 32, 32, 3), np.float32)
        mask = np.zeros((2, 1), bool)
        mask[0, 0] = True
        batch["pixel_values"] = jnp.asarray(px)
        batch["image_mask"] = jnp.asarray(mask)
    if with_gen:
        gp = rng.random((2, 1, 8, 8, 3), np.float32) * 2 - 1
        gmask = np.zeros((2, 1), bool)
        gmask[0, 0] = True
        batch["gen_pixels"] = jnp.asarray(gp)
        batch["gen_image_mask"] = jnp.asarray(gmask)
    return batch


def test_loss_paths_live(model):
    cfg, params = model
    batch = _batch(cfg)
    total, metrics = loss_fn(params, cfg, batch)
    assert np.isfinite(float(total))
    assert int(metrics["gen_ntokens"]) == cfg.gen_vision.tokens_per_image

    # understanding tower is live: changing the image changes the loss
    b2 = dict(batch)
    b2["pixel_values"] = batch["pixel_values"] * -1.0
    assert float(loss_fn(params, cfg, b2)[0]) != float(total)
    # frozen VQ: gen_vision gets zero grads; gen head/aligner get signal
    grads = jax.jit(jax.grad(lambda p: janus.loss_fn(p, cfg, batch)[0]))(params)
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(grads["gen_vision"]))
    assert float(jnp.abs(grads["gen_head"]["fc2"]).sum()) > 0.0
    assert float(jnp.abs(grads["gen_embed"]).sum()) > 0.0


def test_gen_loss_trains(model):
    cfg, params = model
    batch = _batch(cfg, with_images=False)

    import optax

    # adam on the generation head/aligner only (sum-space loss makes raw SGD
    # scale-sensitive on a toy codebook; the trainer uses adamw anyway)
    trainable = {k: params[k] for k in ("gen_aligner", "gen_head")}
    opt = optax.adam(3e-3)
    opt_state = opt.init(trainable)

    @jax.jit
    def step(tr, opt_state):
        def f(tr_):
            return janus.loss_fn({**params, **tr_}, cfg, batch)

        (_, m), g = jax.value_and_grad(f, has_aux=True)(tr)
        updates, opt_state = opt.update(g, opt_state, tr)
        return optax.apply_updates(tr, updates), opt_state, m

    trainable, opt_state, m0 = step(trainable, opt_state)
    for _ in range(10):
        trainable, opt_state, m = step(trainable, opt_state)
    gl0 = float(m0["gen_loss_sum"]) / float(m0["gen_ntokens"])
    gl1 = float(m["gen_loss_sum"]) / float(m["gen_ntokens"])
    assert gl1 < gl0 - 0.05, (gl0, gl1)


def test_vq_roundtrip_and_l2(model):
    cfg, params = model
    gv = params["gen_vision"]
    rng = np.random.default_rng(1)
    px = jnp.asarray(rng.random((2, 8, 8, 3), np.float32) * 2 - 1)
    z_q, idx, vq = gen_vision_encode(gv, cfg.gen_vision, px)
    assert idx.shape == (2, 4, 4) and vq.shape == (2,)
    # straight-through value equals the (l2-normed) codebook entry
    rec = decode_code(gv, cfg.gen_vision, idx.reshape(2, -1))
    assert rec.shape == (2, 8, 8, 3)
    from veomni_tpu.models.janus import gen_vision_decode

    rec2 = gen_vision_decode(gv, cfg.gen_vision, z_q)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(rec2), atol=1e-5)


def test_hf_roundtrip(model, tmp_path):
    from veomni_tpu.models import build_foundation_model

    cfg, params = model
    from veomni_tpu.models.auto import MODEL_REGISTRY

    fam = MODEL_REGISTRY.get("janus")
    out = tmp_path / "hf"
    fam.save_hf_checkpoint(params, cfg, str(out))
    m2 = build_foundation_model(str(out))
    assert m2.config.model_type == "janus"
    assert m2.config.gen_vision.codebook_size == cfg.gen_vision.codebook_size
    p2 = m2.load_hf(str(out))
    flat_a = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(params)}
    flat_b = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(p2)}
    assert flat_a.keys() == flat_b.keys()
    for k in flat_a:
        np.testing.assert_allclose(
            np.asarray(flat_a[k]).astype(np.float32),
            np.asarray(flat_b[k]).astype(np.float32), atol=0, err_msg=k,
        )


def test_janus_trainer_e2e(tmp_path):
    """Trainer drive: understanding + generation images through the omni
    task path (JanusCollator, registry family, replicated VQ plan)."""
    import json

    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer.omni_trainer import OmniTrainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for i in range(24):
            row = {"input_ids": rng.integers(1, 500, int(rng.integers(10, 24))).tolist()}
            if i % 2:
                row["images"] = [rng.random((32, 32, 3)).tolist()]
            if i % 3 == 0:
                row["gen_images"] = [rng.random((8, 8, 3)).tolist()]
            f.write(json.dumps(row) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "janus",
        "text": dict(TEXT),
        "vision": dict(VISION),
        "gen_vision": dict(GEN),
        "image_token_id": IMG_ID, "image_gen_token_id": GEN_ID,
        "gen_head_embed": 48,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.data.max_seq_len = 96
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 3
    args.train.bf16 = False
    args.train.async_save = False
    args.train.log_steps = 100
    destroy_parallel_state()
    try:
        trainer = OmniTrainer(args)
        ctl = trainer.train()
        assert ctl.global_step == 3
        assert np.isfinite(ctl.metrics["loss"])
        trainer.checkpointer.close()
    finally:
        destroy_parallel_state()
