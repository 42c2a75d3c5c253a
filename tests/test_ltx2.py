"""LTX-2 AV DiT: structural self-tests (reference ltx_core transformer; no
torch oracle in this environment — ltx_core isn't installed)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import ltx2
from veomni_tpu.models.ltx2 import LTX2Config, hf_to_params, params_to_hf
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, loss_fn, ltx2_forward = (
    under_jit(f) for f in (ltx2.init_params, ltx2.loss_fn, ltx2.ltx2_forward))

TINY = dict(
    num_attention_heads=2,
    attention_head_dim=24,   # rope ladder 24/(2*3)=4 freqs per axis
    in_channels=8,
    out_channels=8,
    num_layers=2,
    cross_attention_dim=48,
    caption_channels=32,
    with_audio=True,
    audio_num_attention_heads=2,
    audio_attention_head_dim=12,
    audio_in_channels=6,
    audio_out_channels=6,
    video_shape=(2, 4, 4),
    audio_len=8,
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    remat=False,
)


@pytest.fixture(scope="module")
def model():
    cfg = LTX2Config(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # zero-init gates would freeze the attention contributions; nudge the
    # scale-shift tables so every pathway is live for the probes
    rng = np.random.default_rng(0)
    for k in ("scale_shift_table", "audio_scale_shift_table",
              "scale_shift_table_a2v_ca_video", "scale_shift_table_a2v_ca_audio"):
        params["blocks"][k] = jnp.asarray(
            rng.standard_normal(params["blocks"][k].shape) * 0.3, jnp.float32
        )
    return cfg, params


def _inputs(cfg, rng):
    nv = int(np.prod(cfg.video_shape))
    v = jnp.asarray(rng.standard_normal((2, nv, cfg.in_channels)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((2, cfg.audio_len, cfg.audio_in_channels)),
                    jnp.float32)
    t = jnp.asarray([0.3, 0.8], jnp.float32)
    text = jnp.asarray(rng.standard_normal((2, 5, cfg.caption_channels)), jnp.float32)
    return v, a, t, text


def test_forward_shapes_and_conditioning(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    v, a, t, text = _inputs(cfg, rng)
    vo, ao = ltx2_forward(params, cfg, v, t, text, audio_latents=a)
    assert vo.shape == (2, v.shape[1], cfg.out_channels)
    assert ao.shape == (2, cfg.audio_len, cfg.audio_out_channels)
    # timestep / text conditioning are live
    vo2, _ = ltx2_forward(params, cfg, v, t * 0.1, text, audio_latents=a)
    assert np.abs(np.asarray(vo) - np.asarray(vo2)).max() > 1e-6
    vo3, _ = ltx2_forward(params, cfg, v, t, text * -1.0, audio_latents=a)
    assert np.abs(np.asarray(vo) - np.asarray(vo3)).max() > 1e-6


def test_av_cross_coupling(model):
    """Audio must influence the video prediction (and vice versa) through
    the gated A/V cross attention."""
    cfg, params = model
    rng = np.random.default_rng(2)
    v, a, t, text = _inputs(cfg, rng)
    vo, ao = ltx2_forward(params, cfg, v, t, text, audio_latents=a)
    vo2, ao2 = ltx2_forward(params, cfg, v, t, text, audio_latents=a * -1.0)
    assert np.abs(np.asarray(vo) - np.asarray(vo2)).max() > 1e-7
    vo3, ao3 = ltx2_forward(params, cfg, v * -1.0, t, text, audio_latents=a)
    assert np.abs(np.asarray(ao) - np.asarray(ao3)).max() > 1e-7


def test_video_only_config(model):
    cfg0 = dict(TINY, with_audio=False)
    cfg = LTX2Config(**cfg0)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(3)
    nv = int(np.prod(cfg.video_shape))
    v = jnp.asarray(rng.standard_normal((1, nv, cfg.in_channels)), jnp.float32)
    text = jnp.asarray(rng.standard_normal((1, 4, cfg.caption_channels)), jnp.float32)
    vo, ao = ltx2_forward(params, cfg, v, jnp.asarray([0.5]), text)
    assert vo.shape == (1, nv, cfg.out_channels) and ao is None
    assert "audio_attn1" not in params["blocks"]


def test_loss_and_grads(model):
    cfg, params = model
    rng = np.random.default_rng(4)
    v, a, t, text = _inputs(cfg, rng)
    batch = {
        "latents": v, "timestep": t * 1000.0, "text_states": text,
        "text_mask": jnp.ones((2, 5), jnp.int32),
        "target": jnp.asarray(rng.standard_normal(v.shape), jnp.float32),
        "audio_latents": a,
        "audio_target": jnp.asarray(rng.standard_normal(a.shape), jnp.float32),
    }
    total, metrics = loss_fn(params, cfg, batch)
    assert np.isfinite(float(total))
    grads = jax.jit(jax.grad(lambda p: ltx2.loss_fn(p, cfg, batch)[0]))(params)
    # both streams and the A/V cross projections receive signal
    for key in ("patchify_proj", "audio_patchify_proj"):
        assert float(jnp.abs(grads[key]).sum()) > 0.0
    assert float(jnp.abs(grads["blocks"]["audio_to_video_attn"]["to_q"]).sum()) > 0.0


def test_hf_roundtrip(model, tmp_path):
    from safetensors.numpy import save_file

    cfg, params = model
    sd = params_to_hf(params, cfg)
    assert "transformer_blocks.0.audio_to_video_attn.to_q.weight" in sd
    assert "adaln_single.emb.timestep_embedder.linear_1.weight" in sd
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
              str(tmp_path / "model.safetensors"))
    loaded = hf_to_params(str(tmp_path), cfg)
    flat_a = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(params)}
    flat_b = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(loaded)}
    assert flat_a.keys() == flat_b.keys()
    for k in flat_a:
        np.testing.assert_array_equal(
            np.asarray(flat_a[k]), np.asarray(flat_b[k]), err_msg=k
        )


def test_dit_trainer_e2e(tmp_path):
    from veomni_tpu.arguments import VeOmniArguments
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer.dit_trainer import DiTTrainer

    rng = np.random.default_rng(0)
    nv = int(np.prod(TINY["video_shape"]))
    rows = []
    for _ in range(12):
        rows.append({
            "latents": rng.standard_normal((nv, TINY["in_channels"])).tolist(),
            "text_states": rng.standard_normal((5, TINY["caption_channels"])).tolist(),
            "audio_latents": rng.standard_normal(
                (TINY["audio_len"], TINY["audio_in_channels"])).tolist(),
        })
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "ltx2", **TINY,
        "dtype": "float32", "param_dtype": "float32",
        "latent_shape": (nv, TINY["in_channels"]), "text_len": 8,
    }
    args.data.train_path = str(tmp_path / "data.jsonl")
    args.train.output_dir = str(tmp_path / "out")
    args.train.micro_batch_size = 1
    args.train.train_steps = 2
    args.train.bf16 = False
    args.train.async_save = False
    args.train.log_steps = 100
    destroy_parallel_state()
    try:
        trainer = DiTTrainer(args)
        ctl = trainer.train()
        assert ctl.global_step == 2
        assert np.isfinite(ctl.metrics["loss"])
        trainer.checkpointer.close()
    finally:
        destroy_parallel_state()
