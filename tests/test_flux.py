"""FLUX.1 MMDiT: structural self-tests.

No diffusers oracle in this environment (the reference's flux wraps the
public FLUX.1 weights), so these tests pin the architecture's own contract:
double/single-stream flow, conditioning paths (timestep / pooled / guidance),
text-mask semantics, diffusers-format key layout round-trip, and a full
DiTTrainer drive."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import flux
from veomni_tpu.models.flux import FluxConfig, hf_to_params, params_to_hf
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, loss_fn, flux_forward = (
    under_jit(f) for f in (flux.init_params, flux.loss_fn, flux.flux_forward))

TINY = dict(
    in_channels=8,
    num_layers=2,
    num_single_layers=2,
    attention_head_dim=24,   # rope axes 8/8/8
    num_attention_heads=2,
    joint_attention_dim=32,
    pooled_projection_dim=16,
    guidance_embeds=True,
    axes_dims_rope=(8, 8, 8),
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    remat=False,
)


@pytest.fixture(scope="module")
def model():
    cfg = FluxConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_forward_shape_and_conditioning(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    lat = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)  # 4x4 grid
    t = jnp.asarray([100.0, 700.0], jnp.float32)
    text = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    pooled = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    g = jnp.asarray([3.5, 3.5], jnp.float32)

    out = flux_forward(params, cfg, lat, t, text, pooled, guidance=g)
    assert out.shape == (2, 16, 8)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(flux_forward(params, cfg, lat, t, text, pooled, guidance=g)),
    )
    # every conditioning stream is live
    for other in (
        flux_forward(params, cfg, lat, t * 0.1, text, pooled, guidance=g),
        flux_forward(params, cfg, lat, t, text * -1.0, pooled, guidance=g),
        flux_forward(params, cfg, lat, t, text, pooled * -1.0, guidance=g),
        flux_forward(params, cfg, lat, t, text, pooled, guidance=g * 2.0),
    ):
        assert np.abs(np.asarray(out) - np.asarray(other)).max() > 1e-6


def test_text_mask_blocks_padding(model):
    """Padded text tokens (mask 0) must not influence the image stream."""
    cfg, params = model
    rng = np.random.default_rng(1)
    lat = jnp.asarray(rng.standard_normal((1, 16, 8)), jnp.float32)
    t = jnp.asarray([500.0], jnp.float32)
    pooled = jnp.asarray(rng.standard_normal((1, 16)), jnp.float32)
    text = rng.standard_normal((1, 6, 32)).astype(np.float32)
    mask = np.asarray([[1, 1, 1, 0, 0, 0]], np.int32)
    out1 = flux_forward(params, cfg, lat, t, jnp.asarray(text), pooled,
                        text_mask=jnp.asarray(mask))
    text2 = text.copy()
    text2[:, 3:] = rng.standard_normal((1, 3, 32))
    out2 = flux_forward(params, cfg, lat, t, jnp.asarray(text2), pooled,
                        text_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


def test_loss_and_grads_finite(model):
    cfg, params = model
    rng = np.random.default_rng(2)
    batch = {
        "latents": jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32),
        "timestep": jnp.asarray([100.0, 900.0], jnp.float32),
        "text_states": jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32),
        "pooled_text": jnp.asarray(rng.standard_normal((2, 16)), jnp.float32),
        "guidance": jnp.asarray([1.0, 1.0], jnp.float32),
        "target": jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32),
    }
    loss_sum, metrics = loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss_sum))
    grads = jax.jit(jax.grad(lambda p: flux.loss_fn(p, cfg, batch)[0]))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.all(np.isfinite(np.asarray(g))), jax.tree_util.keystr(path)
    # single-stream params receive signal
    assert float(jnp.abs(grads["single_blocks"]["out_w"]).sum()) > 0.0


def test_diffusers_roundtrip(model, tmp_path):
    from safetensors.numpy import save_file

    cfg, params = model
    sd = params_to_hf(params, cfg)
    # diffusers-format names present
    assert "transformer_blocks.0.attn.add_q_proj.weight" in sd
    assert "single_transformer_blocks.1.proj_mlp.weight" in sd
    assert "time_text_embed.guidance_embedder.linear_1.weight" in sd
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
    rows = []
    for _ in range(16):
        rows.append({
            "latents": rng.standard_normal((16, 8)).tolist(),
            "text_states": rng.standard_normal((5, 32)).tolist(),
            "pooled_text": rng.standard_normal(16).tolist(),
        })
    with open(tmp_path / "data.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    args = VeOmniArguments()
    args.model.config_overrides = {
        "model_type": "flux", **TINY,
        "dtype": "float32", "param_dtype": "float32",
        "latent_shape": (16, 8), "text_len": 8,
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
