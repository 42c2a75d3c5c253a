"""Cosmos FSQ tokenizer: wavelet exactness, FSQ invariants, encode/decode,
omni-composite integration (reference decoder/cosmos)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models.cosmos import (
    CosmosConfig,
    _dwt,
    _idwt,
    fsq_indices_to_codes,
    fsq_quantize,
)
from veomni_tpu.models import cosmos, omni
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, encode, decode, decode_code = (
    under_jit(f) for f in (cosmos.init_params, cosmos.encode, cosmos.decode, cosmos.decode_code))
init_omni_params, omni_loss_fn = under_jit(omni.init_omni_params), under_jit(omni.omni_loss_fn)

TINY = dict(channels=8, channels_mult=(1, 2), num_res_blocks=1,
            attn_resolutions=(4,), in_channels=3, out_channels=3,
            resolution=16, patch_size=2, spatial_compression=4,
            z_channels=8, embedding_dim=4, levels=(5, 5, 4, 4),
            num_groups=4)


def test_haar_roundtrip_exact():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    y = _idwt(_dwt(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)


def test_fsq_invariants():
    rng = np.random.default_rng(1)
    levels = (5, 5, 4, 4)
    z = jnp.asarray(rng.standard_normal((7, len(levels))) * 3, jnp.float32)
    zhat, idx = fsq_quantize(z, levels)
    assert np.all(np.asarray(idx) >= 0)
    assert np.all(np.asarray(idx) < int(np.prod(levels)))
    # the implicit codebook reproduces the quantized vector exactly
    codes = fsq_indices_to_codes(idx, levels)
    np.testing.assert_allclose(np.asarray(codes), np.asarray(zhat), atol=1e-6)
    # straight-through: gradient of sum(zhat) wrt z is the bound's gradient
    g = jax.grad(lambda q: fsq_quantize(q, levels)[0].sum())(z)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.abs(g).sum()) > 0.0


def test_encode_decode_shapes():
    cfg = CosmosConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    px = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    zhat, idx, qloss = encode(params, cfg, px)
    assert idx.shape == (2, 4, 4)          # 16 / spatial_compression 4
    assert zhat.shape == (2, 4, 4, len(cfg.levels))
    assert np.allclose(np.asarray(qloss), 0.0)  # FSQ: no commit loss
    rec = decode(params, cfg, zhat)
    assert rec.shape == (2, 16, 16, 3)
    rec2 = decode_code(params, cfg, idx.reshape(2, -1))
    np.testing.assert_allclose(np.asarray(rec), np.asarray(rec2), atol=1e-5)


def test_omni_composite_with_cosmos():
    from veomni_tpu.models.omni import OmniConfig

    TEXT = dict(model_type="qwen2", vocab_size=600, hidden_size=64,
                intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                attention_bias=True)
    cfg = OmniConfig(
        text=TEXT,
        image_gen={"decoder_type": "cosmos", "movq": dict(TINY)},
        image_gen_token_id=512, max_gen_images=1,
    )
    assert cfg.image_gen.tokens_per_image == 16
    assert cfg.image_gen.image_size == 16
    params = init_omni_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    s = 48
    t_gen = 16
    ids = rng.integers(1, 500, (1, s)).astype(np.int32)
    ids[0, 8:8 + t_gen] = 512
    labels = np.roll(ids, -1, 1).astype(np.int32)
    labels[:, -1] = -100
    batch = {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(labels),
        "position_ids": jnp.broadcast_to(jnp.arange(s), (1, s)).astype(jnp.int32),
        "segment_ids": jnp.ones((1, s), jnp.int32),
        "gen_pixels": jnp.asarray(rng.random((1, 1, 16, 16, 3), np.float32) * 2 - 1),
        "gen_image_mask": jnp.ones((1, 1), bool),
    }
    total, metrics = omni_loss_fn(params, cfg, batch)
    assert np.isfinite(float(total))
    assert int(metrics["gen_ntokens"]) == t_gen
    grads = jax.jit(jax.grad(lambda p: omni.omni_loss_fn(p, cfg, batch)[0]))(params)
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(grads["image_gen"]["movq"]))
    assert float(jnp.abs(grads["image_gen"]["gen_head"]["fc2"]).sum()) > 0.0
