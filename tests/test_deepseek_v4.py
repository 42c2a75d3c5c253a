"""DeepSeek-V4 dialect: structure, packing equivalence, mHC invariants,
hash/topk routing, HF io round-trip.

No torch oracle exists for this family (transformers ships only
deepseek_v2/v3; the reference's modeling file is ByteDance-internal), so the
suite leans on *internal invariants* the architecture must satisfy:
packing-equivalence exercises every segment-aware code path (sliding mask,
HCA/CSA window alignment, indexer causality), which is where a sparse
implementation breaks first."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models import deepseek_v4
from veomni_tpu.models.deepseek_v4 import DeepseekV4Config


from veomni_tpu.utils.testing import under_jit

init_params, loss_fn, forward_logits = (
    under_jit(f) for f in (deepseek_v4.init_params, deepseek_v4.loss_fn, deepseek_v4.forward_logits))

CFG = dict(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=32,
    num_hidden_layers=3,
    num_attention_heads=2,
    head_dim=16,
    q_lora_rank=16,
    o_groups=2,
    o_lora_rank=8,
    sliding_window=8,
    layer_types=("sliding_attention", "compressed_sparse_attention",
                 "heavily_compressed_attention"),
    mlp_layer_types=("hash_moe", "topk_moe", "topk_moe"),
    compress_rate_hca=8,
    compress_rate_csa=4,
    index_n_heads=2,
    index_head_dim=8,
    index_topk=3,
    hc_mult=2,
    num_experts=4,
    num_experts_per_tok=2,
    rope_parameters={
        "main": {"rope_theta": 10000.0, "partial_rotary_factor": 0.5},
        "compress": {"rope_theta": 5000.0, "partial_rotary_factor": 0.5},
    },
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    remat=False,
)


@pytest.fixture(scope="module")
def model():
    cfg = DeepseekV4Config(**CFG)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # give the hash router a non-trivial frozen table
    rng = np.random.default_rng(0)
    params["runs"][0]["mlp"]["tid2eid"] = jnp.asarray(
        rng.integers(0, cfg.num_experts,
                     (1, cfg.vocab_size, cfg.num_experts_per_tok)),  # [L=1,V,K]
        jnp.int32,
    )
    return cfg, params


def _batch(cfg, rng, rows, seq):
    ids = rng.integers(1, cfg.vocab_size, (rows, seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)
    labels[:, -1] = -100
    return {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(labels),
        "position_ids": jnp.broadcast_to(jnp.arange(seq), (rows, seq)).astype(jnp.int32),
        "segment_ids": jnp.ones((rows, seq), jnp.int32),
    }


def test_forward_finite_and_grads(model):
    cfg, params = model
    batch = _batch(cfg, np.random.default_rng(1), 2, 32)
    total, metrics = loss_fn(params, cfg, batch)
    assert np.isfinite(float(total))
    assert int(metrics["ntokens"]) == 2 * 31

    # allow_int: the frozen hash table (tid2eid, int32) rides in params
    grads = jax.jit(jax.grad(lambda p: deepseek_v4.loss_fn(p, cfg, batch)[0], allow_int=True))(params)
    # every trainable leaf gets gradient signal, EXCEPT: the frozen hash
    # table (int, non-diff) and the lightning indexer (it only drives the
    # non-differentiable top-k selection; the reference trains it with a
    # separate alignment objective, not the LM loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    dead = [jax.tree_util.keystr(p) for p, g in flat
            if g.dtype.kind == "f" and float(jnp.abs(g).sum()) == 0.0]
    # e_score_correction_bias shifts only the (non-diff) top-k choice —
    # deepseek updates it with the noaux-tc balance rule, not gradients
    allowed_dead = ("tid2eid", "indexer", "e_score_correction_bias")
    assert not [d for d in dead if not any(a in d for a in allowed_dead)], dead


def test_packing_equivalence(model):
    """Loss of two sequences packed into one row (segment ids 1/2) must equal
    the sum of their standalone losses — exercises sliding mask, HCA/CSA
    window alignment, overlap windows, and indexer causality under packing."""
    cfg, params = model
    rng = np.random.default_rng(2)
    la, lb = 24, 16
    ids_a = rng.integers(1, cfg.vocab_size, la).astype(np.int32)
    ids_b = rng.integers(1, cfg.vocab_size, lb).astype(np.int32)

    def solo(ids):
        n = len(ids)
        lab = np.concatenate([ids[1:], [-100]]).astype(np.int32)
        batch = {
            "input_ids": jnp.asarray(ids)[None],
            "labels": jnp.asarray(lab)[None],
            "position_ids": jnp.arange(n, dtype=jnp.int32)[None],
            "segment_ids": jnp.ones((1, n), jnp.int32),
        }
        total, m = loss_fn(params, cfg, batch)
        return float(m["loss_sum"]), int(m["ntokens"])

    sa, na = solo(ids_a)
    sb, nb = solo(ids_b)

    packed_ids = np.concatenate([ids_a, ids_b])
    packed_lab = np.concatenate(
        [ids_a[1:], [-100], ids_b[1:], [-100]]
    ).astype(np.int32)
    packed = {
        "input_ids": jnp.asarray(packed_ids)[None],
        "labels": jnp.asarray(packed_lab)[None],
        "position_ids": jnp.concatenate(
            [jnp.arange(la), jnp.arange(lb)]
        ).astype(jnp.int32)[None],
        "segment_ids": jnp.concatenate(
            [jnp.ones(la, jnp.int32), jnp.full(lb, 2, jnp.int32)]
        )[None],
    }
    _, mp = loss_fn(params, cfg, packed)
    assert int(mp["ntokens"]) == na + nb
    np.testing.assert_allclose(float(mp["loss_sum"]), sa + sb, rtol=2e-5)


def test_padding_invariance(model):
    """Appending padding (segment 0) must not change the loss."""
    cfg, params = model
    rng = np.random.default_rng(3)
    batch = _batch(cfg, rng, 1, 24)
    _, m0 = loss_fn(params, cfg, batch)

    pad = 8
    batch_p = {
        "input_ids": jnp.pad(batch["input_ids"], ((0, 0), (0, pad))),
        "labels": jnp.pad(batch["labels"], ((0, 0), (0, pad)), constant_values=-100),
        "position_ids": jnp.pad(batch["position_ids"], ((0, 0), (0, pad))),
        "segment_ids": jnp.pad(batch["segment_ids"], ((0, 0), (0, pad))),
    }
    _, m1 = loss_fn(params, cfg, batch_p)
    np.testing.assert_allclose(float(m1["loss_sum"]), float(m0["loss_sum"]), rtol=1e-5)
    assert int(m1["ntokens"]) == int(m0["ntokens"])


def test_mhc_doubly_stochastic(model):
    """The Sinkhorn-projected comb matrix must be (approximately) doubly
    stochastic — the mHC manifold constraint."""
    from veomni_tpu.models.deepseek_v4 import _hyper_connection

    cfg, params = model
    rng = np.random.default_rng(4)
    streams = jnp.asarray(rng.standard_normal((2, 8, cfg.hc_mult, cfg.hidden_size)),
                          jnp.float32)
    lp_hc = jax.tree.map(lambda x: x[0], params["runs"][0]["attn_hc"])
    post, comb, collapsed = _hyper_connection(lp_hc, cfg, streams)
    rows = np.asarray(comb.sum(-1))
    cols = np.asarray(comb.sum(-2))
    np.testing.assert_allclose(rows, 1.0, atol=5e-3)
    np.testing.assert_allclose(cols, 1.0, atol=5e-3)
    assert post.shape == (2, 8, cfg.hc_mult)
    assert collapsed.shape == (2, 8, cfg.hidden_size)


def test_hash_router_uses_frozen_table(model):
    """Hash-MoE expert selection must follow tid2eid exactly (selection is
    static; only the mixing weights are learned)."""
    from veomni_tpu.models.deepseek_v4 import _dsv4_moe

    cfg, params = model
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((6, cfg.hidden_size)), jnp.float32)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, 6), jnp.int32)
    lp = jax.tree.map(lambda a: a[0], params["runs"][0]["mlp"])

    out1, _ = _dsv4_moe(lp, cfg, x, ids, "hash_moe")
    # permuting the frozen table for the used ids changes the output
    tbl = np.asarray(lp["tid2eid"])
    tbl2 = tbl.copy()
    tbl2[np.asarray(ids)] = (tbl2[np.asarray(ids)] + 1) % cfg.num_experts
    lp2 = dict(lp, tid2eid=jnp.asarray(tbl2))
    out2, _ = _dsv4_moe(lp2, cfg, x, ids, "hash_moe")
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


def test_sliding_window_layer_masks(model):
    """A pure-sliding config must not attend beyond the window: moving a
    distant token (outside every window + no compressed path) must leave the
    last-token logits unchanged."""
    cfg0 = dict(CFG)
    cfg0.update(layer_types=("sliding_attention",) * 3,
                mlp_layer_types=("topk_moe",) * 3, sliding_window=4)
    cfg = DeepseekV4Config(**cfg0)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(6)
    s = 16
    ids = rng.integers(1, cfg.vocab_size, s).astype(np.int32)
    ids2 = ids.copy()
    ids2[0] = (ids2[0] + 1) % cfg.vocab_size or 1
    pos = jnp.arange(s, dtype=jnp.int32)[None]

    l1 = forward_logits(params, cfg, jnp.asarray(ids)[None], pos)
    l2 = forward_logits(params, cfg, jnp.asarray(ids2)[None], pos)
    np.testing.assert_allclose(
        np.asarray(l1[0, -1]), np.asarray(l2[0, -1]), atol=1e-5
    )
    # sanity: within the window, changing a token does change the logits
    ids3 = ids.copy()
    ids3[-2] = (ids3[-2] + 1) % cfg.vocab_size or 1
    l3 = forward_logits(params, cfg, jnp.asarray(ids3)[None], pos)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l3[0, -1]), atol=1e-5)


def test_hca_reaches_beyond_window(model):
    """An HCA layer must carry long-range signal: with sliding_window=4 and
    one HCA layer, changing a token in a *completed compression window* far
    outside the sliding window must change the last-token logits."""
    cfg0 = dict(CFG)
    cfg0.update(layer_types=("heavily_compressed_attention",),
                mlp_layer_types=("topk_moe",), num_hidden_layers=1,
                sliding_window=4, compress_rate_hca=4)
    cfg = DeepseekV4Config(**cfg0)
    params = init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(7)
    s = 24
    ids = rng.integers(1, cfg.vocab_size, s).astype(np.int32)
    ids2 = ids.copy()
    ids2[1] = (ids2[1] + 1) % cfg.vocab_size or 1  # inside window 0 (complete)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    l1 = forward_logits(params, cfg, jnp.asarray(ids)[None], pos)
    l2 = forward_logits(params, cfg, jnp.asarray(ids2)[None], pos)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]), atol=1e-6)


def test_registry_and_hf_roundtrip(model, tmp_path):
    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models.auto import MODEL_REGISTRY

    cfg, params = model
    fam = MODEL_REGISTRY.get("deepseek_v4")
    out = tmp_path / "hf"
    fam.save_hf_checkpoint(params, cfg, str(out))

    m2 = build_foundation_model(str(out))
    assert m2.config.model_type == "deepseek_v4"
    assert m2.config.layer_types == cfg.layer_types
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
