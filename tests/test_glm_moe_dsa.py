"""glm_moe_dsa: MLA + DSA lightning-indexer sparse attention + sigmoid-MoE.

No torch oracle (the family is not in transformers), so the suite tests the
invariants the DSA machinery must satisfy: with ``index_topk >= seq_len`` the
sparse path must EQUAL the dense MLA path (selection keeps everything); with
a small top-k the output must differ from dense yet stay packing-consistent;
"shared" indexer layers must reuse the previous layer's selection; and the
indexer must receive no gradient from the LM loss (reference
``GlmMoeDsaIndexer.forward`` is @torch.no_grad)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.models import transformer
from veomni_tpu.utils.testing import under_jit

# whole models as one program a shape, not op by op
init_params, loss_fn = under_jit(transformer.init_params), under_jit(transformer.loss_fn)

BASE = dict(
    model_type="glm_moe_dsa",
    vocab_size=128,
    hidden_size=48,
    intermediate_size=64,
    moe_intermediate_size=32,
    num_hidden_layers=3,
    num_attention_heads=4,
    num_key_value_heads=4,
    q_lora_rank=24,
    kv_lora_rank=16,
    qk_nope_head_dim=8,
    qk_rope_head_dim=8,
    v_head_dim=8,
    rope_interleave=True,
    num_experts=4,
    num_experts_per_tok=2,
    scoring_func="sigmoid",
    n_group=2,
    topk_group=1,
    norm_topk_prob=True,
    n_shared_experts=1,
    first_k_dense_replace=1,
    index_n_heads=2,
    index_head_dim=16,
    index_topk=4,
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    remat=False,
)


def _mk(cfg_kw):
    cfg = TransformerConfig(**cfg_kw)
    params = init_params(jax.random.PRNGKey(0), cfg)
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


def test_topk_full_equals_dense():
    """index_topk >= S selects every causal position -> identical to the
    dense MLA model with the same weights minus the indexer."""
    rng = np.random.default_rng(0)
    s = 16
    kw = dict(BASE, index_topk=s)
    cfg, params = _mk(kw)
    batch = _batch(cfg, rng, 2, s)
    sparse_total, sparse_m = loss_fn(params, cfg, batch)

    dense_kw = dict(BASE)
    for k in ("index_n_heads", "index_head_dim", "index_topk"):
        dense_kw.pop(k)
    dense_cfg = TransformerConfig(**dense_kw)
    dense_params = jax.tree.map(lambda x: x, params)
    for tree_name in ("dense_layers", "layers"):
        dense_params[tree_name] = {
            k: v for k, v in params[tree_name].items() if k != "indexer"
        }
    dense_total, dense_m = loss_fn(dense_params, dense_cfg, batch)
    np.testing.assert_allclose(
        float(sparse_m["loss_sum"]), float(dense_m["loss_sum"]), rtol=1e-6
    )


def test_small_topk_differs_and_packs():
    rng = np.random.default_rng(1)
    cfg, params = _mk(BASE)

    # sparse != dense-selection (top-k actually bites)
    s = 16
    batch = _batch(cfg, rng, 1, s)
    _, m_small = loss_fn(params, cfg, batch)
    cfg_full = TransformerConfig(**dict(BASE, index_topk=s))
    _, m_full = loss_fn(params, cfg_full, batch)
    assert abs(float(m_small["loss_sum"]) - float(m_full["loss_sum"])) > 1e-6

    # packing equivalence: two segments in one row == two standalone rows
    la, lb = 12, 8
    ids_a = rng.integers(1, cfg.vocab_size, la).astype(np.int32)
    ids_b = rng.integers(1, cfg.vocab_size, lb).astype(np.int32)

    def solo(ids):
        n = len(ids)
        lab = np.concatenate([ids[1:], [-100]]).astype(np.int32)
        b = {
            "input_ids": jnp.asarray(ids)[None],
            "labels": jnp.asarray(lab)[None],
            "position_ids": jnp.arange(n, dtype=jnp.int32)[None],
            "segment_ids": jnp.ones((1, n), jnp.int32),
        }
        _, m = loss_fn(params, cfg, b)
        return float(m["loss_sum"])

    packed = {
        "input_ids": jnp.asarray(np.concatenate([ids_a, ids_b]))[None],
        "labels": jnp.asarray(np.concatenate(
            [ids_a[1:], [-100], ids_b[1:], [-100]]).astype(np.int32))[None],
        "position_ids": jnp.asarray(
            np.concatenate([np.arange(la), np.arange(lb)]).astype(np.int32))[None],
        "segment_ids": jnp.asarray(np.concatenate(
            [np.ones(la, np.int32), np.full(lb, 2, np.int32)]))[None],
    }
    _, mp = loss_fn(params, cfg, packed)
    np.testing.assert_allclose(
        float(mp["loss_sum"]), solo(ids_a) + solo(ids_b), rtol=2e-5
    )


def test_shared_indexer_reuses_selection():
    """With indexer_types full/shared/shared, perturbing the LAST layer's own
    indexer weights must not change the loss (its selection comes from layer
    1); perturbing layer 1's indexer must."""
    rng = np.random.default_rng(2)
    kw = dict(BASE, first_k_dense_replace=0,
              indexer_types=("full", "shared", "shared"))
    cfg, params = _mk(kw)
    batch = _batch(cfg, rng, 1, 16)
    base_loss = float(loss_fn(params, cfg, batch)[1]["loss_sum"])

    def bump(layer):
        # re-randomize the layer's indexer query projection: a fresh matrix
        # re-ranks the relu scores (a mere scale would preserve the top-k)
        p2 = jax.tree.map(lambda x: x, params)
        idx = dict(p2["layers"]["indexer"])
        wq = np.asarray(idx["wq_b"]).copy()
        wq[layer] = np.random.default_rng(99).standard_normal(wq[layer].shape) * 0.5
        idx["wq_b"] = jnp.asarray(wq)
        p2["layers"] = dict(p2["layers"], indexer=idx)
        return float(loss_fn(p2, cfg, batch)[1]["loss_sum"])

    assert bump(2) == base_loss            # shared layer: own indexer unused
    assert bump(0) != base_loss            # provider layer: selection shifts


def test_indexer_gets_no_lm_gradient():
    rng = np.random.default_rng(3)
    cfg, params = _mk(BASE)
    batch = _batch(cfg, rng, 1, 16)
    grads = jax.jit(jax.grad(lambda p: transformer.loss_fn(p, cfg, batch)[0]))(params)
    for tree in ("dense_layers", "layers"):
        for leaf in jax.tree.leaves(grads[tree]["indexer"]):
            assert float(jnp.abs(leaf).max()) == 0.0


def test_hf_roundtrip(tmp_path):
    from veomni_tpu.models import build_foundation_model, hf_io

    cfg, params = _mk(BASE)
    out = tmp_path / "hf"
    hf_io.save_hf_checkpoint(params, cfg, str(out))
    m2 = build_foundation_model(str(out))
    assert m2.config.model_type == "glm_moe_dsa"
    assert m2.config.use_dsa and m2.config.index_topk == cfg.index_topk
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
