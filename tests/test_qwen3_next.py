"""qwen3_next (hybrid GatedDeltaNet) framework integration: sharded train
step + HF export round-trip. (HF numerical parity lives in
test_hf_parity.py; reference capability: models/transformers/qwen3_5/.)
"""

import jax
import jax.numpy as jnp
import numpy as np


def _cfg(moe=True):
    from veomni_tpu.models.config import TransformerConfig

    return TransformerConfig(
        model_type="qwen3_next",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.25, norm_zero_centered=True,
        attn_output_gate=True,
        linear_num_value_heads=4, linear_num_key_heads=2,
        linear_key_head_dim=16, linear_value_head_dim=16,
        full_attention_interval=4,
        **(dict(num_experts=4, num_experts_per_tok=2, moe_intermediate_size=48,
                shared_expert_intermediate_size=32, shared_expert_gated=True,
                router_aux_loss_coef=0.0) if moe else {}),
        dtype=jnp.float32,
    )


def _batch(bsz=4, seq=32, vocab=256):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (1, bsz, seq))
    return {
        "input_ids": jnp.asarray(ids, jnp.int32),
        "labels": jnp.asarray(ids, jnp.int32),
        "position_ids": jnp.asarray(
            np.broadcast_to(np.arange(seq), ids.shape).copy(), jnp.int32),
        "segment_ids": jnp.ones(ids.shape, jnp.int32),
    }


def test_sharded_train_step_fsdp_ep():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import resolve_state_shardings

    destroy_parallel_state()
    ps = init_parallel_state(ep_size=2, dp_shard_size=4)
    with use_parallel_state(ps):
        model = build_foundation_model(config=_cfg())
        plan = model.get_parallel_plan()
        opt = build_optimizer(
            model.abstract(), lr=build_lr_scheduler(lr=1e-3, train_steps=4))

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, model.config), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, plan, ps)
        # EP rule applies to the double-stacked expert tensors: dim 2 = E
        exp_sh = shardings.params["linear_layers"]["experts"]["gate_proj"]
        assert exp_sh.spec[:3] == (None, None, "ep"), exp_sh.spec
        state = jax.jit(make_state, out_shardings=shardings)(jax.random.PRNGKey(0))
        batch = _batch()
        bsh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes))
               for k in batch}
        step = build_train_step(model.loss_fn, opt, ps,
                                state_shardings=shardings, batch_shardings=bsh)
        batch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]  # trains
    destroy_parallel_state()


def test_hf_export_roundtrip(tmp_path):
    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(config=_cfg(moe=True))
    params = model.init(jax.random.PRNGKey(0))
    out = str(tmp_path / "hf")
    model.save_hf(out)

    model2 = build_foundation_model(out, dtype=jnp.float32)
    params2 = model2.load_hf(out)
    batch = _batch(bsz=2, seq=16)
    batch = {k: v[0] for k, v in batch.items()}
    l1, m1 = jax.jit(model.loss_fn)(params, batch)
    l2, m2 = jax.jit(model2.loss_fn)(params2, batch)
    np.testing.assert_allclose(
        float(l1 / m1["ntokens"]), float(l2 / m2["ntokens"]), rtol=1e-6)

    # streamed shard-aligned load (EP-sliced expert reads) == plain load
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
    try:
        ps = init_parallel_state(ep_size=2, dp_shard_size=4)
        with use_parallel_state(ps):
            shardings = model2.get_parallel_plan().resolve(
                jax.eval_shape(lambda: params2), ps
            )
            sharded = model2.family.hf_to_params(out, model2.config, shardings)
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(params2),
            jax.tree_util.tree_leaves_with_path(sharded),
        ):
            assert pa == pb
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=str(pa))
    finally:
        destroy_parallel_state()


def test_gated_delta_rule_segment_reset():
    """Packed 2-document row == per-document runs (reference varlen
    cu_seqlens semantics: no state leaks across documents). Documents are
    sized so one boundary falls mid-chunk and one document crosses a chunk
    boundary (exercising the in-chunk pair masks AND the carried-state
    continuation/keep masks)."""
    from veomni_tpu.models.qwen3_next import chunk_gated_delta_rule

    rng = np.random.default_rng(7)
    b, h, dk, dv = 2, 3, 8, 8
    la, lb = 40, 56  # chunk=64: boundary at 40; doc B spans chunks 0->1
    s = la + lb

    def mk(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    from veomni_tpu.models.qwen3_next import _l2norm

    # q/k l2-normalized as in the model: the delta-rule in-chunk inversion
    # is only well-conditioned for unit keys (real usage always normalizes)
    q, k = _l2norm(mk(b, s, h, dk)), _l2norm(mk(b, s, h, dk))
    v = mk(b, s, h, dv)
    g = -jnp.abs(mk(b, s, h)) * 0.1
    beta = jax.nn.sigmoid(mk(b, s, h))
    seg = jnp.asarray([[1] * la + [2] * lb] * b, jnp.int32)

    chunk_gated_delta_rule = jax.jit(chunk_gated_delta_rule)   # one program a shape
    packed = chunk_gated_delta_rule(q, k, v, g, beta, segment_ids=seg)
    out_a = chunk_gated_delta_rule(
        q[:, :la], k[:, :la], v[:, :la], g[:, :la], beta[:, :la])
    out_b = chunk_gated_delta_rule(
        q[:, la:], k[:, la:], v[:, la:], g[:, la:], beta[:, la:])
    np.testing.assert_allclose(packed[:, :la], out_a, atol=2e-4)
    np.testing.assert_allclose(packed[:, la:], out_b, atol=2e-4)

    # segment_ids=None (single doc) still matches an all-ones mask run
    ref = chunk_gated_delta_rule(q, k, v, g, beta)
    one = chunk_gated_delta_rule(
        q, k, v, g, beta, segment_ids=jnp.ones((b, s), jnp.int32))
    np.testing.assert_allclose(ref, one, atol=1e-6)


def test_forward_packed_vs_separate_documents():
    """Full hybrid forward: each document of a packed row equals its
    standalone forward (conv taps, delta-rule state, and full attention all
    boundary-isolated)."""
    from veomni_tpu.models.qwen3_next import abstract_params  # noqa: F401
    from veomni_tpu.models import qwen3_next
    from veomni_tpu.utils.testing import under_jit

    # the whole stack as one program a shape, not op by op
    init_params, forward_hidden = under_jit(qwen3_next.init_params), under_jit(qwen3_next.forward_hidden)
    cfg = _cfg(moe=False)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(3)
    la, lb = 20, 12
    ids_a = rng.integers(0, cfg.vocab_size, (1, la))
    ids_b = rng.integers(0, cfg.vocab_size, (1, lb))

    packed = {
        "input_ids": jnp.asarray(np.concatenate([ids_a, ids_b], 1), jnp.int32),
        "position_ids": jnp.asarray(
            np.concatenate([np.arange(la)[None], np.arange(lb)[None]], 1),
            jnp.int32),
        "segment_ids": jnp.asarray([[1] * la + [2] * lb], jnp.int32),
    }
    hp, _, _ = forward_hidden(params, cfg, packed["input_ids"],
                              packed["position_ids"], packed["segment_ids"])
    for ids, lo, hi in ((ids_a, 0, la), (ids_b, la, la + lb)):
        n = hi - lo
        hs, _, _ = forward_hidden(
            params, cfg, jnp.asarray(ids, jnp.int32),
            jnp.asarray(np.arange(n)[None], jnp.int32),
            jnp.ones((1, n), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(hp[:, lo:hi]), np.asarray(hs), atol=2e-4,
            err_msg=f"doc [{lo}:{hi}] leaked cross-document state")
