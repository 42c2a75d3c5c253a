"""JoyAI-LLM-Flash (the deepseek_v3 dialect at other widths) on the train
path, at a tiny size on the CPU: the program against the benchmark's plain
reference (``benchmark/reference/mla_moe.py``) for the loss and every gradient
leaf, the expert layer's shares against the uncut layer, multi-token
prediction's masking in a packed row, the held experts' buffer, checkpoint
names, and the routing counters through ``TextTrainer.train()``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe as ref
from veomni_tpu.models import hf_io, transformer
from veomni_tpu.models.auto import build_config
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.utils.testing import under_jit

# the benchmark configuration's keys (its rehearsal preset's sizes): the
# published names at the top, 4 of 16 experts held from the 5th
MODEL = dict(
    model_type="joyai_llm_flash", vocab_size=128, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, n_routed_experts_published=16, first_expert_held=4,
    num_experts_per_tok=4, moe_intermediate_size=32, n_shared_experts=1,
    first_k_dense_replace=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    num_nextn_predict_layers=1, rms_norm_eps=1e-6, rope_theta=32000000, mtp_loss_weight=0.3)
PROGRAM_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "first_k_dense_replace", "routed_scaling_factor",
    "num_nextn_predict_layers", "rms_norm_eps", "rope_theta", "mtp_loss_weight")


def program_cfg(model=MODEL, **kw) -> TransformerConfig:
    held = model["n_routed_experts"] != model["n_routed_experts_published"]
    return build_config(
        "joyai_llm_flash", **{k: model[k] for k in PROGRAM_KEYS},
        num_experts=model["n_routed_experts_published"],
        moe_experts_held=model["n_routed_experts"] if held else 0,
        moe_experts_held_first=model["first_expert_held"] if held else 0,
        n_group=1, topk_group=1, dtype="float32", **kw)


def packed_batch(seed=0, rows=2, s=64):
    """Two packed rows: documents of several lengths, one of a single token,
    trailing padding; labels and positions as the collator makes them."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, MODEL["vocab_size"], (rows, s)).astype(np.int32)
    seg = np.stack([
        np.concatenate([np.full(20, 1), np.full(30, 2), np.zeros(14)]),
        np.concatenate([np.full(5, 1), np.full(2, 2), np.full(1, 3), np.full(50, 4), np.zeros(6)]),
    ]).astype(np.int32)[:rows, :s]
    pos = np.stack([np.asarray(ref.row_targets(jnp.asarray(i), jnp.asarray(g))[2])
                    for i, g in zip(ids, seg)]).astype(np.int32)
    nxt = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    nseg = np.concatenate([seg[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    labels = np.where((nseg == seg) & (seg > 0), nxt, -100).astype(np.int32)
    return {k: jnp.asarray(v) for k, v in dict(
        input_ids=ids, position_ids=pos, segment_ids=seg, labels=labels).items()}


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# the whole model as one program a (cfg, shape), not op by op
loss_fn = under_jit(transformer.loss_fn)


def seeded(seed, model=MODEL):
    """The reference's seeded weights, drawn as one program (drawn eagerly
    each leaf is a dispatch of its own)."""
    return jax.jit(lambda key: ref.nest(ref.make_params(model, key)))(ref.seed_key(seed))


def moe_layer(x, lp, cfg):
    """``transformer.moe_mlp_with_stats`` as one program."""
    return jax.jit(lambda x, lp: transformer.moe_mlp_with_stats(x, lp, cfg))(x, lp)


def ref_expert_layer(x, lp, model):
    return jax.jit(lambda x, lp: ref.expert_layer(x, lp, model))(x, lp)


# ------------------------------------------------------- program vs reference
def test_seeded_weights_are_the_programs_tree():
    cfg = program_cfg()
    want = transformer.abstract_params(cfg)
    got = jax.eval_shape(lambda k: ref.nest(ref.make_params(MODEL, k)), ref.seed_key(2 ** 31 + 5))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    assert want["layers"]["router"].shape[-1] == 16          # routes over all
    assert want["layers"]["experts"]["gate_proj"].shape[:2] == (2, 4)  # holds its share
    assert set(want["mtp"]) >= {"enorm", "hnorm", "eh_proj", "norm", "router", "q_a_proj"}


@pytest.mark.parametrize("factor", [0.0, 4.0, 1.0], ids=["dropless", "roomy", "rank_capacity"])
def test_program_matches_the_reference_in_float32(factor):
    """Loss (main and MTP) and EVERY gradient leaf. Tolerance: 5e-6 of the
    leaf's largest entry. Both sides are float32 at highest precision and
    differ in the order of their sums alone (the program sorts and groups
    rows, the reference visits every position with every held expert); 1e-6
    was read, and one flipped top-k choice would move a leaf by 1e-2. Under a
    rank capacity of the even share (the cell's) both drop the same
    assignments: those that come after the 128th to a held expert, counted
    over the micro-batch's rows one after another."""
    cfg = program_cfg(moe_capacity_factor=factor)
    model = dict(MODEL, moe_capacity_factor=factor)
    params = seeded(3)
    batch = packed_batch()

    def program(p):
        total, m = transformer.loss_fn(p, cfg, batch)
        return total / m["ntokens"], m

    (loss, m), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
    (want, (main, mtp)), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.total_loss(p, model, batch["input_ids"], batch["segment_ids"]),
        has_aux=True))(params)
    routed, held, dropped, *_ = (float(c) for c in m["moe_assignment_counts"])
    assert routed == 3 * 128 * 4 and 0 < held < routed   # two expert layers and the MTP module's
    if factor == 1.0:
        assert ref.rank_capacity(model, 128) == transformer.held_rows(cfg, 128) == 128
        assert dropped > 0 and float(m["moe_dropped_frac"]) == pytest.approx(dropped / held)
    else:
        assert dropped == 0.0 and float(m["moe_dropped_frac"]) == 0.0
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    np.testing.assert_allclose(float(m["loss_sum"] / m["ntokens"]), float(main), rtol=2e-6)
    np.testing.assert_allclose(float(m["mtp_loss"]), float(mtp), rtol=2e-6)
    got, want_flat = ref.flatten(grads), ref.flatten(want_grads)
    assert set(got) == set(want_flat)
    for name in sorted(want_flat):
        scale = float(jnp.abs(want_flat[name]).max())
        if name.endswith("e_score_correction_bias"):
            assert scale == 0.0 and float(jnp.abs(got[name]).max()) == 0.0  # no gradient
            continue
        assert scale > 0, name
        gap = float(jnp.abs(got[name] - want_flat[name]).max()) / scale
        assert gap < 5e-6, (name, gap)


def test_mtp_term_is_in_the_loss_with_its_weight():
    params = seeded(4)
    batch = packed_batch(1)
    total, m = loss_fn(params, program_cfg(moe_capacity_factor=0.0), batch)
    n = float(m["ntokens"])
    assert float(m["mtp_loss"]) > 1.0
    np.testing.assert_allclose(float(total) / n,
                               float(m["loss_sum"]) / n + 0.3 * float(m["mtp_loss"]), rtol=1e-6)
    none = dict(MODEL, num_nextn_predict_layers=0)
    p0 = {k: v for k, v in params.items() if k != "mtp"}
    total0, m0 = loss_fn(p0, program_cfg(none), batch)
    assert "mtp_loss" not in m0
    np.testing.assert_allclose(float(total0), float(m["loss_sum"]), rtol=1e-6)


# ------------------------------------------------------------------- shares
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The guide's test: what all the shares give, with what every chip
    computes alike (the shared expert) counted once, is what the uncut
    reference layer gives."""
    whole = dict(MODEL, n_routed_experts=16, first_expert_held=0)
    lp = jax.tree.map(lambda t: t[0], seeded(9, whole)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (96, MODEL["hidden_size"]), jnp.float32)
    uncut = ref_expert_layer(x, lp, whole)
    se = lp["shared_experts"]
    shared = ref._swiglu(x, se["gate_proj"], se["up_proj"], se["down_proj"], None)
    total, held_rows, shares = jnp.zeros_like(x), 0.0, 4
    for j in range(shares):
        first = 4 * j
        part = dict(lp, experts={k: v[first:first + 4] for k, v in lp["experts"].items()})
        cfg = program_cfg(dict(MODEL, first_expert_held=first), moe_capacity_factor=0.0)
        out, _, (dropped, held, *_) = moe_layer(x, part, cfg)
        assert float(dropped) == 0.0
        # the reference, given the same share, gives the same part
        want = ref_expert_layer(x, part, dict(MODEL, first_expert_held=first))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6)
        total, held_rows = total + out - shared, held_rows + float(held)
    assert held_rows == x.shape[0] * MODEL["num_experts_per_tok"]  # every assignment, once
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut), rtol=1e-5, atol=1e-6)
    # and the program's own uncut layer (all experts held: the old path)
    out, _, (_, held, *_, load) = moe_layer(x, lp, program_cfg(whole))
    np.testing.assert_allclose(np.asarray(out), np.asarray(uncut), rtol=1e-5, atol=1e-6)
    assert float(held) == held_rows and float(load) >= 1.0


def test_a_buffer_too_short_drops_and_counts_and_a_long_one_is_dropless():
    params = seeded(5)
    lp = jax.tree.map(lambda t: t[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (128, MODEL["hidden_size"]), jnp.float32)
    dropless = program_cfg(moe_capacity_factor=0.0)
    assert transformer.held_rows(dropless, 128) == 128 * 4
    full, _, (d0, held, *_) = moe_layer(x, lp, dropless)
    assert float(d0) == 0.0
    roomy = program_cfg(moe_capacity_factor=2.0)   # 2 x 128 = 256 rows >= what it got
    assert transformer.held_rows(roomy, 128) == 256 and float(held) <= 256
    out, _, (d1, held1, *_) = moe_layer(x, lp, roomy)
    assert float(d1) == 0.0 and float(held1) == float(held)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), rtol=1e-6, atol=1e-7)
    tight = program_cfg(moe_capacity_factor=0.5)   # 128 rows: fewer than it got
    assert transformer.held_rows(tight, 128) == 128 < float(held)
    short, _, (d2, held2, *_) = moe_layer(x, lp, tight)
    assert float(held2) == float(held)             # counted before the cut
    assert float(d2) == float(held) - 128         # the assignments past the buffer
    assert float(jnp.abs(short - full).max()) > 1e-3 and bool(jnp.isfinite(short).all())


def test_the_layer_counts_the_grouped_gemms_tile_visits():
    """At widths the kernel takes (multiples of 128) the layer's stats carry
    the live (row tile, expert) visits of its grouped GEMM and the pairs a
    grid over every pair would walk; at the toy widths both are 0."""
    wide = dict(MODEL, hidden_size=128, moe_intermediate_size=128)
    lp = jax.tree.map(lambda t: t[0], seeded(5, wide)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 128), jnp.float32)
    cfg = program_cfg(wide, moe_capacity_factor=0.0)  # a buffer of 512 rows: 4 tiles of 128
    _, _, (_, held, visits, pairs, _) = moe_layer(x, lp, cfg)
    assert float(pairs) == 4 * 4
    # at least the tiles the held rows fill, at most one more per expert boundary
    assert -(-float(held) // 128) <= float(visits) <= -(-float(held) // 128) + 3
    toy = jax.tree.map(lambda t: t[0], seeded(5)["layers"])
    _, _, (_, _, visits, pairs, _) = transformer.moe_mlp_with_stats(
        x[:, :64], toy, program_cfg(moe_capacity_factor=0.0))
    assert (float(visits), float(pairs)) == (0.0, 0.0)


def test_tile_counters_and_their_share_reach_the_registry():
    from veomni_tpu.observability.callback import ObservabilityCallback
    from veomni_tpu.observability.metrics import MetricsRegistry

    cb = ObservabilityCallback()
    cb.registry = MetricsRegistry()
    cb._moe_pending = [(jnp.asarray([100.0, 40.0, 4.0, 6.0, 64.0]), jnp.float32(2.0)),
                       (jnp.asarray([100.0, 10.0, 0.0, 2.0, 64.0]), jnp.float32(3.0))]
    cb._flush_moe()
    read = lambda name: cb.registry.get(name).value
    assert (read("moe.gmm.tile_visits"), read("moe.gmm.tile_pairs")) == (8.0, 128.0)
    assert read("moe.gmm.tile_visits_share") == 8.0 / 128.0
    assert (read("moe.assignments_held"), read("moe.load_max_over_mean")) == (50.0, 3.0)


def test_held_experts_and_expert_parallel_do_not_mix():
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
    ps = init_parallel_state(ep_size=2)
    params = seeded(6)
    try:
        with use_parallel_state(ps), pytest.raises(ValueError, match="moe_experts_held"):
            transformer.loss_fn(params, program_cfg(), packed_batch(rows=2))
    finally:
        destroy_parallel_state()


# ---------------------------------------------------------------------- MTP
def test_mtp_labels_stop_at_document_boundaries():
    seg = jnp.asarray([[1, 1, 1, 1, 2, 2, 3, 0, 0]], jnp.int32)
    ids = jnp.arange(10, 19, dtype=jnp.int32)[None]
    labels = jnp.where((jnp.roll(seg, -1, 1) == seg) & (seg > 0), jnp.roll(ids, -1, 1), -100)
    labels = labels.at[0, -1].set(-100)
    assert labels.tolist() == [[11, 12, 13, -100, 15, -100, -100, -100, -100]]
    # position i predicts token i+2: only where i, i+1, i+2 share a document
    assert transformer.mtp_labels(labels, seg, 1).tolist() == [
        [12, 13, -100, -100, -100, -100, -100, -100, -100]]
    assert transformer.mtp_labels(labels, seg, 2).tolist() == [
        [13, -100, -100, -100, -100, -100, -100, -100, -100]]
    assert transformer.mtp_labels(labels, None, 1).tolist() == [
        [12, 13, -100, 15, -100, -100, -100, -100, -100]]


def test_mtp_in_a_packed_row_is_each_document_alone():
    """A packed row's MTP loss is the sum over its documents, each run alone:
    the module's attention stays inside a document and no position predicts
    across a boundary."""
    whole = dict(MODEL, n_routed_experts=16, first_expert_held=0)
    cfg = program_cfg(whole)
    params = seeded(7, whole)
    batch = packed_batch(3, rows=1)

    def mtp_sum(b):
        _, m = loss_fn(params, cfg, b)
        n = int((transformer.mtp_labels(b["labels"], b["segment_ids"], 1) != -100).sum())
        return float(m["mtp_loss"]) * n, n

    packed, n_packed = mtp_sum(batch)
    seg = np.asarray(batch["segment_ids"][0])
    alone, n_alone = 0.0, 0
    for doc in (1, 2):
        idx = np.flatnonzero(seg == doc)
        one = {k: v[:, idx] for k, v in batch.items()}
        one["segment_ids"] = jnp.ones_like(one["segment_ids"])
        total, n = mtp_sum(one)
        alone, n_alone = alone + total, n_alone + n
    assert n_packed == n_alone == (20 - 2) + (30 - 2)
    np.testing.assert_allclose(packed, alone, rtol=2e-5)


# ------------------------------------------------------- config, checkpoints
def test_dialect_mapping_and_flops_keys():
    hf = dict(model_type="joyai_llm_flash", n_routed_experts=256, num_experts_per_tok=8,
              scoring_func="sigmoid", topk_method="noaux_tc", q_lora_rank=1536, kv_lora_rank=512,
              qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, head_dim=64,
              hidden_size=2048, num_attention_heads=32, num_key_value_heads=32,
              num_nextn_predict_layers=1, first_k_dense_replace=1, n_shared_experts=1,
              routed_scaling_factor=2.5, n_group=1, topk_group=1, moe_intermediate_size=768)
    cfg = TransformerConfig.from_hf_config(hf)
    assert cfg.num_experts == cfg.experts_held == 256 and cfg.use_mla and cfg.rope_interleave
    assert cfg.scoring_func == "sigmoid" and cfg.router_aux_loss_coef == 0.0
    assert cfg.num_nextn_predict_layers == 1 and cfg.qk_head_dim == 192 and cfg.head_dim == 64
    assert cfg.to_hf_config()["n_routed_experts"] == 256
    # from the dense keys alone it is a plain dense decoder (what the
    # benchmark's flops test builds): no MLA, no experts, no MTP
    bare = build_config("joyai_llm_flash", vocab_size=16160, hidden_size=2048, head_dim=64,
                        intermediate_size=7168, num_hidden_layers=5, num_attention_heads=32,
                        num_key_value_heads=32, num_experts_per_tok=8, moe_intermediate_size=768)
    assert not bare.use_mla and not bare.is_moe and not bare.num_nextn_predict_layers


def test_checkpoint_names_round_trip(tmp_path):
    cfg = program_cfg()
    params = seeded(8)
    hf_io.save_hf_checkpoint(params, cfg, str(tmp_path))
    from safetensors import safe_open

    names = set(safe_open(str(tmp_path / "model.safetensors"), "np").keys())
    # the MTP module is layer `num_hidden_layers`, the held experts keep their numbers
    for name in ("eh_proj", "enorm", "hnorm", "shared_head.norm", "self_attn.kv_b_proj",
                 "mlp.gate", "mlp.gate.e_score_correction_bias", "mlp.experts.4.up_proj",
                 "mlp.experts.7.down_proj", "mlp.shared_experts.gate_proj"):
        assert (f"model.layers.3.{name}.weight" in names
                or f"model.layers.3.{name}" in names), name
    assert "model.layers.3.mlp.experts.0.up_proj.weight" not in names
    assert "model.layers.3.mlp.experts.8.up_proj.weight" not in names
    doc = json.load(open(tmp_path / "config.json"))
    assert doc["n_routed_experts"] == 16 and doc["moe_experts_held"] == 4
    back = TransformerConfig.from_pretrained(str(tmp_path), dtype="float32")
    assert (back.experts_held, back.moe_experts_held_first) == (4, 4)
    again = hf_io.hf_to_params(str(tmp_path), back)
    assert jax.tree.structure(again) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flops_counter_counts_the_held_share_and_mtp():
    from veomni_tpu.utils.count_flops import FlopsCounter

    whole = FlopsCounter.from_config(program_cfg(dict(MODEL, n_routed_experts=16, first_expert_held=0)))
    held = FlopsCounter.from_config(program_cfg())
    no_mtp = FlopsCounter.from_config(program_cfg(dict(MODEL, num_nextn_predict_layers=0)))
    h, im, k = 64, 32, 4
    one_routed = 2 * 3 * h * im * k
    # three expert layers (two and the MTP module's) lose three quarters of the routed term
    assert whole.flops_per_token_fwd(64) - held.flops_per_token_fwd(64) == pytest.approx(
        3 * one_routed * 0.75)
    layer = held._attn_proj_flops() + held._attn_score_flops(64) + held._mlp_flops()
    assert held.flops_per_token_fwd(64) - no_mtp.flops_per_token_fwd(64) == pytest.approx(
        layer + 2 * 2 * h * h + 2 * h * 128)
    # the leading layer is dense
    assert held._mlp_flops(dense=True) == 2 * 3 * h * 128


# ------------------------------------------------- through TextTrainer.train()
def test_routing_counters_reach_the_registry_without_a_sync(tmp_path):
    """moe.assignments[_held] and moe.load_max_over_mean come from the step's
    own metrics and are fetched at the end of train when log_steps never
    comes; train.mtp_loss is published with the other train gauges."""
    from veomni_tpu.observability.metrics import MetricsRegistry, get_registry, set_registry
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import TextTrainer

    from tests.test_e2e_training import _make_args, _write_dummy_data

    destroy_parallel_state()
    _write_dummy_data(tmp_path / "data.jsonl")
    args = _make_args(tmp_path, train_steps=3)
    args.train.log_steps = 1000
    args.train.expert_parallel_size = 1
    args.data.max_seq_len = 64
    args.model.config_overrides = dict(
        {k: MODEL[k] for k in PROGRAM_KEYS}, model_type="joyai_llm_flash", vocab_size=512,
        num_experts=16, moe_experts_held=4, moe_experts_held_first=4, moe_capacity_factor=3.0,
        n_group=1, topk_group=1)
    old = set_registry(MetricsRegistry())
    try:
        trainer = TextTrainer(args)
        assert "mtp" in trainer.train_state.params
        trainer.train()
        trainer.checkpointer.close()
        reg = get_registry()
        routed, held = reg.get("moe.assignments").value, reg.get("moe.assignments_held").value
        tokens = 3 * args.train.micro_batch_size * 64 * trainer.parallel_state.dp_size
        assert routed == 3 * tokens * 4 / 3 * 3 / 3 or routed > 0  # three expert layers, top-4
        assert 0 < held < routed and 0.1 < held / routed < 0.5
        assert reg.get("moe.load_max_over_mean").value >= 1.0
        assert reg.get("train.mtp_loss").value > 1.0
        assert reg.get("train.moe_dropped_frac").value == 0.0
        assert reg.get("moe.assignments_dropped").value == 0.0
        # the toy widths (64, 32) are not the kernel's: nothing to count
        assert reg.get("moe.gmm.tile_pairs").value == 0.0
    finally:
        set_registry(old)
        destroy_parallel_state()
