"""kimi_linear (Kimi Delta Attention layers 3:1 with NoPE latent attention, an
expert layer behind both, scanned by segments of its pattern) on the train
path, at a tiny size on the CPU: ``ops.kda_scan`` against the token-by-token
recurrence, the program against the benchmark's plain reference
(``benchmark/reference/kda_hybrid.py``) for each mixer and a whole layer, the
published pattern and the cut's count, the expert shares against the uncut
layer, the checkpoint's names and the serving refusal.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import flops_kda
from benchmark.reference import kda_hybrid as ref
from veomni_tpu import ops
from veomni_tpu.models import decode, kimi_linear as kl, qwen3_next
from veomni_tpu.models import transformer as core
from veomni_tpu.models.auto import MODEL_REGISTRY, build_config
from veomni_tpu.ops import kda
from veomni_tpu.utils.count_flops import FlopsCounter
from veomni_tpu.utils.testing import once_on_host

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark's rehearsal preset: the file's plain values, as the job hands
# them to the reference
with open(os.path.join(REPO, "benchmark", "configs", "kimi_linear_48b_a3b.json")) as _f:
    CONFIG = json.load(_f)
REHEARSAL = {**CONFIG, **CONFIG["rehearsal"]}
MODEL = {k: v for k, v in REHEARSAL.items() if not isinstance(v, (dict, list))}
FAMILY = MODEL_REGISTRY.get("kimi_linear")
COMMON = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
          "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
          "tie_word_embeddings", "moe_intermediate_size")


def program_cfg(model=MODEL, **kw):
    """As ``jobs/train_packed.py`` builds it: the common keys, then the
    configuration's ``program_overrides``."""
    keys = {k: model[k] for k in COMMON}
    keys.update(CONFIG["program_overrides"])
    keys.update(CONFIG["rehearsal"]["program_overrides"])
    # no recompute in these tests: the same values for half the compile (the
    # benchmark's rehearsal and tests/test_chip_compile_steps.py run the step with it)
    keys.update(dtype="float32", remat=False)
    keys.update(kw)
    return build_config("kimi_linear", **keys)


def packed_batch(seed=0, s=160):
    """Two packed rows over chunks of 64: a boundary inside a chunk (at 70),
    documents within the conv's reach of each other (70, 72, 73), one that
    starts on a chunk's edge (128), trailing padding."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, MODEL["vocab_size"], (2, s)).astype(np.int32)
    seg = np.stack([np.repeat([1, 2, 3, 4, 0], [70, 2, 1, 80, 7]),
                    np.repeat([1, 2], [128, 32])]).astype(np.int32)
    nxt = np.concatenate([ids[:, 1:], np.zeros((2, 1), np.int32)], 1)
    nseg = np.concatenate([seg[:, 1:], np.zeros((2, 1), np.int32)], 1)
    labels = np.where((seg > 0) & (nseg == seg), nxt, -100).astype(np.int32)
    return {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
            "position_ids": jnp.zeros_like(jnp.asarray(ids)), "labels": jnp.asarray(labels)}


@once_on_host
def _seeded():
    return jax.jit(lambda key: ref.nest(ref.make_params(MODEL, key)))(ref.seed_key(5))


def seeded():
    """The reference's seeded weights, drawn once (under jit: drawn eagerly
    each leaf is a dispatch of its own)."""
    return jax.tree.map(jnp.asarray, _seeded())


def program_loss(params, cfg, batch):
    total, metrics = FAMILY.loss_fn(params, cfg, batch)
    return total / metrics["ntokens"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------------ the op
def _scan_inputs(seed, b, s, h, d, strength):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q, k, v = (rng.normal(size=(b, s, h, d)).astype(np.float32) for _ in range(3))
    g = -(rng.uniform(0.001, 1.0, (b, s, h, d)) * strength).astype(np.float32)
    beta = rng.uniform(0, 1, (b, s, h)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (unit(q), unit(k), v, g, beta))


def _recurrence(q, k, v, g, beta, seg):
    """ops.kda_scan's contract one token at a time, rows mapped."""
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return jax.vmap(ref.delta_recurrence)(q, k, v, g, beta, first)


SEGMENTS = np.stack([np.repeat([1, 2, 3, 4, 5, 0], [64, 6, 1, 60, 61, 8]),   # an edge, mid-chunk, one token
                     np.repeat([1, 2, 3], [33, 2, 165])]).astype(np.int32)


@pytest.mark.parametrize("strength", [0.1, 3.0], ids=["gentle", "strong"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_kda_scan_is_the_token_by_token_recurrence(chunk, strength):
    """Forward and every gradient, with documents that start mid-chunk, at a
    chunk's first row and one token long; ``strong``: a log-decay of up to -3
    a token, -190 over a chunk of 64, where ``exp`` of the wrong-way
    difference is far past f32."""
    args, seg = _scan_inputs(1, 2, 200, 3, 16, strength), jnp.asarray(SEGMENTS)
    w = jnp.asarray(np.random.default_rng(2).normal(size=(2, 200, 3, 16)), jnp.float32)
    # each side one program, forward and gradient (not op by op)
    got = jax.jit(lambda *a: ops.kda_scan(*a, seg, chunk))
    want = jax.jit(lambda *a: _recurrence(*a, seg))
    assert float(jnp.abs(got(*args) - want(*args)).max()) < 2e-6
    g_got = jax.jit(jax.grad(lambda *a: (got(*a) * w).sum(), argnums=range(5)))(*args)
    g_want = jax.jit(jax.grad(lambda *a: (want(*a) * w).sum(), argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert float(jnp.abs(a - b).max()) < 3e-5 * max(1.0, float(jnp.abs(b).max())), name


def test_pair_terms_about_the_chunks_start_overflow_where_the_ops_do_not(monkeypatch):
    """The planted wrong-way form: ``(x exp(G)) (k exp(-G))^T`` about the
    chunk's start. It is the same algebra and agrees under gentle decays; under
    strong ones ``exp(-G)`` is inf and the op's own form is the only one that
    stands."""
    def about_the_start(lefts, k, gc, seg, sub, dtype):
        c = k.shape[1]
        same = (seg[:, :, None] == seg[:, None, :])[:, None] & jnp.tril(jnp.ones((c, c), bool))
        k_up = (k * jnp.exp(-gc)).astype(dtype)
        return [jnp.where(same, jnp.einsum("bihd,bjhd->bhij", (x * jnp.exp(gc)).astype(dtype), k_up),
                          0.0) for x in lefts]

    seg = jnp.asarray(SEGMENTS)
    scan = lambda: jax.jit(lambda *a: ops.kda_scan(*a, seg))   # traced under what is planted
    want_of = jax.jit(lambda *a: _recurrence(*a, seg))
    for strength, stands in ((0.05, True), (3.0, False)):
        args = _scan_inputs(1, 2, 200, 3, 16, strength)
        want = want_of(*args)
        assert float(jnp.abs(scan()(*args) - want).max()) < 2e-6
        with monkeypatch.context() as m:
            m.setattr(kda, "_pair_terms", about_the_start)
            wrong = scan()(*args)
        assert bool(jnp.isfinite(wrong).all() and jnp.abs(wrong - want).max() < 1e-4) is stands


def test_the_inverse_is_the_inverse():
    low = jnp.tril(jnp.asarray(np.random.default_rng(0).normal(size=(3, 64, 64)), jnp.float32), -1)
    eye = jnp.eye(64)
    assert float(jnp.abs(kda._inv_unit_lower(low * 0.3, 16) @ (eye + low * 0.3) - eye).max()) < 1e-4


# ----------------------------------------------------------- the reference
def test_seeded_tree_is_the_programs_tree():
    want = FAMILY.abstract_params(program_cfg())
    got = jax.eval_shape(lambda key: ref.nest(ref.make_params(MODEL, key)), ref.seed_key(5))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    flat = ref.flatten(seeded())
    a = jnp.exp(flat["kda_layers.A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert float(jnp.abs(flat["kda_layers.k_conv1d"]).max()) <= 0.5
    dt = jax.nn.softplus(flat["kda_layers.dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001
    assert not flat["mla_layers.e_score_correction_bias"].any()


def _layer_params(kind, at=0):
    return jax.tree.map(lambda t: t[at], seeded()[ref.STACKS[kind]])


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_each_mixer_agrees_with_the_reference(kind):
    cfg, batch = program_cfg(), packed_batch()
    lp = _layer_params(kind)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 160, MODEL["hidden_size"])), jnp.float32)
    seg = batch["segment_ids"]
    # each side one program, not op by op
    if kind == "kda":
        got = jax.jit(lambda x, lp: kl._kda_mixer(x, lp, cfg, seg))(x, lp)
    else:
        got = jax.jit(lambda x, lp: core._mla_attention(
            x, lp, cfg, *kl._mla_tables(cfg, None, (2, 160)), seg, None))(x, lp)
    mixer = ref.kda_mixer if kind == "kda" else ref.mla_mixer
    want = jax.jit(lambda x, lp: jnp.stack(
        [mixer(x[r], lp, MODEL, ref.row_geometry(seg[r]), None) for r in range(2)]))(x, lp)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_a_whole_layer_agrees_with_the_reference():
    cfg, batch = program_cfg(), packed_batch()
    lp = _layer_params("kda", 1)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 160, MODEL["hidden_size"])), jnp.float32)
    seg = batch["segment_ids"]
    got, _ = jax.jit(lambda x, lp: kl._layer(x, lp, kind="kda", cfg=cfg, segment_ids=seg, cos=None, sin=None))(x, lp)
    want = jax.jit(lambda x, lp: ref.one_layer(x, lp, "kda", MODEL, seg, None))(x, lp)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


@jax.jit
def _program_gradient(params, batch):
    """The program's loss and gradient under the rehearsal's configuration:
    one compile for every test that changes the parameters alone."""
    return jax.value_and_grad(program_loss)(params, program_cfg(), batch)


def gaps_to_the_reference(cfg=None, program=None, params=None):
    """(relative gap of the loss, {leaf: relative gap of its gradient})
    between the program (under ``cfg``, or ``program``, or from ``params``)
    and the reference from the seeded weights."""
    batch = packed_batch()
    if cfg is None and program is None:
        got, g_got = _program_gradient(seeded() if params is None else params, batch)
    else:   # a compile of its own: traced under whatever the case planted
        cfg = cfg or program_cfg()
        got, g_got = jax.jit(lambda p, b: jax.value_and_grad(program or program_loss)(p, cfg, b))(
            seeded(), batch)
    want, g_want = _reference_gradient()
    g_got = ref.flatten(g_got)
    assert set(g_got) == set(g_want)
    leaf = {name: float(jnp.linalg.norm(g_got[name] - w) / jnp.maximum(jnp.linalg.norm(w), 1e-30))
            for name, w in g_want.items() if not name.endswith("e_score_correction_bias")}
    return abs(float(got) - float(want)) / float(want), leaf


@once_on_host
def _reference_gradient():
    """The reference's (loss, gradient leaves) from the seeded weights: it does
    not depend on what a case plants in the program, so it is computed once."""
    batch = packed_batch()
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, MODEL, batch["input_ids"], batch["segment_ids"])))(seeded())
    return value, ref.flatten(grads)


def test_loss_and_every_gradient_leaf_agree_with_the_reference():
    loss_gap, leaf = gaps_to_the_reference()
    assert len(leaf) == 61 and loss_gap < 2e-6
    worst = max(leaf, key=leaf.get)
    assert leaf[worst] < 5e-5, worst


def test_two_optimizer_steps_agree_with_the_reference():
    """AdamW, a global-norm clip of 1.0, constant 3e-4: the parameters' change
    after two steps, leaf by leaf, against ``train_reference``."""
    batch = packed_batch()
    opt = {"lr": 3e-4, "betas": [0.9, 0.95], "weight_decay": 0.0, "max_grad_norm": 1.0}
    rows = (np.asarray(batch["input_ids"]), np.asarray(batch["segment_ids"]))
    want = ref.train_reference(MODEL, opt, 5, [rows, rows])
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4, b1=0.9, b2=0.95, eps=1e-8,
                                                               weight_decay=0.0))

    @jax.jit
    def update(grads, state, params):
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    params = p0 = seeded()
    state = tx.init(params)
    losses = []
    for _ in range(2):
        value, grads = _program_gradient(params, batch)
        params, state = update(grads, state, params)
        losses.append(float(value))
    assert np.allclose(losses, want["losses"], rtol=2e-6)
    change = jax.device_get(ref.leaf_norms(jax.tree.map(jnp.subtract, params, p0)))
    for name, norms in want["param_change_norms"].items():
        assert np.allclose(change[name], norms, rtol=2e-4, atol=1e-9), name


# a planted fault for every new group of parameters: what the comparison that
# decides ``correct`` (the first gradient's leaf norms against the
# reference's, limit 3e-5 at the rehearsal) has to see, in leaves OTHER than
# the group's own
def _without(*leaves):
    """The seeded weights with these leaves zeroed: the group left out of the
    mixer (the reference keeps them)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: p * 0 if path[-1].key in leaves else p, seeded())


def _plant(monkeypatch, fault):
    real_conv, real_scan = kl._causal_conv1d, ops.kda_scan
    if fault == "beta":            # beta = 1: the plain delta rule
        monkeypatch.setattr(kl.ops, "kda_scan", lambda q, k, v, g, beta, seg: real_scan(
            q, k, v, g, jnp.ones_like(beta), seg))
    elif fault == "conv":          # the convs without their oldest tap
        monkeypatch.setattr(kl, "_causal_conv1d",
                            lambda x, w, seg: real_conv(x, w.at[:, 0].set(0.0), seg))
    elif fault == "resets":        # the state carried across a document's start
        monkeypatch.setattr(kl.ops, "kda_scan", lambda q, k, v, g, beta, seg: real_scan(
            q, k, v, g, beta, None))


FAULTS = {
    "decay_pair": ("f_a_proj", "f_b_proj"),   # the decay from dt_bias alone
    "dt_bias": ("dt_bias",),
    "output_gate": ("g_a_proj", "g_b_proj"),  # sigmoid(0): every gate a half
    "beta": (), "conv": (), "resets": (),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_in_the_kda_mixer_fails_the_comparison(monkeypatch, fault):
    own = FAULTS[fault]
    if own:
        _, leaf = gaps_to_the_reference(params=_without(*own))
    else:
        _plant(monkeypatch, fault)
        _, leaf = gaps_to_the_reference(program=program_loss)
    seen = {k: v for k, v in leaf.items() if k.rsplit(".", 1)[-1] not in own}
    assert max(seen.values()) > 1e-3, (fault, max(seen, key=seen.get))


def test_rotary_left_on_fails_the_comparison_and_nope_reads_no_position():
    """The NoPE switch: with ``mla_use_nope`` off the MLA layer turns q's and
    k's rope lanes, and the comparison sees it; with it on, neither the
    positions nor ``rope_theta`` reach the logits."""
    _, leaf = gaps_to_the_reference(program_cfg(mla_use_nope=False), program=_with_positions)
    assert max(leaf.values()) > 1e-3
    params, batch = seeded(), packed_batch()
    logits = lambda pos, **kw: jax.jit(lambda pos: FAMILY.forward_logits(
        params, program_cfg(**kw), batch["input_ids"], pos, batch["segment_ids"]))(pos)
    base = logits(batch["position_ids"])
    assert jnp.array_equal(base, logits(batch["position_ids"] + 7, rope_theta=123.0))


def _with_positions(params, cfg, batch):
    pos = np.stack([np.arange(batch["segment_ids"].shape[1])] * 2).astype(np.int32)
    return program_loss(params, cfg, dict(batch, position_ids=jnp.asarray(pos)))


def test_mla_goes_through_the_shared_op_and_says_which_way(monkeypatch):
    """One code path with the DeepSeek-V3 dialect: ``transformer.
    _mla_attention`` and ``ops.mla_qkv_rotary`` (here, on the CPU, the
    registry resolves the op to its XLA form)."""
    seen = []
    real = ops.mla_qkv_rotary
    monkeypatch.setattr(ops, "mla_qkv_rotary", lambda *a, **kw: seen.append(a[3:5]) or real(*a, **kw))
    FAMILY.forward_logits(seeded(), program_cfg(), packed_batch()["input_ids"])
    (cos, sin), = seen
    assert bool((cos == 1).all() and (sin == 0).all()) and cos.shape == (2, 160, 8)


# --------------------------------------------------------- pattern and count
def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")["config"]


def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_the_published_pattern_builds_and_counts_49_billion():
    """``abstract_params`` from the catalog's config.json: no allocation."""
    from veomni_tpu.models.config import TransformerConfig

    cfg = TransformerConfig.from_hf_config(_catalog())
    kinds = kl.layer_kinds(cfg)
    assert kinds[0] == "kda_dense" and kinds.count("mla") == 7 and kinds.count("kda") == 19
    assert kl.segments_of(kinds) == [
        (("kda_dense",), 1), (("kda", "kda", "mla", "kda"), 6), (("kda",), 1), (("mla",), 1)]
    assert (cfg.num_experts_per_tok, cfg.scoring_func, cfg.norm_topk_prob, cfg.n_shared_experts,
            cfg.n_group, cfg.q_lora_rank, cfg.mla_use_nope, cfg.router_aux_loss_coef) == (
        8, "sigmoid", True, 1, 1, 0, True, 0.0)
    n = _count(FAMILY.abstract_params(cfg))
    assert 49.0e9 < n < 49.2e9, n
    back = cfg.to_hf_config()
    assert {k: back[k] for k in ("num_experts_per_token", "moe_router_activation_func",
                                 "moe_renormalize", "num_shared_experts", "num_expert_group",
                                 "linear_attn_config", "mla_use_nope")} == {
        k: _catalog()[k] for k in ("num_experts_per_token", "moe_router_activation_func",
                                   "moe_renormalize", "num_shared_experts", "num_expert_group",
                                   "linear_attn_config", "mla_use_nope")}


def test_the_cells_cut_counts_602_million_and_keeps_every_width():
    model = {k: v for k, v in CONFIG.items() if not isinstance(v, (dict, list))}
    keys = {k: model[k] for k in COMMON}
    cfg = build_config("kimi_linear", **keys, **CONFIG["program_overrides"])
    tree = FAMILY.abstract_params(cfg)
    assert _count(tree) == 602_434_432
    moe = ("router", "e_score_correction_bias", "experts", "shared_experts")
    per_layer = lambda stack: sum(
        int(np.prod(x.shape[1:])) for path, x in jax.tree_util.tree_leaves_with_path(stack)
        if path[0].key not in moe)
    assert per_layer(tree["kda_layers"]) == 39_514_272 + 2 * 2304    # the mixer and two norms
    assert per_layer(tree["mla_layers"]) == 29_114_880 + 2 * 2304
    assert kl.segments_of(kl.layer_kinds(cfg)) == [
        (("kda_dense",), 1), (("kda",), 2), (("mla",), 1), (("kda",), 1)]
    got, want = jax.eval_shape(lambda: ref.nest(ref.make_params(model, ref.seed_key(1)))), tree
    assert jax.tree.structure(got) == jax.tree.structure(want)
    published = _catalog()
    assert {k: CONFIG[k] for k in published if k not in CONFIG["reduced"]} == {
        k: v for k, v in published.items() if k not in CONFIG["reduced"]}
    assert CONFIG["published"] == {k: published[k] for k in CONFIG["reduced"]}
    assert CONFIG["kda_chunk"] == kda.CHUNK


def test_segments_of_finds_the_periods():
    assert kl.segments_of(tuple("aab")) == [(("a",), 2), (("b",), 1)]
    assert kl.segments_of(tuple("abababc")) == [(("a", "b"), 3), (("c",), 1)]
    assert kl.segments_of(tuple("abc")) == [(("a",), 1), (("b",), 1), (("c",), 1)]


def test_the_stack_is_a_plain_loop_over_its_layers():
    """``forward_layers`` (segments, period scans, stacks sliced by kind)
    against one layer after another, at a depth with a repeating period."""
    lac = {"kda_layers": [1, 2, 3, 5, 6, 7, 9], "full_attn_layers": [4, 8], "num_heads": 4,
           "head_dim": 16, "short_conv_kernel_size": 4}
    cfg = program_cfg(num_hidden_layers=9, linear_attn_config=lac)
    kinds = kl.layer_kinds(cfg)
    assert kl.segments_of(kinds) == [(("kda_dense",), 1), (("kda", "kda", "mla", "kda"), 2)]
    batch = packed_batch()
    params = jax.jit(lambda key: FAMILY.init_params(key, cfg))(jax.random.PRNGKey(0))
    got = jax.jit(lambda p, ids, seg: kl.forward_layers(p, cfg, ids, None, seg))(
        params, batch["input_ids"], batch["segment_ids"])
    hidden = params["embed_tokens"][batch["input_ids"]]
    cos, sin = kl._mla_tables(cfg, None, hidden.shape[:2])
    # one layer after another, each kind's layer one program (not op by op)
    layer = {kind: jax.jit(lambda h, lp, kind=kind: kl._layer(
        h, lp, kind=kind, cfg=cfg, segment_ids=batch["segment_ids"], cos=cos, sin=sin))
        for kind in set(kinds)}
    at, load = {k: 0 for k in kl.KINDS}, 0.0
    for kind in kinds:
        lp = jax.tree.map(lambda t: t[at[kind]], params[kl.KINDS[kind]])
        at[kind] += 1
        hidden, stats = layer[kind](hidden, lp)
        load = max(load, float(stats[5]))
    want = core._norm(hidden, params["norm"], cfg)
    assert float(jnp.abs(got["hidden"] - want).max()) < 1e-5
    assert float(got["moe_load_max_over_mean"]) == pytest.approx(load)
    assert float(got["moe_assignment_counts"][0]) == 8 * 2 * 160 * 4


# ------------------------------------------------------------- the share
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """16 experts in four shares of four: the shares' expert parts plus the
    shared expert counted once are what the reference gives for the whole
    layer (every expert held, no capacity)."""
    whole = {**MODEL, "num_experts": 16, "first_expert_held": 0, "moe_capacity_factor": 0}
    lp = jax.tree.map(lambda t: t[0], ref.nest(ref.make_params(whole, ref.seed_key(7)))["mla_layers"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(96, MODEL["hidden_size"])), jnp.float32)
    expert_layer = lambda x, lp, model: jax.jit(lambda x, lp: ref.expert_layer(x, lp, model))(x, lp)   # one program
    want = expert_layer(x, lp, whole)
    se = lp["shared_experts"]
    shared = ref._swiglu(x, se["gate_proj"], se["up_proj"], se["down_proj"], None)
    total = shared
    for first in range(0, 16, 4):
        cfg = program_cfg(moe_experts_held=4, moe_experts_held_first=first, moe_capacity_factor=0.0)
        part = dict(lp, experts=jax.tree.map(lambda t: t[first:first + 4], lp["experts"]))
        got, _, stats = jax.jit(lambda x, part: core.moe_mlp_with_stats(x, part, cfg))(x, part)
        mine = {**whole, "num_experts": 4, "num_experts_published": 16, "first_expert_held": first}
        assert float(jnp.abs(got - expert_layer(x, part, mine)).max()) < 1e-5
        total = total + (got - shared)
    assert float(jnp.abs(total - want).max()) < 2e-5


# ------------------------------------------------- checkpoint, serving, flops
def test_the_checkpoint_round_trips_under_the_published_names(tmp_path):
    from safetensors import safe_open

    cfg, params = program_cfg(), seeded()
    FAMILY.save_hf_checkpoint(params, cfg, str(tmp_path))
    with safe_open(os.path.join(tmp_path, "model.safetensors"), framework="numpy") as f:
        names = set(f.keys())
        assert f.get_tensor("model.layers.0.self_attn.A_log").shape == (1, 1, 4, 1)
        assert f.get_tensor("model.layers.0.self_attn.q_conv1d.weight").shape == (64, 1, 4)
    assert {"model.layers.0.self_attn.f_a_proj.weight", "model.layers.0.self_attn.dt_bias",
            "model.layers.0.self_attn.g_b_proj.weight", "model.layers.0.self_attn.o_norm.weight",
            "model.layers.0.mlp.gate_proj.weight", "model.layers.3.self_attn.kv_a_proj_with_mqa.weight",
            "model.layers.3.self_attn.q_proj.weight", "model.layers.1.block_sparse_moe.gate.weight",
            "model.layers.1.block_sparse_moe.gate.e_score_correction_bias",
            "model.layers.1.block_sparse_moe.experts.4.w1.weight",
            "model.layers.4.block_sparse_moe.experts.7.w2.weight",
            "model.layers.2.block_sparse_moe.shared_experts.up_proj.weight",
            "lm_head.weight", "model.norm.weight"} <= names
    assert "model.layers.1.block_sparse_moe.experts.0.w1.weight" not in names   # another rank's
    with open(os.path.join(tmp_path, "config.json")) as f:
        saved = json.load(f)
    assert saved["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5] and saved["mla_use_nope"]
    from veomni_tpu.models.config import TransformerConfig

    again = TransformerConfig.from_pretrained(str(tmp_path), dtype="float32")
    back = FAMILY.hf_to_params(str(tmp_path), again)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)))


def test_serving_refuses_the_family_and_says_why():
    cfg = program_cfg()
    assert not decode.supports_cached_decode(cfg)
    assert "Kimi Delta Attention" in decode.no_cached_decode_reason(cfg)


def test_what_the_family_refuses_it_refuses_by_name():
    with pytest.raises(ValueError, match="kda_layers and full_attn_layers"):
        kl.layer_kinds(program_cfg(num_hidden_layers=6))
    with pytest.raises(NotImplementedError, match="multi-token"):
        kl.init_params(jax.random.PRNGKey(0), program_cfg(num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="power of two"):
        ops.kda_scan(*_scan_inputs(0, 1, 48, 1, 8, 0.1), None, 48)


def test_flops_counter_has_the_kda_term():
    """The trainer's live MFU gauge against the benchmark's count at the
    cell's sizes, as PR 33's test does for ``flops_ssm.py``."""
    model = {k: v for k, v in CONFIG.items() if not isinstance(v, (dict, list))}
    cfg = build_config("kimi_linear", **{k: model[k] for k in COMMON}, **CONFIG["program_overrides"])
    counter = FlopsCounter.from_config(cfg)
    assert counter.n_kda_layers == 4 and counter.kda_chunk == model["kda_chunk"]
    assert counter._kda_flops() == sum(flops_kda.kda_mixer_flops(model).values())
    assert counter.flops_per_token_fwd(8192) == pytest.approx(
        flops_kda.fwd_flops_per_token(model, 8192), rel=1e-12)
    assert counter.batch_flops(8192, 8192) == pytest.approx(
        8192 * flops_kda.train_flops_per_token(model, 8192), rel=1e-12)


def test_chunk_census_counts_at_the_ops_chunk():
    from veomni_tpu.ops.ssd_scan import chunk_census

    assert chunk_census(SEGMENTS, kda.CHUNK) == (8, 4)
    assert qwen3_next.period_scan.__defaults__ == (None,)   # the fold is opt-in
