"""granitemoehybrid (Mamba-2 state-space layers and NoPE attention, scanned by
the period of ``layer_types``) on the train path, at a tiny size on the CPU:
the program against the benchmark's plain reference
(``benchmark/reference/ssm_hybrid.py``) for the loss and every gradient leaf,
``ops.ssd_scan`` against the token-by-token recurrence, packed rows against
their documents alone, the period scan against a plain loop, the multipliers,
and ``transformers``' own module for unpacked rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssm_hybrid as ref
from veomni_tpu import ops
from veomni_tpu.models import granite_hybrid as gh
from veomni_tpu.models import qwen3_next
from veomni_tpu.models.auto import MODEL_REGISTRY, build_config
from veomni_tpu.ops.ssd_scan import chunk_census
from veomni_tpu.utils.testing import once_on_host, under_jit

PERIOD = ["mamba", "mamba", "attention", "mamba"]
# config.json's keys at a tiny size (the benchmark's rehearsal preset, two periods)
MODEL = dict(
    vocab_size=128, hidden_size=32, shared_intermediate_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    attention_multiplier=0.125, residual_multiplier=0.22, logits_scaling=8.0,
    embedding_multiplier=12.0, tie_word_embeddings=True, rms_norm_eps=1e-5,
    layer_types_run=",".join(PERIOD * 2))
FAMILY = MODEL_REGISTRY.get("granitemoehybrid")


def program_cfg(model=MODEL, **kw):
    keys = {k: v for k, v in model.items() if k != "layer_types_run"}
    keys.update(layer_types=model["layer_types_run"].split(","), mamba_chunk_size=16,
                position_embedding_type="nope", dtype="float32")
    keys.update(kw)
    return build_config("granitemoehybrid", **keys)


def packed_batch(seed=0, s=48):
    """Two packed rows: a boundary inside a chunk of 16 (at 20), a document of
    one token, one that starts on a chunk's edge (16), trailing padding."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, MODEL["vocab_size"], (2, s)).astype(np.int32)
    seg = np.stack([np.repeat([1, 2, 3, 0], [20, 1, 22, 5]),
                    np.repeat([1, 2], [16, 32])]).astype(np.int32)
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


# the whole stack as one program a (cfg, shape), not op by op
forward_hidden = under_jit(gh.forward_hidden)


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


# ----------------------------------------------------------- the reference
def test_seeded_tree_is_the_programs_tree():
    want = FAMILY.abstract_params(program_cfg())
    got = jax.eval_shape(lambda key: ref.nest(ref.make_params(MODEL, key)), ref.seed_key(5))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    flat = ref.flatten(seeded())
    assert np.allclose(flat["mamba_layers.A_log"][0, 0], np.log(np.arange(1, 9)))
    assert float(jnp.abs(flat["mamba_layers.conv_weight"]).max()) <= 0.5
    dt = jax.nn.softplus(flat["mamba_layers.dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001


@once_on_host
def _reference_gradient():
    """The reference's (loss, gradient leaves) from the seeded weights: it does
    not depend on what a case plants in the program, so it is computed once."""
    batch = packed_batch()
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, MODEL, batch["input_ids"], batch["segment_ids"])))(seeded())


def gaps_to_the_reference(cfg):
    """(relative gap of the loss, worst relative gap of a gradient leaf with
    its name) between the program under ``cfg`` and the reference."""
    params, batch = seeded(), packed_batch()
    got, g_got = jax.jit(lambda p, b: jax.value_and_grad(program_loss)(p, cfg, b))(params, batch)
    want, g_want = _reference_gradient()
    g_got, g_want = ref.flatten(g_got), ref.flatten(g_want)
    assert set(g_got) == set(g_want) and len(g_want) == 22
    leaf = {name: float(jnp.linalg.norm(g_got[name] - w) / jnp.linalg.norm(w))
            for name, w in g_want.items()}
    worst = max(leaf, key=leaf.get)
    return abs(float(got) - float(want)) / float(want), leaf[worst], worst


def test_loss_and_every_gradient_leaf_agree_with_the_reference():
    loss_gap, grad_gap, where = gaps_to_the_reference(program_cfg())
    assert loss_gap < 2e-6
    assert grad_gap < 2e-5, where


@pytest.mark.parametrize("field,wrong", [
    ("embedding_multiplier", 11.0), ("residual_multiplier", 0.25), ("logits_scaling", 7.0),
    ("attention_multiplier", 0.25)])
def test_a_multiplier_planted_wrong_fails_the_reference_comparison(field, wrong):
    # at seeded weights every logit is near 0 and the loss near ln V whatever
    # the multipliers are: it is the gradients that tell
    _, grad_gap, where = gaps_to_the_reference(program_cfg(**{field: wrong}))
    assert grad_gap > 1e-2, (where, grad_gap)


def test_no_rotary_is_applied_and_the_scale_is_the_configured_one():
    params, batch = seeded(), packed_batch()
    under = lambda **kw: jax.jit(lambda pos: FAMILY.forward_logits(
        params, program_cfg(**kw), batch["input_ids"], pos, batch["segment_ids"]))
    logits = lambda **kw: under(**kw)(batch["position_ids"])
    plain = under()
    base = plain(batch["position_ids"])
    assert jnp.array_equal(base, logits(rope_theta=123.0))
    assert jnp.array_equal(base, plain(batch["position_ids"] + 7))
    assert float(jnp.abs(base - logits(attention_multiplier=0.5)).max()) > 1e-6


# ------------------------------------------------------------- the scan op
def _recurrence(x, dt, a, bm, cm, d, seg):
    """ops.ssd_scan's contract one token at a time, rows mapped."""
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return jax.vmap(lambda *row: ref.recurrence(*row[:2], a, *row[2:4], d, row[4]))(
        x, dt, bm, cm, first)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_ssd_scan_is_the_token_by_token_recurrence(chunk, groups):
    rng = np.random.default_rng(chunk + groups)
    b, s, h, p, n = 2, 77, 4, 8, 16  # 77: no multiple of any chunk
    x, bm, cm = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for shape in ((b, s, h, p), (b, s, groups, n), (b, s, groups, n)))
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.normal(size=(h,)), jnp.float32))
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    seg = jnp.asarray(np.stack([np.repeat([1, 2, 3, 4, 0], [16, 1, 30, 20, 10]),
                                np.repeat([1, 2], [32, 45])]), jnp.int32)
    w = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    # each side one program, forward and gradient (not op by op)
    got = jax.jit(lambda *t: ops.ssd_scan(*t, seg, chunk))
    want = jax.jit(lambda *t: _recurrence(*t, seg))
    args = (x, dt, a, bm, cm, d)
    y = want(*args)
    assert float(jnp.abs(got(*args) - y).max() / jnp.abs(y).max()) < 2e-5
    grads = lambda fn: jax.jit(jax.grad(lambda *t: jnp.sum(fn(*t) * w), argnums=tuple(range(6))))(*args)
    for name, g, r in zip(("x", "dt", "A", "B", "C", "D"), grads(got), grads(want)):
        assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < 2e-5, name
    # without segment ids it is one document
    one = jax.jit(lambda *t: ops.ssd_scan(*t, None, chunk))(*args)
    y = jax.jit(_recurrence)(*args, jnp.ones_like(seg))
    assert float(jnp.abs(one - y).max() / jnp.abs(y).max()) < 2e-5


def test_chunk_census_counts_the_chunks_a_document_starts_in():
    seg = np.stack([np.repeat([1, 2, 3, 0], [20, 1, 22, 5]), np.repeat([1, 2], [16, 32])])
    # row 0: starts at 20, 21 (chunk 1), 43 (chunk 2); row 1: at 16 (chunk 1's first position)
    assert chunk_census(seg, 16) == (6, 3)
    assert chunk_census(seg, 256) == (2, 2)  # a chunk longer than the row is the row
    assert chunk_census(np.ones((3, 64), np.int32), 16) == (12, 0)


# ------------------------------------------------------------ packed rows
def test_a_packed_row_is_each_document_alone():
    """Scan state AND conv taps: the hidden states of a packed row are those of
    its documents run alone, wherever the boundaries fall in a chunk."""
    cfg, params, batch = program_cfg(), seeded(), packed_batch()
    packed = forward_hidden(params, cfg, batch["input_ids"], None, batch["segment_ids"])
    ids, seg = np.asarray(batch["input_ids"]), np.asarray(batch["segment_ids"])
    for row in range(2):
        for doc in np.unique(seg[row][seg[row] > 0]):
            at = np.flatnonzero(seg[row] == doc)
            alone = forward_hidden(params, cfg, jnp.asarray(ids[row:row + 1, at]))
            gap = float(jnp.abs(alone[0] - packed[row, at]).max())
            assert gap < 2e-5, (row, doc, gap)


# ---------------------------------------------------------- the period scan
@pytest.mark.parametrize("period", [
    ("mamba",) * 5 + ("attention",) + ("mamba",) * 4,   # granite-4.0-h: attention at place 5 of 10
    ("linear",) * 3 + ("full",),                        # qwen3_next: the full layer closes it
])
def test_period_scan_is_a_plain_loop_over_the_layer_types(period):
    rng = np.random.default_rng(0)
    groups, kinds = 3, tuple(dict.fromkeys(period))
    stacks = {k: {"w": jnp.asarray(rng.normal(size=(groups, period.count(k), 4, 4)), jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(groups, period.count(k), 4)), jnp.float32)}
              for k in kinds}
    acts = dict(zip(kinds, (jnp.tanh, jnp.sin)))
    bodies = {k: (lambda h, lp, k=k: (acts[k](h @ lp["w"] + lp["b"]), jnp.sum(lp["b"])))
              for k in kinds}
    h0 = jnp.asarray(rng.normal(size=(2, 4)), jnp.float32)
    got, aux = qwen3_next.period_scan(h0, stacks, period, bodies)
    want, total = h0, []
    for g in range(groups):
        seen, t = dict.fromkeys(kinds, 0), 0.0
        for kind in period:
            lp = jax.tree.map(lambda x: x[g, seen[kind]], stacks[kind])
            seen[kind] += 1
            want, a = bodies[kind](want, lp)
            t = t + a
        total.append(t)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert np.allclose(aux, np.asarray(total), rtol=1e-5)


def test_the_models_own_stack_is_a_plain_loop_over_its_layer_types():
    cfg, params, batch = program_cfg(), seeded(), packed_batch()
    got = forward_hidden(params, cfg, batch["input_ids"], None, batch["segment_ids"])
    h = params["embed_tokens"][batch["input_ids"]] * cfg.embed_scale
    seen = {"mamba": 0, "attention": 0}
    # one layer after another, each kind's layer one program (not op by op)
    layer = {kind: jax.jit(lambda h, lp, kind=kind: gh._layer(
        h, lp, kind=kind, cfg=cfg, segment_ids=batch["segment_ids"])) for kind in seen}
    for i, kind in enumerate(cfg.layer_types):
        at = (i // len(PERIOD), seen[kind] % PERIOD.count(kind))
        seen[kind] += 1
        lp = jax.tree.map(lambda t: t[at], params[gh.KINDS[kind]])
        h, _ = layer[kind](h, lp)
    h = ops.rms_norm(h, params["norm"], cfg.rms_norm_eps)
    assert float(jnp.abs(got - h).max()) < 1e-5


# ------------------------------------------------------------- what it refuses
def test_what_the_family_refuses_it_refuses_by_name():
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        gh.init_params(jax.random.PRNGKey(0), program_cfg(num_local_experts=8))
    with pytest.raises(ValueError, match="whole number of the pattern's periods"):
        gh.period_of(program_cfg(num_hidden_layers=6))
    assert gh.period_of(program_cfg()) == tuple(PERIOD)
    from veomni_tpu.serving.engine import InferenceEngine

    with pytest.raises(ValueError, match="state-space layers"):
        InferenceEngine(seeded(), program_cfg())


def test_flops_counter_has_the_state_space_term():
    from veomni_tpu.utils.count_flops import FlopsCounter

    cfg = program_cfg()
    counter = FlopsCounter.from_config(cfg)
    assert counter.n_ssm_layers == 6
    h, d_inner, bc, heads, c, n = 32, 64, 16, 8, 16, 16
    ssm = (2 * h * (2 * d_inner + 2 * bc + heads) + 2 * d_inner * h + 2 * (d_inner + 2 * bc) * 4
           + 2 * c * bc + 2 * c * d_inner + 4 * d_inner * n)
    mlp = 2 * 3 * h * 64
    attn = 2 * h * (2 * 32 + 2 * 16) + 2 * 2 * 32 * (48 / 2)
    assert counter.flops_per_token_fwd(48) == 6 * (ssm + mlp) + 2 * (attn + mlp) + 2 * h * 128


# ------------------------------------------------------------ transformers
def _torch_model():
    torch = pytest.importorskip("torch")
    import transformers

    torch.manual_seed(0)
    c = transformers.GraniteMoeHybridConfig(
        **{k: v for k, v in MODEL.items() if k != "layer_types_run"},
        intermediate_size=64, layer_types=PERIOD * 2, mamba_chunk_size=16, mamba_conv_bias=True,
        mamba_proj_bias=False, num_local_experts=0, num_experts_per_tok=0,
        position_embedding_type="nope", attn_implementation="eager")
    model = transformers.GraniteMoeHybridForCausalLM(c).eval().float()
    with torch.no_grad():  # dt_bias and the conv as the seeded weights have them
        for layer in model.model.layers:
            if getattr(layer, "mamba", None) is not None:
                layer.mamba.dt_bias.uniform_(-6.0, -2.0)
                layer.mamba.conv1d.weight.uniform_(-0.5, 0.5)
                layer.mamba.conv1d.bias.normal_(0.0, 0.1)
    return torch, model


def test_logits_agree_with_transformers_and_the_checkpoint_round_trips(tmp_path):
    torch, model = _torch_model()
    model.save_pretrained(tmp_path / "hf", safe_serialization=True)
    from veomni_tpu.models.config import TransformerConfig

    cfg = TransformerConfig.from_pretrained(str(tmp_path / "hf"), dtype="float32")
    assert (cfg.embed_scale, cfg.intermediate_size, cfg.attention_multiplier) == (12.0, 64, 0.125)
    params = gh.hf_to_params(str(tmp_path / "hf"), cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 40))
    with torch.no_grad():
        want = model(input_ids=torch.tensor(ids)).logits.numpy()
    got = gh.forward_logits(params, cfg, jnp.asarray(ids, jnp.int32))
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-5 * float(np.abs(want).max()) + 1e-6
    # every name the torch model's state_dict has, and the same numbers back
    gh.save_hf_checkpoint(params, cfg, str(tmp_path / "ours"))
    from safetensors.numpy import load_file

    ours = load_file(str(tmp_path / "ours" / "model.safetensors"))
    theirs = {k: v.numpy() for k, v in model.state_dict().items()}
    assert set(ours) == set(theirs)
    for name, t in theirs.items():
        assert ours[name].shape == t.shape and np.array_equal(ours[name], t), name
    again = gh.hf_to_params(str(tmp_path / "ours"), cfg)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))
