"""``ops.qk_norm_rotary``: the ``pallas`` impl (interpreted on the CPU) against
the ``xla`` composition of ``rms_norm`` and ``apply_rotary``.

Forward to one bf16 ulp (on the CPU it is the composition's to the bit except
where ``rsqrt`` and ``1 / sqrt`` round apart), the gradients of q, k and both
weights to the composition's own tolerance: an ulp of the tensor's largest
entry (a weight's gradient is a sum over every token and head, rounded once
to the weight's dtype).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu import ops
from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.ops.pallas import qk_norm_rope as mod
from veomni_tpu.ops.pallas.qk_norm_rope import qk_norm_rope
from veomni_tpu.ops.qk_norm_rotary import _qk_norm_rotary_xla

BF16_ULP = 2.0 ** -7  # bf16's spacing at 1.0: eight significant bits


def _tables(kind, b, s, d, dtype, seed=0):
    """cos/sin [B, S, D] from plain positions, or from ``rotary_tables``'
    mrope branch (three position streams, a section map over the
    frequencies)."""
    if kind == "plain":
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        cos, sin = ops.rotary_tables(pos, d, 1e6)
    else:
        rng = np.random.default_rng(seed)
        pos = jnp.asarray(np.sort(rng.integers(0, 4 * s, (b, 3, s)), axis=-1))
        third = d // 2 // 4
        scaling = {"rope_type": "default", "mrope_section": [d // 2 - 2 * third, third, third]}
        cos, sin = ops.rotary_tables(pos, d, 1e6, rope_scaling=scaling)
    assert cos.shape == (b, s, d)
    return cos.astype(dtype), sin.astype(dtype)


def _inputs(b, s, hq, hk, d, normed, tables="plain", dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, s, hq * d), jnp.float32).astype(dtype)
    k = (1.5 * jax.random.normal(ks[1], (b, s, hk * d), jnp.float32)).astype(dtype)
    cos, sin = _tables(tables, b, s, d, dtype, seed)
    wq = wk = None
    if normed:
        wq = (1.0 + 0.2 * jax.random.normal(ks[2], (d,))).astype(dtype)
        wk = (1.0 + 0.2 * jax.random.normal(ks[3], (d,))).astype(dtype)
    gq = jax.random.normal(ks[4], (b, s, hq, d), jnp.float32)
    gk = jax.random.normal(ks[5], (b, s, hk, d), jnp.float32)
    return (q, k, cos, sin, wq, wk), (gq, gk)


def _value_and_grads(impl, args, cots, **kw):
    q, k, cos, sin, wq, wk = args
    normed = wq is not None

    def loss(q, k, wq, wk):
        oq, ok = impl(q, k, cos, sin, wq, wk, **kw)
        total = (oq.astype(jnp.float32) * cots[0]).sum() + (ok.astype(jnp.float32) * cots[1]).sum()
        return total, (oq, ok)

    wrt = (0, 1, 2, 3) if normed else (0, 1)
    (_, outs), grads = jax.jit(jax.value_and_grad(loss, argnums=wrt, has_aux=True))(q, k, wq, wk)
    return outs, grads


def _assert_forward(got, want, normed, what):
    """Rope alone: the composition's to the bit. With the norm: ``rsqrt``
    against ``1 / sqrt`` may move the normed value, rounded to bf16 before
    the rotation, by one ulp, and the rotation mixes two such values into an
    entry that may be smaller: one ulp of the head's largest entry, and all
    but a few entries in ten thousand equal."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    if not normed:
        np.testing.assert_array_equal(got, want, err_msg=what)
        return
    largest = np.abs(want).max(axis=-1, keepdims=True)
    tol = BF16_ULP * 2.0 ** np.floor(np.log2(largest))
    assert np.all(np.abs(got - want) <= tol), what
    assert np.mean(got != want) < 5e-4, (what, float(np.mean(got != want)))


def _assert_close_to_largest(got, want, ulps, what):
    """Within ``ulps`` bf16 ulps of the tensor's largest entry (gradients:
    sums of rounded terms)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    tol = ulps * BF16_ULP / 2 * np.abs(want).max()
    assert np.max(np.abs(got - want)) <= tol, (what, float(np.max(np.abs(got - want))), tol)


def _check(args, cots, **kw):
    got_out, got_grads = _value_and_grads(qk_norm_rope, args, cots, **kw)
    want_out, want_grads = _value_and_grads(_qk_norm_rotary_xla, args, cots, **kw)
    for g, w, name in zip(got_out, want_out, ("q", "k")):
        assert g.dtype == w.dtype
        _assert_forward(g, w, args[4] is not None, f"forward {name}")
    for g, w, name in zip(got_grads, want_grads, ("dq", "dk", "dw_q", "dw_k")):
        assert g.dtype == w.dtype and g.shape == w.shape
        _assert_close_to_largest(g, w, 2, name)


# grouped, equal and one kv head: the kernels walk q's and k's heads in two
# unrolled loops of their own, so what a case pays to compile grows with the
# heads it has and what it tests does not (two of each is every boundary).
# The qwen cell's own layout, 16 query heads over 8, stays once a norm mode.
HEADS = [(4, 2), (2, 2), (4, 1)]
CELL_HEADS = [(16, 8), (2, 2), (4, 1)]
NORMS = ["none", "plain", "zero_centered"]
# with / without norm x plain / zero-centred weight x the head counts, at one
# and at three row tiles; the 256-wide head (two lane tiles a head) at one
# tile; tables from the mrope branch at one head count (they reach the
# kernels as the same [B, S, D] arrays, so the head loop has nothing to add)
CASES = (
    [(n, h, 128, s, "plain") for n in NORMS for s, heads in ((128, CELL_HEADS), (384, HEADS)) for h in heads]
    + [(n, h, 256, 128, "plain") for n in NORMS for h in HEADS]
    + [(n, (4, 1), d, s, "mrope") for n in NORMS for d, s in ((128, 128), (128, 384), (256, 128))]
)


@pytest.mark.parametrize(
    "norm,heads,d,s,tables", CASES,
    ids=[f"{n}-{h[0]}q{h[1]}kv-d{d}-s{s}-{t}" for n, h, d, s, t in CASES])
def test_kernels_against_the_composition(norm, heads, d, s, tables):
    args, cots = _inputs(2, s, *heads, d, norm != "none", tables)
    _check(args, cots, eps=1e-6, zero_centered=norm == "zero_centered")


@pytest.mark.parametrize("ts", [128, 256])
def test_row_tiles_do_not_move_the_answer(ts, monkeypatch):
    """Several row tiles a row, and the weights' gradients summed over the
    blocks' partial sums."""
    monkeypatch.setattr(mod, "_row_tile", lambda *a: ts)
    args, cots = _inputs(2, 512, 4, 2, 128, True, seed=3)
    _check(args, cots, eps=1e-5)


def test_float32_inputs_take_the_kernels_too():
    args, cots = _inputs(1, 256, 2, 2, 128, True, dtype=jnp.float32, seed=5)
    got_out, got_grads = _value_and_grads(qk_norm_rope, args, cots)
    want_out, want_grads = _value_and_grads(_qk_norm_rotary_xla, args, cots)
    for g, w in zip(got_out + got_grads, want_out + want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5 * float(np.abs(np.asarray(w)).max()))


def test_row_tile_comes_from_the_shape_under_flashs_budget():
    """The qwen cell's call (16 + 8 heads of 128, bf16) takes 512 rows each
    way; a width whose smallest tile overflows the count takes none."""
    from veomni_tpu.ops.pallas import flash_attention as fa

    bf16 = jnp.bfloat16
    assert [mod._row_tile(k, 4096, 3072, 128, bf16, bf16) for k in ("fwd", "bwd")] == [512, 512]
    assert mod._row_tile("fwd", 384, 3072, 128, bf16, bf16) == 128
    for kernel in ("fwd", "bwd"):
        ts = mod._row_tile(kernel, 32768, 3072, 128, bf16, bf16)
        assert mod._vmem_bytes(kernel, ts, 3072, 128, bf16, bf16) <= fa._VMEM_BUDGET
    assert mod._row_tile("bwd", 4096, 64 * 1024, 128, bf16, bf16) is None


HANDOVERS = {
    "head_dim_64": (dict(d=64), {}, "head_dim 64 not a multiple of 128"),
    "partial_rotary": (dict(rot=64), dict(head_dim=128), "partial rotary (64 of 128)"),
    "interleaved": (dict(), dict(interleaved=True), "interleaved rotary"),
    "ragged_s": (dict(s=100), {}, "S not a multiple of 128"),
    "no_tile_fits": (dict(), {}, "no row tile fits VMEM"),
}


@pytest.mark.parametrize("case", list(HANDOVERS))
def test_what_the_kernels_do_not_take_goes_to_xla_with_one_line(case, monkeypatch):
    shape, kw, reason = HANDOVERS[case]
    seen = []
    monkeypatch.setattr(mod.logger, "info_once", lambda msg, *a: seen.append(msg % a))
    if case == "no_tile_fits":
        monkeypatch.setattr(mod, "_row_tile", lambda *a: None)
    d, s = shape.get("d", 128), shape.get("s", 256)
    args, cots = _inputs(2, s, 4, 2, d, True, seed=2)
    if "rot" in shape:  # tables over the leading dims only (glm4_moe)
        args = args[:2] + tuple(t[..., :shape["rot"]] for t in args[2:4]) + args[4:]
    if kw.get("interleaved"):
        pos = jnp.broadcast_to(jnp.arange(s)[None], (2, s))
        args = args[:2] + tuple(t.astype(jnp.bfloat16) for t in ops.rotary_tables(
            pos, d, 1e6, interleaved=True)) + args[4:]
    got_out, got_grads = _value_and_grads(qk_norm_rope, args, cots, **kw)
    want_out, want_grads = _value_and_grads(_qk_norm_rotary_xla, args, cots, **kw)
    for g, w in zip(got_out + got_grads, want_out + want_grads):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
    assert len(seen) == 1 and seen[0].startswith("op qk_norm_rotary: pallas hands q(2, "), seen
    assert seen[0].endswith(f"to xla ({reason})"), seen


@pytest.mark.parametrize("case", ["dp4", "dp2_sp2", "batch_indivisible", "rows_indivisible"])
def test_under_a_gspmd_mesh(case, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: on a multi-device mesh the op
    runs in a shard_map over (dp, sp, None), the activation's own sharding,
    and hands over where the mesh does not divide the batch or the rows."""
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    seen = []
    monkeypatch.setattr(mod.logger, "info_once",
                        lambda msg, *a: (msg % a) in seen or seen.append(msg % a))
    b, s, ulysses = {"dp4": (4, 128, 1), "dp2_sp2": (2, 256, 2),
                     "batch_indivisible": (2, 128, 1), "rows_indivisible": (2, 128, 2)}[case]
    args, cots = _inputs(b, s, 4, 2, 128, True, seed=4)
    want_out, want_grads = _value_and_grads(_qk_norm_rotary_xla, args, cots)
    ps = init_parallel_state(ulysses_size=ulysses)
    sharded = "indivisible" not in case
    with use_parallel_state(ps):
        rows = ps.batch_sharding() if sharded else ps.replicated()
        placed = tuple(jax.device_put(x, rows if x.ndim == 3 else ps.replicated()) for x in args)
        jaxpr = str(jax.make_jaxpr(lambda *a: qk_norm_rope(*a))(*placed))
        got_out, got_grads = _value_and_grads(qk_norm_rope, placed, cots)
    assert ("shard_map" in jaxpr) is sharded and ("pallas_call" in jaxpr) is sharded
    if sharded:
        assert not seen, seen
    else:
        reason = ("batch not a multiple of the mesh's dp extent 4" if case == "batch_indivisible"
                  else "S over the mesh's sp extent 2 not a multiple of 128")
        assert len(seen) == 1 and seen[0].endswith(f"to xla ({reason})"), seen
    for g, w, name in zip(got_out, want_out, ("q", "k")):
        _assert_forward(g, w, True, name)
    for g, w, name in zip(got_grads, want_grads, ("dq", "dk", "dw_q", "dw_k")):
        _assert_close_to_largest(g, w, 2, name)


def test_registry_resolves_by_platform():
    """``pallas`` on TPU alone; the CPU resolves to the composition."""
    impls = KERNEL_REGISTRY.impls("qk_norm_rotary")
    assert set(impls) == {"xla", "pallas"}
    assert impls["pallas"].device_types == ("tpu",) and impls["pallas"].fn is qk_norm_rope
    assert impls["pallas"].priority > impls["xla"].priority
    assert KERNEL_REGISTRY.resolved_name("qk_norm_rotary") == "xla"


TINY = dict(vocab_size=256, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=1, head_dim=128, tie_word_embeddings=True)


@pytest.mark.parametrize("dialect", ["qwen3", "llama"], ids=["qk_norm", "rope_alone"])
def test_decoder_layers_agree_under_both_impls(dialect):
    """Model level: the loss and every parameter's gradient of a tiny decoder
    (``_standard_attention`` inside its scanned, rematerialised layers) with
    the op pinned to ``pallas`` and to ``xla``."""
    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models.auto import build_config

    cfg = build_config(dialect, **TINY, dtype="bfloat16", param_dtype="float32", remat=True,
                       remat_policy="nothing")
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(0, 256, (2, 256)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 256, (2, 256)), jnp.int32),
        "position_ids": jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32)[None], (2, 256)),
        "segment_ids": jnp.ones((2, 256), jnp.int32),
    }
    results = {}
    try:
        for impl in ("xla", "pallas"):
            model = build_foundation_model(config=cfg, ops_implementation={"qk_norm_rotary": impl})
            assert KERNEL_REGISTRY.resolved_name("qk_norm_rotary") == impl
            params = model.family.init_params(jax.random.PRNGKey(1), cfg)
            if impl == "pallas":
                assert "qk_norm_rope_fwd" in str(jax.make_jaxpr(
                    lambda p: model.loss_fn(p, batch)[0])(params))
            results[impl] = jax.jit(jax.value_and_grad(
                lambda p: model.loss_fn(p, batch)[0]))(params)
    finally:
        KERNEL_REGISTRY.clear_pins()
    (loss_x, grads_x), (loss_p, grads_p) = results["xla"], results["pallas"]
    assert abs(float(loss_x) - float(loss_p)) <= 1e-5 * abs(float(loss_x))
    flat_x, flat_p = (jax.tree_util.tree_leaves_with_path(g) for g in (grads_x, grads_p))
    assert len(flat_x) > 5
    for (path, gx), (_, gp) in zip(flat_x, flat_p):
        _assert_close_to_largest(gp, gx, 4, jax.tree_util.keystr(path))
