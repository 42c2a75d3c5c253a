"""``ops.mla_qkv_rotary``: the ``pallas`` impl (interpreted on the CPU) against
the ``xla`` composition (the lines that stood in ``_mla_attention``).

The nope lanes and v are copies: equal to the bit, forward and backward. The
rope lanes are one f32 rotation rounded once in both impls, so on the CPU they
are equal to the bit too. ``d k_rope`` is where the two differ by design: the
composition rounds the sum over the heads to the input dtype before it rotates
it back, the kernel sums and rotates in f32 and rounds once, so it is held to
an f32 oracle more tightly than the composition is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu import ops
from veomni_tpu.observability.metrics import MetricsRegistry, set_registry
from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
from veomni_tpu.ops.mla_qkv_rotary import _mla_qkv_rotary_xla
from veomni_tpu.ops.pallas import mla_qkv_rope as mod
from veomni_tpu.ops.pallas.mla_qkv_rope import mla_qkv_rope

BF16_ULP = 2.0 ** -7  # bf16's spacing at 1.0: eight significant bits
WIDTHS = (128, 64, 128)  # dn, dr, dv: JoyAI-LLM-Flash's and the DeepSeek-V3 family's


def _inputs(b, s, h, widths=WIDTHS, interleaved=True, dtype=jnp.bfloat16, seed=0):
    dn, dr, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape, scale=1.0: (scale * jax.random.normal(k, shape, jnp.float32))
    q = normal(ks[0], (b, s, h * (dn + dr))).astype(dtype)
    kv = normal(ks[1], (b, s, h * (dn + dv)), 1.5).astype(dtype)
    k_rope = normal(ks[2], (b, s, dr)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    cos, sin = (t.astype(dtype) for t in ops.rotary_tables(pos, dr, 1e4, interleaved=interleaved))
    cots = (normal(ks[3], (b, s, h, dn + dr)), normal(ks[4], (b, s, h, dn + dr)),
            normal(ks[5], (b, s, h, dv)))
    return (q, kv, k_rope, cos, sin), cots


def _value_and_grads(impl, args, cots, widths=WIDTHS, interleaved=True):
    q, kv, k_rope, cos, sin = args

    def loss(q, kv, k_rope):
        outs = impl(q, kv, k_rope, cos, sin, *widths, interleaved)
        return sum((o.astype(jnp.float32) * c).sum() for o, c in zip(outs, cots)), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(
        q, kv, k_rope)
    return outs, grads


def _f32_d_k_rope(args, cots, widths, interleaved):
    """The oracle of ``d k_rope``: the composition in f32 end to end, at the
    bf16 cotangent the attention op hands back."""
    q, kv, k_rope, cos, sin = (a.astype(jnp.float32) for a in args)
    cots = tuple(c.astype(args[0].dtype).astype(jnp.float32) for c in cots)
    _, grads = _value_and_grads(_mla_qkv_rotary_xla, (q, kv, k_rope, cos, sin), cots,
                                widths, interleaved)
    return np.asarray(grads[2])


def _equal(got, want, what):
    assert got.dtype == want.dtype and got.shape == want.shape, what
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                  err_msg=what)


def _check(args, cots, widths=WIDTHS, interleaved=True):
    dn = widths[0]
    h = cots[0].shape[2]
    # the cotangents at the dtype the attention op hands them back in
    cots = tuple(c.astype(args[0].dtype) for c in cots)
    got_out, got_grads = _value_and_grads(mla_qkv_rope, args, cots, widths, interleaved)
    want_out, want_grads = _value_and_grads(_mla_qkv_rotary_xla, args, cots, widths, interleaved)
    for g, w, name in zip(got_out, want_out, ("q", "k", "v")):
        _equal(g, w, f"forward {name}")
    # what the op must not touch, against the inputs themselves
    q, kv = (np.asarray(a, np.float32).reshape(*a.shape[:2], h, -1) for a in args[:2])
    np.testing.assert_array_equal(np.asarray(got_out[0], np.float32)[..., :dn], q[..., :dn])
    np.testing.assert_array_equal(np.asarray(got_out[1], np.float32)[..., :dn], kv[..., :dn])
    np.testing.assert_array_equal(np.asarray(got_out[2], np.float32), kv[..., dn:])
    _equal(got_grads[0], want_grads[0], "dq")
    _equal(got_grads[1], want_grads[1], "dkv")
    # d k_rope: a sum over the heads. The kernel's one rounding lies within
    # half an ulp of the f32 oracle, entry by entry; the composition rounds
    # the sum before it rotates it back, so an entry carries its partner's
    # rounding too: an ulp of the tensor's largest entry
    oracle = _f32_d_k_rope(args, cots, widths, interleaved)
    ulp = BF16_ULP * 2.0 ** np.floor(np.log2(np.maximum(np.abs(oracle), 1e-30)))
    got, want = (np.asarray(g[2], np.float32) for g in (got_grads, want_grads))
    assert got_grads[2].dtype == want_grads[2].dtype and got.shape == want.shape
    assert np.all(np.abs(got - oracle) <= 0.5 * ulp + 1e-6), float(np.max(np.abs(got - oracle) / ulp))
    assert np.max(np.abs(want - oracle)) <= BF16_ULP * np.abs(oracle).max()


@pytest.fixture
def row_tile(monkeypatch):
    """Pin the row tile and the heads a grid step."""
    def pin(ts, hg=None):
        monkeypatch.setattr(mod, "_tiles", lambda s, h, *a: (ts, hg or h))
    return pin


# interleaved or not x one and two row tiles a row x the head counts, at the
# cell's widths; then a head group of two (the backward's sum over the groups
# of heads in its scratch), and wider nope and v lanes
CASES = [(i, ts, h, WIDTHS, None) for i in (True, False) for ts in (128, 256) for h in (2, 4)]
CASES += [(i, 128, 4, WIDTHS, 2) for i in (True, False)]
CASES += [(True, 128, 2, (256, 64, 128), None), (False, 128, 2, (128, 64, 256), None)]


@pytest.mark.parametrize(
    "interleaved,ts,h,widths,hg", CASES,
    ids=[f"{'pairs' if i else 'halves'}-ts{ts}-h{h}-{'x'.join(map(str, w))}" + (f"-hg{g}" if g else "")
         for i, ts, h, w, g in CASES])
def test_kernels_against_the_composition(interleaved, ts, h, widths, hg, row_tile):
    row_tile(ts, hg)
    args, cots = _inputs(2, 256, h, widths, interleaved, seed=h + ts)
    _check(args, cots, widths, interleaved)


def test_float32_inputs_take_the_kernels_too():
    args, cots = _inputs(1, 128, 2, dtype=jnp.float32, seed=5)
    got_out, got_grads = _value_and_grads(mla_qkv_rope, args, cots)
    want_out, want_grads = _value_and_grads(_mla_qkv_rotary_xla, args, cots)
    for g, w in zip(got_out + got_grads, want_out + want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-6,
                                   atol=2e-6 * float(np.abs(np.asarray(w)).max()))


def test_tiles_come_from_the_shape_under_flashs_budget():
    """Rows before heads: the joyai cell's call (32 heads of 128 + 64 / 128,
    bf16) takes 1024 rows of four heads a step, and so do four times the
    heads; a sequence only 128 divides takes 128 rows of all its heads; a
    width nothing fits takes none."""
    from veomni_tpu.ops.pallas import flash_attention as fa

    bf16 = jnp.bfloat16
    cell = (*WIDTHS, bf16, bf16)
    assert mod._tiles(8192, 32, *cell) == (1024, 4)
    assert mod._vmem_bytes(1024, 4, *cell) <= fa._VMEM_BUDGET < mod._vmem_bytes(1024, 8, *cell)
    assert mod._tiles(8192, 128, *cell) == (1024, 4)
    assert mod._tiles(8192, 2, *cell) == (1024, 2)
    assert mod._tiles(384, 4, *cell) == (128, 4)
    assert mod._tiles(8192, 2, 128 * 1024, 64, 128, bf16, bf16) is None


HANDOVERS = {
    "rehearsal_widths": (dict(widths=(16, 8, 16)), "qk_nope_head_dim 16 not a multiple of 128"),
    "v_192": (dict(widths=(128, 64, 192)), "v_head_dim 192 not a multiple of 128"),
    "rope_32": (dict(widths=(128, 32, 128)), "qk_rope_head_dim 32 not 64"),
    "odd_heads": (dict(h=3), "an odd number of heads (3)"),
    "ragged_s": (dict(s=100), "S not a multiple of 128"),
    "no_tile_fits": (dict(), "no row tile fits VMEM"),
}


@pytest.mark.parametrize("case", list(HANDOVERS))
def test_what_the_kernels_do_not_take_goes_to_xla_with_one_line(case, monkeypatch):
    shape, reason = HANDOVERS[case]
    seen = []
    monkeypatch.setattr(mod.logger, "info_once", lambda msg, *a: seen.append(msg % a))
    if case == "no_tile_fits":
        monkeypatch.setattr(mod, "_tiles", lambda *a: None)
    widths, h, s = shape.get("widths", WIDTHS), shape.get("h", 2), shape.get("s", 128)
    args, cots = _inputs(2, s, h, widths, seed=2)
    old = set_registry(MetricsRegistry())
    try:
        got_out, got_grads = _value_and_grads(mla_qkv_rope, args, cots, widths)
        counted = {n: getattr(mod.get_registry().get(f"attn.mla_qkv_rope.{n}"), "value", None)
                   for n in ("calls_kernel", "calls_handed_over")}
    finally:
        set_registry(old)
    want_out, want_grads = _value_and_grads(_mla_qkv_rotary_xla, args, cots, widths)
    for g, w in zip(got_out + got_grads, want_out + want_grads):
        _equal(g, w, case)
    assert len(seen) == 1 and seen[0].startswith("op mla_qkv_rotary: pallas hands q(2, "), seen
    assert seen[0].endswith(f"to xla ({reason})"), seen
    assert counted == {"calls_kernel": None, "calls_handed_over": 1}


def test_a_traced_call_counts_once_as_taken():
    args, _ = _inputs(1, 128, 2)
    old = set_registry(MetricsRegistry())
    try:
        for _ in range(2):  # two call sites of one program
            jax.make_jaxpr(lambda *a: mla_qkv_rope(*a, *WIDTHS, True))(*args)
        assert mod.get_registry().get("attn.mla_qkv_rope.calls_kernel").value == 2
        assert mod.get_registry().get("attn.mla_qkv_rope.calls_handed_over") is None
    finally:
        set_registry(old)


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
    args, cots = _inputs(b, s, 2, seed=4)
    cots = tuple(c.astype(jnp.bfloat16) for c in cots)
    want_out, want_grads = _value_and_grads(_mla_qkv_rotary_xla, args, cots)
    ps = init_parallel_state(ulysses_size=ulysses)
    sharded = "indivisible" not in case
    with use_parallel_state(ps):
        placed = tuple(jax.device_put(x, ps.batch_sharding() if sharded else ps.replicated())
                       for x in args)
        jaxpr = str(jax.make_jaxpr(lambda *a: mla_qkv_rope(*a, *WIDTHS, True))(*placed))
        got_out, got_grads = _value_and_grads(mla_qkv_rope, placed, cots)
    assert ("shard_map" in jaxpr) is sharded and ("pallas_call" in jaxpr) is sharded
    if sharded:
        assert not seen, seen
    else:
        reason = ("batch not a multiple of the mesh's dp extent 4" if case == "batch_indivisible"
                  else "S over the mesh's sp extent 2 not a multiple of 128")
        assert len(seen) == 1 and seen[0].endswith(f"to xla ({reason})"), seen
    for g, w, name in zip(got_out + got_grads[:2], want_out + want_grads[:2],
                          ("q", "k", "v", "dq", "dkv")):
        _equal(g, w, name)
    np.testing.assert_allclose(np.asarray(got_grads[2], np.float32),
                               np.asarray(want_grads[2], np.float32), atol=2 * BF16_ULP, rtol=0.02)


def test_registry_resolves_by_platform():
    """``pallas`` on TPU alone; the CPU resolves to the composition."""
    impls = KERNEL_REGISTRY.impls("mla_qkv_rotary")
    assert set(impls) == {"xla", "pallas"}
    assert impls["pallas"].device_types == ("tpu",) and impls["pallas"].fn is mla_qkv_rope
    assert impls["pallas"].priority > impls["xla"].priority
    assert KERNEL_REGISTRY.resolved_name("mla_qkv_rotary") == "xla"


def test_the_view_handed_to_flash_leaves_no_copy():
    """The kernels write ``[B, H, S, D]`` and hand back the ``[B, S, H, D]``
    view; the flash wrapper's own ``swapaxes`` undoes it: between the two
    kernels' calls the jaxpr holds that round trip of q, k and v and nothing
    else."""
    from veomni_tpu.ops.pallas.flash_attention import flash_attention

    args, _ = _inputs(1, 256, 2)

    def attend(*a):
        q, k, v = mla_qkv_rope(*a, *WIDTHS, True)
        return flash_attention(q, k, v, causal=True)

    jaxpr = jax.make_jaxpr(attend)(*args).jaxpr
    kernels = [i for i, e in enumerate(jaxpr.eqns)
               if e.primitive.name in ("pallas_call", "custom_vjp_call")]
    assert len(kernels) == 2, [e.primitive.name for e in jaxpr.eqns]
    rope, flash = (jaxpr.eqns[i] for i in kernels)
    between = jaxpr.eqns[kernels[0] + 1:kernels[1]]
    assert {e.primitive.name for e in between} == {"transpose"}, between
    made_by = {e.outvars[0]: e for e in between}
    reads = [v for v in flash.invars if v in made_by]
    assert len(reads) == 3
    for written, read in zip(rope.outvars, reads):
        back, there = made_by[read], made_by[made_by[read].invars[0]]
        assert there.invars[0] is written
        assert back.params["permutation"] == there.params["permutation"] == (0, 2, 1, 3)
    # and the compiler folds each pair away: what it keeps of q and k (the
    # only 192-wide arrays) is no transpose
    compiled = jax.jit(attend).lower(*args).compile().as_text()
    kept = [line for line in compiled.splitlines() if " transpose(" in line]
    assert len(kept) == 1 and ",192]" not in kept[0], kept  # the attention output's own


TINY = dict(vocab_size=256, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=2, q_lora_rank=64, kv_lora_rank=64,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            tie_word_embeddings=True)


@pytest.mark.parametrize("interleave", [True, False], ids=["pairs", "halves"])
def test_mla_layers_agree_under_both_impls(interleave):
    """Model level: the loss and every parameter's gradient of a tiny MLA
    decoder (``_mla_attention`` inside its scanned, rematerialised layers)
    with the op pinned to ``pallas`` and to ``xla``."""
    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models.auto import build_config

    cfg = build_config("deepseek_v3", **TINY, rope_interleave=interleave, dtype="bfloat16",
                       param_dtype="float32", remat=True, remat_policy="nothing")
    assert cfg.use_mla
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
            model = build_foundation_model(config=cfg, ops_implementation={"mla_qkv_rotary": impl})
            assert KERNEL_REGISTRY.resolved_name("mla_qkv_rotary") == impl
            params = model.family.init_params(jax.random.PRNGKey(1), cfg)
            if impl == "pallas":
                assert "mla_qkv_rope_fwd" in str(jax.make_jaxpr(
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
        gx, gp = np.asarray(gx, np.float32), np.asarray(gp, np.float32)
        tol = 4 * BF16_ULP / 2 * np.abs(gx).max()
        assert np.max(np.abs(gp - gx)) <= tol, (jax.tree_util.keystr(path),
                                                float(np.max(np.abs(gp - gx))), tol)
