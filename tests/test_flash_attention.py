"""Pallas flash attention numerics vs the XLA reference impl.

Reference test model: ``tests/ops/test_kernel_registry_numerical.py``
(per-(op,impl) alignment). Runs the kernel in interpret mode on CPU.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.ops.attention import _attention_dense, _attention_xla
from veomni_tpu.ops.pallas import flash_attention as fa
from veomni_tpu.ops.pallas.flash_attention import flash_attention


def _inputs(b=2, s=256, hq=4, hkv=2, d=64, seed=0, packed=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    if packed:
        seg = np.ones((b, s), np.int32)
        seg[:, s // 3:] = 2
        seg[:, 2 * s // 3:] = 3
        seg[:, -7:] = 0  # trailing padding segment
        seg = jnp.asarray(seg)
    else:
        seg = None
    return q, k, v, seg


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_forward_matches_xla(packed, causal):
    q, k, v, seg = _inputs(packed=packed)
    ref = _attention_xla(q, k, v, segment_ids=seg, causal=causal)
    got = flash_attention(q, k, v, segment_ids=seg, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_backward_matches_xla():
    q, k, v, seg = _inputs(s=256)

    def loss_ref(q, k, v):
        return (_attention_xla(q, k, v, segment_ids=seg, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, segment_ids=seg, causal=True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
            err_msg=f"grad d{name} mismatch",
        )


# ---------------------------------------------------------------------------
# The tile schedule: small tiles through the internal entry, so that a call
# has several tile pairs: dead ones, partly live ones and full ones.
# ---------------------------------------------------------------------------
def _segments(kind, b, s, seed=0):
    """[b, s] int32 ids of the kind, or None."""
    rng = np.random.default_rng(seed)
    if kind == "none":
        return None
    if kind == "unsorted":  # ids that come back: nothing a range test can order
        return rng.integers(0, 3, (b, s)).astype(np.int32)
    seg = np.zeros((b, s), np.int32)
    for row in seg:
        end = s - (int(rng.integers(5, s // 3)) if kind == "packed_pad" else 0)
        cuts = np.sort(rng.choice(np.arange(1, end), size=3, replace=False))
        for i, part in enumerate(np.split(np.arange(end), cuts)):
            row[part] = i + 1
    return seg


SEG_KINDS = ["none", "packed", "packed_pad", "unsorted"]


def _bhsd_inputs(b, s, hq, hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, hq, d), jnp.float32),
            jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32),
            jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32),
            jax.random.normal(ks[3], (b, s, hq, d), jnp.float32))


def _tiled_flash(q, k, v, seg, causal, tiles):
    out = fa._flash_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        None if seg is None else jnp.asarray(seg), q.shape[-1] ** -0.5, causal, tiles)
    return jnp.swapaxes(out, 1, 2)


@contextlib.contextmanager
def _split_backward():
    """No dQ row fits: every backward traced under this is the split pair."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(fa, "_DQ_ROW_CEILING", 0)
        yield


@pytest.fixture
def registry():
    """A registry of this test's own, for the trace-time counters."""
    from veomni_tpu.observability.metrics import MetricsRegistry, set_registry

    fresh = MetricsRegistry()
    old = set_registry(fresh)
    yield fresh
    set_registry(old)


def _bwd_calls(registry):
    return tuple(registry.counter(f"attn.flash.bwd.calls_{form}").value
                 for form in ("fused", "split"))


def _grads(q, k, v, w, seg, causal, tiles):
    """One program of its own a call (not op by op): traced under whatever
    the case planted, and one traced backward for the counters."""
    loss = lambda q, k, v: (_tiled_flash(q, k, v, seg, causal, tiles) * w).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _dense_grads(q, k, v, w, seg, causal):
    seg = None if seg is None else jnp.asarray(seg)
    loss = lambda q, k, v: (_attention_dense(q, k, v, segment_ids=seg, causal=causal) * w).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _check_parity(q, k, v, w, seg, causal, tiles):
    """Forward against the dense impl; the backward in BOTH its forms from
    one forward's residuals: the fused kernel (what a row this short takes)
    and the split pair, each against the dense impl's gradients, and against
    each other: dK and dV bit for bit (the fused kernel's dK, dV are the
    split dKV kernel's own lines at its own tiles), dQ within the tolerance
    (other tiles, another order of the same f32 sums)."""
    seg = None if seg is None else jnp.asarray(seg)
    scale = q.shape[-1] ** -0.5
    bhsd = lambda x: jnp.swapaxes(x, 1, 2)
    # each side one program, not op by op; the backward's a fresh one a form,
    # so that it is traced under that form's ceiling
    out, residuals = jax.jit(lambda q, k, v: fa._flash_fwd_rule(
        bhsd(q), bhsd(k), bhsd(v), seg, scale, causal, tiles))(q, k, v)
    ref = jax.jit(lambda q, k, v: _attention_dense(q, k, v, segment_ids=seg, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(bhsd(out)), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert fa._fuses_bwd(q.shape[1], q.shape[-1], q.dtype, tiles)
    g_ref = _dense_grads(q, k, v, w, seg, causal)
    backward = lambda: jax.jit(lambda res, w: [
        bhsd(g) for g in fa._bwd(scale, causal, tiles, res, bhsd(w))[:3]])(residuals, w)
    g_fused = backward()
    with _split_backward():
        g_split = backward()
    for form, g_got in (("fused", g_fused), ("split", g_split)):
        for a, b_, name in zip(g_got, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
                                       err_msg=f"{form} backward: grad d{name} mismatch")
    for a, b_, name in zip(g_fused[1:], g_split[1:], "kv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=f"fused d{name} is not the split dKV kernel's")


def _tiled_case(causal, kind, group, b=2, s=512, hkv=1, d=64):
    q, k, v, w = _bhsd_inputs(b, s, hkv * group, hkv, d)
    seg = _segments(kind, b, s, seed=3)
    t = (128, 128)
    if kind in ("packed", "packed_pad"):
        live = fa.tile_liveness(seg, s, 128, 128, causal)
        assert 0 < live.sum() < live.size  # some tile pairs are dead, some are not
    _check_parity(q, k, v, w, seg, causal, fa.Tiles(t, t, t))


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("kind", SEG_KINDS)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_tiled_flash_matches_dense(causal, kind, group):
    """Forward and backward at 4 x 4 tiles of 128 against the dense impl."""
    _tiled_case(causal, kind, group)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_tiled_flash_with_a_gqa_group_of_four(causal):
    """Four q heads a kv head: the kv index map's ``hi // group``, the f32
    per-q-head dK, dV and XLA's group sum, in both backward forms."""
    _tiled_case(causal, "packed_pad", 4, b=1)


@pytest.mark.parametrize("tiles", [
    fa.Tiles((256, 128), (128, 256), (256, 128)),
    fa.Tiles((128, 256), (256, 128), (128, 256)),
    fa.Tiles((256, 256), (256, 256), (256, 256)),
], ids=["tall", "wide", "square"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_tiled_flash_tiles_that_differ_by_kernel(causal, tiles):
    """q and kv tiles of different sizes, and other tiles in the backward's
    kernels than in the forward (the chooser gives each kernel its own)."""
    b, s, hq, hkv, d = 1, 512, 2, 1, 64
    q, k, v, w = _bhsd_inputs(b, s, hq, hkv, d, seed=1)
    _check_parity(q, k, v, w, _segments("packed_pad", b, s, seed=5), causal, tiles)


@pytest.mark.parametrize("kind", SEG_KINDS)
def test_tiled_flash_with_v_narrower_than_qk(kind):
    """MLA's training form (q, k of nope + rope, v of nope alone): forward
    and the three gradients at 4 x 4 tiles against the dense impl, causal,
    scores scaled by the q/k width; dQ, dK come out as wide as q, dV as v."""
    b, s, h, d, dv = 2, 512, 2, 96, 64
    q, k, _, _ = _bhsd_inputs(b, s, h, h, d, seed=4)
    _, _, v, w = _bhsd_inputs(b, s, h, h, dv, seed=5)
    t = (128, 128)
    _check_parity(q, k, v, w, _segments(kind, b, s, seed=3), True, fa.Tiles(t, t, t))


def test_flash_facade_keeps_mla_widths_on_the_kernels(monkeypatch):
    """The facade no longer hands a call whose v is narrower than q, k to
    XLA: no hand-off line, the XLA path's answer, o as wide as v."""
    seen = []
    monkeypatch.setattr(fa.logger, "info_once", lambda msg, *a: seen.append(msg % a))
    q, k, v, seg = _inputs(d=96)
    v = v[..., :64]
    got = flash_attention(q, k, v, segment_ids=seg, causal=True)
    ref = _attention_xla(q, k, v, segment_ids=seg, causal=True)
    assert got.shape == q.shape[:-1] + (64,) and not seen, seen
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_tiled_flash_without_a_table(monkeypatch):
    """A table too large for SMEM: the call goes without one (the causal
    skip by arithmetic, every copy made), and answers the same."""
    monkeypatch.setattr(fa, "_TABLE_WORDS", 4)
    b, s, hq, hkv, d = 1, 512, 2, 2, 64
    q, k, v, w = _bhsd_inputs(b, s, hq, hkv, d, seed=2)
    t = (128, 128)
    assert fa._fetch_table(fa.tile_liveness(None, s, 128, 128, True)) is None
    _check_parity(q, k, v, w, _segments("packed", b, s), True, fa.Tiles(t, t, t))


@pytest.mark.parametrize("dead", [0, 3])
def test_fused_backward_hands_over_zeros_for_a_q_tile_no_step_computes(dead, monkeypatch):
    """The zeroing of a q tile's dQ rows and their hand-over run whether or
    not the step is live: with a table (forged, for the backward's walk alone)
    in which q tile ``dead`` is live under no kv tile, its dQ rows come out
    zeros and not what VMEM held; every other q tile's rows are bit for bit
    what the honest table gives, and dK, dV lose that tile's share alone."""
    b, s, h, d, t = 2, 512, 2, 64, 128
    q, k, v, w = _bhsd_inputs(b, s, h, h, d, seed=6)
    seg = _segments("packed_pad", b, s, seed=3)
    tiles = fa.Tiles((t, t), (t, t), (t, t))
    honest = _grads(q, k, v, w, seg, True, tiles)
    schedule = fa._schedule

    def forged(segment_ids, s_, bq, bk, causal, q_outer):
        tbl, where = schedule(segment_ids, s_, bq, bk, causal, q_outer)
        if not q_outer:  # [B, kv tile, q tile]: q tile `dead` names another's block
            tbl = tbl.reshape(b, s // t, s // t).at[:, :, dead].set((dead + 1) % 4).reshape(-1)
        return tbl, where

    monkeypatch.setattr(fa, "_schedule", forged)
    got = _grads(q, k, v, w, seg, True, tiles)
    rows = slice(dead * t, (dead + 1) * t)
    assert np.abs(np.asarray(honest[0][:, rows])).max() > 1e-3  # the honest rows are not zeros
    np.testing.assert_array_equal(np.asarray(got[0][:, rows]), 0.0)
    kept = np.ones(s, bool)
    kept[rows] = False
    np.testing.assert_array_equal(np.asarray(got[0][:, kept]), np.asarray(honest[0][:, kept]))
    # dK, dV without that q tile's queries: the dense impl's with their weights zeroed
    # (dK also loses the dead tile's ds q, which zeroed weights give too: ds = p (dp - delta))
    g_ref = _dense_grads(q, k, v, w.at[:, rows].set(0.0), seg, True)
    for a, b_, name in zip(got[1:], g_ref[1:], "kv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
                                   err_msg=f"grad d{name} mismatch")


# the benchmark's four cells' calls: (S, q/k width, v width)
CELL_CALLS = {"qwen3_0p6b": (4096, 128, 128), "joyai_llm_flash": (8192, 192, 128),
              "granite_4_0_h_micro": (8192, 64, 64), "kimi_linear_48b_a3b": (8192, 192, 128)}


@pytest.mark.parametrize("cell", list(CELL_CALLS))
def test_every_cells_backward_is_the_fused_one_at_dkvs_tiles(cell):
    """The rule reads S and D alone: each cell's call keeps its dQ row in
    VMEM, beside the tiles the split dKV kernel had (the row is counted on
    its own line, against its own ceiling, and moves no tile)."""
    s, d, dv = CELL_CALLS[cell]
    tiles = fa.choose_tiles(s, d, jnp.bfloat16, True, fa._other_width(d, dv))
    assert fa._fuses_bwd(s, d, jnp.bfloat16, tiles)
    lanes = fa._lane_padded(d)
    row = fa._dq_row_bytes(s, tiles.dkv[0], d, 2)
    assert row == s * lanes * 4 + 2 * tiles.dkv[0] * lanes * 2 <= fa._DQ_ROW_CEILING
    bq, bk = tiles.dkv
    assert fa._vmem_bytes("dkv", bq, bk, d, 2, fa._other_width(d, dv)) <= fa._VMEM_BUDGET
    assert bq * bk >= 512 * 1024 or d > 128  # what Tiles.dkv was before there was a row


def test_a_row_too_long_for_the_ceiling_keeps_the_split_pair(monkeypatch, registry):
    """A 32k row of 128-wide heads is fused (16 MiB of dQ), a 64k or 128k row
    is not; and with the ceiling between two small rows, the shorter goes
    fused, the longer split, by the counters, and both agree with the dense
    impl."""
    fuses = lambda s, d: fa._fuses_bwd(s, d, jnp.bfloat16, fa.choose_tiles(s, d, jnp.bfloat16, True))
    assert fuses(32768, 128) and not fuses(65536, 128) and not fuses(131072, 128)
    assert fuses(16384, 256) and not fuses(32768, 256) and not fuses(65536, 64)  # 64 fills 128 lanes
    t = (128, 128)
    tiles = fa.Tiles(t, t, t)
    monkeypatch.setattr(fa, "_DQ_ROW_CEILING", fa._dq_row_bytes(128, 128, 64, 4))
    for s, calls in ((128, (1, 0)), (256, (1, 1))):
        q, k, v, w = _bhsd_inputs(1, s, 2, 1, 64, seed=s)
        seg = _segments("packed_pad", 1, s, seed=2)
        got = _grads(q, k, v, w, seg, True, tiles)
        assert _bwd_calls(registry) == calls
        for a, b_ in zip(got, _dense_grads(q, k, v, w, seg, True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4)


def test_backward_counters_count_traced_call_sites(registry):
    """``attn.flash.bwd.calls_fused`` / ``.calls_split`` rise once a traced
    backward (a call site: a jitted gradient that runs three times traces
    once), under the form the call took; a forward alone counts nothing."""
    q, k, v, seg = _inputs()
    loss = lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, causal=True).sum()
    flash_attention(q, k, v, segment_ids=seg, causal=True)
    assert _bwd_calls(registry) == (0, 0)
    g = jax.jit(jax.grad(loss))
    for _ in range(3):
        g(q, k, v)
    assert _bwd_calls(registry) == (1, 0)
    with _split_backward():
        jax.grad(loss)(q, k, v)
    assert _bwd_calls(registry) == (1, 1)


def _dense_liveness(seg, s, bq, bk, causal):
    admitted = seg[:, :, None] == seg[:, None, :]
    if causal:
        admitted &= np.tri(s, dtype=bool)[None]
    return admitted.reshape(len(seg), s // bq, bq, s // bk, bk).any(axis=(2, 4))


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("kind", ["random", "runs", "packed", "packed_pad"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_liveness_is_never_optimistic_and_exact_for_sorted_ids(causal, kind, seed):
    rng = np.random.default_rng(seed)
    b, s = 3, 512
    bq, bk = [(64, 64), (128, 64), (64, 128)][seed % 3]
    if kind == "random":
        seg = rng.integers(-2, 6, (b, s)).astype(np.int32)
    elif kind == "runs":  # runs of any id in any order
        seg = np.repeat(rng.integers(0, 5, (b, s // 32)), 32, axis=1).astype(np.int32)
    else:
        seg = _segments(kind, b, s, seed=seed)
    want = _dense_liveness(seg, s, bq, bk, causal)
    for ids in (seg, jnp.asarray(seg)):  # numpy on the host, jax in the wrapper
        live = np.asarray(fa.tile_liveness(ids, s, bq, bk, causal))
        assert live.shape == want.shape
        assert not (want & ~live).any(), "a tile that holds an admitted pair was called dead"
        if kind in ("packed", "packed_pad"):
            np.testing.assert_array_equal(live, want)
    assert isinstance(fa.tile_liveness(seg, s, bq, bk, causal), np.ndarray)


def test_liveness_without_segments_is_the_causal_triangle():
    live = fa.tile_liveness(None, 512, 256, 128, True)
    assert live.shape == (1, 2, 4)
    np.testing.assert_array_equal(live[0], [[1, 1, 0, 0], [1, 1, 1, 1]])
    assert fa.tile_liveness(None, 512, 128, 128, False).all()


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd_dq", "dkv"])
def test_a_dead_step_names_the_last_live_block(transposed):
    """The index maps read the table: a live step names its own block, a
    dead one the block of the last live step before it in its row (before
    the row's first live step: that one's), so nothing is copied for it."""
    b, s, t = 2, 1024, 128
    seg = _segments("packed_pad", b, s, seed=7)
    live = fa.tile_liveness(seg, s, t, t, True)
    if transposed:
        live = np.swapaxes(live, 1, 2)
    n = s // t
    table = np.asarray(fa._fetch_table(jnp.asarray(live)))
    assert table.dtype == np.int32 and table.shape == (b * n * n,)
    where = dict(n_outer=n, n_inner=n, per_batch=True)
    # the BlockSpecs the kernels are built with: the inner axis' side reads
    # the table, the outer axis' side is the step's own tile
    specs = fa._block_specs(t, t, 64, 2, True, not transposed, where)
    inner_side, outer_side = ("q", "kv") if transposed else ("kv", "q")
    dead_seen = 0
    for bi in range(b):
        for outer in range(n):
            alive = np.flatnonzero(live[bi, outer])
            assert len(alive)  # the tile on the diagonal always is
            for inner in range(n):
                named = fa._inner_block((table,), bi, outer, inner, **where)
                assert specs[inner_side].index_map(bi, 2, outer, inner, table)[2] == named
                assert specs[outer_side].index_map(bi, 2, outer, inner, table)[2] == outer
                assert specs["segs"][1].index_map(bi, 2, outer, inner, table) == (bi, 0, named)
                if live[bi, outer, inner]:
                    assert named == inner
                    continue
                dead_seen += 1
                before = alive[alive < inner]
                assert named == (before[-1] if len(before) else alive[0])
                assert named != inner  # which is how the kernel tells
    assert dead_seen > n
    # without a table an index map names the step's own block
    assert fa._inner_block((), 1, 2, 3, **where) == 3


@pytest.mark.parametrize("s,d,dtype", [
    (4096, 128, jnp.bfloat16), (32768, 128, jnp.bfloat16), (1024, 64, jnp.bfloat16),
    (4096, 128, jnp.float32), (2048, 256, jnp.bfloat16), (512, 64, jnp.float32),
    (384, 128, jnp.bfloat16), (3968, 128, jnp.bfloat16), (128, 64, jnp.bfloat16),
    (1536, 72, jnp.bfloat16),
])
def test_tile_chooser(s, d, dtype):
    tiles = fa.choose_tiles(s, d, dtype, True)
    assert tiles == fa.choose_tiles(s, d, dtype, True)  # equal shapes, equal tiles
    itemsize = jnp.dtype(dtype).itemsize
    for kernel, (bq, bk) in zip(("fwd", "dkv", "dq"), tiles):
        assert s % bq == 0 and s % bk == 0
        assert bq in fa._TILE_SIZES and bk in fa._TILE_SIZES
        if (bq, bk) != (128, 128):
            assert fa._vmem_bytes(kernel, bq, bk, d, itemsize) <= fa._VMEM_BUDGET
        if s % 256:
            assert (bq, bk) == (128, 128)  # nothing larger divides: as before
        if s % 256 == 0 and s >= 256:
            assert bq * bk > 128 * 128  # and a shape that can, does better


def test_tile_chooser_counts_both_widths():
    """q, k of 192 and v of 128 at the MLA cell's S: every kernel's tiles fit
    the budget with 192 counted as the 256 lanes it fills, lie between the
    choices for 128 and for 256 all round, and equal widths are counted as
    they were before the two were told apart."""
    s, itemsize = 8192, 2
    mla = fa.choose_tiles(s, 192, jnp.bfloat16, True, 128)
    narrow, wide = (fa.choose_tiles(s, d, jnp.bfloat16, True) for d in (128, 256))
    for kernel, (bq, bk), lo, hi in zip(("fwd", "dkv", "dq"), mla, wide, narrow):
        assert fa._vmem_bytes(kernel, bq, bk, 192, itemsize, 128) <= fa._VMEM_BUDGET
        assert lo[0] * lo[1] <= bq * bk <= hi[0] * hi[1]
        assert fa._vmem_bytes(kernel, bq, bk, 128, itemsize) \
            < fa._vmem_bytes(kernel, bq, bk, 192, itemsize, 128) \
            < fa._vmem_bytes(kernel, bq, bk, 256, itemsize)
        assert fa._vmem_bytes(kernel, bq, bk, 128, itemsize, 128) \
            == fa._vmem_bytes(kernel, bq, bk, 128, itemsize)
    assert fa._other_width(128, 128) is None and fa._other_width(192, 128) == 128
    seg = _segments("packed_pad", 2, s, seed=11)
    assert fa.tile_census(seg, 192, jnp.bfloat16, v_head_dim=128)[0] \
        == 2 * (s // mla.fwd[0]) * (s // mla.fwd[1])


def test_host_census_counts_the_wrappers_table():
    """What the trainer loop counts on the host is the table the kernel
    wrapper builds on the device for the same batch."""
    s, d = 4096, 64
    seg = _segments("packed_pad", 4, s, seed=11)
    pairs, live_pairs = fa.tile_census(seg.reshape(2, 2, s), d, jnp.float32)
    bq, bk = fa.choose_tiles(s, d, jnp.float32, True).fwd
    nq, nk = s // bq, s // bk
    table = np.asarray(fa._fetch_table(fa.tile_liveness(jnp.asarray(seg), s, bq, bk, True)))
    is_live = table.reshape(4, nq, nk) == np.arange(nk)
    assert pairs == 4 * nq * nk
    assert live_pairs == int(is_live.sum()) and 0 < live_pairs < pairs
    assert fa.tile_census(seg[:, :100], d, jnp.float32) == (0, 0)  # not the kernel's


@pytest.mark.parametrize("case", ["ragged_s", "cross", "window"])
def test_flash_fallback_paths(case, monkeypatch):
    # shapes/features the kernel doesn't cover go to XLA, and say so once
    from veomni_tpu.ops.pallas import flash_attention as fa

    seen = []
    monkeypatch.setattr(
        fa.logger, "info_once", lambda msg, *a: seen.append(msg % a)
    )
    q, k, v, seg = _inputs(s=100 if case == "ragged_s" else 256)
    kwargs = dict(segment_ids=seg, causal=True)
    if case == "cross":
        k, v = k[:, :128], v[:, :128]
        kwargs = dict(segment_ids=None, causal=False)
    if case == "window":
        kwargs["sliding_window"] = 64
    out = flash_attention(q, k, v, **kwargs)
    ref = _attention_xla(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
    assert len(seen) == 1 and "pallas_flash hands" in seen[0], seen


@pytest.mark.parametrize("batch,sharded", [(4, True), (2, False)],
                         ids=["divisible", "indivisible"])
def test_flash_under_gspmd_mesh(batch, sharded, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: on a multi-device mesh the
    wrapper runs it in a shard_map over the batch axes, or, where the batch
    does not divide, hands it to XLA and says so."""
    from veomni_tpu.ops.pallas import flash_attention as fa
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    seen = []
    monkeypatch.setattr(
        fa.logger, "info_once", lambda msg, *a: seen.append(msg % a)
    )
    q, k, v, seg = _inputs(b=batch)
    ref = _attention_xla(q, k, v, segment_ids=seg, causal=True)
    ps = init_parallel_state()  # fsdp=4: the batch is sharded four ways
    fn = lambda q, k, v, seg: flash_attention(q, k, v, segment_ids=seg, causal=True)
    with use_parallel_state(ps):
        sh = ps.batch_sharding() if sharded else ps.replicated()
        args = [jax.device_put(x, sh) for x in (q, k, v, seg)]
        jaxpr = str(jax.make_jaxpr(fn)(*args))
        got = jax.jit(fn)(*args)
    assert ("shard_map" in jaxpr) is sharded
    assert ("pallas_call" in jaxpr) is sharded
    assert bool(seen) is not sharded, seen
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Blockwise online-softmax XLA attention (the long-context path on platforms
# where Pallas is unavailable) vs the dense reference impl.
# ---------------------------------------------------------------------------
from veomni_tpu.ops.attention import _attention_dense, _attention_xla_chunked


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_chunked_forward_matches_dense(packed, causal):
    q, k, v, seg = _inputs(s=512, packed=packed)
    ref = _attention_dense(q, k, v, segment_ids=seg, causal=causal)
    got = _attention_xla_chunked(
        q, k, v, segment_ids=seg, causal=causal, q_chunk=128, k_chunk=128
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_chunked_sliding_window_and_sinks():
    q, k, v, seg = _inputs(s=512, packed=True)
    sinks = jnp.linspace(-1.0, 1.0, q.shape[2])
    for window in (64, None):
        ref = _attention_dense(
            q, k, v, segment_ids=seg, causal=True,
            sliding_window=window, sinks=sinks,
        )
        got = _attention_xla_chunked(
            q, k, v, segment_ids=seg, causal=True,
            sliding_window=window, sinks=sinks, q_chunk=128, k_chunk=128,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_chunked_grads_match_dense():
    q, k, v, seg = _inputs(s=512, packed=True)

    def loss(fn, q, k, v):
        out = fn(q, k, v, segment_ids=seg, causal=True)
        return (out * jnp.arange(out.size).reshape(out.shape) / out.size).sum()

    ref_g = jax.grad(lambda *a: loss(_attention_dense, *a), argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(
        lambda *a: loss(
            lambda *b, **kw: _attention_xla_chunked(*b, q_chunk=128, k_chunk=128, **kw),
            *a,
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for r, g in zip(ref_g, got_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


def test_chunked_threshold_dispatch(monkeypatch):
    """The default 'xla' impl must route long sequences through the chunked
    path (no [B,H,S,S] tensor) — probe via a tiny threshold."""
    monkeypatch.setenv("VEOMNI_ATTN_CHUNK_THRESHOLD", "128")
    q, k, v, seg = _inputs(s=512, packed=True)
    ref = _attention_dense(q, k, v, segment_ids=seg, causal=True)
    got = _attention_xla(q, k, v, segment_ids=seg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


from veomni_tpu.ops.attention import _attention_xla_twopass


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_twopass_forward_matches_dense(packed, causal):
    q, k, v, seg = _inputs(s=512, packed=packed)
    ref = _attention_dense(q, k, v, segment_ids=seg, causal=causal)
    got = _attention_xla_twopass(
        q, k, v, segment_ids=seg, causal=causal, q_chunk=128
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_twopass_no_segments_window_sinks():
    q, k, v, _ = _inputs(s=512, packed=False)
    sinks = jnp.linspace(-1.0, 1.0, q.shape[2])
    for window in (64, None):
        ref = _attention_dense(
            q, k, v, causal=True, sliding_window=window, sinks=sinks,
        )
        got = _attention_xla_twopass(
            q, k, v, causal=True, sliding_window=window, sinks=sinks,
            q_chunk=128,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_twopass_grads_match_dense():
    q, k, v, seg = _inputs(s=512, packed=True)

    def loss(fn, q, k, v):
        out = fn(q, k, v, segment_ids=seg, causal=True)
        return (out * jnp.arange(out.size).reshape(out.shape) / out.size).sum()

    ref_g = jax.grad(lambda *a: loss(_attention_dense, *a), argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(
        lambda *a: loss(
            lambda *b, **kw: _attention_xla_twopass(*b, q_chunk=128, **kw), *a
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for r, g in zip(ref_g, got_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


def test_mask_mod_flex_attention():
    """FlexAttention analogue: a prefix-LM mask_mod (bidirectional inside a
    per-row prefix, causal after) must match a hand-masked dense softmax and
    agree across the dense and blockwise XLA impls."""
    from veomni_tpu.ops.attention import (
        _attention_dense,
        _attention_xla_chunked,
    )

    rng = np.random.default_rng(0)
    b, s, h, d = 2, 256, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    prefix = jnp.asarray([64, 100])

    def mask_mod(qi, ki):
        # [B, Sq, Sk]: ki within the row's prefix OR causal
        return (ki[None, :, :] < prefix[:, None, None]) | (ki <= qi)[None]

    out = _attention_dense(q, k, v, causal=False, mask_mod=mask_mod)

    # manual oracle
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(d)
    qi = np.arange(s)[:, None]
    ki = np.arange(s)[None, :]
    allowed = (ki[None] < np.asarray(prefix)[:, None, None]) | (ki <= qi)[None]
    scores = np.where(allowed[:, None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", probs, np.asarray(v))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    # blockwise path agrees (q_chunk/k_chunk force real blocking)
    out_blk = _attention_xla_chunked(
        q, k, v, causal=False, mask_mod=mask_mod, q_chunk=128, k_chunk=128
    )
    np.testing.assert_allclose(np.asarray(out_blk), np.asarray(out),
                               rtol=2e-5, atol=2e-5)
