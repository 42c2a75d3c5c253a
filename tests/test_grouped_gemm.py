"""Pallas grouped-matmul numerics vs the XLA ragged_dot reference (interpret
mode on CPU), forward + backward, incl. empty groups and boundary tiles; the
visit table alone; the tile choice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.ops.group_gemm import _group_gemm_ragged
from veomni_tpu.ops.pallas import grouped_gemm as gg
from veomni_tpu.ops.pallas.grouped_gemm import pallas_group_gemm


def _inputs(m=512, k=128, n=256, e=4, sizes=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (e, k, n), jnp.float32)
    if sizes is None:
        sizes = [m // e] * e
    assert sum(sizes) == m
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("sizes", [
    None,                       # even groups (tile-aligned)
    [100, 156, 0, 256],         # boundary-crossing + empty group
    [512, 0, 0, 0],             # everything in one expert
], ids=["even", "ragged", "single"])
def test_gmm_forward_matches_ragged(sizes):
    lhs, rhs, gs = _inputs(sizes=sizes)
    ref = _group_gemm_ragged(lhs, rhs, gs)
    got = pallas_group_gemm(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_gmm_backward_matches_ragged():
    lhs, rhs, gs = _inputs(sizes=[100, 156, 0, 256])

    def loss_p(lhs, rhs):
        return (pallas_group_gemm(lhs, rhs, gs) ** 2).sum()

    def loss_r(lhs, rhs):
        return (_group_gemm_ragged(lhs, rhs, gs) ** 2).sum()

    gp = jax.grad(loss_p, argnums=(0, 1))(lhs, rhs)
    gr = jax.grad(loss_r, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]), rtol=2e-4, atol=2e-4)


def test_gmm_fallback_unaligned():
    lhs, rhs, gs = _inputs(m=200, k=64, n=96, e=4, sizes=[50, 50, 50, 50])
    ref = _group_gemm_ragged(lhs, rhs, gs)
    got = pallas_group_gemm(lhs, rhs, gs)  # falls back to ragged path
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_gmm_under_gspmd_mesh_goes_to_ragged(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, and expert-sorted rows have no
    per-device split: outside shard_map on a multi-device mesh the wrapper
    hands over to xla_ragged and says so."""
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    seen = []
    monkeypatch.setattr(
        gg.logger, "info_once", lambda msg, *a: seen.append(msg % a)
    )
    lhs, rhs, gs = _inputs(sizes=[100, 156, 0, 256])
    ref = _group_gemm_ragged(lhs, rhs, gs)
    with use_parallel_state(init_parallel_state()):
        jaxpr = str(jax.make_jaxpr(pallas_group_gemm)(lhs, rhs, gs))
        got = jax.jit(pallas_group_gemm)(lhs, rhs, gs)
    assert "pallas_call" not in jaxpr
    assert len(seen) == 1 and "GSPMD" in seen[0], seen
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


# ---------------------------------------------------------------------------
# The schedule: group layouts that break one
# ---------------------------------------------------------------------------
# (id, m, k, n, group sizes); sum(sizes) < m leaves the buffer's tail empty
LAYOUTS = [
    ("empty_head", 512, 128, 256, [0, 0, 200, 312]),
    ("empty_middle", 512, 128, 256, [130, 0, 0, 382]),
    ("empty_tail", 512, 128, 256, [300, 212, 0, 0]),
    ("one_expert", 512, 128, 256, [0, 512, 0, 0]),
    ("no_row", 512, 128, 256, [0, 0, 0, 0]),
    ("boundary_in_tile", 512, 128, 256, [1, 127, 129, 255]),
    ("tail_empty", 1024, 128, 256, [100, 0, 156, 40]),       # 296 of 1024 rows held
    ("tail_empty_at_tile_edge", 1024, 128, 128, [128, 128, 0, 0]),
    ("k768", 512, 768, 128, [100, 156, 0, 200]),
    ("n768", 512, 128, 768, [256, 0, 56, 100]),
    ("e16", 2048, 128, 128, [0, 300, 0, 0, 5, 123, 128, 128, 0, 0, 700, 1, 0, 63, 0, 200]),
    ("e16_even_full", 2048, 256, 128, [128] * 16),
    ("e128", 1024, 128, 128, [(7 * i) % 13 if i % 3 else 0 for i in range(128)]),
    ("e128_one_each", 128, 128, 128, [1] * 128),
]


@pytest.mark.parametrize("m,k,n,sizes", [l[1:] for l in LAYOUTS], ids=[l[0] for l in LAYOUTS])
def test_gmm_schedule_matches_ragged(m, k, n, sizes):
    """Forward, dlhs and drhs against ``jax.lax.ragged_dot`` in float32. The
    inputs' rows past the last group hold NaN: the outputs there are exactly
    zero, an expert with no rows has an exactly zero weight gradient, and
    nothing else is touched by what the tail holds."""
    e, held = len(sizes), sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(m + k + n + e), 3)
    tail = (jnp.arange(m) >= held)[:, None]
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (e, k, n), jnp.float32)
    g = jax.random.normal(ks[2], (m, n), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    out, vjp = jax.vjp(lambda a, w: pallas_group_gemm(a, w, gs),
                       jnp.where(tail, jnp.nan, lhs), rhs)
    dlhs, drhs = vjp(jnp.where(tail, jnp.nan, g))
    # the reference never sees the tail: it multiplies the held rows alone
    ref, ref_vjp = jax.vjp(lambda a, w: _group_gemm_ragged(a, w, gs), lhs[:held], rhs)
    ref_dlhs, ref_drhs = ref_vjp(g[:held])

    for got, want in ((out, ref), (dlhs, ref_dlhs)):
        np.testing.assert_allclose(np.asarray(got[:held]), np.asarray(want), rtol=2e-4, atol=2e-4)
        assert not np.asarray(got[held:]).any()  # exactly zero, and no NaN
    np.testing.assert_allclose(np.asarray(drhs), np.asarray(ref_drhs), rtol=2e-4, atol=2e-4)
    assert not np.asarray(drhs)[np.asarray(sizes) == 0].any()


@pytest.mark.parametrize("bm,bk,bn", [(128, 128, 128), (256, 256, 384), (512, 128, 384),
                                      (1024, 256, 128)], ids=str)
def test_gmm_kernels_at_every_tile_size(bm, bk, bn):
    """The three kernels at tiles given by hand (the public op only ever
    takes the chosen ones): row tiles that meet one, two and three experts,
    column tiles of 3 x 128, the tail past the last group NaN."""
    m, k, n, sizes = 1024, 256, 384, [100, 0, 412, 300]
    held = sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(bm + bk + bn), 3)
    tail = (jnp.arange(m) >= held)[:, None]
    lhs = jnp.where(tail, jnp.nan, jax.random.normal(ks[0], (m, k), jnp.float32))
    g = jnp.where(tail, jnp.nan, jax.random.normal(ks[2], (m, n), jnp.float32))
    rhs = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    starts = gg._group_starts(gs)
    ref, ref_vjp = jax.vjp(lambda a, w: _group_gemm_ragged(a, w, gs), lhs[:held], rhs)
    ref_dlhs, ref_drhs = ref_vjp(g[:held])

    out = gg._gmm_rows(lhs, rhs, starts, bm, bn, name="gmm_fwd")
    dlhs = gg._gmm_rows(g, rhs, starts, bm, bk, name="gmm_dlhs")
    drhs = gg._gmm_drhs(lhs, g, starts, bm, bk, bn)
    for got, want in ((out, ref), (dlhs, ref_dlhs)):
        np.testing.assert_allclose(np.asarray(got[:held]), np.asarray(want), rtol=2e-4, atol=2e-4)
        assert not np.asarray(got[held:]).any()
    np.testing.assert_allclose(np.asarray(drhs), np.asarray(ref_drhs), rtol=2e-4, atol=2e-4)
    assert not np.asarray(drhs[1]).any()


def _pairs_that_meet(starts, m, bm):
    """By hand: every (tile, expert) whose row ranges intersect, in row order."""
    return [(t, e) for e in range(len(starts) - 1) for t in range(m // bm)
            if starts[e + 1] > starts[e]
            and starts[e + 1] > t * bm and starts[e] < (t + 1) * bm]


@pytest.mark.parametrize("bm", [128, 256, 512])
@pytest.mark.parametrize("seed", range(6))
def test_visit_table_walks_every_meeting_pair_once(seed, bm):
    """The table alone, over drawn group sizes (many experts empty, the
    buffer's tail empty or not): every intersecting pair exactly once, in row
    order, the live count exact, dead visits repeating the last live one; with
    ``empty_experts`` one more visit per empty expert, in expert order."""
    rng = np.random.default_rng(seed)
    e, m = int(rng.choice([1, 4, 16, 128])), 2048
    sizes = rng.integers(0, 2 * m // e + 1, e) * (rng.random(e) < 0.6)
    if seed % 2:  # fill the buffer to its last row
        sizes = np.floor(sizes * (m / max(sizes.sum(), 1))).astype(int)
        sizes[-1] += m - sizes.sum()
    while sizes.sum() > m:
        sizes[np.argmax(sizes)] //= 2
    starts = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    want = _pairs_that_meet(starts, m, bm)

    tile, expert, count = (np.asarray(a) for a in gg.visit_table(jnp.asarray(starts), m, bm))
    assert tile.shape == expert.shape == (m // bm + e - 1,) and count.shape == (1,)
    assert int(count[0]) == len(want)
    assert list(zip(tile[:len(want)], expert[:len(want)])) == want
    dead = list(zip(tile[len(want):], expert[len(want):]))
    assert set(dead) <= {want[-1] if want else (0, e - 1)}

    tile, expert, count = (np.asarray(a) for a in gg.visit_table(
        jnp.asarray(starts), m, bm, empty_experts=True))
    live = list(zip(tile[:int(count[0])], expert[:int(count[0])]))
    assert [p for p in live if sizes[p[1]]] == want
    assert sorted(p[1] for p in live if not sizes[p[1]]) == list(np.flatnonzero(sizes == 0))
    assert [p[1] for p in live] == sorted(p[1] for p in live)  # expert order
    assert 0 <= tile.min() and tile.max() < m // bm


@pytest.mark.parametrize("m,k,n,e", [
    (8192, 2048, 768, 16), (8192, 768, 2048, 16),   # the joyai cell's two shapes
    (8192, 2048, 1536, 16),                         # a fused gate_up
    (8192, 2048, 768, 128), (131072, 2048, 768, 128),
    (256, 128, 128, 8), (384, 640, 896, 4),
], ids=lambda v: str(v))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_choose_tiles_divide_the_shape_and_fit_vmem(m, k, n, e, dtype):
    tiles = gg.choose_tiles(m, k, n, e, jnp.dtype(dtype))
    size = jnp.dtype(dtype).itemsize
    for (bm, bo), contract, cols in ((tiles.fwd, k, n), (tiles.dlhs, n, k)):
        assert m % bm == 0 and cols % bo == 0 and bo % 128 == 0
        assert bm <= max(128, m // e // 2)
        assert gg._rows_vmem_bytes(bm, contract, bo, size) <= gg._VMEM_BUDGET
    bm, bk, bn = tiles.drhs
    assert m % bm == 0 and k % bk == 0 and n % bn == 0 and bk % 128 == 0 and bn % 128 == 0
    assert gg._drhs_vmem_bytes(bm, bk, bn, size) <= gg._VMEM_BUDGET


def test_tile_census_counts_live_visits_and_all_pairs():
    """What ``moe.gmm.tile_visits`` / ``moe.gmm.tile_pairs`` are summed from."""
    sizes = jnp.asarray([100, 0, 156, 40] + [0] * 12, jnp.int32)  # 296 rows of 2048
    bm = gg.choose_tiles(2048, 128, 256, 16, jnp.dtype(jnp.float32)).fwd[0]
    assert bm == 128
    visits, pairs = gg.tile_census(sizes, 2048, 128, 256, jnp.float32)
    # rows 0-99 | 100-255 | 256-295: tiles 0 | 0, 1 | 2
    assert (float(visits), float(pairs)) == (4.0, 16 * 16)
    assert [float(x) for x in gg.tile_census(sizes, 200, 128, 256, jnp.float32)] == [0.0, 0.0]
