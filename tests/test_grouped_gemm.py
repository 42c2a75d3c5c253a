"""Pallas grouped-matmul numerics vs the XLA ragged_dot reference (interpret
mode on CPU), forward + backward, incl. empty groups and boundary tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.ops.group_gemm import _group_gemm_ragged
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
    from veomni_tpu.ops.pallas import grouped_gemm as gg
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
