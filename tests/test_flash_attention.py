"""Pallas flash attention numerics vs the XLA reference impl.

Reference test model: ``tests/ops/test_kernel_registry_numerical.py``
(per-(op,impl) alignment). Runs the kernel in interpret mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.ops.attention import _attention_xla
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
