"""Long-context memory levers must not change math.

ChunkMBS analogue (sequence-chunked MLP, reference distributed/chunk_mbs.py)
and remat policies are pure memory/scheduling levers: loss and grads must be
bit-comparable with the unchunked path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _run(cfg, batch):
    from veomni_tpu.models import build_foundation_model

    model = build_foundation_model(config=cfg)
    params = model.init(jax.random.PRNGKey(0))

    def norm_loss(p, b):
        loss_sum, metrics = model.loss_fn(p, b)
        return loss_sum / jnp.maximum(metrics["ntokens"], 1)

    loss, grads = jax.jit(jax.value_and_grad(norm_loss))(params, batch)
    import optax

    return float(loss), float(jax.jit(optax.global_norm)(grads))


def test_chunk_mbs_equivalence():
    from tests.test_parallel_equivalence import _batch, _toy_cfg

    cfg = _toy_cfg()
    batch = _batch(bsz=2, seq=64)
    base = _run(cfg, batch)
    chunked = _run(dataclasses.replace(cfg, chunk_mbs=16), batch)
    np.testing.assert_allclose(chunked[0], base[0], rtol=1e-6)
    np.testing.assert_allclose(chunked[1], base[1], rtol=1e-5)


def test_ctx_remat_under_sequence_parallel():
    """The ctx policy's checkpoint_name sits outside the Ulysses shard_map
    body — saving the attention context must not change loss/grad-norm
    under an sp layout."""
    from tests.test_parallel_equivalence import _batch, _loss_and_gnorm, _toy_cfg

    cfg = _toy_cfg()
    batch = _batch(bsz=2, seq=64)
    layout = dict(ulysses_size=2, cp_size=2, dp_shard_size=1)
    base = _loss_and_gnorm(
        dataclasses.replace(cfg, remat=True, remat_policy="nothing"),
        layout, batch,
    )
    ctx = _loss_and_gnorm(
        dataclasses.replace(cfg, remat=True, remat_policy="ctx"),
        layout, batch,
    )
    np.testing.assert_allclose(ctx[0], base[0], rtol=1e-6)
    np.testing.assert_allclose(ctx[1], base[1], rtol=1e-5)
