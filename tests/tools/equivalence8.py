"""8-virtual-device equivalence driver (subprocess: own jax config).

Covers the round-1 gaps: HSDP (dp_replicate=2), ep=4, sp=4, the combined
2x2x2 layout, pure-DDP replication, and capacity-mode EP vs dropless.
Prints one JSON line with loss/grad_norm per layout.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax

from veomni_tpu.utils.testing import set_virtual_cpu_devices

set_virtual_cpu_devices(8)
jax.config.update("jax_cpu_enable_async_dispatch", False)

import jax.numpy as jnp
import numpy as np


def toy_cfg(moe=False, capacity=0.0):
    from veomni_tpu.models.config import TransformerConfig

    kw = dict(
        model_type="qwen3_moe" if moe else "qwen3",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, qk_norm=True, dtype=jnp.float32,
        moe_capacity_factor=capacity,
    )
    if moe:
        kw.update(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=64)
    return TransformerConfig(**kw)


def batch(bsz=8, seq=64, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (bsz, seq))
    seg = np.ones((bsz, seq), np.int32)
    seg[:, seq // 2:] = 2
    pos = np.concatenate([np.arange(seq // 2), np.arange(seq - seq // 2)])
    return {
        "input_ids": jnp.asarray(ids, jnp.int32),
        "labels": jnp.asarray(ids, jnp.int32),
        "position_ids": jnp.asarray(np.broadcast_to(pos, (bsz, seq)).copy(), jnp.int32),
        "segment_ids": jnp.asarray(seg),
    }


def run(cfg, mesh_kwargs, b):
    import optax

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
    ps = init_parallel_state(**mesh_kwargs)
    model = build_foundation_model(config=cfg)
    with use_parallel_state(ps):
        params = model.init(jax.random.PRNGKey(0))
        shardings = model.get_parallel_plan().resolve(params, ps)
        params = jax.jit(lambda p: p, out_shardings=shardings)(params)
        bs = {k: ps.batch_sharding() for k in b}
        bb = {k: jax.device_put(v, bs[k]) for k, v in b.items()}

        def norm_loss(p, x):
            loss_sum, metrics = model.loss_fn(p, x)
            return loss_sum / jnp.maximum(metrics["ntokens"], 1), metrics

        (loss, metrics), grads = jax.jit(
            jax.value_and_grad(norm_loss, has_aux=True)
        )(params, bb)
        gnorm = jax.jit(optax.global_norm)(grads)
        dropped = float(metrics.get("moe_dropped_frac", 0.0))
        return float(loss), float(gnorm), dropped


def main():
    out = {}
    for moe in (False, True):
        cfg = toy_cfg(moe)
        b = batch()
        name = "moe" if moe else "dense"
        out[f"{name}/base"] = run(cfg, dict(dp_shard_size=8), b)
        layouts = {
            "hsdp2": dict(dp_replicate_size=2, dp_shard_size=4),
            "ddp": dict(dp_replicate_size=-1, dp_shard_size=1),
            "sp4": dict(ulysses_size=4, dp_shard_size=2),
        }
        if moe:
            layouts.update({
                "ep4": dict(ep_size=4, dp_shard_size=8),
                "ep2sp2rep2": dict(dp_replicate_size=2, ep_size=2,
                                   dp_shard_size=2, ulysses_size=2),
            })
        for lname, kw in layouts.items():
            out[f"{name}/{lname}"] = run(cfg, kw, b)
    # capacity-mode EP: bounded loss delta vs dropless + visible drop metric
    cfg_cap = toy_cfg(True, capacity=1.0)
    out["moe/ep4_capacity"] = run(cfg_cap, dict(ep_size=4, dp_shard_size=8), batch())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
