"""MoE router monitoring: expert-load capture + imbalance callback.

Reference: ``veomni/utils/moe_monitor.py:83-267`` (MoERouterMonitor expert-
load heatmaps via router forward hooks) and ``moe_router_replay.py``
(capture/replay routing decisions).

TPU design: inside jit there are no hooks, so the monitor does an *eager
replay* — a python-loop forward over layer slices with a capture list that
``_moe_mlp`` appends its top-k choices to. Run it occasionally on a probe
batch (it costs one un-jitted forward).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.models import transformer
from veomni_tpu.trainer.callbacks import Callback
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@contextlib.contextmanager
def capture_routing():
    captured: List[jax.Array] = []
    transformer.ROUTER_CAPTURE = captured
    try:
        yield captured
    finally:
        transformer.ROUTER_CAPTURE = None


def capture_router_stats(model, params, batch) -> Dict[str, np.ndarray]:
    """Eager replay forward -> per-layer expert load fractions [L, E]."""
    cfg = model.config
    with capture_routing():
        # python-loop forward (no scan -> one capture entry per MoE layer)
        compute = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
        hidden = compute["embed_tokens"][batch["input_ids"]]
        if cfg.embed_scale:
            hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
        rope_dim = (
            cfg.qk_rope_head_dim if cfg.use_mla
            else int(cfg.head_dim * cfg.partial_rotary_factor)
        )
        cos, sin = transformer.ops.rotary_tables(
            batch["position_ids"], rope_dim, cfg.rope_theta, cfg.rope_scaling,
            interleaved=transformer.mla_rope_interleaved(cfg),
        )
        cos, sin = cos.astype(cfg.dtype), sin.astype(cfg.dtype)
        L = cfg.num_hidden_layers
        k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0
        trees = ([("dense_layers", k_dense, False)] if k_dense else []) + [
            ("layers", L - k_dense, cfg.is_moe)
        ]
        caps: List[jax.Array] = transformer.ROUTER_CAPTURE
        offset = 0
        for name, count, is_moe in trees:
            tree = compute[name]
            for i in range(count):
                lp = jax.tree.map(lambda t: t[i], tree)
                # non-carry call: 2-tuple always (DSA layers compute their
                # own selection; "shared" reuse is a train-path optimization)
                hidden, _ = transformer._decoder_layer(
                    hidden, lp, cfg=cfg, cos=cos, sin=sin,
                    segment_ids=batch.get("segment_ids"),
                    window=cfg.window_for_layer(offset + i) or None,
                    is_moe_segment=is_moe,
                )[:2]
            offset += count
    loads = []
    for topk in caps:
        counts = np.bincount(
            np.asarray(topk).reshape(-1), minlength=cfg.num_experts
        ).astype(np.float64)
        loads.append(counts / max(counts.sum(), 1))
    return {"expert_load": np.stack(loads) if loads else np.zeros((0, cfg.num_experts))}


def publish_router_stats(load: "np.ndarray", registry=None) -> None:
    """Per-layer router health -> registry gauges (``moe.layer{i}.*``):

    * ``entropy``   — routing entropy in nats (ln E = perfectly balanced);
    * ``max_load``  — the hottest expert's load fraction;
    * ``drop_frac`` — load mass above the per-expert fair share, i.e. the
      fraction a capacity-factor-1.0 dispatcher would drop. This impl
      dispatches dropless, so it measures imbalance *pressure*, not actual
      token loss.
    """
    from veomni_tpu.observability.metrics import get_registry

    reg = registry or get_registry()
    for li, row in enumerate(np.asarray(load, np.float64)):
        nz = row[row > 0]
        reg.gauge(f"moe.layer{li}.entropy").set(
            float(-(nz * np.log(nz)).sum()) if len(nz) else 0.0
        )
        reg.gauge(f"moe.layer{li}.max_load").set(float(row.max()))
        reg.gauge(f"moe.layer{li}.drop_frac").set(
            float(np.clip(row - 1.0 / len(row), 0.0, None).sum())
        )


class MoERouterMonitorCallback(Callback):
    """Periodically replays routing on the current batch, publishes
    per-layer gauges (entropy / max-load / drop-fraction) through the
    observability registry, and logs the min/max imbalance summary."""

    def __init__(self, every_steps: int = 100):
        self.every = every_steps

    def on_step_end(self, trainer, state):
        if not getattr(trainer.model.config, "is_moe", False):
            return
        if state.global_step % self.every:
            return
        batch = {
            k: jnp.asarray(v[0]) for k, v in trainer.current_batch.items()
        }  # first micro-batch
        stats = capture_router_stats(trainer.model, trainer.train_state.params, batch)
        load = stats["expert_load"]
        if len(load):
            publish_router_stats(load)
            logger.info_rank0(
                "moe router load: min=%.3f max=%.3f (ideal %.3f) worst layer %d",
                load.min(), load.max(), 1.0 / load.shape[1], int(load.max(1).argmax()),
            )
