"""Expert-parallel MoE token dispatch.

Reference: ``veomni/distributed/moe/moe_layer.py:48-567`` — one-hot routing,
all-gather of per-expert counts, variable-split ``dist.all_to_all``, grouped
GEMM, reverse a2a, weighted unpermute.

TPU design (SURVEY.md §7.3 hard part 1): XLA wants **static shapes**, so the
variable-split a2a becomes a *capacity-bucketed* ``lax.all_to_all`` inside a
``shard_map`` over the ``ep`` axis:

  1. routing (logits/topk/aux loss) runs OUTSIDE the shard_map on the
     globally-sharded activations — cheap, and keeps the aux loss global;
  2. each device packs its assignments into per-destination buckets
     ``[ep, C, H]`` (C = capacity per src->dst pair), a2a exchanges them;
  3. local experts run via grouped GEMM (``ops.group_gemm`` ->
     ``lax.ragged_dot`` or Pallas);
  4. reverse a2a; weighted scatter-add combines results per source token.

``capacity_factor <= 0`` means **dropless** (C = local_tokens * top_k: no
assignment can exceed it) — exact equality with the single-device path, used
by the equivalence tests; production configs set ~2.0 for balanced memory.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from veomni_tpu import ops
from veomni_tpu.parallel.parallel_state import AXIS_EP, ParallelState


def _dispatch_combine(x2d, topk_idx, topk_probs, experts_local, *, cfg,
                      ep: int, e_loc: int, capacity: int, dtype):
    """Per-device body. x2d [T,H]; topk_* [T,K]; experts_local: dict of
    expert tensors with local expert dim [e_loc, ...]."""
    t, h = x2d.shape
    k = topk_idx.shape[-1]
    n_assign = t * k

    with jax.named_scope("moe.dispatch"):
        flat_e = topk_idx.reshape(-1)                       # [T*K] global expert id
        flat_w = topk_probs.reshape(-1).astype(dtype)
        dest = flat_e // e_loc                              # destination ep rank
        order = jnp.argsort(dest, stable=True)              # assignments grouped by dest
        dest_s = dest[order]
        tok_s = order // k                                  # source token per assignment
        le_s = (flat_e % e_loc)[order]                      # local expert id at dest
        w_s = flat_w[order]

        # slot within destination bucket (rank among same-dest assignments)
        onehot = jax.nn.one_hot(dest_s, ep, dtype=jnp.int32)         # [T*K, ep]
        slot = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1     # [T*K]
        keep = slot < capacity

        dropped_frac = 1.0 - keep.astype(jnp.float32).mean()

        send_x = jnp.zeros((ep, capacity, h), dtype)
        send_le = jnp.full((ep, capacity), -1, jnp.int32)
        # dropped assignments get an out-of-bounds destination -> mode="drop"
        # discards them without clobbering live slots
        d_idx = jnp.where(keep, dest_s, ep)
        s_idx = jnp.where(keep, slot, 0)
        send_x = send_x.at[d_idx, s_idx].set(x2d[tok_s], mode="drop")
        send_le = send_le.at[d_idx, s_idx].set(le_s, mode="drop")

        a2a = partial(jax.lax.all_to_all, axis_name=AXIS_EP,
                      split_axis=0, concat_axis=0, tiled=True)
        recv_x = a2a(send_x)                                # [ep*C? -> [ep, C, H]]
        recv_le = a2a(send_le[..., None])[..., 0]

        # local expert compute over [ep*C] slots
        rx = recv_x.reshape(ep * capacity, h)
        rle = recv_le.reshape(ep * capacity)
        valid = rle >= 0
        rle_safe = jnp.where(valid, rle, e_loc - 1)
        rx = jnp.where(valid[:, None], rx, 0.0)
        sort_idx = jnp.argsort(rle_safe, stable=True)
        xs = rx[sort_idx]
        group_sizes = jnp.bincount(rle_safe, length=e_loc)

    from veomni_tpu.models.transformer import experts_apply_sorted

    with jax.named_scope("moe.experts"):
        out_s = experts_apply_sorted(
            xs, experts_local, group_sizes, rle_safe[sort_idx], cfg
        )

    with jax.named_scope("moe.combine"):
        out = jnp.zeros_like(rx).at[sort_idx].set(out_s)
        out = out.reshape(ep, capacity, h)
        back = a2a(out)                                     # [ep, C, H] on src side

        # combine: weighted scatter-add into source tokens (OOB gather yields
        # clamped values but `keep` zeroes those lanes)
        flat_back = back[jnp.where(keep, dest_s, 0), jnp.where(keep, slot, 0)]
        contrib = jnp.where(keep[:, None], flat_back * w_s[:, None], 0.0)
        combined = jnp.zeros((t, h), dtype).at[tok_s].add(contrib)
    return combined, dropped_frac


def ep_moe_mlp(x, lp, cfg, pstate: ParallelState, with_load: bool = False):
    """Expert-parallel MoE layer forward. x [B, S, H] globally sharded
    (dp, sp, -); returns ([B, S, H], aux_loss, dropped_frac) where
    dropped_frac is the mesh-mean fraction of (token, expert) assignments
    discarded by the capacity bound (0 in dropless mode) — the observability
    counterpart of the reference's dropless variable-split a2a. ``with_load``
    appends the busiest expert's assignments over the mean."""
    b, s, h = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    ep = pstate.ep_size
    e_loc = e // ep

    # ---- routing + aux loss on the global view (cheap; GSPMD-sharded),
    # shared with the single-device path so every dialect matches
    from veomni_tpu.models.transformer import route_tokens

    with jax.named_scope("moe.route"):
        topk_idx, topk_probs, aux = route_tokens(x.reshape(b * s, h), lp, cfg)
        topk_idx = topk_idx.reshape(b, s, k)
        topk_probs = topk_probs.reshape(b, s, k)

    # ---- dispatch/compute/combine inside shard_map
    dp, spx = pstate.dp_axes, pstate.sp_axes
    t_loc = (b // max(1, math.prod(pstate.mesh.shape[a] for a in dp))) * (
        s // max(1, math.prod(pstate.mesh.shape[a] for a in spx))
    )
    if cfg.moe_capacity_factor and cfg.moe_capacity_factor > 0:
        capacity = max(1, int(cfg.moe_capacity_factor * t_loc * k / ep))
        capacity = -(-capacity // 8) * 8  # sublane-align
    else:
        capacity = t_loc * k  # dropless

    x_spec = P(dp, spx, None)
    topk_spec = P(dp, spx, None)
    experts = lp["experts"]
    # expert tensors shard dim 0 (experts) over ep; other dims gathered local
    experts_specs = jax.tree.map(
        lambda t: P(AXIS_EP, *([None] * (t.ndim - 1))), experts
    )

    def body(x3, ti, tp, experts_local):
        bl, sl, _ = x3.shape
        out, dropped = _dispatch_combine(
            x3.reshape(bl * sl, h), ti.reshape(bl * sl, k), tp.reshape(bl * sl, k),
            experts_local, cfg=cfg, ep=ep, e_loc=e_loc, capacity=capacity,
            dtype=x3.dtype,
        )
        dropped = jax.lax.pmean(dropped, axis_name=pstate.mesh.axis_names)
        return out.reshape(bl, sl, h), dropped

    fn = jax.shard_map(
        body,
        mesh=pstate.mesh,
        in_specs=(x_spec, topk_spec, topk_spec, experts_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, dropped = fn(x, topk_idx, topk_probs, experts)
    if cfg.n_shared_experts or cfg.shared_expert_intermediate_size:
        from veomni_tpu.models.transformer import _shared_experts_out

        with jax.named_scope("moe.combine"):
            out = out + _shared_experts_out(x, lp, cfg)
    if with_load:
        counts = jnp.bincount(topk_idx.reshape(-1), length=e)
        return out, aux, dropped, counts.max() * (e / (b * s * k))
    return out, aux, dropped
