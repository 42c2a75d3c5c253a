"""Model-centric parallel plans: param-path patterns -> PartitionSpec.

Reference: ``veomni/distributed/parallel_plan.py:30-106`` — models declare
which params are EP/TP-sharded via ``get_parallel_plan()``; the framework
composes that with FSDP. Here the whole concept collapses to *resolving a
pytree of PartitionSpecs*: GSPMD then inserts all all-gathers/reduce-scatters
(the torch FSDP2 ``fully_shard`` machinery, prefetch lists, reshard deferral,
and SpecInfo tagging have no TPU counterpart — the compiler owns comm
scheduling).

Spec templates are written with *symbolic* axis tokens resolved against the
ambient ParallelState:

  "fsdp"  -> state.fsdp_axes  (= ep x fsdp x ulysses x cp, the dp_shard_sp group)
  "ep"    -> the expert-parallel axis
  "ep_fsdp" -> state.ep_fsdp_axes (feature-dim shard of EP params)
  "tp"    -> tensor-parallel axis
  None    -> replicated dim

Example (qwen3_moe):
  ParallelPlan(rules={
      r".*experts.*(gate_proj|up_proj|down_proj)$": ("ep", "ep_fsdp", None),
  })
Dense params not matched by any rule get the default FSDP policy: shard the
first divisible dim over ``fsdp`` axes, else replicate.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from veomni_tpu.parallel.parallel_state import AXIS_EP, AXIS_TP, ParallelState
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

SpecTemplate = Tuple[Optional[str], ...]


def _axis_product(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _resolve_token(token: Optional[str], state: ParallelState):
    if token is None:
        return None
    if token == "fsdp":
        return state.fsdp_axes
    if token == "ep":
        return AXIS_EP
    if token == "ep_fsdp":
        return state.ep_fsdp_axes
    if token == "tp":
        return AXIS_TP
    if token == "sp":
        return state.sp_axes
    raise ValueError(f"unknown spec token {token!r}")


def param_path_str(path) -> str:
    """KeyPath -> dotted string, e.g. 'layers.self_attn.q_proj.kernel'."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


@dataclass
class ParallelPlan:
    """Declarative sharding rules, owned by the model family.

    rules: ordered {regex: spec template}; first match wins.
    default_fsdp: apply auto-FSDP to unmatched params.
    stacked_layer_prefixes: param paths under these prefixes carry leading
      scan-over-layers dim(s) that must never be sharded (specs are shifted).
      Entries are either a prefix string (one stacked dim) or a
      ``(prefix, ndims)`` tuple (e.g. qwen3_next's [groups, per_group] double
      stack).
    """

    rules: Dict[str, SpecTemplate] = field(default_factory=dict)
    default_fsdp: bool = True
    stacked_layer_prefixes: Tuple = ("layers", "dense_layers", "mtp")

    def _default_spec(self, shape, state: ParallelState) -> SpecTemplate:
        if not self.default_fsdp or not shape:
            return ()
        fsdp_n = _axis_product(state.mesh, state.fsdp_axes)
        if fsdp_n == 1:
            return ()
        for dim, size in enumerate(shape):
            if size % fsdp_n == 0 and size >= fsdp_n:
                return tuple(["fsdp" if d == dim else None for d in range(len(shape))])
        return ()

    def spec_for(self, path: str, shape, state: ParallelState) -> P:
        # Stacked-layer detection matches the prefix as a path *component* so
        # optimizer-state paths ('mu.layers.q_proj') inherit the layer shift.
        shift = 0
        for entry in self.stacked_layer_prefixes:
            pfx, nd = entry if isinstance(entry, tuple) else (entry, 1)
            if re.search(rf"(^|\.){re.escape(pfx)}\.", path + "."):
                shift = max(shift, nd)
        shift = min(shift, max(len(shape) - 1, 0))
        stacked = shift > 0
        logical_shape = shape[shift:] if stacked else shape
        template: Optional[SpecTemplate] = None
        for pattern, tmpl in self.rules.items():
            if re.search(pattern, path):
                template = tmpl
                break
        if template is None:
            template = self._default_spec(logical_shape, state)
        # validate divisibility; drop shard on mismatch rather than failing
        resolved = []
        for dim, token in enumerate(template):
            axes = _resolve_token(token, state)
            if axes is not None and dim < len(logical_shape):
                n = _axis_product(state.mesh, axes)
                if logical_shape[dim] % n:
                    logger.warning_once(
                        "param %s dim %d size %d not divisible by %s=%d; replicating",
                        path, dim, logical_shape[dim], token, n,
                    )
                    axes = None
            resolved.append(axes)
        if stacked:
            resolved = [None] * shift + resolved
        return P(*resolved[: len(shape)])

    def resolve(self, params, state: ParallelState):
        """params (pytree of arrays or ShapeDtypeStructs) -> pytree of NamedSharding."""

        def _one(path, leaf):
            spec = self.spec_for(param_path_str(path), leaf.shape, state)
            return NamedSharding(state.mesh, spec)

        return jax.tree_util.tree_map_with_path(_one, params)

    def merge(self, other: "ParallelPlan") -> "ParallelPlan":
        rules = dict(self.rules)
        rules.update(other.rules)
        return ParallelPlan(
            rules=rules,
            default_fsdp=self.default_fsdp and other.default_fsdp,
            stacked_layer_prefixes=tuple(
                dict.fromkeys(self.stacked_layer_prefixes + other.stacked_layer_prefixes)
            ),
        )
