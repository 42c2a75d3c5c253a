"""ParallelState: one device mesh, many parallel axes.

TPU-native counterpart of ``veomni/distributed/parallel_state.py:444-701``.
The reference builds a torch ``DeviceMesh`` with dims
``(pp, dp_replicate, dp_shard, ulysses, cp, tp)`` plus flattened submeshes
(``dp``, ``dp_shard_sp``, ``dp_sp``, ``sp``) and a *second* mesh
``(ep_replicate, ep_fsdp, ep)`` for expert parallelism.

On TPU we use a single ``jax.sharding.Mesh``. Flattened "groups" become
tuples of axis names inside a ``PartitionSpec`` (GSPMD shards over the axis
product), and the EP mesh is obtained by *factoring* the FSDP-shard dimension:

    mesh axes = (pp, dp_replicate, ep, fsdp, ulysses, cp, tp)
    reference dp_shard      == ep * fsdp
    reference dp            == dp_replicate * ep * fsdp      (batch axis)
    reference sp            == ulysses * cp                  (sequence axis)
    reference dp_shard_sp   == (ep, fsdp, ulysses, cp)       (param shard axes)
    reference ep_fsdp       == (fsdp,)                       (expert param shard)

This keeps EP and FSDP composable in one jit program: expert weights shard
their expert dim over ``ep`` and their feature dim over ``fsdp``; dense
weights shard over the full ``(ep, fsdp, ulysses, cp)`` product, exactly the
reference's semantics (SP ranks included in the FSDP shard group).

The named registry + ambient-scoping (``use_parallel_state``) surface mirrors
``parallel_state.py:38-45,659-691`` so multiple modules of an omni model can
run at different SP sizes in one process.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Canonical axis names, in mesh order.
AXIS_PP = "pp"
AXIS_DP_REPLICATE = "dp_replicate"
AXIS_EP = "ep"
AXIS_FSDP = "fsdp"
AXIS_ULYSSES = "ulysses"
AXIS_CP = "cp"
AXIS_TP = "tp"

MESH_AXES: Tuple[str, ...] = (
    AXIS_PP,
    AXIS_DP_REPLICATE,
    AXIS_EP,
    AXIS_FSDP,
    AXIS_ULYSSES,
    AXIS_CP,
    AXIS_TP,
)


@dataclass(frozen=True)
class ParallelState:
    """Frozen view over one Mesh; mirrors the reference's property surface."""

    mesh: Mesh
    pp_size: int = 1
    dp_replicate_size: int = 1
    ep_size: int = 1
    fsdp_size: int = 1
    ulysses_size: int = 1
    cp_size: int = 1
    tp_size: int = 1
    name: str = "base"

    # ------------------------------------------------------------------ sizes
    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def dp_shard_size(self) -> int:
        """Reference's dp_shard (FSDP shard w/o SP) == ep * fsdp."""
        return self.ep_size * self.fsdp_size

    @property
    def dp_size(self) -> int:
        return self.dp_replicate_size * self.dp_shard_size

    @property
    def sp_size(self) -> int:
        return self.ulysses_size * self.cp_size

    @property
    def sp_enabled(self) -> bool:
        return self.sp_size > 1

    @property
    def ep_enabled(self) -> bool:
        return self.ep_size > 1

    @property
    def tp_enabled(self) -> bool:
        return self.tp_size > 1

    @property
    def pp_enabled(self) -> bool:
        return self.pp_size > 1

    @property
    def hsdp_enabled(self) -> bool:
        return self.dp_replicate_size > 1

    # ------------------------------------------------------------- axis views
    @property
    def dp_axes(self) -> Tuple[str, ...]:
        """Axes the global batch is sharded over (reference flattened 'dp')."""
        return (AXIS_DP_REPLICATE, AXIS_EP, AXIS_FSDP)

    @property
    def sp_axes(self) -> Tuple[str, ...]:
        """Sequence-parallel axes (reference flattened 'sp' = ulysses x cp).

        ``cp`` is the *outer* axis on purpose: each cp rank then owns one
        contiguous chunk of the global sequence, which is what the ring
        schedule's chunk-level causal skip assumes; the ulysses all-to-all
        (tiled concat over the inner axis) reassembles each cp chunk
        contiguously."""
        return (AXIS_CP, AXIS_ULYSSES)

    @property
    def fsdp_axes(self) -> Tuple[str, ...]:
        """Param-shard axes (reference 'dp_shard_sp': SP ranks shard params too)."""
        return (AXIS_EP, AXIS_FSDP, AXIS_ULYSSES, AXIS_CP)

    @property
    def ep_fsdp_axes(self) -> Tuple[str, ...]:
        """Axes an EP-sharded param's *feature* dim shards over."""
        return (AXIS_FSDP,)

    @property
    def dp_sp_axes(self) -> Tuple[str, ...]:
        """Loss-reduction axes (reference flattened 'dp_sp')."""
        return self.dp_axes + self.sp_axes

    # --------------------------------------------------------------- shardings
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def batch_sharding(self) -> NamedSharding:
        """[B, S, ...] batch: B over dp axes, S over sp axes."""
        return self.sharding(self.dp_axes, self.sp_axes)

    def replicated(self) -> NamedSharding:
        return self.sharding()

    def data_parallel_index(self) -> int:
        """This process's position along the dp axes (for data sharding)."""
        # Single-controller: process 0 drives; per-process index derives from
        # the first local device's coords in the mesh.
        if jax.process_count() == 1:
            return 0
        dev = jax.local_devices()[0]
        idx = self.mesh.devices.flatten().tolist().index(dev)
        shape = self.mesh.shape
        coords = np.unravel_index(idx, tuple(shape.values()))
        named = dict(zip(shape.keys(), coords))
        rank = 0
        for ax in self.dp_axes:
            rank = rank * shape[ax] + int(named[ax])
        return rank

    def without_sp(self) -> "ParallelState":
        """A scoped view that reports sp=1 over the same mesh — the
        per-module heterogeneous-SP mechanism (reference
        ``use_parallel_state`` scoping + ``sp_gather_seqs``,
        sequence_parallel/data.py:149-298): modules whose activations are
        replicated along the sequence (vision/audio towers) run under this
        view so the Ulysses attention wrap and SP loss reduction disengage,
        while the surrounding LM keeps the full SP layout."""
        import dataclasses

        return dataclasses.replace(
            self, ulysses_size=1, cp_size=1, name=f"{self.name}:no_sp"
        )

    def describe(self) -> str:
        return (
            f"ParallelState(name={self.name!r}, world={self.world_size}, "
            f"pp={self.pp_size}, dp_replicate={self.dp_replicate_size}, "
            f"ep={self.ep_size}, fsdp={self.fsdp_size}, "
            f"ulysses={self.ulysses_size}, cp={self.cp_size}, tp={self.tp_size})"
        )


# --------------------------------------------------------------------------
# Registry + ambient scoping (reference parallel_state.py:659-691)
# --------------------------------------------------------------------------
_REGISTRY: Dict[str, ParallelState] = {}
_tls = threading.local()


def init_parallel_state(
    *,
    dp_replicate_size: int = 1,
    dp_shard_size: int = -1,
    ep_size: int = 1,
    ulysses_size: int = 1,
    cp_size: int = 1,
    tp_size: int = 1,
    pp_size: int = 1,
    name: str = "base",
    devices: Optional[Sequence[jax.Device]] = None,
) -> ParallelState:
    """Build the Mesh and register a ParallelState under ``name``.

    ``dp_shard_size=-1`` infers the FSDP shard extent from the device count
    (reference behavior); ``dp_replicate_size=-1`` infers the replicate extent
    instead (the DDP mapping: all non-shard/sp/tp devices replicate).
    ``ep_size`` must divide the (inferred) dp_shard.
    """
    for label, size in (("dp_replicate_size", dp_replicate_size),
                        ("dp_shard_size", dp_shard_size)):
        if size < 1 and size != -1:
            raise ValueError(f"{label} must be >= 1 or -1 (infer), got {size}")
    devs = list(devices) if devices is not None else jax.devices()
    world = len(devs)
    if dp_replicate_size == -1:
        if dp_shard_size == -1:
            raise ValueError(
                "at most one of dp_replicate_size/dp_shard_size may be -1"
            )
        known = pp_size * dp_shard_size * ulysses_size * cp_size * tp_size
        if world % known:
            raise ValueError(f"world size {world} not divisible by {known}")
        dp_replicate_size = world // known
    known = pp_size * dp_replicate_size * ulysses_size * cp_size * tp_size
    if dp_shard_size == -1:
        if world % known:
            raise ValueError(f"world size {world} not divisible by {known}")
        dp_shard_size = world // known
    if known * dp_shard_size != world:
        raise ValueError(
            f"mesh sizes {known * dp_shard_size} != device count {world}"
        )
    if dp_shard_size % ep_size:
        raise ValueError(f"ep_size {ep_size} must divide dp_shard {dp_shard_size}")
    fsdp_size = dp_shard_size // ep_size

    shape = (pp_size, dp_replicate_size, ep_size, fsdp_size, ulysses_size, cp_size, tp_size)
    grid = np.array(devs).reshape(shape)
    mesh = Mesh(grid, MESH_AXES)
    state = ParallelState(
        mesh=mesh,
        pp_size=pp_size,
        dp_replicate_size=dp_replicate_size,
        ep_size=ep_size,
        fsdp_size=fsdp_size,
        ulysses_size=ulysses_size,
        cp_size=cp_size,
        tp_size=tp_size,
        name=name,
    )
    _REGISTRY[name] = state
    logger.info_rank0("%s", state.describe())
    return state


def get_parallel_state(name: Optional[str] = None) -> ParallelState:
    """Current ambient state (innermost ``use_parallel_state``), or by name."""
    if name is not None:
        if name not in _REGISTRY:
            raise KeyError(f"no ParallelState named {name!r}; call init_parallel_state")
        return _REGISTRY[name]
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    if "base" in _REGISTRY:
        return _REGISTRY["base"]
    raise RuntimeError("init_parallel_state() has not been called")


def parallel_state_initialized(name: str = "base") -> bool:
    return name in _REGISTRY


def get_parallel_state_or_none() -> Optional[ParallelState]:
    """Ambient state, or None when no mesh has been initialized (pure
    single-device use) — the probe used by ops/model code paths."""
    try:
        return get_parallel_state()
    except RuntimeError:
        return None


def gspmd_parallel_state() -> Optional[ParallelState]:
    """The ambient state when the caller is being traced under GSPMD on a
    mesh of more than one device; None on one device, with no mesh, and
    inside a ``shard_map`` region (whose body is already per-device). What a
    Mosaic kernel asks before it runs: GSPMD cannot partition one."""
    state = get_parallel_state_or_none()
    if state is None or state.world_size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return state


@contextlib.contextmanager
def use_parallel_state(state_or_name):
    """Scope the ambient ParallelState (reference ``use_parallel_state``)."""
    state = (
        get_parallel_state(state_or_name)
        if isinstance(state_or_name, str)
        else state_or_name
    )
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(state)
    try:
        yield state
    finally:
        stack.pop()


def destroy_parallel_state() -> None:
    _REGISTRY.clear()
    if hasattr(_tls, "stack"):
        _tls.stack = []
