"""Kernel registry: (op_name, impl_name) -> callable with hardware gating.

Reference: ``veomni/ops/kernel_registry.py:34-172`` — global registry of
``(op_name, variant) -> {impl_name: KernelSpec}`` with lazy factories and
HardwareRequirement gates (device type + SM capability). TPU translation:
gates are device type ("tpu"/"cpu"/"any"); selection prefers the highest
priority impl whose requirements are met, and ``VEOMNI_FORCE_EAGER_OPS=1`` or
an explicit ops-config pin can force the XLA-eager impl.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

from veomni_tpu.utils.device import get_device_type
from veomni_tpu.utils.env import env_bool
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class KernelSpec:
    fn: Callable
    device_types: Tuple[str, ...] = ("any",)
    priority: int = 0  # higher wins
    name: str = ""

    def available(self) -> bool:
        if "any" in self.device_types:
            return True
        return get_device_type() in self.device_types


class _KernelRegistry:
    def __init__(self):
        self._ops: Dict[str, Dict[str, KernelSpec]] = {}
        self._pins: Dict[str, str] = {}  # op -> impl name forced by config

    def register(
        self,
        op_name: str,
        impl_name: str,
        *,
        device_types: Tuple[str, ...] = ("any",),
        priority: int = 0,
    ):
        def _do(fn):
            self._ops.setdefault(op_name, {})[impl_name] = KernelSpec(
                fn=fn, device_types=device_types, priority=priority,
                name=impl_name,
            )
            return fn

        return _do

    def pin(self, op_name: str, impl_name: str) -> None:
        """Force an implementation (the ops_implementation config surface)."""
        self._pins[op_name] = impl_name
        self.resolve.cache_clear()

    def clear_pins(self) -> None:
        self._pins.clear()
        self.resolve.cache_clear()

    def pinned(self, op_name: str) -> Optional[str]:
        """The impl name an op is pinned to (None = auto-select), validated
        against the registered impls exactly like resolve() would — a typo'd
        pin fails fast even on ops dispatched outside resolve()."""
        pin = self._pins.get(op_name)
        if pin is not None:
            impls = self._ops.get(op_name, {})
            if pin not in impls:
                raise KeyError(
                    f"op {op_name!r} has no impl {pin!r}: {sorted(impls)}"
                )
        return pin

    def impls(self, op_name: str) -> Dict[str, KernelSpec]:
        return dict(self._ops.get(op_name, {}))

    @functools.lru_cache(maxsize=None)
    def resolve(self, op_name: str) -> Callable:
        return self._select(op_name).fn

    def resolved_name(self, op_name: str) -> str:
        """Name of the impl :meth:`resolve` returns for ``op_name``."""
        return self._select(op_name).name

    def _select(self, op_name: str) -> KernelSpec:
        impls = self._ops.get(op_name)
        if not impls:
            raise KeyError(f"no kernels registered for op {op_name!r}")
        pin = self._pins.get(op_name)
        if pin is not None:
            if pin not in impls:
                raise KeyError(f"op {op_name!r} has no impl {pin!r}: {sorted(impls)}")
            return impls[pin]
        if env_bool("VEOMNI_FORCE_EAGER_OPS") and "xla" in impls:
            return impls["xla"]
        candidates = [s for s in impls.values() if s.available()]
        if not candidates:
            raise RuntimeError(f"no available impl for op {op_name!r} on {get_device_type()}")
        best = max(candidates, key=lambda s: s.priority)
        logger.info_once("op %s -> impl %s", op_name, best.name)
        return best


KERNEL_REGISTRY = _KernelRegistry()


def resolve_op(op_name: str) -> Callable:
    return KERNEL_REGISTRY.resolve(op_name)


def apply_ops_config(pins: Optional[Dict[str, str]]) -> None:
    """Apply an ops_implementation config mapping {op: impl}.

    Reference: ``veomni/ops/__init__.py:54-100`` apply_ops_config.
    """
    KERNEL_REGISTRY.clear_pins()
    for op, impl in (pins or {}).items():
        KERNEL_REGISTRY.pin(op, impl)
