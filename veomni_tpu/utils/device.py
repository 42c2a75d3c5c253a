"""Device abstraction layer (reference: ``veomni/utils/device.py:28-123``).

On the reference this switches CUDA vs Ascend-NPU; here it abstracts over TPU
generations and the CPU fallback used for tests (virtual multi-device CPU via
``--xla_force_host_platform_device_count``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax


@functools.lru_cache(maxsize=None)
def get_device_type() -> str:
    """"tpu" | "gpu" | "cpu", from the installed runtime's own platform
    name for the first device."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return "tpu"
    if platform in ("gpu", "cuda", "rocm"):
        return "gpu"
    if platform == "cpu":
        return "cpu"
    raise RuntimeError(f"unsupported JAX platform {platform!r}")


def is_tpu_available() -> bool:
    return get_device_type() == "tpu"


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def synchronize() -> None:
    """Block until all dispatched device work is done (cf. torch.cuda.synchronize)."""
    # Executions on one device run in dispatch order, so a tiny program
    # enqueued now finishes after everything dispatched before it.
    for d in jax.local_devices():
        (jax.device_put(0.0, d) + 0.0).block_until_ready()


# Published per-chip peaks, keyed by ``device_kind`` prefix (longest prefix
# wins): bf16 dense FLOP/s, HBM bytes/s, aggregate ICI bytes/s (links x
# per-link one-way bandwidth — an order-of-magnitude link budget, not
# measured all-reduce goodput). Source: Google Cloud TPU documentation,
# system-architecture pages per generation (v5e: 197 TFLOP/s bf16, 819 GB/s
# HBM, 4 ICI links). An accelerator that is not listed is an error, never a
# default.
_PEAKS = {
    #              flops    hbm      ici
    "tpu v2":      (45e12,  700e9,   100e9),
    "tpu v3":      (123e12, 900e9,   140e9),
    "tpu v4":      (275e12, 1228e9,  270e9),   # 6 links x 45 GB/s (3D torus)
    "tpu v5 lite": (197e12, 819e9,   180e9),   # v5e: 4 links x 45 GB/s
    "tpu v5e":     (197e12, 819e9,   180e9),
    "tpu v5":      (459e12, 2765e9,  540e9),   # v5p: 6 links x 90 GB/s
    "tpu v5p":     (459e12, 2765e9,  540e9),
    "tpu v6 lite": (918e12, 1640e9,  360e9),   # trillium: 4 links x 90 GB/s
    "tpu v6e":     (918e12, 1640e9,  360e9),
    "tpu7x":       (4614e12, 7400e9, 1200e9),
}
# The CPU has no published peak. These nominal values exist only so the cost
# census can form a finite machine balance (roofline verdicts in its tests);
# nothing reported under a device metric's name (MFU, TFLOP/s, utilisation)
# may be derived from them — see EnvironMeter.step.
_CPU_NOMINAL = (1e12, 1e11, 1e10)


@functools.lru_cache(maxsize=None)
def _device_peaks() -> Tuple[float, float, float]:
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _CPU_NOMINAL
    kind = dev.device_kind.lower()
    for key in sorted(_PEAKS, key=len, reverse=True):
        if kind.startswith(key):
            return _PEAKS[key]
    raise KeyError(
        f"no published peaks for device_kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}); add it to utils/device.py::_PEAKS "
        "with its source"
    )


def get_device_peak_flops() -> float:
    """Peak bf16 dense FLOP/s per chip (cf. reference ``count_flops.py:25``
    get_device_flops). Raises KeyError for an unlisted accelerator."""
    return _device_peaks()[0]


def get_device_peak_bandwidth() -> float:
    """Peak HBM bandwidth per chip in bytes/s. Feeds the roofline machine
    balance (peak FLOP/s ÷ peak bytes/s) the cost census classifies compiled
    programs against, and the ``bandwidth_util_pct`` window gauge."""
    return _device_peaks()[1]


def get_device_peak_interconnect_bandwidth() -> float:
    """Nominal per-chip aggregate ICI bandwidth in bytes/s. Feeds the comm
    observatory's predicted-comm-time gauges and the ``comm``-bound
    extension of the roofline verdict (``observability/comm.py``): the
    estimate says *where to look*, it is not an SLA."""
    return _device_peaks()[2]


def mesh_devices_grid(shape: Tuple[int, ...]):
    """Devices reshaped to ``shape`` for building a Mesh; validates count."""
    import numpy as np

    devs = np.array(jax.devices())
    n = int(np.prod(shape))
    if n != devs.size:
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {devs.size}")
    return devs.reshape(shape)
