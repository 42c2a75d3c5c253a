"""A described (not attached) v5e for the three files that compile for it
(``test_chip_compile.py``: the kernels; ``test_chip_compile_steps.py``: whole
train steps; ``test_chip_smoke.py``: the flag channel): the fixtures and the
readers of a compiled text they share.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
# libtpu lets one process at a time load it (/tmp/libtpu_lockfile). Nothing
# here touches a device, so this process and the child it starts may share
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import pytest


@pytest.fixture(scope="module")
def v5e():
    """Devices of a described (not attached) v5e 2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a compile for a described device is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def for_mosaic(monkeypatch):
    from veomni_tpu.ops.pallas import flash_attention, grouped_gemm

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_gemm, "_interpret", lambda: False)


@pytest.fixture
def on_chip_kernels(monkeypatch):
    """Compile the kernels for Mosaic, not for the interpreter."""
    for_mosaic(monkeypatch)


def kernel_instructions(text):
    """{kernel name: custom calls named after it} in a compiled text."""
    from veomni_tpu.observability.scopes import ALL_KERNEL_NAMES

    found = re.findall(r"^\s*(?:ROOT\s+)?%?([a-z_]+)\.\d+ = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.MULTILINE)
    assert set(found) <= set(ALL_KERNEL_NAMES), found
    return {k: found.count(k) for k in set(found)}


def flash_bwd_calls():
    """(fused, split): the backward's trace-time counters as they stand."""
    from veomni_tpu.observability.metrics import get_registry

    return tuple(get_registry().counter(f"attn.flash.bwd.calls_{form}").value
                 for form in ("fused", "split"))


def described(device, shape, dtype):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(device))
