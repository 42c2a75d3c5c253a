"""Test harness: all tests run on a virtual 4-device CPU mesh.

Mirrors the reference's mp.spawn+gloo fallback strategy (SURVEY.md §4): the
collective/sharding logic runs on CPU with 4 virtual devices; numerics match
TPU because XLA semantics are backend-uniform. The platform and device count
are set through jax.config before first backend use, so the suite does not
depend on the caller's JAX_PLATFORMS.
"""

import os

os.environ.setdefault("VEOMNI_LOG_LEVEL", "WARNING")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from veomni_tpu.utils.testing import (
    apply_cpu_collective_timeout_flags,
    set_virtual_cpu_devices,
)

# The virtual devices share a few physical cores: XLA:CPU collective
# rendezvous can exceed its default 40s termination timeout under load and
# SIGABRT the process. Give the rendezvous generous timeouts.
apply_cpu_collective_timeout_flags(warn_s=120, terminate_s=600)
set_virtual_cpu_devices(4)
# With several virtual devices on a 1-core box, async dispatch lets several
# executions be in flight; their collective rendezvous can starve each other
# of pool threads and deadlock (observed SIGABRT in rendezvous.cc). Run CPU
# executions synchronously — one program in flight at a time.
jax.config.update("jax_cpu_enable_async_dispatch", False)
# NOTE: do NOT enable the persistent compilation cache here — reloading
# cached executables with in-process CPU collectives has been observed to
# deadlock the rendezvous on this box (cold runs pass, warm runs hang).

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
