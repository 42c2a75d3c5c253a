"""Test harness: all tests run on a virtual 4-device CPU mesh.

Mirrors the reference's mp.spawn+gloo fallback strategy (SURVEY.md §4): the
collective/sharding logic runs on CPU with 4 virtual devices; numerics match
TPU because XLA semantics are backend-uniform. The platform and device count
are set through jax.config before first backend use, so the suite does not
depend on the caller's JAX_PLATFORMS.

The one marker the suite declares is ``slow``, and tier-1 runs ``-m 'not
slow'``. Here ``slow`` means BOTH: the case takes over 120 s alone on a quiet
core, and a named cell of ``BENCHMARK.json`` holds the same thing on the chip
in every PR's check (the case's docstring names the cell). Nothing else is
marked: a case that is merely long is made shorter (docs/testing.md, "Tier-1").
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
# What the suite pays for is compiling: thousands of small programs, each run
# once or a few times at toy size. XLA:CPU's LLVM back end at its level 0 takes
# a fifth to a third off a file's CPU seconds (docs/testing.md "Tier-1"); the
# optimized HLO, and so the program's arithmetic, is as it was. Children
# inherit it with the environment; libtpu's compiles for a described chip
# cost and hold what they did.
if "xla_backend_optimization_level" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
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


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: over 120 s alone on a quiet core AND held on the chip by a named "
        "benchmark cell in every PR's check; tier-1 deselects it (-m 'not slow')")


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
