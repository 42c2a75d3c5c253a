"""Test/dry-run helpers: virtual CPU devices and XLA:CPU rendezvous timeouts.

Everything here must run before the first JAX backend initialization (both
mechanisms only apply then).
"""

from __future__ import annotations

import os

import jax


def apply_cpu_collective_timeout_flags(
    warn_s: int = 120, terminate_s: int = 600
) -> None:
    """Append the XLA:CPU collective-rendezvous timeout flags to XLA_FLAGS
    (idempotent). N virtual devices time-share a few physical cores, so a
    slow participant can exceed the default 40s and SIGABRT the process
    mid-step (observed: CollectivePermute AwaitAndLogIfStuck at seq 32k)."""
    flags = os.environ.get("XLA_FLAGS", "")
    for f in (
        f"--xla_cpu_collective_call_warn_stuck_timeout_seconds={warn_s}",
        f"--xla_cpu_collective_call_terminate_timeout_seconds={terminate_s}",
        f"--xla_cpu_collective_timeout_seconds={terminate_s}",
    ):
        if f.split("=")[0] not in flags:
            flags += " " + f
    os.environ["XLA_FLAGS"] = flags.strip()


def set_virtual_cpu_devices(n: int) -> None:
    """Force the CPU platform with ``n`` virtual devices."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def force_cpu_devices(n: int = 8) -> None:
    """Run on N virtual CPU devices with generous rendezvous timeouts."""
    apply_cpu_collective_timeout_flags(warn_s=300, terminate_s=1800)
    set_virtual_cpu_devices(n)
