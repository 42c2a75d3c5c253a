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


def once_on_host(make):
    """``make()``'s value, computed at the first call and kept for the module's
    later tests ON THE HOST: a device array kept would stay live for every
    later file of the worker (tests/test_cost_observatory.py counts them)."""
    kept = []

    def get():
        if not kept:
            kept.append(jax.device_get(make()))
        return kept[0]

    return get


def under_jit(fn):
    """``fn(first, cfg, *rest, **kw)`` as ONE program a (``cfg``, shape), ``cfg``
    closed over: for tests that run a whole model. The suite runs with
    asynchronous dispatch off (tests/conftest.py), so an eager ``loss_fn`` or
    ``init_params`` is hundreds of one-operation programs compiled and
    dispatched in a row. A later call under the same ``cfg`` object reuses the
    compile; the programs and their ``cfg`` live as long as the wrapper does
    (a module-level one: as long as a module-level ``@jax.jit``'s). Not for a
    case that counts traces."""
    programs = {}

    def call(first, cfg, *rest, **kw):
        if id(cfg) not in programs:  # cfg is kept, so its id is not reused
            programs[id(cfg)] = (cfg, jax.jit(lambda first, *rest, **kw: fn(first, cfg, *rest, **kw)))
        return programs[id(cfg)][1](first, *rest, **kw)

    return call
