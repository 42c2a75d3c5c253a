"""TPU compiler flags and the persistent compilation cache (the TPU analogue
of the reference's async-Ulysses comm/compute overlap,
``distributed/sequence_parallel/async_ulysses.py``: on TPU, overlap is the
compiler's job — the latency-hiding scheduler reorders collectives behind
compute when these flags are on).

The flags travel in ``LIBTPU_INIT_ARGS``, which only the TPU runtime reads:
``XLA_FLAGS`` is parsed by every backend and jaxlib aborts the process on a
``--xla_tpu_*`` flag it does not know. Must run BEFORE the first JAX backend
initialization; entrypoints (tasks/*, scripts/serve.py,
chip_smoke.py) call ``apply_performance_flags()`` first thing. Disable with
``VEOMNI_XLA_PERF_FLAGS=0``.
"""

from __future__ import annotations

import os

_PERF_FLAGS = (
    # overlap ICI collectives (Ulysses a2a, FSDP all-gather/reduce-scatter)
    # with compute instead of scheduling them synchronously
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    # allow collectives to combine into fewer, larger transfers
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache so repeat runs skip the
    compile (a whole train step takes tens of seconds cold). The directory
    is placed from outside: with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads
    the variable itself and nothing here names a directory; otherwise the
    cache lives at the fixed path ``<checkout>/.jax_cache`` (the path is part
    of the cache key, so a directory that moves never hits). Returns the
    directory in use. Disable with ``VEOMNI_COMPILATION_CACHE=0``."""
    if os.environ.get("VEOMNI_COMPILATION_CACHE", "1") in ("0", "false"):
        return None
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything, even fast compiles: a cold process compiles hundreds
    # of small programs before its first step
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def apply_performance_flags() -> bool:
    """Enable the persistent compilation cache and add the TPU perf flags to
    ``LIBTPU_INIT_ARGS`` (idempotent; a flag the caller already set keeps
    the caller's value). Returns whether the flags are active."""
    # the cache has its own kill switch (VEOMNI_COMPILATION_CACHE) and must
    # stay on even when the perf flags are disabled for debugging
    enable_compilation_cache()
    if os.environ.get("VEOMNI_XLA_PERF_FLAGS", "1") in ("0", "false"):
        return False
    current = os.environ.get("LIBTPU_INIT_ARGS", "")
    present = {tok.split("=")[0] for tok in current.split()}
    added = [f for f in _PERF_FLAGS if f.split("=")[0] not in present]
    if added:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join([current, *added]).strip()
    return True
