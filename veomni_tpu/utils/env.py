"""Centralized env-flag system (reference: ``veomni/utils/env.py:23-34``).

All VEOMNI_* environment flags are declared here with defaults so they can be
printed at import and discovered in one place.
"""

from __future__ import annotations

import os
from typing import Any, Dict

ENV_DEFAULTS: Dict[str, Any] = {
    # "native" = our own model zoo; "hf" reserved for torch-free HF-config load.
    "VEOMNI_MODELING_BACKEND": "native",
    # Log level for the framework logger.
    "VEOMNI_LOG_LEVEL": "INFO",
    # Force all kernel-registry ops to the eager XLA impl (skip Pallas).
    "VEOMNI_FORCE_EAGER_OPS": "0",
    # Use donated buffers in the train step (disable when debugging).
    "VEOMNI_DONATE_STATE": "1",
    # Seq length above which the default XLA attention switches to the
    # blockwise online-softmax (flash-style) path instead of materializing
    # the [B, H, S, S] score tensor.
    "VEOMNI_ATTN_CHUNK_THRESHOLD": "2048",
    # Route Ulysses SP attention through the chunked async a2a/compute
    # pipeline (parallel/async_ulysses.py) instead of the monolithic a2a.
    "VEOMNI_ULYSSES_ASYNC": "0",
    # Head-chunk count for the async Ulysses pipeline (clamped to the
    # feasible maximum of the model's head layout).
    "VEOMNI_ULYSSES_ASYNC_CHUNKS": "4",
    # Deterministic fault-injection plan (JSON text or @file) arming the
    # resilience fault points — see docs/resilience.md. "" = unarmed.
    "VEOMNI_FAULT_PLAN": "",
}


def get_env(name: str) -> str:
    if name not in ENV_DEFAULTS:
        raise KeyError(f"Unknown env flag {name}; declare it in ENV_DEFAULTS")
    return os.environ.get(name, str(ENV_DEFAULTS[name]))


def env_bool(name: str) -> bool:
    return get_env(name).lower() in ("1", "true", "yes", "on")


def describe_env() -> str:
    lines = []
    for k, default in sorted(ENV_DEFAULTS.items()):
        v = os.environ.get(k)
        lines.append(f"  {k}={v if v is not None else default}{'' if v is None else ' (set)'}")
    return "Environment flags:\n" + "\n".join(lines)
