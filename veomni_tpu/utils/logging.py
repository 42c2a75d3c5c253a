"""Rank-aware logging.

TPU-native counterpart of the reference's ``veomni/utils/logging.py`` (rank0
filtering, warn-once). On a single-controller JAX deployment "rank" means
``jax.process_index()``; we read it lazily so the logger works before
``jax.distributed.initialize``.
"""

from __future__ import annotations

import functools
import logging
import os
import sys
import threading

_FORMAT = "[%(asctime)s] [%(levelname)s] [%(name)s:%(lineno)d] %(message)s"
_lock = threading.Lock()
_configured = False


def _process_index() -> int:
    """Rank for *_rank0 gating WITHOUT forcing backend init: jax.process_index
    would start the backend, and an attached chip belongs to the one process
    that starts it — a log call must never be the thing that initializes
    the backend. Pre-init we trust the launcher env."""
    try:
        from jax._src import xla_bridge

        if xla_bridge._backends:  # already initialized: authoritative
            import jax

            return jax.process_index()
    except Exception:
        pass
    return int(
        os.environ.get(
            "VEOMNI_PROCESS_ID", os.environ.get("JAX_PROCESS_INDEX", "0")
        )
    )


def _configure_root() -> None:
    global _configured
    with _lock:
        if _configured:
            return
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(logging.Formatter(_FORMAT, datefmt="%Y-%m-%d %H:%M:%S"))
        root = logging.getLogger("veomni_tpu")
        root.addHandler(handler)
        root.setLevel(os.environ.get("VEOMNI_LOG_LEVEL", "INFO").upper())
        root.propagate = False
        _configured = True


class _RankLogger(logging.LoggerAdapter):
    """Adds ``*_rank0`` / ``*_once`` variants like the reference logger."""

    def info_rank0(self, msg, *args, **kwargs):
        if _process_index() == 0:
            self.info(msg, *args, **kwargs)

    def warning_rank0(self, msg, *args, **kwargs):
        if _process_index() == 0:
            self.warning(msg, *args, **kwargs)

    @functools.lru_cache(maxsize=None)
    def _seen(self, msg: str) -> bool:  # lru_cache as the dedupe set
        return True

    def warning_once(self, msg, *args, **kwargs):
        key = msg % args if args else msg
        if key not in getattr(self, "_once_seen", set()):
            if not hasattr(self, "_once_seen"):
                self._once_seen = set()
            self._once_seen.add(key)
            self.warning(msg, *args, **kwargs)

    def info_once(self, msg, *args, **kwargs):
        key = msg % args if args else msg
        if not hasattr(self, "_once_seen"):
            self._once_seen = set()
        if key not in self._once_seen:
            self._once_seen.add(key)
            self.info(msg, *args, **kwargs)


def get_logger(name: str = "veomni_tpu") -> _RankLogger:
    _configure_root()
    if not name.startswith("veomni_tpu"):
        name = f"veomni_tpu.{name}"
    return _RankLogger(logging.getLogger(name), {})
