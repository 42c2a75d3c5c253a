"""Import-time device hygiene (TPU analogue of the reference's
``tests/special_sanity/check_device_api_usage.py`` .cuda()-literal gate).

On this stack the portable-device sin is *initializing a JAX backend at
import time*: an attached chip belongs to the one process that starts a
backend on it, so any module that calls jax.devices() / jax.device_count()
at import turns `import veomni_tpu.x` into a chip claim (and a parent that
only meant to import then starves the child that needs the chip). Every
veomni_tpu module must import cleanly with backend construction forbidden.

Runs in a SUBPROCESS: in a full-suite run earlier tests have already
imported (and cached in sys.modules) nearly every module, which would make
an in-process walk vacuous.
"""

import subprocess
import sys

_WALK = r"""
import importlib, pkgutil, sys
from jax._src import xla_bridge

def _forbidden(*a, **k):
    raise AssertionError("backend-init-at-import")

xla_bridge.backends = _forbidden
xla_bridge.get_backend = _forbidden

import veomni_tpu

failures = []
visited = []
for m in pkgutil.walk_packages(veomni_tpu.__path__, "veomni_tpu."):
    visited.append(m.name)
    try:
        importlib.import_module(m.name)
    except AssertionError:
        failures.append(m.name)
    except Exception:
        pass  # unrelated import errors (optional deps) are other tests' job
if failures:
    print("FAILURES:" + ",".join(failures))
    sys.exit(1)
# these packages must be part of the walk (a missing __init__.py would
# silently drop a whole subtree from this gate)
for required in ("veomni_tpu.serving", "veomni_tpu.serving.engine",
                 "veomni_tpu.resilience", "veomni_tpu.resilience.faults",
                 "veomni_tpu.resilience.integrity",
                 "veomni_tpu.resilience.retry", "veomni_tpu.resilience.supervisor",
                 "veomni_tpu.observability", "veomni_tpu.observability.metrics",
                 "veomni_tpu.observability.spans",
                 "veomni_tpu.observability.goodput",
                 "veomni_tpu.observability.exporter",
                 "veomni_tpu.observability.callback",
                 "veomni_tpu.observability.flight_recorder",
                 "veomni_tpu.observability.request_trace",
                 "veomni_tpu.observability.cost",
                 "veomni_tpu.observability.numerics",
                 "veomni_tpu.observability.devmem",
                 "veomni_tpu.observability.comm",
                 "veomni_tpu.observability.fleet"):
    if required not in visited:
        print("MISSING:" + required)
        sys.exit(1)
print("CLEAN")
"""


def test_no_backend_init_at_import():
    p = subprocess.run(
        [sys.executable, "-c", _WALK], capture_output=True, text=True,
        cwd="/root/repo", timeout=600,
    )
    assert p.returncode == 0 and "CLEAN" in p.stdout, (
        f"backend init at import: {p.stdout}\n{p.stderr[-500:]}"
    )
