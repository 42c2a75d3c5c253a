"""Compiled-program cost census: what did XLA actually build, per jit site?

PRs 4 and 6 made the *host* attributable (spans, goodput, flight recorder);
this module does the same for the *device*. Every instrumented jit site —
the train/eval steps, the decode prompt buckets, the serving engine's
paged decode/prefill buckets — routes its compiles through
:func:`instrument_jit`, which owns the ahead-of-time ``lower()`` /
``compile()`` pair and records, per compiled program:

* XLA ``cost_analysis()``   — FLOPs and bytes accessed (per device: the
  analysis runs on the SPMD-partitioned module, so a 4-way-sharded step
  reports global/4 — exactly the number MFU-per-chip wants), corrected
  for XLA's count-loop-bodies-once blind spot via the traced jaxpr's
  static ``lax.scan`` trip counts (see the correction block below);
* XLA ``memory_analysis()`` — temp / argument / output / generated-code
  bytes of the optimized executable (how much HBM the *program* needs on
  top of the live buffers);
* compile wall time and invocation counts.

That census makes MFU a continuous per-sync-window gauge beside the
benchmark's offline one (``benchmark/flops.py``): :class:`CostWindow` deltas the census
call counts over the trainer's existing sync cadence and divides achieved
FLOPs/bytes by the window wall and the device peaks
(``utils/device.py::get_device_peak_flops`` /
``get_device_peak_bandwidth``) — the accounting the TPUv4 pjit paper
treats as a first-class training signal (PAPERS.md). Each program also
gets a roofline-style verdict: arithmetic intensity (flops / bytes)
against the machine balance says whether the program is compute- or
bandwidth-bound — i.e. where a kernel PR should even look.

Failure policy: the census must never cost a training step. The AOT path
preserves jit semantics (same lowering, same donation, same shardings);
any surprise — an aval/sharding drift the key missed, a backend without
the analysis APIs — logs one warning, permanently falls back to the plain
jit call for that site, and the run continues census-blind but correct.
``VEOMNI_COST_CENSUS=0`` disables instrumentation entirely.

Registry families (``docs/observability.md``): per program
``cost.{site}.{bucket}.flops`` / ``.bytes_accessed`` / ``.temp_bytes`` /
``.argument_bytes`` / ``.output_bytes`` / ``.compile_s`` gauges and a
``.calls`` counter, plus the aggregate ``cost.programs`` counter and
``cost.compile_s`` histogram. ``/debug/cost`` (exporter) serves the full
census plus a scrape-to-scrape live MFU window.
"""

from __future__ import annotations

import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from veomni_tpu.observability.metrics import MetricsRegistry, get_registry
from veomni_tpu.observability.spans import span
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def census_enabled() -> bool:
    """``VEOMNI_COST_CENSUS=0`` turns :func:`instrument_jit` into identity."""
    return os.environ.get("VEOMNI_COST_CENSUS", "1") not in ("0", "")


def scan_correction_enabled() -> bool:
    """``VEOMNI_COST_CENSUS_SCAN_CORRECT=0`` keeps the raw XLA numbers."""
    return os.environ.get(
        "VEOMNI_COST_CENSUS_SCAN_CORRECT", "1"
    ) not in ("0", "")


# ------------------------------------------------- scan-trip-count correction
#
# XLA's HloCostAnalysis counts a while-loop BODY exactly once, regardless of
# trip count (verified empirically: a 4-iteration lax.scan of a matmul
# reports one matmul's FLOPs). Every model in this repo scans over stacked
# layers — and the train step additionally scans over grad-accum micro
# batches — so the raw census would under-report a 28-layer model ~28x and
# the MFU gauge would be decorative. The correction walks the traced jaxpr:
# for each ``scan`` equation the true cost is ``n x T(body)`` where T
# recurses into nested scans, and bodies are measured with a LOWERED-only
# cost analysis (no XLA compile — tracing cost only, paid once per program
# bucket at census time):
#
#   T(j) = M(j) + sum_scans( n_i * T(body_i) - M(body_i) )
#
# (the ``- M(body_i)`` term removes the one copy XLA already counted).
# ``while_loop``/``cond`` have no static trip count and stay uncorrected.
# Body avals are GLOBAL shapes while the compiled module is per-device, so
# the extra divides by the program's device count — exact for evenly
# partitioned work, the same assumption every MFU formula makes.

_MAX_CORRECTION_BODIES = 64  # runaway-nesting guard; beyond it, keep raw


def _measure_jaxpr(closed) -> Tuple[float, float]:
    """(flops, bytes) of a closed jaxpr via lowered-only cost analysis."""
    import jax

    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in closed.in_avals]
    from jax._src import core as jcore

    d = jax.jit(jcore.jaxpr_as_fun(closed)).lower(*avals).cost_analysis() or {}
    return (float(d.get("flops", 0.0) or 0.0),
            float(d.get("bytes accessed", 0.0) or 0.0))


def _iter_sub_jaxprs(eqn):
    from jax._src import core as jcore

    for v in eqn.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v
        elif isinstance(getattr(v, "jaxpr", None), jcore.Jaxpr):
            yield v  # e.g. a pjit param already closed


def _contains_scan(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            return True
        for sub in _iter_sub_jaxprs(eqn):
            if _contains_scan(sub.jaxpr):
                return True
    return False


def _loop_extras(jaxpr, budget: List[int]) -> Tuple[float, float]:
    """ONE walk over a jaxpr's equations collecting the scan undercount:
    ``n*T(body) - M(body)`` per scan (the ``-M`` removes the copy XLA
    already counted), plus the extras of any scan-containing sub-jaxpr
    (pjit/remat/...) whose body is inlined once."""
    ef = eb = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"]
            n = int(eqn.params["length"])
            tf, tb, bmf, bmb = _true_cost(body, budget)
            ef += n * tf - bmf
            eb += n * tb - bmb
        else:
            for sub in _iter_sub_jaxprs(eqn):
                if _contains_scan(sub.jaxpr):
                    tf, tb, smf, smb = _true_cost(sub, budget)
                    ef += tf - smf
                    eb += tb - smb
    return ef, eb


def _true_cost(closed, budget: List[int]) -> Tuple[float, float, float, float]:
    """Recursive (T_flops, T_bytes, M_flops, M_bytes) for a closed jaxpr."""
    budget[0] -= 1
    if budget[0] < 0:
        raise RuntimeError(
            f"scan correction exceeded {_MAX_CORRECTION_BODIES} bodies"
        )
    mf, mb = _measure_jaxpr(closed)
    ef, eb = _loop_extras(closed.jaxpr, budget)
    return mf + ef, mb + eb, mf, mb


def scan_extras(closed) -> Tuple[float, float]:
    """Extra (flops, bytes) the compiled module's analysis missed because
    scan bodies are counted once. Global-shape units."""
    return _loop_extras(closed.jaxpr, [_MAX_CORRECTION_BODIES])


def apply_scan_correction(traced, fields: Dict[str, float],
                          num_devices: int) -> Dict[str, float]:
    """Fold the scan-trip-count extras into an ``analyze_compiled`` dict;
    the raw XLA readings survive as ``xla_flops_raw``/``xla_bytes_raw``.
    Fail-open: any surprise keeps the raw numbers."""
    if not scan_correction_enabled():
        return fields
    try:
        closed = traced.jaxpr
        if not _contains_scan(closed.jaxpr):
            return fields
        ef, eb = scan_extras(closed)
        if ef or eb:
            fields["xla_flops_raw"] = fields["flops"]
            fields["xla_bytes_raw"] = fields["bytes_accessed"]
            fields["flops"] += ef / max(1, num_devices)
            fields["bytes_accessed"] += eb / max(1, num_devices)
    except Exception as e:
        logger.debug("scan correction skipped: %s", e)
    return fields


@dataclass
class ProgramCost:
    """One compiled program's census record (per (site, bucket))."""

    site: str
    bucket: str
    flops: float = 0.0            # per device, scan-trip-count corrected
    bytes_accessed: float = 0.0   # per device, scan-trip-count corrected
    xla_flops_raw: float = 0.0    # as HloCostAnalysis reported (bodies once)
    xla_bytes_raw: float = 0.0
    temp_bytes: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    generated_code_bytes: float = 0.0
    comm_bytes: float = 0.0       # per device, collective result payloads
                                  # (observability/comm.py rides the same
                                  # compile; full breakdown lives there)
    compile_time_s: float = 0.0
    num_devices: int = 1
    calls: int = 0                # invocations (all compiles of this bucket)
    traces: int = 0               # distinct compiles recorded here
    _call_counter: Any = field(default=None, repr=False)
    _stamp: int = field(default=0, repr=False)  # recency, see latest()

    @property
    def intensity(self) -> float:
        """Arithmetic intensity in FLOPs/byte (0 when bytes unknown)."""
        return self.flops / self.bytes_accessed if self.bytes_accessed else 0.0

    def bound(self) -> str:
        """Roofline verdict: ``compute`` | ``bandwidth`` | ``comm`` |
        ``unknown`` (no analysis / no backend yet). Compute and HBM stay the
        classic intensity-vs-machine-balance comparison; ``comm`` wins when
        the program's estimated collective time (comm census bytes over the
        ICI peak) exceeds both device-local times — i.e. a kernel PR should
        look at overlap/sharding, not the MXU."""
        if not self.flops or not self.bytes_accessed:
            return "unknown"
        try:
            from veomni_tpu.utils.device import (
                get_device_peak_bandwidth,
                get_device_peak_flops,
            )

            t_compute = self.flops / get_device_peak_flops()
            t_mem = self.bytes_accessed / get_device_peak_bandwidth()
        except Exception:
            return "unknown"
        if self.comm_bytes:
            try:
                from veomni_tpu.utils.device import (
                    get_device_peak_interconnect_bandwidth,
                )

                t_comm = (
                    self.comm_bytes / get_device_peak_interconnect_bandwidth()
                )
                if t_comm > t_compute and t_comm > t_mem:
                    return "comm"
            except Exception:
                pass
        return "compute" if t_compute >= t_mem else "bandwidth"

    def to_doc(self) -> Dict[str, Any]:
        return {
            "site": self.site,
            "bucket": self.bucket,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "xla_flops_raw": self.xla_flops_raw,
            "xla_bytes_raw": self.xla_bytes_raw,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "comm_bytes": self.comm_bytes,
            "compile_time_s": self.compile_time_s,
            "num_devices": self.num_devices,
            "calls": self.calls,
            "traces": self.traces,
            "intensity_flops_per_byte": self.intensity,
            "bound": self.bound(),
        }


# one instruction of ``Compiled.as_text()``: its name (with or without the
# ``%`` sigil, ``ROOT`` or not) and the ``op_name`` of its metadata, which
# carries the ``jax.named_scope`` path (observability/scopes.py)
_INSTRUCTION_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bmetadata=\{[^}]*\bop_name="([^"]*)"',
    re.MULTILINE,
)


def parse_scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of an optimized HLO module's text.
    Instruction names are unique within a module, fused computations'
    included, so one flat dict serves; an instruction without metadata
    (most copies, tuples, loop plumbing) is simply absent."""
    return dict(_INSTRUCTION_OP_NAME.findall(hlo_text))


def _first_dict(analysis) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returns a dict on some jax versions and
    a one-element list of dicts on others; normalize."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    return analysis or {}


def analyze_compiled(compiled) -> Dict[str, float]:
    """Best-effort extraction of the census fields from a ``Compiled``
    stage. Missing/unimplemented analyses (some backends return ``None``)
    yield zeros rather than raising."""
    out = {
        "flops": 0.0, "bytes_accessed": 0.0, "temp_bytes": 0.0,
        "argument_bytes": 0.0, "output_bytes": 0.0,
        "generated_code_bytes": 0.0,
    }
    try:
        ca = _first_dict(compiled.cost_analysis())
        out["flops"] = max(0.0, float(ca.get("flops", 0.0) or 0.0))
        out["bytes_accessed"] = max(
            0.0, float(ca.get("bytes accessed", 0.0) or 0.0)
        )
    except Exception as e:
        logger.debug("cost_analysis unavailable: %s", e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out["temp_bytes"] = float(
                getattr(ma, "temp_size_in_bytes", 0) or 0
            )
            out["argument_bytes"] = float(
                getattr(ma, "argument_size_in_bytes", 0) or 0
            )
            out["output_bytes"] = float(
                getattr(ma, "output_size_in_bytes", 0) or 0
            )
            out["generated_code_bytes"] = float(
                getattr(ma, "generated_code_size_in_bytes", 0) or 0
            )
    except Exception as e:
        logger.debug("memory_analysis unavailable: %s", e)
    return out


class CostCensus:
    """Thread-safe (site, bucket) -> :class:`ProgramCost` map.

    ``record`` happens once per compile (cold path: it also publishes the
    ``cost.*`` registry families); ``note_call`` is the hot-path accounting
    — one dict lookup plus a counter increment."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, str], ProgramCost] = {}
        self._registry = registry
        self._stamp = 0  # bumped per record(); recency for latest()
        # site -> [newest compiled executable, its parsed scope map or None].
        # Held HERE, not by the InstrumentedJit that dies with its trainer or
        # engine: a reader (the benchmark's reducers) asks after they are gone
        self._executables: Dict[str, list] = {}

    def _reg(self) -> MetricsRegistry:
        return self._registry or get_registry()

    # ----------------------------------------------------------------- record
    def record(self, site: str, bucket: str, *, compile_time_s: float = 0.0,
               num_devices: int = 1, **fields: float) -> ProgramCost:
        """Register one compiled program. Re-recording an existing bucket
        (e.g. the same shape re-lowered with different shardings) keeps the
        call count, accumulates compile time, and overwrites the analysis
        with the newest program's."""
        reg = self._reg()
        with self._lock:
            rec = self._programs.get((site, bucket))
            fresh = rec is None
            if fresh:
                rec = ProgramCost(site=site, bucket=bucket)
                self._programs[(site, bucket)] = rec
            for k, v in fields.items():
                if hasattr(rec, k):
                    setattr(rec, k, float(v))
            rec.compile_time_s += float(compile_time_s)
            rec.num_devices = max(1, int(num_devices))
            rec.traces += 1
            self._stamp += 1
            rec._stamp = self._stamp  # recency survives in-place re-records
            if rec._call_counter is None:
                rec._call_counter = reg.counter(
                    f"cost.{site}.{bucket}.calls"
                )
        # registry publication outside the census lock (the registry has its
        # own); gauge names carry the bucket so /metrics shows the full
        # per-program census, bounded by the pow2 bucket discipline
        prefix = f"cost.{site}.{bucket}"
        reg.gauge(f"{prefix}.flops").set(rec.flops)
        reg.gauge(f"{prefix}.bytes_accessed").set(rec.bytes_accessed)
        reg.gauge(f"{prefix}.temp_bytes").set(rec.temp_bytes)
        reg.gauge(f"{prefix}.argument_bytes").set(rec.argument_bytes)
        reg.gauge(f"{prefix}.output_bytes").set(rec.output_bytes)
        reg.gauge(f"{prefix}.compile_s").set(rec.compile_time_s)
        if fresh:  # distinct programs only, per the documented meaning
            reg.counter("cost.programs").inc()
        reg.histogram("cost.compile_s").observe(compile_time_s)
        logger.info_rank0(
            "cost census: %s/%s compiled in %.3gs — %.3g GFLOPs, %.3g MB "
            "accessed, %.3g MB temp (%s-bound)",
            site, bucket, compile_time_s, rec.flops / 1e9,
            rec.bytes_accessed / 1e6, rec.temp_bytes / 1e6, rec.bound(),
        )
        return rec

    def note_call(self, site: str, bucket: str) -> None:
        with self._lock:
            rec = self._programs.get((site, bucket))
            if rec is None:
                return
            rec.calls += 1
            counter = rec._call_counter
        if counter is not None:
            counter.inc()

    def note_executable(self, site: str, compiled) -> None:
        """Keep the site's newest compiled executable for :meth:`scope_map`
        (a reference: nothing is read from it until someone asks)."""
        with self._lock:
            self._executables[site] = [compiled, None]

    def scope_map(self, site: str) -> Optional[Dict[str, str]]:
        """``{instruction name: op_name}`` of the site's newest compiled
        program: what joins a profiler trace's device events, which carry
        the instruction's name, to the ``jax.named_scope`` taxonomy of
        ``observability/scopes.py``. Parsed on the first request and kept; a
        run that never asks pays neither the ``as_text()`` nor the parse.
        None for a site that has compiled nothing (or whose text cannot be
        had)."""
        with self._lock:
            held = self._executables.get(site)
        if held is None:
            return None
        if held[1] is None:
            try:
                from veomni_tpu.observability.comm import _compiled_text

                held[1] = parse_scope_map(_compiled_text(held[0]))
            except Exception as e:
                logger.debug("scope map unavailable for %s: %s", site, e)
                return None
        return held[1]

    # ---------------------------------------------------------------- queries
    def get(self, site: str, bucket: str) -> Optional[ProgramCost]:
        with self._lock:
            return self._programs.get((site, bucket))

    def latest(self, site: str) -> Optional[ProgramCost]:
        """The most recently *recorded* program for a site. Recency is a
        per-record() stamp, not dict insertion order: a caller that revisits
        an earlier bucket re-records it in place and needs THAT record, not
        the last-inserted one."""
        with self._lock:
            out = None
            for (s, _b), rec in self._programs.items():
                if s == site and (out is None or rec._stamp > out._stamp):
                    out = rec
            return out

    def programs(self, site: Optional[str] = None) -> List[ProgramCost]:
        with self._lock:
            return [
                rec for (s, _b), rec in self._programs.items()
                if site is None or s == site
            ]

    def call_counts(self) -> Dict[Tuple[str, str], int]:
        """Per-program invocation counts (the :class:`CostWindow` baseline)."""
        with self._lock:
            return {k: rec.calls for k, rec in self._programs.items()}

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready census for ``/debug/cost`` and post-mortems."""
        progs = [rec.to_doc() for rec in self.programs()]
        return {
            "programs": progs,
            "totals": {
                "programs": len(progs),
                "compile_time_s": sum(p["compile_time_s"] for p in progs),
                "calls": sum(p["calls"] for p in progs),
            },
        }

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self._executables.clear()


_GLOBAL: Optional[CostCensus] = None
_GLOBAL_LOCK = threading.Lock()


def get_cost_census() -> CostCensus:
    """The process-wide census every instrumented jit site records into."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = CostCensus()
    return _GLOBAL


# --------------------------------------------------------------- window MFU
#: jit sites the MFU window ignores by default: diagnostic programs whose
#: occasional invocations would otherwise inflate the achieved-FLOPs sum of
#: the window they land in (the numerics observatory's instrumented sibling
#: step re-runs a batch the train-step site already counted)
DIAGNOSTIC_SITES: Tuple[str, ...] = ("numerics_step",)


class CostWindow:
    """Census-delta MFU/bandwidth over a wall-clock window.

    ``begin()`` snapshots the per-program call counts; ``end()`` multiplies
    each program's new invocations by its census FLOPs/bytes and divides by
    the elapsed wall and the per-device peaks — the continuous analogue of
    an offline ``flops / dt / peak``. Census FLOPs are already
    per device (partitioned module), so no world-size factor appears.
    ``exclude_sites`` (default :data:`DIAGNOSTIC_SITES`) keeps diagnostic
    programs out of the utilization math; an explicit ``sites`` allowlist
    wins over the exclusion."""

    def __init__(self, census: Optional[CostCensus] = None,
                 sites: Optional[Tuple[str, ...]] = None,
                 exclude_sites: Optional[Tuple[str, ...]] = DIAGNOSTIC_SITES):
        self.census = census or get_cost_census()
        self.sites = tuple(sites) if sites else None
        self.exclude_sites = tuple(exclude_sites) if exclude_sites else ()
        self._t0: Optional[float] = None
        self._base: Dict[Tuple[str, str], int] = {}

    def begin(self) -> None:
        self._t0 = time.perf_counter()
        self._base = self.census.call_counts()

    def end(self) -> Dict[str, float]:
        """Close the window -> metric dict; re-arms for the next window."""
        if self._t0 is None:
            self.begin()
            return {}
        now = time.perf_counter()
        wall = max(now - self._t0, 1e-9)
        cur = self.census.call_counts()
        flops = bytes_acc = comm_bytes = 0.0
        ran = 0
        for key, calls in cur.items():
            if self.sites is not None and key[0] not in self.sites:
                continue
            if self.sites is None and key[0] in self.exclude_sites:
                continue
            delta = calls - self._base.get(key, 0)
            if delta <= 0:
                continue
            ran += delta
            rec = self.census.get(*key)
            if rec is not None:
                flops += delta * rec.flops
                bytes_acc += delta * rec.bytes_accessed
                comm_bytes += delta * rec.comm_bytes
        if not ran:
            # no instrumented program ran: re-arm and make no utilization
            # statement (the degenerate train-end window must not overwrite
            # the last real sync window's gauges with zeros)
            self._t0, self._base = now, cur
            return {}
        try:
            from veomni_tpu.utils.device import (
                get_device_peak_bandwidth,
                get_device_peak_flops,
                get_device_peak_interconnect_bandwidth,
            )

            peak_flops = get_device_peak_flops()
            peak_bw = get_device_peak_bandwidth()
            peak_ici = get_device_peak_interconnect_bandwidth()
        except Exception:  # no backend yet: report achieved, not utilization
            peak_flops = peak_bw = peak_ici = float("inf")
        out = {
            "mfu_pct": 100.0 * flops / wall / peak_flops,
            "bandwidth_util_pct": 100.0 * bytes_acc / wall / peak_bw,
            "census_tflops_s": flops / wall / 1e12,
            "census_window_s": wall,
            # estimated share of window wall the programs' collectives would
            # take UNHIDDEN (comm census bytes / peak ICI): an exposure
            # *estimate* reported alongside the goodput split — it overlaps
            # the dispatch/other fractions and is deliberately not part of
            # their sum-to-1 set (observability/comm.py)
            "comm_est_frac": min(1.0, comm_bytes / peak_ici / wall)
            if peak_ici != float("inf") else 0.0,
        }
        self._t0, self._base = now, cur
        return out


_DEBUG_WINDOW: Optional[CostWindow] = None
_DEBUG_LOCK = threading.Lock()


def debug_cost_doc() -> Dict[str, Any]:
    """``/debug/cost`` body: the full census plus a scrape-to-scrape live
    MFU window (the first scrape arms it and reports an empty window)."""
    global _DEBUG_WINDOW
    census = get_cost_census()
    with _DEBUG_LOCK:
        if _DEBUG_WINDOW is None:
            _DEBUG_WINDOW = CostWindow(census)
        live = _DEBUG_WINDOW.end()
    doc = census.snapshot()
    doc["live"] = live
    return doc


# ----------------------------------------------------------- jit instrument
def _leaf_key(x) -> Tuple:
    """Jit-signature component for one dynamic argument leaf: shape/dtype/
    weak-type plus the committed sharding (two calls that jit would compile
    separately must never share a census entry). Kept allocation-light —
    this runs per leaf per call on the serving decode hot path (the param
    trees are layer-stacked, so "per leaf" is tens, not thousands); an
    unhashable sharding surfaces as a TypeError at the cache lookup and
    disables the census for the site (fail open, never fail slow)."""
    shape = getattr(x, "shape", None)
    if shape is None:  # python scalar: jit keys on type, not value
        return ("py", type(x).__name__)
    return (shape, getattr(x, "dtype", None),
            bool(getattr(x, "weak_type", False)),
            getattr(x, "sharding", None))


def _num_devices(leaves) -> int:
    n = 1
    for x in leaves:
        ds = getattr(getattr(x, "sharding", None), "device_set", None)
        if ds:
            n = max(n, len(ds))
    return n


class InstrumentedJit:
    """A jit callable whose compiles flow through the cost census.

    Owns an AOT cache keyed on the same signature jit keys on (dynamic
    avals + shardings + static values): a key miss runs
    ``fn.lower(*args).compile()`` — ONE compile, timed, analyzed, recorded
    — and every hit calls the cached executable directly. Attribute access
    (``.lower``, ``.trace``) falls through to the wrapped jit function, so
    HLO-census tooling (``utils/overlap_evidence.py``) keeps working.

    Any failure in the census path disables it for this site permanently
    and falls back to the plain jit call — census loss is acceptable,
    a broken step is not."""

    def __init__(self, site: str, fn: Callable, *,
                 static_argnums: Tuple[int, ...] = (),
                 bucket_fn: Optional[Callable[[tuple], str]] = None,
                 census: Optional[CostCensus] = None):
        self._site = site
        self._fn = fn
        self._static = tuple(static_argnums)
        self._bucket_fn = bucket_fn
        self._census_ref = census
        self._compiled: Dict[Tuple, Tuple[Any, Tuple[str, str]]] = {}
        self._disabled = False
        self._lock = threading.Lock()

    @property
    def _census(self) -> CostCensus:
        return self._census_ref or get_cost_census()

    def __getattr__(self, name):  # .lower/.trace/.clear_cache/...
        if name.startswith("_"):  # never recurse through our own slots
            raise AttributeError(name)
        return getattr(self._fn, name)

    def _disable(self, why: str, exc: Exception) -> None:
        self._disabled = True
        logger.warning_rank0(
            "cost census disabled for jit site %r (%s: %s: %s) — falling "
            "back to the plain jit path; the run continues census-blind",
            self._site, why, type(exc).__name__, exc,
        )

    def _key(self, args) -> Tuple:
        import jax

        static_vals = tuple(
            (i, args[i]) for i in self._static if i < len(args)
        )
        dyn = tuple(
            a for i, a in enumerate(args) if i not in self._static
        )
        leaves, treedef = jax.tree_util.tree_flatten(dyn)
        return (treedef, tuple(_leaf_key(x) for x in leaves), static_vals)

    def _bucket(self, args) -> str:
        if self._bucket_fn is not None:
            try:
                return str(self._bucket_fn(args))
            except Exception:
                pass
        return f"prog{len(self._compiled)}"

    def __call__(self, *args, **kwargs):
        if self._disabled or kwargs:
            # kwargs never appear at the instrumented call sites; if a new
            # caller passes them, jit semantics win over the census
            return self._fn(*args, **kwargs)
        try:
            key = self._key(args)
            entry = self._compiled.get(key)  # TypeError: unhashable leaf
        except Exception as e:
            self._disable("signature", e)
            return self._fn(*args)
        if entry is None:
            with self._lock:
                entry = self._compiled.get(key)
                if entry is None:
                    import jax

                    try:
                        # the span covers what compile_time_s times: a
                        # compile (or a cache load) shows on the host
                        # timeline that names the device's idle gaps
                        with span("jit.compile"):
                            t0 = time.perf_counter()
                            traced = None
                            try:
                                # trace -> lower -> compile keeps the jaxpr
                                # in hand for the scan-trip-count correction
                                traced = self._fn.trace(*args)
                                lowered = traced.lower()
                            except AttributeError:  # older jax: no .trace
                                lowered = self._fn.lower(*args)
                            compiled = lowered.compile()
                            dt = time.perf_counter() - t0
                    except Exception as e:
                        self._disable("lower/compile", e)
                        return self._fn(*args)
                    bucket = self._bucket(args)
                    dyn_leaves = jax.tree_util.tree_leaves(tuple(
                        a for i, a in enumerate(args)
                        if i not in self._static
                    ))
                    ndev = _num_devices(dyn_leaves)
                    fields = analyze_compiled(compiled)
                    if traced is not None:
                        fields = apply_scan_correction(traced, fields, ndev)
                    # comm observatory (observability/comm.py): parse the
                    # ALREADY-compiled program's HLO for the collective
                    # census — zero extra compiles, fail-open, and the
                    # comm_bytes field rides into this ProgramCost so the
                    # roofline verdict can say "comm"-bound
                    try:
                        from veomni_tpu.observability.comm import (
                            maybe_comm_census,
                        )

                        fields.update(maybe_comm_census(
                            self._site, bucket, compiled, ndev
                        ))
                    except Exception as e:
                        logger.debug("comm census unavailable: %s", e)
                    self._census.record(
                        self._site, bucket,
                        compile_time_s=dt,
                        num_devices=ndev,
                        **fields,
                    )
                    self._census.note_executable(self._site, compiled)
                    entry = (compiled, (self._site, bucket))
                    self._compiled[key] = entry
        compiled, site_bucket = entry
        self._census.note_call(*site_bucket)
        dyn = tuple(a for i, a in enumerate(args) if i not in self._static)
        try:
            return compiled(*dyn)
        except TypeError as e:
            # aval/pytree mismatch the key missed (jit would have silently
            # recompiled): fall back for good rather than guess
            self._disable("compiled call", e)
            return self._fn(*args)


def instrument_jit(site: str, fn: Callable, *,
                   static_argnums: Tuple[int, ...] = (),
                   bucket_fn: Optional[Callable[[tuple], str]] = None,
                   census: Optional[CostCensus] = None) -> Callable:
    """Wrap a jitted callable so its compiles land in the cost census.
    Identity when ``VEOMNI_COST_CENSUS=0``."""
    if not census_enabled():
        return fn
    return InstrumentedJit(
        site, fn, static_argnums=static_argnums, bucket_fn=bucket_fn,
        census=census,
    )
