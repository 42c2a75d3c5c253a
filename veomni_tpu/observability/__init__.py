"""Unified observability: one layer every hot subsystem emits into.

The paper's framework lives or dies on utilization, and the first question
about any slow step is *where the time went* — data, dispatch, checkpoint,
host callbacks, or a recompile. This package answers it without attaching a
full profiler:

* ``metrics``  — process-wide, thread-safe :class:`MetricsRegistry`
                 (counters, gauges, bounded-reservoir histograms with
                 p50/p95/max), rank-aware, with a rank-local JSONL sink and
                 pluggable export hooks.
* ``spans``    — near-zero-overhead host-side span tracing
                 (``with span("data.wait"): ...``) feeding duration
                 histograms, mirrored into ``jax.profiler.TraceAnnotation``
                 when a device trace is active, and dumpable as chrome-trace
                 JSON (``scripts/merge_chrome_trace.py`` consumes it).
* ``goodput``  — per-window wall-time decomposition (data-wait /
                 host-callback / dispatch / checkpoint / other), a goodput
                 percentage, live device-memory gauges, and a recompile
                 detector over the ``TRACE_COUNTS`` machinery.
* ``exporter`` — optional stdlib-only HTTP daemon serving ``/metrics``
                 (Prometheus text), ``/healthz`` (resilience supervisor
                 state), ``/debug/flight`` (flight-recorder tail) and
                 ``/debug/requests`` (in-flight request timelines), shared
                 by the trainer and ``serving.InferenceEngine``.
* ``flight_recorder`` — always-on bounded ring of structured events from
                 every hot subsystem, dumped to ``postmortem-<rank>.json``
                 on watchdog fire / supervisor abort / uncaught exception /
                 SIGTERM (``scripts/postmortem.py`` merges ranks).
* ``request_trace`` — per-request lifecycle timelines through the serving
                 engine (queue-wait / TPOT histograms, per-slot chrome
                 trace).
* ``cost``     — compiled-program cost census: every instrumented jit site
                 records XLA ``cost_analysis``/``memory_analysis`` + compile
                 wall-time per bucket, feeding continuous per-window MFU /
                 bandwidth-utilization gauges and ``/debug/cost``.
* ``scopes``   — the names the device's work carries out of the program:
                 Pallas kernel names and the ``jax.named_scope`` taxonomy of
                 the train and engine steps; ``CostCensus.scope_map(site)``
                 hands out ``{instruction: op_name}`` to join a profiler
                 trace's device events to them.
* ``devmem``   — live HBM accounting: ``jax.live_arrays()`` buffer census,
                 high-watermark tracking with a CPU fallback, KV-pool
                 capacity stats, and the OOM post-mortem payload
                 (``/debug/memory``).
* ``numerics`` — numerics & training-health observatory: the instrumented
                 sibling train step's per-param-group grad/param RMS,
                 absmax, non-finite counts, update/weight ratio and
                 overflow-margin bits (scan-stacked layers as per-layer
                 vectors), a bounded health-history ring, and the
                 non-finite provenance doc the resilience supervisor's
                 anomaly re-run produces (``/debug/numerics``, the
                 ``numerics.nonfinite`` flight event, the anomaly
                 post-mortem).
* ``comm``     — live collective census riding the cost census's compile:
                 per-program bytes by collective kind, predicted comm time
                 against the ICI peak, overlappable-vs-serialized pair
                 counts, the ``comm``-bound roofline extension and the
                 window ``comm_est_frac``.
* ``fleet``    — cross-rank view: per-sync-window step-time skew exchange
                 (straggler warnings + ``fleet.straggler`` flight events),
                 host-side per-rank heartbeat files for out-of-process
                 wedge diagnosis, and ``/debug/fleet``
                 (``scripts/fleet.py`` merges ranks offline).

``callback.ObservabilityCallback`` (imported lazily by the trainer — it
depends on ``trainer.callbacks``) ties them together in the train loop.
See ``docs/observability.md``.
"""

from veomni_tpu.observability.comm import (
    CommCensus,
    CommCost,
    get_comm_census,
)
from veomni_tpu.observability.cost import (
    CostCensus,
    CostWindow,
    ProgramCost,
    get_cost_census,
    instrument_jit,
)
from veomni_tpu.observability.devmem import (
    attach_oom_extra,
    buffer_census,
    is_resource_exhausted,
    kv_capacity_stats,
    oom_report,
    publish_memory_gauges,
)
from veomni_tpu.observability.exporter import MetricsExporter, render_prometheus
from veomni_tpu.observability.fleet import (
    FleetMonitor,
    get_active_monitor,
    heartbeat_ages,
    read_heartbeats,
    write_heartbeat,
)
from veomni_tpu.observability.flight_recorder import (
    FlightRecorder,
    configure_flight_recorder,
    dump_postmortem,
    get_flight_recorder,
    record,
)
from veomni_tpu.observability.goodput import (
    GoodputTracker,
    RecompileDetector,
    update_memory_gauges,
)
from veomni_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from veomni_tpu.observability.numerics import (
    NumericsMonitor,
    NumericsSpec,
    attach_numerics_extra,
    debug_numerics_doc,
    tree_health,
)
from veomni_tpu.observability.request_trace import RequestTimeline, RequestTracer
from veomni_tpu.observability.spans import (
    disable_spans,
    dump_chrome_trace,
    enable_spans,
    span,
    spans_enabled,
)

__all__ = [
    "CommCensus",
    "CommCost",
    "CostCensus",
    "CostWindow",
    "Counter",
    "FleetMonitor",
    "FlightRecorder",
    "Gauge",
    "ProgramCost",
    "GoodputTracker",
    "Histogram",
    "MetricsExporter",
    "MetricsRegistry",
    "NumericsMonitor",
    "NumericsSpec",
    "RecompileDetector",
    "RequestTimeline",
    "RequestTracer",
    "attach_numerics_extra",
    "attach_oom_extra",
    "buffer_census",
    "configure_flight_recorder",
    "debug_numerics_doc",
    "disable_spans",
    "dump_chrome_trace",
    "dump_postmortem",
    "enable_spans",
    "get_active_monitor",
    "get_comm_census",
    "get_cost_census",
    "get_flight_recorder",
    "get_registry",
    "heartbeat_ages",
    "instrument_jit",
    "is_resource_exhausted",
    "kv_capacity_stats",
    "oom_report",
    "publish_memory_gauges",
    "read_heartbeats",
    "record",
    "render_prometheus",
    "set_registry",
    "span",
    "spans_enabled",
    "tree_health",
    "update_memory_gauges",
    "write_heartbeat",
]
