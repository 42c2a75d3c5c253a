"""ObservabilityCallback: the train loop's wiring into the registry.

Imported lazily by ``BaseTrainer._init_callbacks`` (this module depends on
``trainer.callbacks``; everything else in ``observability`` is trainer-
agnostic). Placed right after ``EnvironMeterCallback`` so the published
payload already contains the meter's throughput/MFU rollup, and before
``LoggingCallback``/``WandbCallback`` so their export-hook consumption sees
this step's publish.

Per sync step (the loop's existing host<->device sync cadence — zero added
syncs): closes the goodput window, refreshes memory gauges, publishes
``train.*`` gauges, and fires ``registry.export`` (JSONL sink + hooks).
Every step: checks the recompile detector (a host-side dict compare).
"""

from __future__ import annotations

import os

from veomni_tpu.observability.cost import CostWindow
from veomni_tpu.observability.exporter import MetricsExporter, resolve_port
from veomni_tpu.observability.goodput import (
    GoodputTracker,
    RecompileDetector,
    update_memory_gauges,
)
from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.observability.spans import dump_chrome_trace
from veomni_tpu.trainer.callbacks import Callback
from veomni_tpu.utils.helper import host_floats
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class ObservabilityCallback(Callback):
    def __init__(self):
        self.registry = None
        self.tracker = None
        self.detector = None
        self.exporter = None
        self.cost_window = None
        self.fleet = None
        self._chrome_trace_path = ""
        self._armed = False
        # per-sync-window host step timing (feeds the fleet skew exchange):
        # one perf_counter read per step, no device syncs
        self._win_t0 = 0.0
        self._win_last = 0.0
        self._win_steps = 0
        self._win_max_step_s = 0.0
        self._fleet_warm = False
        # MoE routing counts of the steps since the last sync, still on the
        # device: (the step's summed [assignments, held, dropped], load)
        self._moe_pending = []

    def _flush_moe(self):
        """``moe.assignments`` / ``moe.assignments_held`` /
        ``moe.assignments_dropped`` / ``moe.gmm.tile_visits`` /
        ``moe.gmm.tile_pairs`` / ``moe.load_max_over_mean`` (and the gauge
        ``moe.gmm.tile_visits_share``, over the run so far) from the queued
        steps' metrics (the loss function's ``moe_assignment_counts``, which
        the step sums over its micro-steps, and ``moe_load_max_over_mean``).
        Called where the loop has synced anyway (a sync step, the end of
        train), so the fetch waits for nothing."""
        if not self._moe_pending or self.registry is None:
            return
        import jax

        pending, self._moe_pending = self._moe_pending, []
        counters = [self.registry.counter(f"moe.{name}")
                    for name in ("assignments", "assignments_held", "assignments_dropped",
                                 "gmm.tile_visits", "gmm.tile_pairs")]
        for counts, load in jax.device_get(pending):
            for counter, value in zip(counters, counts):
                counter.inc(float(value))
        self.registry.gauge("moe.load_max_over_mean").set(float(load))
        visits, pairs = (counter.value for counter in counters[3:])
        if pairs:
            self.registry.gauge("moe.gmm.tile_visits_share").set(visits / pairs)

    def on_train_begin(self, trainer, state):
        t = trainer.args.train
        self.registry = get_registry()
        # (spans were switched on when the trainer was built, so that its
        # set-up is inside them: trainer/base.py::BaseTrainer.__init__)
        # (the flight recorder's ring size + dump dir are wired in train()'s
        # prologue, BEFORE any callback can raise — not here)
        if t.observability_jsonl:
            path = os.path.join(
                t.output_dir, f"metrics_rank{self.registry.rank()}.jsonl"
            )
            self.registry.attach_jsonl(path)
        self._chrome_trace_path = t.observability_chrome_trace
        self.tracker = GoodputTracker(self.registry)
        from veomni_tpu.train import train_step as train_step_mod

        # watch ONLY the train step: a first eval jit or a decode bucket
        # compile is a fresh program, not a steady-state retrace
        self.detector = RecompileDetector(
            [("train_step", train_step_mod.TRACE_COUNTS, ("train_step",))],
            shape_source=train_step_mod.LAST_TRACE_SHAPES,
            registry=self.registry,
        )
        # fleet tier (observability/fleet.py): heartbeats always (a wedged
        # rank must be diagnosable from outside), skew exchange only with
        # >= 2 processes; train.observability_fleet=0 turns it all off
        if t.observability_fleet:
            from veomni_tpu.observability.fleet import FleetMonitor

            self.fleet = FleetMonitor(
                registry=self.registry,
                straggler_factor=t.observability_straggler_factor,
                heartbeat_dir=t.output_dir,
            )
        port = resolve_port(t.observability_port)
        if port is not None:
            sup = getattr(trainer, "_supervisor", None)
            health_fn = sup.health if sup is not None else None
            self.exporter = MetricsExporter(
                port=port, registry=self.registry, health_fn=health_fn,
                fleet_fn=self.fleet.debug_doc if self.fleet else None,
            )
            self.exporter.start()
        self.tracker.begin_window()
        # compiled-program cost census window (observability/cost.py): the
        # same sync cadence turns census FLOPs/bytes × step counts into the
        # continuous train.mfu_pct / train.bandwidth_util_pct gauges
        self.cost_window = CostWindow()
        self.cost_window.begin()
        self._armed = False
        import time as _time

        self._win_t0 = self._win_last = _time.perf_counter()
        self._win_steps = 0
        self._win_max_step_s = 0.0
        self._fleet_warm = False  # window 1 = compile warmup, no exchange

    def on_step_end(self, trainer, state):
        import time as _time

        now = _time.perf_counter()
        self._win_steps += 1
        self._win_max_step_s = max(self._win_max_step_s, now - self._win_last)
        self._win_last = now
        if not self._armed:
            # absorb the warmup compile of step 1; everything after is a
            # recompile worth shouting about
            self.detector.arm()
            self._armed = True
        else:
            self.detector.check()
        if "moe_assignment_counts" in state.metrics:
            self._moe_pending.append(
                (state.metrics["moe_assignment_counts"], state.metrics["moe_load_max_over_mean"]))
            if state.synced:
                self._flush_moe()
        if not state.synced:
            return
        state.metrics.update(self.tracker.end_window())
        state.metrics.update(self.cost_window.end())
        state.metrics["recompiles"] = float(self.detector.total_recompiles)
        update_memory_gauges(self.registry)
        if self.fleet is not None and self._win_steps:
            # heartbeat + skew exchange on the loop's existing sync cadence
            # (the host just blocked on the device fetch anyway). The FIRST
            # window carries step-1's compile wall — cross-host compile
            # skew (cold vs warm persistent cache) is not a straggler, so
            # it heartbeats but skips the exchange, mirroring the recompile
            # detector's warmup arm. Deterministic per window on every
            # rank: the exchange is a collective.
            self.fleet.observe_window(
                state.global_step,
                (now - self._win_t0) / self._win_steps,
                max_step_s=self._win_max_step_s,
                steps=self._win_steps,
                exchange=self._fleet_warm,
            )
            self._fleet_warm = True
        self._win_t0 = self._win_last = _time.perf_counter()
        self._win_steps = 0
        self._win_max_step_s = 0.0
        payload = host_floats(state.metrics)
        self.registry.set_gauges("train", payload)
        self.registry.export(state.global_step, payload)

    def on_train_end(self, trainer, state):
        if self.registry is None:  # train() without on_train_begin (tests)
            return
        self._flush_moe()
        routed = self.registry.get("moe.assignments")
        if routed is not None and routed.value:
            logger.info_rank0(
                "moe routing over the run: %d assignments, %d to held experts, %d of "
                "those dropped, load max/mean of the last step %.2f; the grouped GEMM "
                "walked %d of %d (row tile, expert) pairs", routed.value,
                self.registry.counter("moe.assignments_held").value,
                self.registry.counter("moe.assignments_dropped").value,
                self.registry.gauge("moe.load_max_over_mean").value,
                self.registry.counter("moe.gmm.tile_visits").value,
                self.registry.counter("moe.gmm.tile_pairs").value)
        if self.tracker is not None:
            state.metrics.update(self.tracker.end_window())
        if self.cost_window is not None:
            state.metrics.update(self.cost_window.end())
        payload = host_floats(state.metrics)
        self.registry.set_gauges("train", payload)
        self.registry.export(state.global_step, payload)
        if self._chrome_trace_path:
            n = dump_chrome_trace(self._chrome_trace_path)
            logger.info_rank0(
                "wrote %d host span events to %s", n, self._chrome_trace_path
            )
        self.close()

    def close(self):
        """Exception-safe teardown (BaseTrainer calls every callback's
        ``close`` in its finally block): the exporter thread must not
        outlive a crashed run."""
        if self.exporter is not None:
            self.exporter.stop()
            self.exporter = None
        if self.fleet is not None:
            from veomni_tpu.observability.fleet import (
                get_active_monitor,
                set_active_monitor,
            )

            # only un-register our own monitor: a second trainer in the
            # same process may already have installed its own
            if get_active_monitor() is self.fleet:
                set_active_monitor(None)
            self.fleet = None
