"""Observability subsystem: registry, spans, goodput, recompile detection,
Prometheus exporter.

Acceptance contract (ISSUE 4): registry thread-safety + percentiles; spans
disabled cost ≈ nothing and produce chrome-trace JSON that
``scripts/merge_chrome_trace.py`` accepts; goodput fractions for a synthetic
step sum to ~1.0; a forced re-trace trips the recompile warning; ``/metrics``
serves parseable Prometheus text with trainer *and* serving metrics on CPU.
"""

import gzip
import importlib.util
import json
import logging
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from veomni_tpu.observability import (
    GoodputTracker,
    MetricsExporter,
    MetricsRegistry,
    RecompileDetector,
    render_prometheus,
)
from veomni_tpu.observability import spans as spans_mod
from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.observability.spans import (
    disable_spans,
    dump_chrome_trace,
    enable_spans,
    span,
)


def _load_merge_script():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "merge_chrome_trace.py")
    spec = importlib.util.spec_from_file_location("merge_chrome_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def spans_off():
    """Leave the process-global span switch the way we found it."""
    was = spans_mod.spans_enabled()
    disable_spans()
    yield
    if was:
        enable_spans()


@pytest.fixture
def spans_on():
    was = spans_mod.spans_enabled()
    enable_spans()
    yield
    if not was:
        disable_spans()


# ----------------------------------------------------------------- registry
def test_registry_thread_safety():
    reg = MetricsRegistry()
    threads = 8
    per_thread = 1000

    def work():
        c = reg.counter("t.count")
        h = reg.histogram("t.hist")
        g = reg.gauge("t.gauge")
        for i in range(per_thread):
            c.inc()
            h.observe(float(i))
            g.set(i)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("t.count").value == threads * per_thread
    assert reg.histogram("t.hist").count == threads * per_thread


def test_histogram_percentiles_and_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("lat", max_samples=512)
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["sum"] == pytest.approx(5050.0)
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["p50"] == pytest.approx(50.0, abs=2.0)
    assert snap["p95"] == pytest.approx(95.0, abs=2.0)
    # reservoir stays bounded while count/sum stay exact
    small = reg.histogram("small", max_samples=16)
    for v in range(10_000):
        small.observe(float(v))
    assert small.count == 10_000
    assert len(small._samples) == 16
    assert small.snapshot()["max"] == 9999.0


def test_registry_kind_conflict_and_get_or_create():
    reg = MetricsRegistry()
    c1 = reg.counter("x")
    assert reg.counter("x") is c1  # shared instrument
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_jsonl_sink_and_export_hook(tmp_path):
    reg = MetricsRegistry()
    path = str(tmp_path / "metrics.jsonl")
    reg.attach_jsonl(path)
    seen = []
    reg.add_export_hook(lambda step, payload: seen.append((step, payload)))
    reg.counter("c").inc(3)
    merged = reg.export(7, {"loss": 1.5, "future": object()})
    assert merged["c"] == 3.0 and merged["loss"] == 1.5
    assert "future" not in merged  # non-numeric payload values dropped
    assert seen and seen[0][0] == 7 and seen[0][1]["loss"] == 1.5
    assert reg.last_export(step=7)["loss"] == 1.5
    assert reg.last_export(step=8) is None
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["step"] == 7 and lines[0]["c"] == 3.0
    assert "rank" in lines[0]


# -------------------------------------------------------------------- spans
def test_span_disabled_is_allocation_free(spans_off):
    # the disabled path hands back ONE shared no-op context manager: no
    # per-call object, no clock read, no histogram feed
    assert span("a") is span("b")
    before = len(get_registry().items_snapshot())
    with span("disabled.phase"):
        pass
    # no histogram was created/fed: the disabled path never touches the
    # registry (or the clock, or an allocator)
    assert len(get_registry().items_snapshot()) == before
    assert get_registry().get("span.disabled.phase") is None


def test_span_feeds_histograms_and_chrome_trace(tmp_path, spans_on):
    spans_mod.clear_events()
    reg = get_registry()
    base = reg.histogram_sum("span.unit.phase")
    with span("unit.phase"):
        time.sleep(0.002)
    with span("unit.phase"):
        time.sleep(0.002)
    assert reg.histogram_sum("span.unit.phase") - base >= 0.004

    plain = str(tmp_path / "trace.json")
    gz = str(tmp_path / "trace.json.gz")
    assert dump_chrome_trace(plain) >= 2
    assert dump_chrome_trace(gz) >= 2
    doc = json.load(open(plain))
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs, "no complete events"
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] > 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert any(e.get("name") == "process_name" for e in events)
    with gzip.open(gz, "rt") as f:
        assert json.load(f)["traceEvents"]

    # ... and merge_chrome_trace accepts both (gzip + plain roundtrip)
    merge = _load_merge_script()
    merged = merge.merge_traces([plain, gz])
    assert len(merged) == 2 * len(events)


def test_merge_chrome_trace_monotonic_pid_remap(tmp_path):
    merge = _load_merge_script()
    host0 = [
        {"name": "process_name", "ph": "M", "pid": 3, "args": {"name": "p"}},
        {"name": "a", "ph": "X", "pid": 0, "tid": 1, "ts": 0, "dur": 5},
        {"name": "b", "ph": "X", "pid": 3, "tid": 1, "ts": 1, "dur": 5},
    ]
    host1 = [
        {"name": "a", "ph": "X", "pid": 0, "tid": 2, "ts": 0, "dur": 5},
        {"name": "b", "ph": "X", "pid": 1, "tid": 2, "ts": 2, "dur": 5},
    ]
    p0 = str(tmp_path / "h0.json")
    p1 = str(tmp_path / "h1.json.gz")
    json.dump({"traceEvents": host0}, open(p0, "w"))
    with gzip.open(p1, "wt") as f:
        json.dump(host1, f)  # bare event-list form must load too
    merged = merge.merge_traces([p0, p1])
    assert len(merged) == 5
    pids0 = {e["pid"] for e in merged[:3]}
    pids1 = {e["pid"] for e in merged[3:]}
    assert pids0 == {0, 3}  # first host unshifted
    assert pids1 == {4, 5}  # offset past host0's max pid (3) + 1
    assert max(pids0) < min(pids1)  # monotonic: later hosts sort after
    # host tag folded into process names
    pnames = [e for e in merged if e.get("name") == "process_name"]
    assert pnames and pnames[0]["args"]["name"].startswith("host0/")
    # roundtrip through main()'s output shape
    out = str(tmp_path / "merged.json")
    json.dump({"traceEvents": merged}, open(out, "w"))
    again = merge.load(out)
    assert len(again) == 5


# ------------------------------------------------------------------ goodput
class _Clock:
    """A clock the test moves: what ``time.sleep`` did, without the wall."""

    def __init__(self):
        self.ns = 1_000_000_000

    def advance(self, seconds: float) -> None:
        self.ns += int(seconds * 1e9)

    def perf_counter_ns(self) -> int:
        return self.ns

    def perf_counter(self) -> float:
        return self.ns * 1e-9


def test_goodput_fractions_sum_to_one(spans_on, monkeypatch):
    from veomni_tpu.observability import goodput as goodput_mod

    clock = _Clock()
    # the spans' and the tracker's clock: shares of a window under six loaded
    # workers are no property of the code
    monkeypatch.setattr(spans_mod, "time", clock)
    monkeypatch.setattr(goodput_mod, "time", clock)
    reg = MetricsRegistry()
    tracker = GoodputTracker(reg)
    # synthetic step built from the exact spans the trainer emits — but fed
    # through a private registry so other tests' spans can't skew it
    prev = spans_mod.get_registry
    spans_mod.get_registry = lambda: reg
    try:
        tracker.begin_window()
        with span("data.wait"):
            clock.advance(0.03)
        with span("data.ship"):
            clock.advance(0.005)
        with span("step.dispatch"):
            clock.advance(0.01)
        with span("host.callbacks"):
            with span("ckpt.save"):
                clock.advance(0.01)
            clock.advance(0.005)
        with span("step.backpressure"):
            clock.advance(0.015)  # the loop's wait for the oldest in-flight step
        clock.advance(0.005)  # unattributed (the sync fetch)
        w = tracker.end_window()
    finally:
        spans_mod.get_registry = prev
    fracs = {k: v for k, v in w.items() if k.endswith("_frac")}
    assert set(fracs) == {"data_wait_frac", "host_frac", "dispatch_frac",
                          "checkpoint_frac", "device_wait_frac", "other_frac"}
    assert sum(fracs.values()) == pytest.approx(1.0, abs=1e-6)
    assert w["data_wait_frac"] > 0.15  # the dominant injected stall
    assert w["checkpoint_frac"] > 0.05
    assert w["device_wait_frac"] > 0.08 > w["other_frac"]  # no longer hidden
    # ckpt time nested in the callback hook must not be double counted
    assert w["host_frac"] < w["checkpoint_frac"] + 0.15
    # the published percentage is what it was before the wait had a span:
    # everything but the three host-side stalls
    assert w["goodput_pct"] == pytest.approx(
        100.0 * (1.0 - w["data_wait_frac"] - w["host_frac"] - w["checkpoint_frac"]),
        abs=1e-6)
    assert w["goodput_pct"] == pytest.approx(
        100.0 * (w["dispatch_frac"] + w["device_wait_frac"] + w["other_frac"]), abs=1e-6)
    # next window starts clean
    w2 = tracker.end_window()
    assert w2["data_wait_frac"] == pytest.approx(0.0, abs=1e-3)


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_forced_retrace_trips_recompile_warning():
    import jax
    import jax.numpy as jnp

    from veomni_tpu.train import train_step as train_step_mod

    reg = MetricsRegistry()
    det = RecompileDetector(
        [("train_step", train_step_mod.TRACE_COUNTS, ("train_step",))],
        shape_source=train_step_mod.LAST_TRACE_SHAPES,
        registry=reg,
    )

    def impl(batch):
        # the same trace-time counting discipline the real step_fn uses
        train_step_mod.TRACE_COUNTS["train_step"] += 1
        train_step_mod.LAST_TRACE_SHAPES["train_step"] = {
            k: tuple(v.shape) for k, v in batch.items()
        }
        return batch["input_ids"] * 2

    f = jax.jit(impl)
    f({"input_ids": jnp.ones((1, 8), jnp.int32)})  # warmup compile
    det.arm()
    assert det.check() == 0  # steady state: same shape, no retrace
    f({"input_ids": jnp.ones((1, 8), jnp.int32)})
    assert det.check() == 0

    cap = _Capture()
    root = logging.getLogger("veomni_tpu")
    root.addHandler(cap)
    try:
        f({"input_ids": jnp.ones((1, 16), jnp.int32)})  # forced re-trace
        assert det.check() == 1
    finally:
        root.removeHandler(cap)
    msgs = [r.getMessage() for r in cap.records]
    assert any("RECOMPILE" in m for m in msgs), msgs
    assert any("(1, 16)" in m for m in msgs), "offending shapes not logged"
    assert reg.counter("recompiles").value == 1
    assert det.total_recompiles == 1


# ----------------------------------------------------------------- exporter
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?[0-9.eE+-]+$"
)


def _parse_prometheus(body: str):
    names = set()
    for line in body.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE "), line
            continue
        assert _PROM_LINE.match(line), f"unparseable exposition line: {line!r}"
        names.add(line.split("{")[0].split(" ")[0])
    return names


def test_metrics_endpoint_serves_trainer_and_serving_metrics(tmp_path):
    """The acceptance check: one /metrics endpoint, trainer + serving
    families, parseable Prometheus text, all under JAX_PLATFORMS=cpu."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.serving import EngineConfig, InferenceEngine, Request, SamplingParams
    from veomni_tpu.trainer import TextTrainer

    from tests.test_e2e_training import TOY, _make_args, _write_dummy_data

    destroy_parallel_state()
    _write_dummy_data(tmp_path / "data.jsonl")
    args = _make_args(tmp_path, train_steps=4, log_steps=2)
    trainer = TextTrainer(args)
    ctl = trainer.train()
    assert ctl.global_step == 4
    trainer.checkpointer.close()
    destroy_parallel_state()

    # the trainer's sync-step export also wrote the rank-local JSONL sink
    jsonl = os.path.join(args.train.output_dir, "metrics_rank0.jsonl")
    rows = [json.loads(l) for l in open(jsonl)]
    assert rows and rows[-1]["step"] == 4
    assert "loss" in rows[-1] and "goodput_pct" in rows[-1]
    frac_keys = ("data_wait_frac", "host_frac", "dispatch_frac",
                 "checkpoint_frac", "device_wait_frac", "other_frac")
    assert sum(rows[-1][k] for k in frac_keys) == pytest.approx(1.0, abs=1e-3)

    # serving metrics land in the same registry
    cfg = TransformerConfig(dtype=jnp.float32, **{
        **TOY, "vocab_size": 128, "num_hidden_layers": 2})
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=16, max_model_len=128))
    eng.run([Request(prompt_ids=[1, 2, 3, 4],
                     sampling=SamplingParams(max_new_tokens=4))])
    eng.metrics()

    sup_health = {"healthy": True, "anomalies": 0}
    exp = MetricsExporter(port=0, health_fn=lambda: dict(sup_health))
    port = exp.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        names = _parse_prometheus(body)
        # trainer family
        assert "veomni_train_loss" in names
        assert "veomni_train_goodput_pct" in names
        assert any(n.startswith("veomni_span_") for n in names)
        # serving family
        assert "veomni_serve_generated_tokens" in names
        assert "veomni_serve_ttft_s_sum" in names
        assert "veomni_serve_kv_utilization" in names
        # healthz: healthy -> 200, unhealthy -> 503 (no body parsing needed)
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        assert doc["healthy"] is True
        sup_health["healthy"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                   timeout=10)
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        exp.stop()


def test_moe_router_stats_published():
    from veomni_tpu.utils.moe_monitor import publish_router_stats

    reg = MetricsRegistry()
    load = np.array([
        [0.5, 0.5, 0.0, 0.0],      # collapsed onto two experts
        [0.25, 0.25, 0.25, 0.25],  # perfectly balanced
    ])
    publish_router_stats(load, registry=reg)
    assert reg.gauge("moe.layer0.max_load").value == 0.5
    assert reg.gauge("moe.layer0.entropy").value == pytest.approx(np.log(2))
    # mass above the 1/E fair share = what a capacity-1.0 router would drop
    assert reg.gauge("moe.layer0.drop_frac").value == pytest.approx(0.5)
    assert reg.gauge("moe.layer1.entropy").value == pytest.approx(np.log(4))
    assert reg.gauge("moe.layer1.drop_frac").value == pytest.approx(0.0)


def test_supervisor_health_document():
    from veomni_tpu.resilience import SupervisorPolicy, TrainSupervisor

    sup = TrainSupervisor(SupervisorPolicy(
        anomaly_budget=1, rollback_after=5, inflight_depth=0))
    assert sup.health()["healthy"] is True
    sup.observe(1, {"loss": float("nan"), "step_ok": np.False_})
    sup.drain()
    h = sup.health()
    assert h["healthy"] is True and h["last_verdict"] == "skip"
    sup.observe(2, {"loss": float("nan"), "step_ok": np.False_})
    sup.drain()  # budget (1) blown -> abort, sticky
    assert sup.health()["healthy"] is False
    assert sup.health()["last_verdict"] == "abort"


# ---------------------------------------------------- ProfileCallback fix
def test_profile_callback_exception_safe_and_env_overrides(tmp_path, monkeypatch):
    import veomni_tpu.trainer.callbacks as cb_mod

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(
        cb_mod.jax.profiler, "start_trace",
        lambda d: calls.__setitem__("start", calls["start"] + 1))

    def fake_stop():
        calls["stop"] += 1
        if calls["stop"] > calls["start"]:
            raise RuntimeError("No profile data")  # double-stop would raise

    monkeypatch.setattr(cb_mod.jax.profiler, "stop_trace", fake_stop)
    monkeypatch.setenv("VEOMNI_PROFILE_START", "2")
    monkeypatch.setenv("VEOMNI_PROFILE_END", "9")

    cb = cb_mod.ProfileCallback(str(tmp_path), start_step=3, end_step=5)
    assert cb.start == 2 and cb.end == 9  # env overrides win
    state = cb_mod.TrainerControlState()
    state.global_step = 2
    cb.on_step_begin(None, state)
    assert calls["start"] == 1 and cb._active
    # crash inside the traced window: close() (the trainer's finally path)
    # must stop the trace exactly once; every later stop is a guarded no-op
    cb.close()
    assert calls["stop"] == 1 and not cb._active
    cb.close()
    cb.on_train_end(None, state)
    state.global_step = 9
    cb.on_step_end(None, state)
    assert calls["stop"] == 1  # double-stop guard held everywhere


# ------------------------------------------- device-side names, loop, set-up
@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """A toy TextTrainer built and run for 8 steps with spans on (the
    default): the span ring's events of that run, and what it published."""
    from veomni_tpu.observability import get_cost_census
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import TextTrainer

    from tests.test_e2e_training import _make_args, _write_dummy_data

    tmp_path = tmp_path_factory.mktemp("toy_run")
    was = spans_mod.spans_enabled()
    disable_spans()
    destroy_parallel_state()
    _write_dummy_data(tmp_path / "data.jsonl")
    args = _make_args(tmp_path, train_steps=8, log_steps=100)
    mark = len(spans_mod.live_span_events())
    trainer = TextTrainer(args)
    enabled_by_the_trainer = spans_mod.spans_enabled()
    built = [e[0] for e in spans_mod.live_span_events()[mark:]]
    ctl = trainer.train()
    trainer.checkpointer.close()
    destroy_parallel_state()
    events = spans_mod.live_span_events()[mark:]
    doc = {"events": events, "built": built, "steps": ctl.global_step, "metrics": ctl.metrics,
           "enabled_by_the_trainer": enabled_by_the_trainer,
           "gauge": get_registry().get("setup.launch_to_trainer_s"),
           "scope_map": get_cost_census().scope_map("train_step")}
    if not was:
        disable_spans()
    return doc


def test_trainer_enables_spans_when_it_is_built(toy_run):
    assert toy_run["enabled_by_the_trainer"] is True
    # set-up spans close before train() is ever called
    assert toy_run["built"].count("setup.build") == 1
    assert toy_run["built"].index("setup.data") < toy_run["built"].index("setup.build")


@pytest.mark.parametrize("name,count", [
    ("setup.build", 1), ("setup.state", 1), ("setup.train_begin", 1),
    ("setup.data", 2),      # dataset and loader in __init__, the prefetcher in train()
    ("jit.compile", 1),     # the train step's one program
])
def test_set_up_spans_of_a_toy_run(toy_run, name, count):
    names = [e[0] for e in toy_run["events"]]
    assert names.count(name) == count
    by = {e[0]: e for e in toy_run["events"]}
    if name in ("setup.state", "setup.data"):
        # inside setup.build (the first setup.data; the second is train()'s)
        first = next(e for e in toy_run["events"] if e[0] == name)
        build = by["setup.build"]
        assert build[1] <= first[1] and first[1] + first[2] <= build[1] + build[2]


def test_one_backpressure_span_a_step(toy_run):
    names = [e[0] for e in toy_run["events"]]
    assert toy_run["steps"] == 8
    assert names.count("step.backpressure") == 8 == names.count("step.dispatch")
    # and it is the loop's: between a step's dispatch and its callbacks
    order = [n for n in names if n in ("step.dispatch", "step.backpressure")]
    assert order == ["step.dispatch", "step.backpressure"] * 8


def test_live_span_events_stay_four_tuples(toy_run):
    assert toy_run["events"]
    for ev in toy_run["events"]:
        name, t0_ns, dur_ns, tid = ev  # benchmark/jobs/train_packed.py unpacks four
        assert isinstance(name, str) and isinstance(t0_ns, int) and dur_ns >= 0
        assert isinstance(tid, int)


def test_launch_gauge_is_process_age_at_the_trainers_build(toy_run):
    from veomni_tpu.trainer.base import _seconds_since_process_start

    assert toy_run["gauge"] is not None
    assert 0 < toy_run["gauge"].value <= _seconds_since_process_start()


def test_goodput_of_a_toy_run_has_its_device_wait(toy_run):
    m = toy_run["metrics"]
    fracs = [m[k] for k in ("data_wait_frac", "host_frac", "dispatch_frac", "checkpoint_frac",
                            "device_wait_frac", "other_frac")]
    assert sum(fracs) == pytest.approx(1.0, abs=1e-3)
    assert m["goodput_pct"] == pytest.approx(
        100.0 * (1.0 - m["data_wait_frac"] - m["host_frac"] - m["checkpoint_frac"]), abs=1e-3)


def test_scope_map_of_the_toy_train_step(toy_run):
    """The census hands out {instruction: op_name} after the trainer is
    gone, and the op_names carry the taxonomy."""
    from veomni_tpu.observability.scopes import TRAIN_SCOPES

    scope_map = toy_run["scope_map"]
    assert scope_map and all(isinstance(v, str) for v in scope_map.values())
    joined = "\n".join(scope_map.values())
    dense = [s for s in TRAIN_SCOPES if not s.startswith("moe.")]
    assert [s for s in dense if s not in joined] == []
    assert "rematted_computation" in joined  # the toy config recomputes too


def test_scope_map_is_parsed_only_on_request(monkeypatch):
    import jax
    import jax.numpy as jnp

    from veomni_tpu.observability import cost

    parses = []
    real = cost.parse_scope_map
    monkeypatch.setattr(cost, "parse_scope_map", lambda text: parses.append(1) or real(text))
    census = cost.CostCensus(registry=MetricsRegistry())

    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x) @ x

    step = cost.InstrumentedJit("unit_site", jax.jit(f), census=census)
    step(jnp.ones((8, 8)))
    step(jnp.ones((8, 8)))
    assert parses == [] and census.scope_map("other_site") is None
    first = census.scope_map("unit_site")
    assert parses == [1] and any("mlp" in v for v in first.values())
    assert census.scope_map("unit_site") is first and parses == [1]  # kept
    step(jnp.ones((4, 4)))  # a newer program of the site: parsed anew, on request
    assert parses == [1]
    assert census.scope_map("unit_site") is not first and parses == [1, 1]
    census.reset()
    assert census.scope_map("unit_site") is None


def test_parse_scope_map_reads_names_and_op_names():
    from veomni_tpu.observability.cost import parse_scope_map

    text = """
HloModule jit_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/mlp/mul" source_file="a.py" source_line=3}
}
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/mlp/mul"}
  %flash_fwd.15 = (bf16[4,16]{1,0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn.flash/flash_fwd/pallas_call"}
  copy.2 = f32[8]{0} copy(%fusion.7)
  ROOT %tuple.1 = (f32[8]{0}) tuple(%copy.2)
}
"""
    assert parse_scope_map(text) == {
        "multiply.3": "jit(step)/mlp/mul",
        "fusion.7": "jit(step)/transpose(jvp())/checkpoint/rematted_computation/mlp/mul",
        "flash_fwd.15": "jit(step)/attn.flash/flash_fwd/pallas_call",
    }


def test_spans_off_makes_no_span_object_and_no_scope_map(spans_off, monkeypatch):
    """train.observability_spans=false: every span() of the loop is the one
    shared null object, and nobody parses a scope map."""
    from veomni_tpu.observability import cost
    from veomni_tpu.resilience.supervisor import SupervisorPolicy, TrainSupervisor

    made = []
    monkeypatch.setattr(spans_mod, "_Span", lambda name: made.append(name))
    monkeypatch.setattr(cost, "parse_scope_map", lambda text: made.append("parse") or {})
    for name in ("step.backpressure", "setup.build", "setup.state", "setup.data",
                 "setup.train_begin", "jit.compile", "data.wait"):
        assert span(name) is spans_mod._NULL
    sup = TrainSupervisor(SupervisorPolicy(inflight_depth=1))
    before = len(spans_mod.live_span_events())
    for step in range(1, 4):
        assert sup.observe(step, {"loss": np.float32(1.0), "step_ok": np.bool_(True)}) == "ok"
    assert made == [] and len(spans_mod.live_span_events()) == before


def test_scope_names_in_the_code_are_the_taxonomy():
    """Every jax.named_scope literal and every pallas_call name in the
    program is in observability/scopes.py, and every name there is used."""
    from veomni_tpu.observability.scopes import ALL_KERNEL_NAMES, MODULE_SCOPES, SCOPES

    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "veomni_tpu")
    scope_lits, kernel_lits = set(), set()
    for dp, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                src = open(os.path.join(dp, f)).read()
                scope_lits.update(re.findall(r'named_scope\(\s*"([^"]+)"', src))
                scope_lits.update(x for pair in re.findall(
                    r'named_scope\(\s*"([^"]+)" if \w+ else "([^"]+)"', src) for x in pair)
                if "pallas_call(" in src:
                    kernel_lits.update(re.findall(r'\bname="([a-z_]+)"', src))
    assert scope_lits == set(SCOPES) | set(MODULE_SCOPES)
    assert not set(SCOPES) & set(MODULE_SCOPES)
    assert kernel_lits == set(ALL_KERNEL_NAMES)


def _observability_doc():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "observability.md")) as f:
        return f.read()


def test_every_kernel_name_is_in_the_metric_reference():
    from veomni_tpu.observability.scopes import ALL_KERNEL_NAMES, SCOPED_KERNEL_NAMES

    doc = _observability_doc()
    assert [k for k in ALL_KERNEL_NAMES if f"`{k}`" not in doc] == []
    # the kernels a reader files by the scope in their op_name, and that scope's row
    assert set(SCOPED_KERNEL_NAMES) == {"qk_norm_rope_fwd", "qk_norm_rope_bwd",
                                        "mla_qkv_rope_fwd", "mla_qkv_rope_bwd"}
    row = next(line for line in doc.splitlines() if line.startswith("| `attn.qkv` |"))
    assert "`qk_norm_rope_*`" in row and "`mla_qkv_rope_*`" in row


@pytest.mark.parametrize("name", [
    "attn.mla_qkv_rope.calls_kernel", "attn.mla_qkv_rope.calls_handed_over",
    "attn.flash.bwd.calls_fused", "attn.flash.bwd.calls_split",
    "op mla_qkv_rotary: pallas", "op qk_norm_rotary:", "op attention:",
])
def test_kernel_engagement_counters_and_hand_over_lines_are_in_the_metric_reference(name):
    """And the program emits them under these names."""
    import glob

    assert name in " ".join(_observability_doc().split())
    kernels = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "veomni_tpu", "ops", "pallas", "*.py")
    assert any(f'"{name}' in open(path).read() for path in glob.glob(kernels))


# ------------------------------------------------- flash attention's tiles
@pytest.mark.parametrize("impl,counted", [("pallas_flash", True), ("auto", False)])
def test_flash_tile_counters_follow_the_resolved_attention(tmp_path, impl, counted):
    """attn.flash.tile_pairs[_live] and their ratio are counted on the host,
    once a step, from the host batch's segment ids, and only when attention
    resolves to the kernel whose tiles they count."""
    from veomni_tpu.observability.metrics import MetricsRegistry, set_registry
    from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
    from veomni_tpu.ops.pallas.flash_attention import tile_census
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.trainer import TextTrainer
    from veomni_tpu.trainer.callbacks import Callback

    from tests.test_e2e_training import TOY, _make_args, _write_dummy_data

    destroy_parallel_state()
    _write_dummy_data(tmp_path / "data.jsonl")
    args = _make_args(tmp_path, train_steps=3)
    args.data.max_seq_len = 256
    args.model.attn_implementation = impl
    seen = []

    class Seen(Callback):
        def on_step_begin(self, trainer, state):
            seen.append(tile_census(trainer.current_batch["segment_ids"], TOY["head_dim"],
                                    trainer.model.config.dtype))

    old = set_registry(MetricsRegistry())
    try:
        trainer = TextTrainer(args)
        trainer.callbacks.append(Seen())
        trainer.train()
        trainer.checkpointer.close()
        reg = get_registry()
        got = {n: reg.get(n) for n in ("attn.flash.tile_pairs", "attn.flash.tile_pairs_live",
                                       "attn.flash.tiles_live_share")}
    finally:
        set_registry(old)
        KERNEL_REGISTRY.clear_pins()
        destroy_parallel_state()
    assert len(seen) == 3
    if not counted:
        assert all(v is None for v in got.values())
        return
    pairs, live = (sum(x) for x in zip(*seen))
    assert got["attn.flash.tile_pairs"].value == pairs > 0
    assert got["attn.flash.tile_pairs_live"].value == live > 0
    assert got["attn.flash.tiles_live_share"].value == pytest.approx(live / pairs)
