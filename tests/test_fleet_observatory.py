"""Fleet & comm observatory (ISSUE 11 acceptance).

The fourth observability tier must be CPU-exercisable end to end: the live
collective census on a sharded train step agrees with the offline
``overlap_evidence`` analysis of the same compiled HLO (nonzero all-reduce
bytes on a 4-device mesh), the goodput window fracs still sum to 1.0 while
``comm_est_frac`` is reported, a ``delay``-fault straggler drill fires the
rank-0 warning + ``fleet.straggler`` flight event, heartbeat staleness is
detectable from outside the process, ``/debug/fleet`` is well-formed, and
``scripts/fleet.py`` merges rank artifacts onto one monotonic timeline.
Satellites ride along: the chunked-prefill recompile warning, native
Prometheus buckets for the serving latency SLOs, and deterministic tier-1
shard partitioning.
"""

import json
import logging
import os
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.observability.comm import (
    analyze_hlo_comm,
    get_comm_census,
)
from veomni_tpu.observability.cost import get_cost_census
from veomni_tpu.observability.fleet import (
    FleetMonitor,
    compute_skew,
    heartbeat_ages,
    read_heartbeats,
    write_heartbeat,
)
from veomni_tpu.observability.metrics import MetricsRegistry, get_registry
from veomni_tpu.utils.overlap_evidence import collective_bytes_census

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY = dict(
    model_type="qwen3", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True,
)


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


# ---------------------------------------------------------- HLO byte census
def test_collective_bytes_census_parses_shapes_and_kinds():
    hlo = "\n".join([
        "ENTRY %main (p0: f32[128]) -> f32[128] {",
        "  %p0 = f32[128]{0} parameter(0)",
        "  %all-reduce.1 = f32[128]{0} all-reduce(%p0), replica_groups={}",
        "  %ag = f32[4,128]{1,0} all-gather(%all-reduce.1), dimensions={0}",
        "  %a2a = (bf16[64]{0}, bf16[64]{0}) all-to-all(%p0, %p0)",
        # async pairs count ONCE, at the -done, whose result is the pure
        # output payload (the -start tuple mixes input aliases + context
        # words whose layout differs per kind)
        "  %cp-start = u32[16]{0} collective-permute-start(%p0)",
        "  %cp-done = u32[16]{0} collective-permute-done(%cp-start)",
        "  %rs-start = (f32[128]{0}, f32[32]{0}, u32[2]{0}) "
        "reduce-scatter-start(%p0)",
        "  %rs-done = f32[32]{0} reduce-scatter-done(%rs-start)",
        "  ROOT %r = f32[128]{0} add(%all-reduce.1, %all-reduce.1)",
        "}",
    ])
    c = collective_bytes_census(hlo)
    assert c["all-reduce"] == {"count": 1, "bytes": 128 * 4}
    assert c["all-gather"] == {"count": 1, "bytes": 4 * 128 * 4}
    # sync tuple = genuine variadic payload: leaves sum
    assert c["all-to-all"] == {"count": 1, "bytes": 2 * 64 * 2}
    assert c["collective-permute"] == {"count": 1, "bytes": 16 * 4}
    # reduce-scatter's OUTPUT (f32[32], from the -done) — not the f32[128]
    # input the -start tuple happens to carry as its largest leaf
    assert c["reduce-scatter"] == {"count": 1, "bytes": 32 * 4}
    # the dependency census rides the same text
    fields = analyze_hlo_comm(hlo)
    assert fields["comm_bytes"] == sum(v["bytes"] for v in c.values())
    # 5 collectives: the -start halves count, the -done halves never do
    assert fields["collectives"] == 5
    assert fields["overlappable"] + fields["serialized"] == 5


def test_collective_bytes_census_concatenated_modules():
    """compiled.as_text() returns a LIST of module texts on some jax
    versions and the joiners concatenate them; each module has its own
    ENTRY and identically-named computations, so the census must count
    every module, not let the last shadow the rest."""
    one = "\n".join([
        "HloModule jit_f, entry_computation_layout={...}",
        "ENTRY %main (p0: f32[64]) -> f32[64] {",
        "  %p0 = f32[64]{0} parameter(0)",
        "  ROOT %ar = f32[64]{0} all-reduce(%p0)",
        "}",
    ])
    c = collective_bytes_census(one + "\n" + one)
    assert c["all-reduce"] == {"count": 2, "bytes": 2 * 64 * 4}
    # the computation iterator sees both modules' blocks too
    from veomni_tpu.utils.overlap_evidence import hlo_computations

    assert len(list(hlo_computations(one + "\n" + one))) == 2


def test_collective_bytes_census_variadic_async_and_trip_counts():
    """The TPU-critical shapes: XLA's all-reduce combiner emits variadic
    async pairs whose ``-done`` result is the ``(out...)`` tuple — counted
    once, at the output payload; a scan-lowered while body's collectives
    multiply by the loop's known_trip_count; conditional branches count
    only the heaviest (exactly one executes per visit)."""
    hlo = "\n".join([
        "%body (p: (s32[], f32[256])) -> (s32[], f32[256]) {",
        "  %p = (s32[], f32[256]{0}) parameter(0)",
        "  %gte = f32[256]{0} get-tuple-element(%p), index=1",
        # fused variadic async all-reduce: ((in,in),(out,out))
        "  %ar-start = ((f32[256]{0}, f32[256]{0}), (f32[256]{0}, "
        "f32[256]{0})) all-reduce-start(%gte, %gte)",
        "  %ar-done = (f32[256]{0}, f32[256]{0}) all-reduce-done(%ar-start)",
        "  ROOT %t = (s32[], f32[256]{0}) tuple(%gte, %gte)",
        "}",
        "%cond (p: (s32[], f32[256])) -> pred[] {",
        "  %p2 = (s32[], f32[256]{0}) parameter(0)",
        "  ROOT %lt = pred[] compare(%p2, %p2), direction=LT",
        "}",
        "%branch_a (q: f32[64]) -> f32[64] {",
        "  %q = f32[64]{0} parameter(0)",
        "  ROOT %ara = f32[64]{0} all-reduce(%q)",
        "}",
        "%branch_b (q2: f32[64]) -> f32[64] {",
        "  %q2 = f32[64]{0} parameter(0)",
        "  ROOT %arb = f32[64]{0} all-reduce(%q2)",
        "}",
        "ENTRY %main (x: f32[256]) -> f32[256] {",
        "  %x = f32[256]{0} parameter(0)",
        "  %t0 = (s32[], f32[256]{0}) tuple(%x, %x)",
        "  %w = (s32[], f32[256]{0}) while(%t0), condition=%cond, "
        'body=%body, backend_config={"known_trip_count":{"n":"7"}}',
        "  %y = f32[64]{0} slice(%x), slice={[0:64]}",
        "  %c = f32[64]{0} conditional(%y, %y, %y), "
        "branch_computations={%branch_a, %branch_b}",
        "  ROOT %r = f32[256]{0} get-tuple-element(%w), index=1",
        "}",
    ])
    c = collective_bytes_census(hlo)
    # body: one variadic start = 2 outputs x 256 x 4B = 2048B, x 7 trips;
    # conditional: ONE 64x4B branch (not two)
    assert c["all-reduce"]["count"] == 7 * 1 + 1
    assert c["all-reduce"]["bytes"] == pytest.approx(7 * 2048 + 256)


# --------------------------------------------- live census vs offline parity
def _build_sharded_step():
    """A genuinely data-parallel (ddp: grads all-reduce) train step on the
    4-device CPU mesh, mirroring the trainer's wiring."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import resolve_state_shardings

    ps = init_parallel_state(dp_replicate_size=4, dp_shard_size=1)
    cfg = TransformerConfig(dtype=jnp.float32, **TOY)
    with use_parallel_state(ps):
        model = build_foundation_model(config=cfg)
        plan = model.get_parallel_plan()
        opt = build_optimizer(
            model.abstract(), optimizer="adamw",
            lr=build_lr_scheduler(lr=1e-3, train_steps=10),
        )

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, plan, ps)
        state = jax.jit(make_state, out_shardings=shardings)(
            jax.random.PRNGKey(0)
        )
        keys = ("input_ids", "labels", "position_ids", "segment_ids")
        bsh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes))
               for k in keys}
        step = build_train_step(
            model.loss_fn, opt, ps,
            state_shardings=shardings, batch_shardings=bsh,
        )
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 4, 32))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(32), ids.shape).copy(), jnp.int32
            ),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        batch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    return ps, step, state, batch


def test_train_step_comm_census_matches_offline_and_window_fracs():
    """Acceptance: on a 4-device CPU mesh the live ``comm.train_step.*``
    gauges show nonzero all-reduce bytes agreeing with the offline
    ``overlap_evidence`` census on the same compiled HLO, and the goodput
    window fracs still sum to 1.0 with ``comm_est_frac`` reported."""
    from veomni_tpu.observability.cost import CostWindow
    from veomni_tpu.observability.goodput import GoodputTracker
    from veomni_tpu.parallel import use_parallel_state
    from veomni_tpu.utils.overlap_evidence import compiled_hlo_text

    ps, step, state, batch = _build_sharded_step()
    tracker = GoodputTracker()
    window = CostWindow(sites=("train_step",))
    tracker.begin_window()
    window.begin()
    with use_parallel_state(ps):
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    bucket = "1x4x32"
    rec = get_comm_census().get("train_step", bucket)
    assert rec is not None, "train_step bucket missing from the comm census"
    assert rec.bytes_by_kind["all-reduce"] > 0, (
        "a ddp train step must all-reduce gradients"
    )
    assert rec.comm_bytes > 0 and rec.comm_time_est_s > 0
    assert rec.collectives == rec.overlappable + rec.serialized

    # offline parity: the SAME program via the PR 1 offline path (the
    # instrumented wrapper passes .lower through to the wrapped jit)
    with use_parallel_state(ps):
        offline = collective_bytes_census(compiled_hlo_text(step, state, batch))
    for kind, agg in offline.items():
        assert rec.bytes_by_kind[kind] == pytest.approx(agg["bytes"]), kind
        assert rec.counts_by_kind[kind] == agg["count"], kind

    # live gauges landed (global registry — the same one /metrics renders)
    reg = get_registry()
    prefix = f"comm.train_step.{bucket}"
    assert reg.gauge(f"{prefix}.bytes_all_reduce").value == \
        rec.bytes_by_kind["all-reduce"]
    assert reg.gauge(f"{prefix}.comm_bytes").value == rec.comm_bytes
    assert reg.gauge(f"{prefix}.serialized").value == rec.serialized

    # the cost census carries the comm_bytes too (roofline 'comm' input)
    cost_rec = get_cost_census().get("train_step", bucket)
    assert cost_rec is not None and cost_rec.comm_bytes == rec.comm_bytes
    assert cost_rec.bound() in ("compute", "bandwidth", "comm")

    # window accounting: goodput fracs sum to 1.0, comm_est_frac alongside
    gp = tracker.end_window()
    fracs = [v for k, v in gp.items() if k.endswith("_frac")]
    assert sum(fracs) == pytest.approx(1.0, abs=1e-6)
    cw = window.end()
    assert "comm_est_frac" in cw
    assert 0.0 <= cw["comm_est_frac"] <= 1.0


def test_comm_census_disabled_by_env(monkeypatch):
    """VEOMNI_COMM_CENSUS=0: the compile stays comm-census-free (no record,
    no comm_bytes folded into the cost census) and nothing raises."""
    from veomni_tpu.observability.comm import CommCensus, maybe_comm_census

    monkeypatch.setenv("VEOMNI_COMM_CENSUS", "0")
    f = jax.jit(lambda x: x + 1)
    compiled = f.lower(jnp.ones((4,))).compile()
    assert maybe_comm_census("off_site", "b", compiled, 1) == {}
    assert CommCensus().get("off_site", "b") is None


def test_roofline_comm_verdict():
    """A program whose estimated collective time dominates both device-local
    times is 'comm'-bound; without comm bytes the verdict is unchanged."""
    from veomni_tpu.observability.cost import ProgramCost
    from veomni_tpu.utils.device import (
        get_device_peak_bandwidth,
        get_device_peak_flops,
        get_device_peak_interconnect_bandwidth,
    )

    pc = ProgramCost(site="s", bucket="b", flops=1e6, bytes_accessed=1e3)
    assert pc.bound() in ("compute", "bandwidth")
    base = pc.bound()
    # comm bytes sized to dwarf compute AND memory time on any peak table
    t_dev = max(pc.flops / get_device_peak_flops(),
                pc.bytes_accessed / get_device_peak_bandwidth())
    pc.comm_bytes = 10.0 * t_dev * get_device_peak_interconnect_bandwidth()
    assert pc.bound() == "comm"
    pc.comm_bytes = 0.0
    assert pc.bound() == base


# ------------------------------------------------------------- skew + drills
def test_skew_math_units():
    table = np.array([
        [0.0, 0.010, 0.012, 7.0],
        [1.0, 0.011, 0.013, 7.0],
        [2.0, 0.050, 0.061, 7.0],   # the straggler
        [3.0, 0.009, 0.010, 7.0],
    ])
    skew = compute_skew(table)
    assert skew["slowest_rank"] == 2
    assert skew["step_time_max_s"] == pytest.approx(0.050)
    # the baseline median EXCLUDES the slowest rank (it must not inflate
    # its own detection threshold)
    assert skew["step_time_median_s"] == pytest.approx(0.010)
    assert skew["step_time_skew_s"] == pytest.approx(0.050 - 0.010)


def test_skew_two_rank_fleet_can_fire():
    """With the straggler included in the median, max > 2*median is
    unsatisfiable on a 2-rank fleet (median=(a+b)/2 ⇒ b > a+b): a 100x
    straggler on a two-host fleet would never be named. Excluding the
    suspect, the baseline is the healthy rank."""
    table = np.array([
        [0.0, 0.010, 0.010, 3.0],
        [1.0, 1.000, 1.000, 3.0],   # 100x slower
    ])
    skew = compute_skew(table)
    assert skew["slowest_rank"] == 1
    assert skew["step_time_median_s"] == pytest.approx(0.010)
    assert skew["step_time_max_s"] > 2.0 * skew["step_time_median_s"]


def test_fleet_monitor_off_below_two_ranks(tmp_path):
    reg = MetricsRegistry()
    mon = FleetMonitor(registry=reg, world_size=1, rank=0,
                       heartbeat_dir=str(tmp_path))
    assert not mon.exchange_enabled
    assert mon.observe_window(5, 0.01) is None
    # the heartbeat still flows: a single-rank wedge is diagnosable too
    assert read_heartbeats(str(tmp_path))[0]["global_step"] == 5


def test_delay_fault_straggler_drill(tmp_path):
    """Acceptance: a ``delay``-mode fault (same hit/times windowing as every
    other mode) slows this rank's loop deterministically; the skew exchange
    then produces the rank-0 STRAGGLER warning and the ``fleet.straggler``
    flight event naming the slow rank."""
    from veomni_tpu.observability.flight_recorder import (
        configure_flight_recorder,
        get_flight_recorder,
    )
    from veomni_tpu.resilience.faults import (
        configure_faults,
        disarm_faults,
        fault_point,
        fired_faults,
    )

    configure_flight_recorder(max_events=256, fresh=True)
    reg = MetricsRegistry()
    BASELINE = 0.001

    def fake_fleet(local):
        # three healthy ranks at the baseline; our (delayed) row passes
        # through — exactly what the all-gather returns on a real fleet
        rows = [np.array([r, BASELINE, BASELINE, local[3]])
                for r in range(4)]
        rows[int(local[0])] = local
        return np.stack(rows)

    mon = FleetMonitor(registry=reg, world_size=4, rank=3,
                       straggler_factor=2.0, heartbeat_dir=str(tmp_path),
                       exchange_fn=fake_fleet)
    # delay steps 2..4 by 30ms each — the deterministic straggler
    configure_faults([{"point": "step.delay", "mode": "delay", "ms": 30,
                       "hit": 2, "times": 3}])
    cap = _Capture()
    root = logging.getLogger("veomni_tpu")
    root.addHandler(cap)
    try:
        t0 = time.perf_counter()
        steps = 4
        for _ in range(steps):
            fault_point("step.delay")  # the trainer loop's drill site
        mean = (time.perf_counter() - t0) / steps
        skew = mon.observe_window(4, mean, steps=steps)
        fired = [a for a in fired_faults() if a.point == "step.delay"]
    finally:
        root.removeHandler(cap)
        disarm_faults()
    assert [a.hit for a in fired] == [2, 3, 4]  # hit/times window honored
    assert mean >= 3 * 0.030 / steps  # the delay actually slowed the loop
    assert skew is not None and skew["slowest_rank"] == 3
    assert reg.counter("fleet.stragglers").value == 1
    assert any("STRAGGLER" in r.getMessage() and "rank 3" in r.getMessage()
               for r in cap.records)
    evs = [e for e in get_flight_recorder().events()
           if e[1] == "fleet.straggler"]
    assert len(evs) == 1 and evs[0][2] == "3"  # cid names the slow rank


def test_fleet_exchange_failure_retries_then_disables(tmp_path):
    """A failed exchange never raises, and is RETRIED before the disable:
    a rank that stopped calling on the first transient would wedge its
    peers' next gather. Only a persistent failure earns the disable."""
    reg = MetricsRegistry()
    calls = [0]

    def broken(local):
        calls[0] += 1
        raise RuntimeError("collective transport down")

    mon = FleetMonitor(registry=reg, world_size=4, rank=0,
                       heartbeat_dir=str(tmp_path), exchange_fn=broken)
    budget = FleetMonitor.MAX_CONSECUTIVE_EXCHANGE_FAILURES
    for i in range(budget):
        assert mon.observe_window(i + 1, 0.01) is None
        # still retrying until the consecutive budget is spent
        assert mon.exchange_enabled == (i + 1 < budget)
    assert calls[0] == budget
    assert mon.observe_window(budget + 1, 0.01) is None
    assert calls[0] == budget  # disabled: no further transport attempts
    # heartbeats keep flowing fleet-blind
    assert read_heartbeats(str(tmp_path))[0]["global_step"] == budget + 1


def test_fleet_exchange_transient_failure_self_heals(tmp_path):
    reg = MetricsRegistry()
    fail_next = [True]

    def flaky(local):
        if fail_next[0]:
            fail_next[0] = False
            raise RuntimeError("one dropped round")
        rows = [np.array([r, 0.01, 0.01, local[3]]) for r in range(4)]
        rows[0] = local
        return np.stack(rows)

    mon = FleetMonitor(registry=reg, world_size=4, rank=0,
                       heartbeat_dir=str(tmp_path), exchange_fn=flaky)
    assert mon.observe_window(1, 0.01) is None
    assert mon.exchange_enabled
    skew = mon.observe_window(2, 0.01)
    assert skew is not None  # recovered; consecutive counter reset
    assert mon._exchange_failures == 0


# ------------------------------------------------------ heartbeat staleness
def test_heartbeat_staleness_detection(tmp_path):
    d = str(tmp_path)
    write_heartbeat(d, rank=0, global_step=40, phase="train")
    write_heartbeat(d, rank=1, global_step=12, phase="train")
    # age rank 1's beat by rewriting its wall stamp (a wedged rank stops
    # rewriting; from outside, that IS the signal)
    p = os.path.join(d, "heartbeat-1.json")
    doc = json.load(open(p))
    doc["wall_time_s"] -= 600.0
    json.dump(doc, open(p, "w"))
    rows = heartbeat_ages(d, stale_after_s=120.0)
    by_rank = {r["rank"]: r for r in rows}
    assert not by_rank[0]["stale"] and by_rank[0]["age_s"] < 60
    assert by_rank[1]["stale"] and by_rank[1]["age_s"] >= 600
    assert by_rank[1]["global_step"] == 12  # last progress step survives
    # torn/garbage heartbeat files are skipped, not fatal
    open(os.path.join(d, "heartbeat-2.json"), "w").write("{not json")
    assert {r["rank"] for r in heartbeat_ages(d)} == {0, 1}


# ------------------------------------------------------------- /debug/fleet
def test_debug_fleet_endpoint(tmp_path):
    from veomni_tpu.observability.exporter import MetricsExporter

    reg = get_registry()
    mon = FleetMonitor(registry=reg, world_size=4, rank=0,
                       straggler_factor=2.0, heartbeat_dir=str(tmp_path),
                       exchange_fn=lambda local: np.stack([
                           np.array([0.0, 0.001, 0.001, 9.0]),
                           np.array([1.0, 0.030, 0.030, 9.0]),
                           np.array([2.0, 0.001, 0.001, 9.0]),
                           np.array([3.0, 0.001, 0.001, 9.0]),
                       ]))
    mon.observe_window(9, 0.001)
    exp = MetricsExporter(port=0, registry=reg, fleet_fn=mon.debug_doc)
    port = exp.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/fleet", timeout=10
        ).read()
        doc = json.loads(body)
    finally:
        exp.stop()
    assert doc["enabled"] and doc["world_size"] == 4
    assert doc["last_window"]["slowest_rank"] == 1  # names the slow rank
    assert doc["last_window"]["straggling"] is True
    assert {row["rank"] for row in doc["last_window"]["table"]} == {0, 1, 2, 3}
    assert doc["heartbeats"] and doc["heartbeats"][0]["rank"] == 0
    assert "comm_census" in doc and "programs" in doc["comm_census"]


# --------------------------------------------------------- fleet CLI merge
def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_fleet_test_{name}", os.path.join(_REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_merge_monotonic(tmp_path):
    d = str(tmp_path)
    now = time.time()
    # two ranks' metrics JSONL (rank 1 stops progressing at step 10)
    with open(os.path.join(d, "metrics_rank0.jsonl"), "w") as f:
        for i, step in enumerate((10, 20)):
            f.write(json.dumps({
                "ts": now - 30 + 10 * i, "step": step, "rank": 0,
                "loss": 1.0, "fleet.slowest_rank": 1,
            }) + "\n")
    with open(os.path.join(d, "metrics_rank1.jsonl"), "w") as f:
        f.write(json.dumps({"ts": now - 30, "step": 10, "rank": 1,
                            "loss": 1.0}) + "\n")
    # heartbeats: rank 1 wedged 300s ago at step 10
    write_heartbeat(d, rank=0, global_step=20)
    write_heartbeat(d, rank=1, global_step=10)
    p = os.path.join(d, "heartbeat-1.json")
    hb = json.load(open(p))
    hb["wall_time_s"] = now - 300
    json.dump(hb, open(p, "w"))
    # one post-mortem with the PR 6 anchor pair
    perf = time.perf_counter_ns()
    json.dump({
        "rank": 1, "reason": "watchdog:train loop",
        "anchor": {"wall_time_s": now - 290, "perf_ns": perf},
        "events": [
            {"ts_ns": perf - 5_000_000_000, "kind": "step.dispatch",
             "cid": "10"},
            {"ts_ns": perf - 1_000_000_000, "kind": "watchdog.stall"},
        ],
    }, open(os.path.join(d, "postmortem-1.json"), "w"))

    doc = _load_script("fleet").merge_fleet(d, now=now)
    walls = [e["wall_s"] for e in doc["events"]]
    assert walls == sorted(walls)  # ONE monotonic cluster timeline
    kinds = {e["kind"] for e in doc["events"]}
    assert {"metrics", "heartbeat", "step.dispatch", "watchdog.stall"} <= kinds
    by_rank = {r["rank"]: r for r in doc["ranks"]}
    assert by_rank[1]["postmortem_reason"] == "watchdog:train loop"
    assert by_rank[1]["heartbeat_age_s"] == pytest.approx(300, abs=5)
    v = doc["verdict"]
    assert v["stalest_rank"] == 1 and v["lagging_rank"] == 1
    assert v["telemetry_slowest_rank"] == 1
    # and the human renderer doesn't crash
    text = _load_script("fleet").format_fleet(doc, tail=5)
    assert "VERDICT" in text and "rank 1" in text


# ----------------------------------------------------------- satellites
def test_recompile_detector_covers_paged_prefill():
    """Satellite: a chunked-prefill compile storm (new paged_prefill chunk/
    table buckets after the warmup grace) fires the loud RECOMPILE warning,
    not just decode-bucket storms."""
    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.models import decode as decode_mod
    from veomni_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        Request,
        SamplingParams,
    )

    cfg = TransformerConfig(dtype=jnp.float32, **TOY)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, block_size=16, max_model_len=256,
        prefill_chunk=16, recompile_warmup_ticks=1))
    # warmup: compiles the short prompt's paged-prefill buckets, arms at
    # tick 1
    eng.run([Request(prompt_ids=list(range(1, 9)),
                     sampling=SamplingParams(max_new_tokens=2))])
    base = get_registry().counter("recompiles").value
    prefill_traces0 = decode_mod.TRACE_COUNTS["paged_prefill"]

    cap = _Capture()
    root = logging.getLogger("veomni_tpu")
    root.addHandler(cap)
    try:
        # a much longer prompt forces NEW paged-prefill buckets mid-run
        eng.run([Request(prompt_ids=list(range(1, 100)),
                         sampling=SamplingParams(max_new_tokens=2))])
    finally:
        root.removeHandler(cap)
    assert decode_mod.TRACE_COUNTS["paged_prefill"] > prefill_traces0
    assert get_registry().counter("recompiles").value > base
    assert any("RECOMPILE" in r.getMessage() for r in cap.records)


def test_native_prometheus_buckets_for_serve_latency():
    """Satellite: serve.ttft_s/serve.tpot_s additionally render as native
    cumulative-bucket histograms so PromQL histogram_quantile (p99 SLO
    queries) works — not just the fixed p50/p95 summary quantiles."""
    from veomni_tpu.observability.exporter import render_prometheus

    reg = MetricsRegistry()
    h = reg.histogram("serve.ttft_s")
    for v in (0.002, 0.02, 0.02, 0.2, 2.0):
        h.observe(v)
    reg.histogram("span.other")  # non-SLO family: summary only
    text = render_prometheus(reg)
    assert "# TYPE veomni_serve_ttft_s summary" in text
    assert "# TYPE veomni_serve_ttft_s_hist histogram" in text
    # cumulative counts at the documented bounds
    lines = dict(
        line.rsplit(" ", 1)
        for line in text.splitlines() if "_hist_bucket" in line
    )
    assert lines['veomni_serve_ttft_s_hist_bucket{rank="0",le="0.005"}'] == "1"
    assert lines['veomni_serve_ttft_s_hist_bucket{rank="0",le="0.025"}'] == "3"
    assert lines['veomni_serve_ttft_s_hist_bucket{rank="0",le="0.25"}'] == "4"
    assert lines['veomni_serve_ttft_s_hist_bucket{rank="0",le="+Inf"}'] == "5"
    # cumulative counts are monotone non-decreasing in bound order
    counts = [int(lines[k]) for k in sorted(
        lines, key=lambda k: float(k.split('le="')[1].rstrip('"}'))
        if "+Inf" not in k else float("inf"))]
    assert counts == sorted(counts)
    assert 'veomni_serve_ttft_s_hist_count{rank="0"} 5' in text
    assert "veomni_span_other_hist" not in text


def test_cumulative_buckets_scale_past_reservoir():
    reg = MetricsRegistry()
    h = reg.histogram("serve.tpot_s", max_samples=64)
    for _ in range(1000):
        h.observe(0.01)
    for _ in range(1000):
        h.observe(1.0)
    # ad-hoc bounds (not the attached SLO set): reservoir-scaled estimate
    buckets = dict(h.cumulative_buckets((0.1, 10.0)))
    assert buckets["+Inf"] == 2000
    assert buckets[0.1] == pytest.approx(1000, rel=0.35)
    assert buckets[10.0] == 2000


def test_native_buckets_exact_and_monotone_past_reservoir():
    """The SLO families' bucket counts are EXACT counters maintained at
    observe() time — monotone non-decreasing across scrapes at any
    observation count, as PromQL rate() over _bucket series requires (a
    reservoir estimate can DECREASE between scrapes once samples churn,
    which rate() reads as a counter reset)."""
    from veomni_tpu.observability.exporter import NATIVE_HISTOGRAM_FAMILIES

    reg = MetricsRegistry()
    h = reg.histogram("serve.ttft_s", max_samples=64)  # tiny reservoir
    bounds = NATIVE_HISTOGRAM_FAMILIES["serve.ttft_s"]
    prev = None
    for round_ in range(4):
        for _ in range(500):
            h.observe(0.02)
        for _ in range(500):
            h.observe(2.0)
        cur = dict(h.cumulative_buckets(bounds))
        n = 1000 * (round_ + 1)
        assert cur["+Inf"] == n
        assert cur[0.025] == n // 2  # exact despite the 64-sample reservoir
        assert cur[2.5] == n
        if prev is not None:  # scrape-to-scrape monotone, every bound
            for le, count in cur.items():
                assert count >= prev[le], le
        prev = cur


def test_delay_mode_plan_grammar():
    """The delay mode parses from the JSON plan grammar with its ms knob
    and rejects nothing a drill needs."""
    from veomni_tpu.resilience.faults import (
        configure_faults,
        disarm_faults,
        fault_point,
        fired_faults,
    )

    configure_faults(json.dumps(
        [{"point": "step.delay", "mode": "delay", "ms": 5, "hit": 1}]
    ))
    try:
        t0 = time.perf_counter()
        action = fault_point("step.delay")
        dt = time.perf_counter() - t0
        assert action is not None and action.mode == "delay"
        assert dt >= 0.004
        assert fault_point("step.delay") is None  # times=1 window closed
        assert len(fired_faults()) == 1
    finally:
        disarm_faults()
