"""Device cost & capacity observatory (ISSUE 10 acceptance).

The census must be CPU-exercisable end to end: non-zero XLA FLOPs/bytes for
the train-step and paged-decode jit sites, a window MFU gauge that agrees
with the offline computation, a live buffer census aggregated
by dtype, well-formed ``/debug/memory`` + ``/debug/cost`` documents, a
serving-side recompile warning after the warmup grace, and a subprocess
drill proving a simulated ``RESOURCE_EXHAUSTED`` produces a post-mortem
carrying the buffer census.
"""

import json
import logging
import os
import subprocess
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.observability.cost import (
    CostCensus,
    CostWindow,
    get_cost_census,
    instrument_jit,
)
from veomni_tpu.observability.devmem import (
    buffer_census,
    is_resource_exhausted,
    kv_capacity_stats,
    oom_report,
    publish_memory_gauges,
)
from veomni_tpu.observability.metrics import MetricsRegistry, get_registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY = dict(
    model_type="qwen3", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, qk_norm=True,
)


# ------------------------------------------------------------- jit census
def test_instrument_jit_records_cost_and_calls():
    reg = MetricsRegistry()
    census = CostCensus(registry=reg)
    f = jax.jit(lambda x, n: (x @ x) * n, static_argnums=(1,))
    wf = instrument_jit(
        "unit", f, static_argnums=(1,), census=census,
        bucket_fn=lambda a: f"m{a[0].shape[0]}_n{a[1]}",
    )
    x = jnp.ones((32, 32))
    r1 = np.asarray(wf(x, 3))
    r2 = np.asarray(wf(x, 3))          # cached executable, same program
    r3 = np.asarray(wf(jnp.ones((16, 16)), 2))  # new bucket
    assert np.array_equal(r1, r2)
    assert np.array_equal(r1, np.asarray(f(x, 3)))  # parity with plain jit
    assert r3.shape == (16, 16)

    recs = {p.bucket: p for p in census.programs("unit")}
    assert set(recs) == {"m32_n3", "m16_n2"}
    big = recs["m32_n3"]
    assert big.flops > 0 and big.bytes_accessed > 0
    assert big.argument_bytes > 0 and big.output_bytes > 0
    assert big.compile_time_s > 0
    assert big.calls == 2 and recs["m16_n2"].calls == 1
    assert big.bound() in ("compute", "bandwidth")
    # the registry families landed
    assert reg.gauge("cost.unit.m32_n3.flops").value == big.flops
    assert reg.counter("cost.unit.m32_n3.calls").value == 2
    assert reg.counter("cost.programs").value == 2
    assert reg.histogram("cost.compile_s").count == 2
    # the wrapper stays a jit function for AOT tooling
    assert hasattr(wf, "lower") and wf.lower(x, 3) is not None


def test_scan_trip_count_correction():
    """XLA's HloCostAnalysis counts a scan body once; the census must
    multiply by the static trip count (incl. nested scans) or a layer-
    stacked model under-reports ~L-fold."""
    W = jnp.ones((64, 64))

    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ W, None

            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None

        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    census = CostCensus(registry=MetricsRegistry())
    wf = instrument_jit("scan_unit", jax.jit(nested), census=census)
    wf(jnp.ones((32, 64))).block_until_ready()
    rec = census.latest("scan_unit")
    matmul = 2.0 * 32 * 64 * 64
    # 4 x 3 = 12 matmuls; the raw XLA reading saw ~1
    assert rec.flops == pytest.approx(12 * matmul, rel=0.05)
    assert rec.xla_flops_raw == pytest.approx(matmul, rel=0.05)
    assert rec.bytes_accessed > rec.xla_bytes_raw


def test_instrument_jit_disabled_by_env(monkeypatch):
    monkeypatch.setenv("VEOMNI_COST_CENSUS", "0")
    f = jax.jit(lambda x: x + 1)
    assert instrument_jit("off", f) is f


def test_train_step_census_nonzero_on_cpu():
    """Acceptance: the train-step jit site lands in the census with real
    XLA FLOPs/bytes under JAX_PLATFORMS=cpu (no chip required)."""
    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step

    cfg = TransformerConfig(dtype=jnp.float32, **TOY)
    model = build_foundation_model(config=cfg)
    ps = init_parallel_state()
    with use_parallel_state(ps):
        opt = build_optimizer(
            model.abstract(), optimizer="adamw",
            lr=build_lr_scheduler(lr=1e-3, train_steps=10),
        )
        params = model.family.init_params(jax.random.PRNGKey(0), cfg)
        state = build_train_state(params, opt)
        step = build_train_step(model.loss_fn, opt, ps)

        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 2, 32))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(32), ids.shape).copy(), jnp.int32
            ),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))

    rec = get_cost_census().get("train_step", "1x2x32")
    assert rec is not None, "train_step bucket missing from the census"
    assert rec.flops > 0 and rec.bytes_accessed > 0
    assert rec.compile_time_s > 0 and rec.calls >= 1
    assert rec.argument_bytes > 0  # memory_analysis ran on CPU too


def test_window_mfu_agrees_with_offline_computation():
    """Acceptance: the window MFU gauge agrees with the offline
    computation (census FLOPs x steps / dt / peak) within 5% over the same
    step loop."""
    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.utils.device import get_device_peak_flops

    cfg = TransformerConfig(dtype=jnp.float32, **TOY)
    model = build_foundation_model(config=cfg)
    ps = init_parallel_state()
    with use_parallel_state(ps):
        opt = build_optimizer(
            model.abstract(), optimizer="adamw",
            lr=build_lr_scheduler(lr=1e-3, train_steps=100),
        )
        params = model.family.init_params(jax.random.PRNGKey(0), cfg)
        state = build_train_state(params, opt)
        step = build_train_step(model.loss_fn, opt, ps)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 2, 64))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(64), ids.shape).copy(), jnp.int32
            ),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        state, metrics = step(state, batch)  # warmup: compile + record
        _ = float(metrics["loss"])

        steps = 6
        window = CostWindow(sites=("train_step",))
        window.begin()
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        _ = float(metrics["loss"])  # host fetch: the loop really finished
        dt = time.perf_counter() - t0
        out = window.end()

    rec = get_cost_census().get("train_step", "1x2x64")
    assert rec is not None and rec.flops > 0
    offline_mfu = 100.0 * rec.flops * steps / dt / get_device_peak_flops()
    assert out["mfu_pct"] > 0
    assert out["mfu_pct"] == pytest.approx(offline_mfu, rel=0.05)
    assert out["bandwidth_util_pct"] > 0
    assert out["census_tflops_s"] == pytest.approx(
        rec.flops * steps / dt / 1e12, rel=0.05)


def test_census_latest_tracks_recency_and_programs_stay_distinct():
    """latest() must follow record() recency, not dict insertion order —
    a sweep that revisits an earlier bucket re-records it in place; and
    cost.programs counts DISTINCT programs, not record() calls."""
    reg = MetricsRegistry()
    census = CostCensus(registry=reg)
    census.record("sweep", "a", flops=1.0)
    census.record("sweep", "b", flops=2.0)
    assert census.latest("sweep").bucket == "b"
    census.record("sweep", "a", flops=3.0)  # revisit: in-place re-record
    assert census.latest("sweep").bucket == "a"
    assert census.latest("sweep").flops == 3.0
    assert reg.counter("cost.programs").value == 2  # a, b — not 3 records


def test_window_mfu_from_fake_census():
    """The window math itself, decoupled from XLA: a hand-built census
    record + N invocations must yield exactly calls x flops / wall / peak."""
    from veomni_tpu.utils.device import (
        get_device_peak_bandwidth,
        get_device_peak_flops,
    )

    census = CostCensus(registry=MetricsRegistry())
    census.record("fake", "b0", compile_time_s=0.5, flops=1e9,
                  bytes_accessed=2e9)
    window = CostWindow(census=census)
    window.begin()
    for _ in range(5):
        census.note_call("fake", "b0")
    time.sleep(0.01)
    out = window.end()
    wall = out["census_window_s"]
    assert out["mfu_pct"] == pytest.approx(
        100.0 * 5e9 / wall / get_device_peak_flops(), rel=1e-6)
    assert out["bandwidth_util_pct"] == pytest.approx(
        100.0 * 1e10 / wall / get_device_peak_bandwidth(), rel=1e-6)
    # an idle window makes no utilization statement (the degenerate
    # train-end window must not zero the last real sync window's gauges)
    assert window.end() == {}


def test_paged_decode_census_and_kv_gauges():
    """Acceptance: the serving engine's paged decode bucket lands in the
    census, and the pool capacity gauges answer 'how many users fit'."""
    from veomni_tpu.models import TransformerConfig, build_foundation_model
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
        num_slots=2, block_size=16, max_model_len=128))
    outs = eng.run([Request(prompt_ids=[1, 2, 3, 4],
                            sampling=SamplingParams(max_new_tokens=4))])
    assert len(next(iter(outs.values())).token_ids) == 4

    rec = get_cost_census().latest("paged_decode")
    assert rec is not None
    assert rec.flops > 0 and rec.bytes_accessed > 0
    assert rec.compile_time_s > 0 and rec.calls >= 1

    cap = eng.kv_capacity()
    pool_bytes = eng.k_pool.nbytes + eng.v_pool.nbytes
    assert cap["pool_bytes"] == pool_bytes
    # 17 blocks (1 null + 2 slots x 8), 8 blocks per max-length sequence
    assert cap["max_concurrent_seqs"] == 2.0
    assert cap["free_concurrent_seqs"] == 2.0  # request finished, all free
    reg = get_registry()
    assert reg.gauge("serve.kv_pool_bytes").value == pool_bytes
    assert reg.gauge("serve.kv_max_concurrent_seqs").value == 2.0


def test_kv_capacity_stats_units():
    from veomni_tpu.serving import KVBlockManager

    bm = KVBlockManager(num_blocks=9, block_size=4)
    bm.allocate("a", 2)
    cap = kv_capacity_stats(bm, max_model_len=16)  # 4 blocks per seq
    assert cap["blocks_per_max_len_seq"] == 4.0
    assert cap["max_concurrent_seqs"] == 2.0  # 8 usable // 4
    assert cap["free_concurrent_seqs"] == 1.0  # 6 free // 4
    assert cap["blocks_free"] == 6.0


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_serving_recompile_detector_fires_after_grace():
    """A decode-bucket compile past the warmup grace window gets the same
    loud RECOMPILE treatment the train step has had since PR 4."""
    from veomni_tpu.models import TransformerConfig, build_foundation_model
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
        num_slots=2, block_size=16, max_model_len=128,
        recompile_warmup_ticks=1))
    # warmup request: compiles prefill + decode buckets, arms at tick 1
    eng.run([Request(prompt_ids=[1, 2, 3],
                     sampling=SamplingParams(max_new_tokens=3))])
    base = get_registry().counter("recompiles").value

    cap = _Capture()
    root = logging.getLogger("veomni_tpu")
    root.addHandler(cap)
    try:
        # a longer prompt forces a NEW prefill bucket mid-run — exactly the
        # "serving compile storm" signature the detector now watches
        eng.run([Request(prompt_ids=list(range(1, 41)),
                         sampling=SamplingParams(max_new_tokens=3))])
    finally:
        root.removeHandler(cap)
    assert get_registry().counter("recompiles").value > base
    assert any("RECOMPILE" in r.getMessage() for r in cap.records)


# --------------------------------------------------------------- devmem
def test_buffer_census_aggregates_by_dtype():
    big = jnp.ones((128, 128), jnp.float32)   # 64 KiB
    small = jnp.ones((8,), jnp.int32)
    census = buffer_census(top_k=5)
    assert census["num_arrays"] >= 2
    assert census["total_bytes"] >= big.nbytes + small.nbytes
    assert "float32" in census["by_dtype"] and "int32" in census["by_dtype"]
    assert census["by_dtype"]["float32"]["bytes"] >= big.nbytes
    tops = census["top"]
    assert len(tops) <= 5
    # sorted by aggregate bytes descending
    assert all(tops[i]["bytes"] >= tops[i + 1]["bytes"]
               for i in range(len(tops) - 1))
    assert any(tuple(t["shape"]) == (128, 128) and t["dtype"] == "float32"
               for t in tops)
    del big, small


def test_memory_gauges_live_on_cpu():
    """The mem.* family must be live under JAX_PLATFORMS=cpu (the satellite
    fix: tier-1 used to never exercise the gauge path)."""
    from veomni_tpu.utils.helper import live_memory_stats

    stats = live_memory_stats()
    assert stats.get("host_rss_bytes", 0) > 0  # the RSS fallback, always on

    reg = MetricsRegistry()
    anchor = jnp.ones((64, 64))  # keep a live buffer during the publish
    published = publish_memory_gauges(reg)
    assert reg.gauge("mem.host_rss_bytes").value > 0
    assert reg.gauge("mem.live_buffer_bytes").value >= anchor.nbytes
    # the watermark is monotone and at least the current live total
    assert (reg.gauge("mem.high_watermark_bytes").value
            >= published["live_buffer_bytes"])
    wm1 = reg.gauge("mem.high_watermark_bytes").value
    del anchor
    publish_memory_gauges(reg)
    assert reg.gauge("mem.high_watermark_bytes").value >= wm1 - 1e-6


def test_is_resource_exhausted_matches_oom_shapes():
    assert is_resource_exhausted(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate"))
    assert is_resource_exhausted(RuntimeError(
        "Allocator ran out of memory trying to allocate 2.0GiB"))
    assert not is_resource_exhausted(ValueError("shape mismatch"))


def test_oom_report_carries_both_censuses():
    anchor = jnp.ones((32, 32))
    rep = oom_report(top_k=4)
    assert rep["buffer_census"]["num_arrays"] >= 1
    assert "programs" in rep["cost_census"]
    assert rep["host_rss_bytes"] > 0
    del anchor


# -------------------------------------------------------------- exporter
def test_debug_memory_and_cost_endpoints():
    from veomni_tpu.observability import MetricsExporter

    get_cost_census().record("endpoint_unit", "b0", compile_time_s=0.1,
                             flops=123.0, bytes_accessed=456.0)
    anchor = jnp.ones((64, 64))
    exp = MetricsExporter(port=0, memory_fn=lambda: {"pool_bytes": 99.0})
    port = exp.start()
    try:
        mem = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/memory?k=3", timeout=10).read())
        assert mem["buffer_census"]["total_bytes"] >= anchor.nbytes
        assert len(mem["buffer_census"]["top"]) <= 3
        assert mem["host_rss_bytes"] > 0
        assert mem["pool"] == {"pool_bytes": 99.0}

        cost = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/cost", timeout=10).read())
        sites = {p["site"] for p in cost["programs"]}
        assert "endpoint_unit" in sites
        rec = next(p for p in cost["programs"]
                   if p["site"] == "endpoint_unit")
        assert rec["flops"] == 123.0 and rec["bytes_accessed"] == 456.0
        assert cost["totals"]["programs"] >= 1
        assert "live" in cost  # scrape-to-scrape MFU window armed
    finally:
        exp.stop()
    del anchor


# ------------------------------------------------------ subprocess drill
_OOM_DRIVER = """\
import json, os, sys

cfg = json.load(open(sys.argv[1]))
sys.path.insert(0, cfg["repo"])

from veomni_tpu.arguments import VeOmniArguments
from veomni_tpu.trainer import TextTrainer

args = VeOmniArguments()
args.model.config_overrides = cfg["toy"]
args.data.train_path = cfg["data"]
args.data.data_type = "pretokenized"
args.data.max_seq_len = 64
t = args.train
t.output_dir = cfg["out"]
t.micro_batch_size = 2
t.train_steps = 6
t.async_save = False
t.lr = 1e-3
t.bf16 = False
t.save_hf_weights = False
t.log_steps = 1

trainer = TextTrainer(args)
res = {"error": ""}
try:
    trainer.train()
except Exception as e:
    res["error"] = type(e).__name__
    res["message"] = str(e)
finally:
    trainer.checkpointer.close()
with open(cfg["result"], "w") as f:
    json.dump(res, f)
"""


def test_oom_drill_postmortem_contains_buffer_census(tmp_path):
    """Acceptance drill: a simulated RESOURCE_EXHAUSTED escaping the train
    loop auto-dumps a post-mortem whose extra payload carries the top-K
    buffer census and the compiled-program cost census."""
    rng = np.random.default_rng(0)
    with open(tmp_path / "data.jsonl", "w") as f:
        for _ in range(64):
            f.write(json.dumps({
                "input_ids": rng.integers(
                    0, 128, int(rng.integers(16, 60))).tolist(),
            }) + "\n")
    driver = tmp_path / "driver.py"
    driver.write_text(_OOM_DRIVER)
    cfg = {
        "repo": _REPO, "toy": TOY,
        "data": str(tmp_path / "data.jsonl"),
        "out": str(tmp_path / "out"),
        "result": str(tmp_path / "result.json"),
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    fault_plan = [{
        "point": "step.loss", "mode": "exception", "hit": 3,
        "message": ("RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 9437184 bytes (simulated OOM drill)"),
    }]
    env = dict(os.environ, JAX_PLATFORMS="cpu", VEOMNI_LOG_LEVEL="WARNING",
               VEOMNI_FAULT_PLAN=json.dumps(fault_plan))
    p = subprocess.run(
        [sys.executable, str(driver), str(cfg_path)],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=240,
    )
    assert os.path.exists(cfg["result"]), (
        f"driver died rc={p.returncode}:\n{p.stderr[-3000:]}"
    )
    res = json.load(open(cfg["result"]))
    assert res["error"] == "InjectedFault"
    assert "RESOURCE_EXHAUSTED" in res["message"]

    pm_path = os.path.join(cfg["out"], "postmortem-0.json")
    assert os.path.exists(pm_path), "OOM must auto-dump a post-mortem"
    doc = json.load(open(pm_path))
    assert doc["reason"] == "exception:InjectedFault"
    assert "RESOURCE_EXHAUSTED" in doc["error"]
    # the OOM forensics: what held the memory...
    census = doc["buffer_census"]
    assert census["num_arrays"] > 0 and census["total_bytes"] > 0
    assert census["top"], "top-K buffer table missing"
    top = census["top"][0]
    assert top["bytes"] > 0 and top["dtype"]
    # ... and what each compiled program needs on top of it
    sites = {prog["site"] for prog in doc["cost_census"]["programs"]}
    assert "train_step" in sites
    tstep = next(prog for prog in doc["cost_census"]["programs"]
                 if prog["site"] == "train_step")
    assert tstep["flops"] > 0
