"""Tests of what PR 27 added to the benchmark (``python -m pytest
benchmark/tests``): ``scopes.py`` and the six new reducers, on a trace
recorded on a v5e with the program's kernel names and its scope map
(``data/trace_scoped_small.json``). On the CPU; no topology is described
here. (That what the benchmark had stands is ``test_unchanged.py``'s.)
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import peaks, scopes as sc, trace as tr  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.reducers import (  # noqa: E402
    host_busy_share, kernel_roofline, program_gauge, scope_ms, span_ring_sum, trace_op_ms)

CELL = "qwen3_0p6b.train_packed_4k"
MANIFEST = bench_run.load_manifest()
NEW = ["flash_fwd_roofline", "flash_bwd_roofline", "attn_kernel_ms.train",
       "recompute_ms.train", "dense_ms.train", "lm_head_loss_ms.train", "optimizer_ms.train",
       "unattributed_ms.train", "host_busy_share.train", "launch_to_trainer_s.setup",
       "trainer_build_s.setup", "compile_s.setup"]
STEP = "jit(step_fn)/while/body/closed_call/"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_scoped_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(HERE, "data", "trace_scoped_small.expected.json")) as f:
        return json.load(f)


@pytest.fixture
def obs(recorded, monkeypatch):
    """What ``run.py`` hands a reducer, with the recorded trace and its map
    in the program's place."""
    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3_0p6b.json")) as f:
        model = {k: v for k, v in json.load(f).items() if not isinstance(v, (dict, list))}
    monkeypatch.setattr(sc, "program_scope_map", lambda site="train_step": recorded["scope_map"])
    lines = []
    return {"trace": recorded, "log": lines.append, "lines": lines, "chips": 1, "model": model,
            "peaks": peaks.peaks_for("TPU v5 lite"), "window_s": 30.0, "spans": {}, "timers": {},
            "counters": {}, "values": {},
            # the recorded stretch is one step; the job's mean step admitted
            # 14.0e6 (query, key) pairs over 16384 positions
            "shapes": {"traced_steps": 1, "attention_pairs": 14.0e6, "attention_tokens": 16384.0,
                       "seq_len": 4096}}


def _reader(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- taxonomy
def test_taxonomy_is_the_programs():
    from veomni_tpu.observability import scopes as prog

    # the program's train scopes, then the mixers' scopes the program lists
    # among its module scopes (all of them but ``mtp``, which holds other
    # scopes of the taxonomy inside it and stays a cut across them)
    assert sc.TRAIN_SCOPES == prog.TRAIN_SCOPES + sc.MIXER_SCOPES
    assert sc.MIXER_SCOPES == tuple(s for s in prog.MODULE_SCOPES if s != "mtp")
    assert sc.SERVE_SCOPES == prog.SERVE_SCOPES and sc.SCOPES == sc.TRAIN_SCOPES + sc.SERVE_SCOPES
    assert tuple(sc.KERNELS) == prog.ALL_KERNEL_NAMES
    assert {sc.KERNELS[k][0] for k in prog.SCOPED_KERNEL_NAMES} == {"attn.qkv"}
    assert {scope for scope, _ in sc.KERNELS.values()} <= set(sc.SCOPES)


@pytest.mark.parametrize("event,want", [
    ("%fusion.12 = bf16[4,4096]{1,0} fusion(%p), kind=kLoop", "fusion.12"),
    ("%flash_fwd.16 = (bf16[4,16,4096,128]{3,2,1,0}, f32[4]) custom-call(%a)", "flash_fwd.16"),
    ("while.123", "while.123"),
    ("  %copy-start.26 = (bf16[1]) copy-start(%x)", "copy-start.26"),
])
def test_instruction_name(event, want):
    assert sc.instruction_name(event) == want


@pytest.mark.parametrize("op_name,scope,phase", [
    (STEP + "jvp()/while/body/closed_call/mlp/dot_general", "mlp", "forward"),
    (STEP + "transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/mul",
     "mlp", "recompute"),
    (STEP + "transpose(jvp())/while/body/closed_call/checkpoint/attn.qkv/dot_general",
     "attn.qkv", "backward"),
    ("jvp(lm_head_loss)/reduce_sum", "lm_head_loss", "forward"),
    ("transpose(jvp(attn.out))/dot_general", "attn.out", "backward"),
    ("jit(step_fn)/optimizer/jit(_where)/select_n", "optimizer", "optimizer"),
    ("jit(step_fn)/grad_clip/mul", "grad_clip", "optimizer"),
    (STEP + "jvp()/while/body/squeeze", None, "forward"),
    (STEP + "jvp()/moe.experts/mlp_like/dot_general", "moe.experts", "forward"),
    (STEP + "jvp()/attn.flash/attn.qkvx/mul", "attn.flash", "forward"),  # a whole component only
    # a mixer's leaf wins over the module's own name, which keeps what lies
    # under it and under none of its leaves (the mixer's norm and residual)
    (STEP + "jvp(ssm)/ssm.scan/while/body/dot_general", "ssm.scan", "forward"),
    (STEP + "transpose(jvp(ssm))/ssm.gate_norm/mul", "ssm.gate_norm", "backward"),
    (STEP + "checkpoint/rematted_computation/ssm/ssm.proj/dot_general", "ssm.proj", "recompute"),
    (STEP + "jvp(ssm)/add", "ssm", "forward"),
    (STEP + "jvp(ssm)/ssm.scanner/mul", "ssm", "forward"),
    (STEP + "transpose(jvp(kda))/kda.scan/exp", "kda.scan", "backward"),
    (STEP + "jvp(kda)/kda.conv/conv_general_dilated", "kda.conv", "forward"),
    (STEP + "jvp(kda)/kda.gate/logistic", "kda.gate", "forward"),
    (STEP + "jvp(kda)/rsqrt", "kda", "forward"),
    ("", None, "forward"),
])
def test_scope_and_phase_of_an_op_name(op_name, scope, phase):
    assert sc.scope_of(op_name) == scope
    assert sc.phase_of(op_name, scope) == phase


def test_a_kernel_is_classed_by_its_name_where_the_map_lacks_it():
    assert sc.classify("flash_bwd_dq.10", {}) == ("attn.flash", "backward")
    assert sc.classify("gmm_drhs.3", {}) == ("moe.experts", "backward")
    remat = {"flash_fwd.16": STEP + "transpose(jvp())/checkpoint/rematted_computation/"
                             "attn.flash/flash_fwd/pallas_call"}
    assert sc.classify("flash_fwd.16", remat) == ("attn.flash", "recompute")
    assert sc.classify("flash_fwd.15", {}) == ("attn.flash", "forward")
    assert sc.classify("qk_norm_rope_bwd.2", {}) == ("attn.qkv", "backward")
    assert sc.classify("mla_qkv_rope_fwd.7", remat | {
        "mla_qkv_rope_fwd.7": STEP + "checkpoint/rematted_computation/attn.qkv/pallas_call"}) == (
        "attn.qkv", "recompute")
    assert sc.classify("fusion.99", {}) == (sc.UNATTRIBUTED, "forward")


# ------------------------------------------------------------ recorded trace
def test_table_of_the_recorded_trace_sums_to_its_busy_time(recorded, expected):
    tab = sc.table(recorded, recorded["scope_map"])
    busy, _ = tr.busy_and_window_s(recorded)
    assert sum(tab["by_scope"].values()) == pytest.approx(busy, rel=1e-9)
    assert sum(tab["by_phase"].values()) == pytest.approx(busy, rel=1e-9)
    assert set(tab["by_phase"]) == set(sc.PHASES)
    for scope, seconds in expected["by_scope_s"].items():
        assert tab["by_scope"][scope] == pytest.approx(seconds, rel=1e-9), scope
    for phase, seconds in expected["by_phase_s"].items():
        assert tab["by_phase"][phase] == pytest.approx(seconds, rel=1e-9), phase
    # three forward calls (one of them the rematerialized copy), one backward
    flash = tab["by_scope_phase"]["attn.flash"]
    assert flash["recompute"] < flash["forward"] < flash["backward"]
    assert tab["by_scope"][sc.UNATTRIBUTED] < 0.03 * busy
    assert all(sc.classify(n, recorded["scope_map"])[0] == sc.UNATTRIBUTED
               for n, _, _ in tab["unattributed_top"])


def test_seconds_by_scopes_and_by_phase(recorded):
    tab = sc.table(recorded, recorded["scope_map"])
    dense = sc.seconds(tab, ("attn.qkv", "attn.out", "mlp"))
    assert dense == pytest.approx(sum(tab["by_scope"][s] for s in ("attn.qkv", "attn.out", "mlp")))
    assert sc.seconds(tab, (), "recompute") == pytest.approx(tab["by_phase"]["recompute"])
    assert sc.seconds(tab, ("mlp",), "recompute") == pytest.approx(
        tab["by_scope_phase"]["mlp"]["recompute"])
    assert sc.seconds(tab, ("moe.experts",)) == 0.0


@pytest.mark.parametrize("metric", ["flash_fwd_roofline", "flash_bwd_roofline"])
def test_kernel_roofline_counts_calls(obs, expected, metric):
    args = _reader(metric)["args"]
    value = kernel_roofline.reduce(obs, args)
    assert value == pytest.approx(expected["metrics"][metric], rel=1e-9)
    assert 0 < value < 100
    calls = kernel_roofline.calls_in_window(obs["trace"], args.get("call_pattern", args["pattern"]))
    assert calls == pytest.approx(expected["calls"][metric])
    # twice the calls in twice the time: the share stands (a remat policy
    # that runs the forward once more does not move it)
    double = json.loads(json.dumps(obs["trace"]))
    shift = 10 * 10 ** 9
    for plane in double["planes"]:
        for line in plane["lines"]:
            if line["name"] == tr.OPS_LINE:
                line["events"] += [[n, s + shift, d] for n, s, d in line["events"]]
            elif plane["name"].startswith("/host"):
                line["events"] = [[n, s, d + shift if n == tr.WINDOW_SPAN else d]
                                  for n, s, d in line["events"]]
    assert kernel_roofline.reduce(dict(obs, trace=double), args) == pytest.approx(value, rel=1e-9)


def test_backward_roofline_counts_the_backward_alone(obs):
    from benchmark import flops

    kw = dict(pairs=14.0e6, tokens=16384.0, num_q_heads=16, num_kv_heads=8, head_dim=128)
    both = flops.flash_attention_ops_bytes(**kw, backward=True)
    fwd = flops.flash_attention_ops_bytes(**kw, backward=False)
    assert (both["ops"] - fwd["ops"]) / fwd["ops"] == pytest.approx(5 / 2)
    kernel_roofline.reduce(obs, _reader("flash_bwd_roofline")["args"])
    line = [x for x in obs["lines"] if "kernel roofline" in x][-1]
    assert f"one call {both['ops'] - fwd['ops']:.4g} ops" in line


def test_attn_kernel_ms_is_the_three_kernels(obs, expected):
    value = trace_op_ms.reduce(obs, _reader("attn_kernel_ms.train")["args"])
    assert value == pytest.approx(expected["metrics"]["attn_kernel_ms.train"], rel=1e-9)
    parts = sum(tr.op_seconds(obs["trace"], rf"^%?{k}\.\d+ = ")
                for k in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"))
    assert value == pytest.approx(parts * 1e3, rel=1e-9)
    assert trace_op_ms.reduce(obs, {"pattern": "^%no_such_kernel"}) is None


@pytest.mark.parametrize("metric", ["recompute_ms.train", "dense_ms.train", "lm_head_loss_ms.train",
                                    "optimizer_ms.train", "unattributed_ms.train"])
def test_scope_ms_on_the_recorded_trace(obs, expected, metric):
    value = scope_ms.reduce(obs, _reader(metric)["args"])
    assert value == pytest.approx(expected["metrics"][metric], rel=1e-9) and value > 0
    assert any("scope sum" in x and "off by +0.00%" in x for x in obs["lines"])


def test_host_busy_share_is_the_loops_time_less_the_waits(obs):
    """From the program's span ring over the measured window, up to the loop's
    last return in it: both waits (the loop blocks in step.backpressure, and
    in step.dispatch where memory is tight), which follow one another."""
    args = _reader("host_busy_share.train")["args"]
    assert args["wait_spans"] == ["step.backpressure", "step.dispatch"]
    waited = dict(obs, loop_s=2.0, spans={"step.backpressure": [0.5, 0.6],
                                         "step.dispatch": [0.1, 0.05], "data.wait": [0.3]})
    assert host_busy_share.reduce(waited, args) == pytest.approx(100.0 * (1 - 1.25 / 2.0))
    assert host_busy_share.reduce(waited, {"wait_spans": ["step.backpressure"]}) == \
        pytest.approx(100.0 * (1 - 1.1 / 2.0))
    assert any("4 step.backpressure / step.dispatch spans cover 1.2500 s of the 2.0000 s"
               in x for x in obs["lines"])
    # no trace is read: the trace holds the run's first steps, where the loop
    # waits in the job's own sync alone
    assert host_busy_share.reduce(dict(waited, trace=None), args) == pytest.approx(37.5)
    # a program without the spans, or a job that did not say how long the loop ran
    assert host_busy_share.reduce(dict(waited, spans={"data.wait": [0.3]}), args) is None
    assert host_busy_share.reduce(dict(waited, loop_s=None), args) is None


# ------------------------------------------------- the program's side missing
@pytest.mark.parametrize("metric", NEW)
def test_new_reducers_leave_the_metric_out_against_an_older_program(obs, monkeypatch, metric):
    """The parent of PR 27: no kernel names, no scope map, no wait span, no
    set-up spans, no gauge. Every new reader returns None and none raises."""
    import importlib

    old = json.loads(json.dumps(obs["trace"]))
    for plane in old["planes"]:
        for line in plane["lines"]:
            line["events"] = [[n.replace("%flash_", "%closed_call_"), s, d]
                              for n, s, d in line["events"]
                              if n not in ("step.backpressure", "step.dispatch")]
    monkeypatch.setattr(sc, "program_scope_map", lambda site="train_step": None)
    from veomni_tpu.observability import spans
    from veomni_tpu.observability.metrics import MetricsRegistry, set_registry

    monkeypatch.setattr(spans, "live_span_events", lambda limit=0: [])
    prev = set_registry(MetricsRegistry())
    try:
        reader = _reader(metric)
        reducer = importlib.import_module(f"benchmark.reducers.{reader['reducer']}")
        assert reducer.reduce(dict(obs, trace=old), reader["args"]) is None
        assert reducer.reduce(dict(obs, trace=None), reader["args"]) is None
    finally:
        set_registry(prev)


def test_program_scope_map_is_none_for_a_site_that_compiled_nothing():
    assert sc.program_scope_map("no_such_site") is None


def test_set_up_readers_read_the_programs_ring_and_registry(obs):
    from veomni_tpu.observability import spans
    from veomni_tpu.observability.metrics import get_registry

    was = spans.spans_enabled()
    spans.enable_spans()
    try:
        before = span_ring_sum.reduce(obs, {"span": "setup.build"}) or 0.0
        with spans.span("setup.build"):
            pass
        after = span_ring_sum.reduce(obs, {"span": "setup.build"})
        assert after is not None and after >= before
    finally:
        if not was:
            spans.disable_spans()
    get_registry().gauge("setup.launch_to_trainer_s").set(12.5)
    assert program_gauge.reduce(obs, _reader("launch_to_trainer_s.setup")["args"]) == 12.5
    assert program_gauge.reduce(obs, {"gauge": "no.such.gauge"}) is None


# ---------------------------------------------------------------- rehearsal
def test_traced_rehearsal_reports_the_new_names_that_need_no_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed",
         str(2 ** 31 + 2727), "--seconds", "2", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert {"host_busy_share.train", "launch_to_trainer_s.setup", "trainer_build_s.setup",
            "compile_s.setup"} <= set(line["rehearsal_metric_names"])
    # no device plane on the CPU: nothing that reads one is reported
    assert not {"flash_fwd_roofline", "unattributed_ms.train"} & set(line["rehearsal_metric_names"])
