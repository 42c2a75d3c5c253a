"""Tests of what PR 43 changed in what a traced run reads: the window cut on
the step program's whole executions (``trace.py::cut_to_steps``, the job's
``cut_on_steps``), a trace with too few whole steps, the scan readers off
``flash_fwd``'s call count, and the mixers' scopes inside the taxonomy.

On the recorded trace ``data/trace_scoped_small.json`` (one traced step of
the qwen cell on a v5e, cut to three stretches, with the scope map its
instructions need), laid out several times in a row as a traced run holds
its steps: one that began before the profiler was on, whole ones, one the
profiler's stop cut. No model is compiled here.
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, flops_kda, flops_ssm, peaks, scopes as sc, trace as tr  # noqa: E402
from benchmark.jobs import train_packed  # noqa: E402
from benchmark.reducers import (  # noqa: E402
    kernel_roofline, scope_cut_ms, scope_ms, ssm_scan_roofline, trace_idle_share, trace_op_ms)

PROGRAM = "jit_step_fn(14782096952073079876)"
GAP = 1_000_000  # the device idles a millisecond between two steps


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_scoped_small.json")) as f:
        return json.load(f)


def steps_trace(recorded, whole, cut_first=True, cut_last=True, host_window=True):
    """The recorded step's device events laid out as a traced run holds its
    steps: ``whole`` executions of the step program inside the host's
    ``bench.window`` span, before them one whose start lies before the span
    (``cut_first``), after them one that outlasts it (``cut_last``)."""
    device = next(p for p in recorded["planes"] if p["name"].startswith("/device"))
    ops = tr.line_events(device, tr.OPS_LINE)
    lo, hi = min(s for _, s, _ in ops), max(s + d for _, s, d in ops)
    period = hi - lo + GAP
    copies = int(cut_first) + whole + int(cut_last)
    events, modules = [], []
    for i in range(copies):
        events += [[n, s + i * period, d] for n, s, d in ops]
        modules.append([PROGRAM, lo + i * period, hi - lo])
    # the profiler is on from the middle of the first copy to the middle of
    # the last where those are cut, else from just before to just after
    on = lo + (hi - lo) // 2 if cut_first else lo - GAP // 2
    off = (lo + (copies - 1) * period + (hi - lo) // 2 if cut_last
           else lo + (copies - 1) * period + (hi - lo) + GAP // 2)
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": tr.clip(modules, on, off) if not host_window else modules},
        {"name": tr.OPS_LINE, "events": tr.clip(events, on, off)}]}]
    if host_window:
        planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [tr.WINDOW_SPAN, on, off - on], ["step.backpressure", on, (off - on) // 2]]}]})
    first_whole = lo + int(cut_first) * period
    return ({"planes": planes, "scope_map": recorded["scope_map"]},
            (first_whole, first_whole + (whole - 1) * period + (hi - lo)))


@pytest.fixture
def obs(monkeypatch, recorded):
    with open(os.path.join(BENCH, "configs", "qwen3_0p6b.json")) as f:
        model = {k: v for k, v in json.load(f).items() if not isinstance(v, (dict, list))}
    monkeypatch.setattr(sc, "program_scope_map", lambda site="train_step": recorded["scope_map"])
    lines = []
    return {"log": lines.append, "lines": lines, "chips": 1, "model": model,
            "peaks": peaks.peaks_for("TPU v5 lite"), "window_s": 30.0, "spans": {}, "timers": {},
            "counters": {}, "values": {},
            "shapes": {"traced_steps": None, "attention_pairs": None, "attention_tokens": None,
                       "seq_len": 4096}}


# what the job keeps a step it dispatched (the warm-up's two included): step k
# admitted k million pairs over 16384 positions
BY_STEP = {"attention_pairs": [k * 1e6 for k in range(1, 31)], "attention_tokens": [16384] * 30}


# ------------------------------------------------------------------ the cut
@pytest.mark.parametrize("whole", [2, 3, 4])
def test_window_is_cut_on_whole_step_executions(recorded, whole):
    trace, (lo, hi) = steps_trace(recorded, whole)
    assert len(tr.program_executions(trace, train_packed.STEP_PROGRAM)) == whole + 2
    assert len(tr.whole_executions(trace, train_packed.STEP_PROGRAM)) == whole
    assert tr.cut_to_steps(trace, train_packed.STEP_PROGRAM) == whole
    assert tr.window_ns(trace) == (lo, hi)
    # every reduction reads that window: busy is the whole steps' alone
    one_busy, _ = tr.busy_and_window_s(recorded)
    busy, window = tr.busy_and_window_s(trace)
    assert window == pytest.approx((hi - lo) * 1e-9)
    assert busy == pytest.approx(whole * one_busy, rel=1e-9)
    assert tr.op_seconds(trace, "flash_fwd") == pytest.approx(
        whole * tr.op_seconds(recorded, "flash_fwd"), rel=1e-9)
    assert kernel_roofline.calls_in_window(trace, r"^%?flash_fwd\.\d+ = ") == pytest.approx(
        whole * kernel_roofline.calls_in_window(recorded, r"^%?flash_fwd\.\d+ = "))
    assert sum(s for _, s in tr.idle_gaps(trace, 100)) == pytest.approx(window - busy, rel=1e-6)
    # another program's executions are not steps
    assert tr.cut_to_steps(trace, r"^jit_norms_of\(") == 0
    assert tr.STEP_WINDOW not in trace


def test_a_step_cut_by_the_traces_edge_is_left_out(recorded):
    """With and without a host span to say when the profiler was on: the
    execution that was running when it started and the one its stop cut do
    not count, and the window ends where the last whole one ends."""
    with_span, window = steps_trace(recorded, 3)
    bare, bare_window = steps_trace(recorded, 3, host_window=False)
    assert window == bare_window
    for trace in (with_span, bare):
        runs = tr.program_executions(trace, train_packed.STEP_PROGRAM)
        assert len(runs) == 5
        assert tr.whole_executions(trace, train_packed.STEP_PROGRAM) == runs[1:4]
        assert tr.cut_to_steps(trace, train_packed.STEP_PROGRAM) == 3
        assert tr.window_ns(trace) == window
    # the device's events may begin a hair AFTER the host's span does: the
    # execution cut to the device's first event is still a cut one
    late, _ = steps_trace(recorded, 3)
    span = next(ev for p in late["planes"] if p["name"].startswith("/host")
                for ev in p["lines"][0]["events"] if ev[0] == tr.WINDOW_SPAN)
    device = late["planes"][0]["lines"]
    first_op = min(s for _, s, _ in device[1]["events"])
    cut_end = device[0]["events"][0][1] + device[0]["events"][0][2]
    device[0]["events"][0] = [PROGRAM, first_op - 1, cut_end - first_op + 1]
    span[2] += span[1] - (first_op - 50_000)
    span[1] = first_op - 50_000
    assert tr.program_executions(late, train_packed.STEP_PROGRAM)[0][0] > tr.traced_span_ns(late)[0]
    assert tr.cut_to_steps(late, train_packed.STEP_PROGRAM) == 3 and tr.window_ns(late) == window
    # an execution that touches the device's first or last event counts as cut,
    # also where the device was idle before it: one step fewer, never a cut one
    idle_before, _ = steps_trace(recorded, 3, cut_first=False)
    assert tr.cut_to_steps(idle_before, train_packed.STEP_PROGRAM) == 2
    idle_after, _ = steps_trace(recorded, 3, cut_last=False)
    assert tr.cut_to_steps(idle_after, train_packed.STEP_PROGRAM) == 2
    # the first whole steps where the trace holds more than are wanted
    more, _ = steps_trace(recorded, 5)
    assert tr.cut_to_steps(more, train_packed.STEP_PROGRAM, at_most=3) == 3
    assert tr.window_ns(more) == window
    # ``skip`` counts over what the trace shows, cut or whole, and the execution
    # after it has to be whole: two skipped leave the second whole one first,
    # none skipped but the cut one asked for leaves nothing
    runs = tr.program_executions(more, train_packed.STEP_PROGRAM)
    assert tr.cut_to_steps(more, train_packed.STEP_PROGRAM, at_most=3, skip=2) == 3
    assert tr.window_ns(more) == (runs[2][0], runs[4][1])
    assert tr.cut_to_steps(more, train_packed.STEP_PROGRAM, skip=6) == 0
    assert tr.STEP_WINDOW not in more


def job_trace(recorded):
    """A trace as the job leaves it: the profiler on with the device idle, the
    ``TRACE_DISPATCHED`` steps that went out, a sync, the profiler off."""
    return steps_trace(recorded, train_packed.TRACE_DISPATCHED, cut_first=False, cut_last=False)


def test_the_job_counts_fixed_steps_of_the_run(recorded, obs):
    trace, (lo, hi) = job_trace(recorded)
    # the profiler went on after the warm-up's 2 steps: the trace's five
    # executions are steps 3 to 7, the window steps 4 to 6 whatever the
    # program's speed, and the shapes are those very steps' own
    train_packed.cut_on_steps(trace, obs["shapes"], BY_STEP, 2, obs["log"])
    assert obs["shapes"]["traced_steps"] == train_packed.TRACED_STEPS == 3
    assert obs["shapes"]["attention_pairs"] == (4 + 5 + 6) * 1e6
    assert obs["shapes"]["attention_tokens"] == 3 * 16384.0
    assert any("3 whole device steps" in x and "steps 4..6 of the run" in x
               and "attention_pairs [4000000.0, 5000000.0, 6000000.0]" in x for x in obs["lines"])
    step_ns = (hi - lo - 4 * GAP) // 5
    assert tr.window_ns(trace) == (lo + step_ns + GAP, lo + 4 * step_ns + 3 * GAP)
    lo, hi = lo + step_ns + GAP, hi  # as the older arithmetic below has them: four steps from lo
    # a step's milliseconds are the recorded step's, whatever the trace held
    obs["trace"] = trace
    with open(os.path.join(HERE, "data", "trace_scoped_small.expected.json")) as f:
        want = json.load(f)["metrics"]
    for metric in ("recompute_ms.train", "dense_ms.train", "lm_head_loss_ms.train",
                   "optimizer_ms.train", "unattributed_ms.train", "attn_kernel_ms.train"):
        with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
            reader = json.load(f)
        reducer = importlib.import_module(f"benchmark.reducers.{reader['reducer']}")
        assert reducer.reduce(obs, reader["args"]) == pytest.approx(want[metric], rel=1e-9), metric
    assert any("scope sum" in x and "off by +0.00%" in x for x in obs["lines"])
    # and the idle share is the three whole steps' own: the gaps between them
    # and inside them, nothing of the cut steps at the trace's edges
    one_busy, _ = tr.busy_and_window_s(recorded)
    assert trace_idle_share.reduce(obs, {}) == pytest.approx(
        100.0 * (1 - 3 * one_busy / ((3 * step_ns + 2 * GAP) * 1e-9)))


def _no_step_metric(obs, trace):
    """No count, no cut, and no reader of the trace but the idle share gives a
    number: none divides by, or multiplies by the shapes of, a step it did
    not see."""
    assert tr.STEP_WINDOW not in trace
    assert all(obs["shapes"][k] is None for k in ("traced_steps", "attention_pairs",
                                                  "attention_tokens"))
    obs["trace"] = trace
    for name in os.listdir(os.path.join(BENCH, "layer_metrics")):
        with open(os.path.join(BENCH, "layer_metrics", name)) as f:
            reader = json.load(f)
        if reader["source"] != "device_trace" or reader["reducer"] == "trace_idle_share":
            continue
        reducer = importlib.import_module(f"benchmark.reducers.{reader['reducer']}")
        assert reducer.reduce(obs, reader.get("args", {})) is None, name
    busy, window = tr.busy_and_window_s(trace)
    on, off = tr.traced_span_ns(trace)
    assert 0 < busy < window and window == pytest.approx((off - on) * 1e-9)


@pytest.mark.parametrize("shown", [3, 4, 6])
def test_a_trace_that_shows_other_executions_than_went_out_yields_no_step_metric(
        recorded, obs, shown):
    """The steps are named by counting the trace's executions from its first:
    where the trace shows more or fewer than the job sent out the count cannot
    be trusted, and nothing is reported from guessed steps."""
    trace, _ = steps_trace(recorded, shown, cut_first=False, cut_last=False)
    train_packed.cut_on_steps(trace, obs["shapes"], BY_STEP, 2, obs["log"])
    assert any(f"shows {shown} execution(s)" in x and "5 went out" in x
               and "no `ms a step`" in x for x in obs["lines"])
    _no_step_metric(obs, trace)


def test_a_trace_with_one_whole_step_yields_no_ms_a_step(recorded, obs):
    """Fewer than two whole steps after the trace's first execution (the
    profiler's stop cut the rest short): the job says so and leaves the count
    out; busy and window stay the traced span's."""
    trace, _ = job_trace(recorded)
    device, host = trace["planes"]
    runs = tr.program_executions(trace, train_packed.STEP_PROGRAM)
    off = runs[2][0] + (runs[2][1] - runs[2][0]) // 2  # the profiler off inside the third
    for line in device["lines"]:
        line["events"] = tr.clip(line["events"], 0, off)
    host["lines"][0]["events"] = tr.clip(host["lines"][0]["events"], 0, off)
    while len(tr.program_executions(trace, train_packed.STEP_PROGRAM)) < 5:
        # two more that the device shows a sliver of, as a trace cut short would not: the
        # count of executions stands, the whole ones are too few
        device["lines"][0]["events"].append([PROGRAM, off - 1, 1])
    train_packed.cut_on_steps(trace, obs["shapes"], BY_STEP, 2, obs["log"])
    assert any("holds 1 whole execution(s)" in x and "no `ms a step`" in x for x in obs["lines"])
    _no_step_metric(obs, trace)


# --------------------------------------------------- the scans' own step count
def _mixer_trace(kind, steps=2, flash_calls=2):
    """``steps`` whole executions of a step whose events lie under the
    ``kind`` mixer's scopes, between two cut ones; ``flash_calls`` flash
    forward calls a step."""
    events, modules, scope_map = [], [], {}
    names = {"fusion.1": f"jit(step_fn)/while/body/jvp({kind})/{kind}.scan/while/body/dot_general",
             "fusion.2": f"jit(step_fn)/transpose(jvp({kind}))/{kind}.scan/exp",
             "fusion.3": f"jit(step_fn)/while/body/jvp({kind})/{kind}.proj/dot_general",
             "fusion.4": f"jit(step_fn)/jvp({kind})/{kind}.scanner/mul",
             "fusion.5": f"jit(step_fn)/jvp({kind})/{kind}.conv/conv_general_dilated",
             "fusion.6": f"jit(step_fn)/jvp({kind})/add",
             "fusion.7": "jit(step_fn)/jvp()/mlp/dot_general",
             "fusion.8": "jit(step_fn)/jvp()/while/body/squeeze"}
    durs = {"fusion.1": 1000, "fusion.2": 3000, "fusion.3": 2000, "fusion.4": 500,
            "fusion.5": 700, "fusion.6": 300, "fusion.7": 900, "fusion.8": 100}
    for i in range(steps + 2):
        at = i * 20_000
        modules.append([PROGRAM, at, 10_000])
        for name, dur in durs.items():
            events.append([f"%{name} = f32[] fusion()", at, dur])
            at += dur
        for k in range(flash_calls):
            events.append([f"%flash_fwd.{7 + k} = bf16[] custom-call()", at, 100])
            at += 100
    scope_map.update(names)
    on, off = 5_000, (steps + 1) * 20_000 + 5_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": tr.MODULES_LINE, "events": modules},
                                            {"name": tr.OPS_LINE, "events": tr.clip(events, on, off)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [[tr.WINDOW_SPAN, on, off - on]]}]}]}
    return trace, scope_map


SCAN = {"ssm": ("ssm_scan_roofline", flops_ssm.ssd_scan_ops_bytes,
                dict(tokens=8192, heads=64, head_dim=64, state=128, groups=1, chunk=256), 9,
                "granite_4_0_h_micro"),
        "kda": ("kda_scan_roofline", flops_kda.kda_scan_ops_bytes,
                dict(tokens=8192, heads=32, head_dim=128, chunk=64), 4, "kimi_linear_48b_a3b")}


@pytest.mark.parametrize("kind", ["ssm", "kda"])
def test_scan_roofline_does_not_move_with_flash_fwds_calls(monkeypatch, kind):
    """Both sides are a device step's, counted on the step program's own
    executions: halve ``flash_fwd``'s events (what keeping the kernel's output
    through the backward would do) and the share stands where it was."""
    metric, fn, kw, layers, config = SCAN[kind]
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        reader = json.load(f)
    assert "step_proxy" not in reader["args"] and reader["args"]["forward_runs"] == 2
    assert "step_proxy" not in open(ssm_scan_roofline.__file__).read()
    assert "flash" not in json.dumps(reader["args"])
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        model = {k: v for k, v in json.load(f).items() if not isinstance(v, (dict, list))}
    values = []
    for flash_calls in (2, 1):
        trace, scope_map = _mixer_trace(kind, steps=3, flash_calls=flash_calls)
        monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map",
                            lambda site="train_step", m=scope_map: m)
        obs = {"trace": trace, "peaks": peaks.peaks_for("TPU v5e"), "chips": 1,
               "log": lambda m: None, "model": model,
               "shapes": {"traced_steps": None, "attention_tokens": None}}
        train_packed.cut_on_steps(trace, obs["shapes"], {"attention_tokens": [8192] * 9}, 2,
                                  obs["log"])
        assert obs["shapes"]["traced_steps"] == 3
        values.append(ssm_scan_roofline.reduce(obs, reader["args"]))
    fwd = flops.roofline_seconds(fn(**kw), peaks.peaks_for("TPU v5e"))["seconds"]
    bwd = flops.roofline_seconds(fn(**kw, backward=True), peaks.peaks_for("TPU v5e"))["seconds"]
    under_scope_a_step = (1000 + 3000) * 1e-9
    assert values[0] == pytest.approx(100 * layers * (2 * fwd + bwd) / under_scope_a_step)
    assert values[1] == values[0]


# ------------------------------------------------ the mixers inside the taxonomy
@pytest.mark.parametrize("kind", ["ssm", "kda"])
def test_mixer_events_land_in_their_scopes_and_the_table_sums_to_busy(monkeypatch, kind):
    trace, scope_map = _mixer_trace(kind, steps=3)
    assert tr.cut_to_steps(trace, train_packed.STEP_PROGRAM) == 3
    tab = sc.table(trace, scope_map)
    busy, _ = tr.busy_and_window_s(trace)
    assert sum(tab["by_scope"].values()) == pytest.approx(busy, rel=1e-9)
    ns = 3e-9
    want = {f"{kind}.scan": 4000 * ns, f"{kind}.proj": 2000 * ns, f"{kind}.conv": 700 * ns,
            # the module's own name keeps what no leaf holds, ``<kind>.scanner`` too
            kind: (500 + 300) * ns, "mlp": 900 * ns, "attn.flash": 200 * ns,
            sc.UNATTRIBUTED: 100 * ns}
    assert tab["by_scope"] == pytest.approx(want)
    # the cuts by module scope agree with the table's sums for the same names
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": scope_map)
    obs = {"trace": trace, "shapes": {"traced_steps": 3}, "log": lambda m: None}
    mixer = sum(v for k, v in tab["by_scope"].items() if k == kind or k.startswith(kind + "."))
    assert scope_cut_ms.reduce(obs, {"name": kind}) == pytest.approx(mixer / 3 * 1e3)
    for leaf in ("scan", "proj"):
        assert scope_cut_ms.reduce(obs, {"name": f"{kind}.{leaf}"}) == pytest.approx(
            tab["by_scope"][f"{kind}.{leaf}"] / 3 * 1e3)
    assert scope_ms.reduce(obs, {"unattributed": True}) == pytest.approx(100 * 1e-9 * 1e3)
    assert trace_op_ms.reduce(obs, {"pattern": r"^%?flash_fwd\.\d+ = "}) == pytest.approx(
        200 * 1e-9 * 1e3)
