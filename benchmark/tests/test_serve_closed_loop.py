"""The serving cell's own tests (``python -m pytest benchmark/tests``), on the
CPU at the mix's ``rehearsal`` sizes (4 slots, prompts of median 24, outputs of
median 8, the configuration's 2-layer preset): the generator, the closed loop,
the comparison through the reference with its control and two planted faults,
the counts of ``flops_serve.py`` against hand counts, the trace readers on a
made-up trace, and the new cell's lists."""

import importlib
import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, flops_serve, peaks, traffic, traffic_serve  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace as tr  # noqa: E402

CELL = "qwen3_0p6b.serve_chat_over"
E2E = "serve_output_tokens_per_s"
MANIFEST = bench_run.load_manifest()
MIX = traffic.load_mix("serve_chat_over")
with open(os.path.join(BENCH, "configs", "qwen3_0p6b.json")) as f:
    CONFIG = json.load(f)
MODEL = {k: v for k, v in CONFIG.items() if not isinstance(v, (dict, list))}


# ---------------------------------------------------------------- the mix
def test_the_mix_is_the_issues():
    assert MIX["kind"] == "serve_closed_loop" and MIX["clients"] == 64
    assert MIX["prompt_tokens"] == {"median": 512, "sigma": 1.0, "min": 32, "max": 4096}
    assert MIX["output_tokens"] == {"median": 128, "sigma": 0.8, "min": 8, "max": 1024}
    heads = MIX["shared_heads"]
    assert (heads["count"], heads["tokens"], heads["share"]) == (4, 256, 0.5)
    assert MIX["sampling"]["temperature"] == 0.0 and MIX["sampling"]["eos_id"] == -1
    assert MIX["n_requests"] == 4096 and MIX["traced_ticks"] == 40
    # the window never outruns the lengths whose pad programs were built ahead
    assert MIX["prompt_lengths_ahead"] >= 2 * MIX["clients"]
    engine = MIX["engine"]
    assert (engine["num_slots"], engine["block_size"], engine["max_model_len"]) == (32, 16, 5120)
    assert engine["prefix_cache"] is True and engine["prefill_chunk"] == 0
    assert engine["kv_quant"] == "none" and engine["spec_k"] == 0
    assert engine["param_dtype"] == "bfloat16"
    # the longest prompt with the longest answer is a request the engine takes
    assert MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"] <= engine["max_model_len"]


def test_the_stream_is_the_size_seeds_and_the_ids_are_the_seeds():
    a, b = traffic_serve.request_sizes(MIX), traffic_serve.request_sizes(MIX)
    assert all((a[k] == b[k]).all() for k in a) and len(a["prompt"]) == 4096
    assert abs(float(np.mean(a["head"] >= 0)) - 0.5) < 0.03
    assert set(a["head"][a["head"] >= 0]) == {0, 1, 2, 3}
    assert 450 < np.median(a["prompt"]) < 580 and 110 < np.median(a["output"]) < 150
    assert a["prompt"].min() >= 32 and a["prompt"].max() == 4096 and a["output"].max() <= 1024
    mix = bench_run.overlay(MIX, MIX["rehearsal"])
    big = 2 ** 31 + 17
    x, y, z = (traffic_serve.request_stream(mix, 512, s) for s in (big, big, big + 1))
    assert all((p["prompt_ids"] == q["prompt_ids"]).all() and p["max_new_tokens"] == q["max_new_tokens"]
               for p, q in zip(x, y))
    assert [len(p["prompt_ids"]) for p in x] == [len(p["prompt_ids"]) for p in z]
    assert [p["head"] for p in x] == [p["head"] for p in z], "every seed: the same sizes and heads"
    assert any((p["prompt_ids"] != q["prompt_ids"]).any() for p, q in zip(x, z))
    heads = traffic_serve.shared_heads(mix, 512, big)
    sizes = traffic_serve.request_sizes(mix)
    for req, n in zip(x, sizes["prompt"]):
        assert len(req["prompt_ids"]) == n, "a head counts inside its prompt's length"
        if req["head"] >= 0:
            k = min(n, heads.shape[1])
            assert (req["prompt_ids"][:k] == heads[req["head"], :k]).all()


# ------------------------------------------------------------ the counts
def test_decode_ticks_least_bytes_against_a_hand_count():
    cfg = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
           "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}
    # a layer: q 8x16, k and v 8x8 each, o 16x8, three 8x16 of the MLP
    layer = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 3 * 8 * 16
    assert flops_serve.layer_matmul_params(cfg) == layer == 768
    norms = 3 * (2 * 8 + 2 * 4) + 8
    assert flops_serve.weight_bytes(cfg) == 2 * (3 * layer + norms + 8 * 100)
    assert flops_serve.kv_bytes_per_position(cfg) == 3 * 2 * 2 * 4 * 2
    least = flops_serve.decode_tick_least_bytes(cfg, context_positions=10)
    assert least == flops_serve.weight_bytes(cfg) + 10 * 96
    # the cell's own: 1.19 GB of weights and 112 KiB a cached position
    assert flops_serve.weight_bytes(MODEL) == 1_192_099_840
    assert flops_serve.kv_bytes_per_position(MODEL) == 112 * 1024


def test_forward_flops_are_a_third_of_the_train_count():
    for seq in (512, 4096):
        assert flops_serve.forward_flops(MODEL, tokens=1, context_sum=seq / 2, logit_rows=1) \
            == pytest.approx(flops.fwd_flops_per_token(MODEL, seq), rel=1e-12)
    # a prompt of 5 behind 2 cached positions reads contexts 3, 4, 5
    one = flops_serve.forward_flops(MODEL, tokens=3, context_sum=12, logit_rows=1)
    per_pair = 4 * MODEL["num_attention_heads"] * MODEL["head_dim"] * MODEL["num_hidden_layers"]
    assert one - flops_serve.forward_flops(MODEL, tokens=3, context_sum=0, logit_rows=1) == 12 * per_pair


# ------------------------------------------------------- the trace's readers
def _trace():
    """Two ticks' worth of a made-up trace inside a window of four: a prefill
    (its ``fusion.1`` is NOT the decode step's), then a decode span each."""
    ops = [["%fusion.1 = f32[] fusion()", 2100, 50],      # tick 2's prefill
           ["%fusion.1 = f32[] fusion()", 2300, 100],     # decode 2: gather (bucket a)
           ["%fusion.2 = f32[] fusion()", 2400, 200],     # decode 2: attend
           ["%copy.3 = f32[] copy()", 2600, 100],         # decode 2: nothing
           ["%fusion.1 = f32[] fusion()", 3200, 300],     # decode 3: attend (bucket b)
           ["%fusion.9 = f32[] fusion()", 3500, 100]]     # decode 3: sampler
    host = [["bench.window", 0, 5000], ["bench.tick", 1000, 900], ["bench.tick", 2000, 900],
            ["bench.tick", 3000, 900], ["bench.tick", 4000, 900],
            ["serve.prefill", 2050, 150], ["serve.decode", 1200, 600], ["serve.decode", 2250, 500],
            ["serve.decode", 3100, 600], ["serve.decode", 4100, 600]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def _obs(trace, ticks):
    job = importlib.import_module("benchmark.jobs.serve_closed_loop")
    shapes = {"traced_steps": None}
    logs = []
    job.cut_on_ticks(trace, shapes, ticks, logs.append)
    maps = {"a": {"fusion.1": "jit(impl)/while/body/paged.gather/gather",
                  "fusion.2": "jit(impl)/while/body/paged.attend/dot_general"},
            "b": {"fusion.1": "jit(impl)/while/body/paged.attend/dot_general",
                  "fusion.9": "jit(impl)/sampler/argmax"}}
    return {"trace": trace, "shapes": shapes, "scope_maps": {"paged_decode": maps}, "log": logs.append,
            "model": MODEL, "chips": 1, "peaks": peaks.peaks_for("TPU v5e")}, logs


def _ticks(buckets=("a", "a", "b", "b")):
    return [{"decode_context": 1000 * (i + 1), "decoded": 4, "prefilled": int(i == 1),
             "decode_bucket": b} for i, b in enumerate(buckets)]


def test_the_window_is_cut_on_the_jobs_own_ticks():
    obs, _ = _obs(_trace(), _ticks())
    assert tr.window_ns(obs["trace"]) == (2000, 3900)
    assert obs["shapes"]["traced_steps"] == 2
    assert obs["shapes"]["decode_context_positions"] == 2000 + 3000
    assert obs["shapes"]["span_buckets"] == {"serve.decode": ["a", "b"]}
    # another number of ticks in the trace than were run: no count, and the log says so
    obs, logs = _obs(_trace(), _ticks()[:3])
    assert obs["shapes"]["traced_steps"] is None and "no `ms a tick`" in logs[-1]


def test_scope_time_inside_the_decode_spans_by_each_ticks_own_program():
    from benchmark.reducers import span_scope_ms

    obs, _ = _obs(_trace(), _ticks())
    args = {"span": "serve.decode"}
    # ms a tick over 2 ticks; the prefill's fusion.1 (outside the spans) is in none
    assert span_scope_ms.reduce(obs, {**args, "scopes": ["paged.gather"]}) == pytest.approx(100e-6 / 2)
    assert span_scope_ms.reduce(obs, {**args, "scopes": ["paged.attend"]}) == pytest.approx(500e-6 / 2)
    assert span_scope_ms.reduce(obs, {**args, "scopes": ["sampler"]}) == pytest.approx(100e-6 / 2)
    assert span_scope_ms.reduce(obs, {**args, "unattributed": True}) == pytest.approx(100e-6 / 2)
    assert span_scope_ms.reduce(obs, {**args, "scopes": ["mlp"]}) is None
    # a tick whose program has no map: no scope metric at all, never a wrong one
    obs, logs = _obs(_trace(), _ticks(("a", "a", "c", "b")))
    assert span_scope_ms.reduce(obs, {**args, "scopes": ["paged.attend"]}) is None
    assert "no scope metric" in logs[-1]


def test_host_share_and_the_decode_roofline():
    from benchmark.reducers import decode_bytes_roofline, span_host_share

    obs, _ = _obs(_trace(), _ticks())
    # the spans last 500 + 600 ns, the device runs 400 + 400 ns inside them
    assert span_host_share.reduce(obs, {"span": "serve.decode"}) == pytest.approx(100 * (1 - 800 / 1100))
    share = decode_bytes_roofline.reduce(obs, {"span": "serve.decode"})
    least = 2 * flops_serve.weight_bytes(MODEL) + 5000 * flops_serve.kv_bytes_per_position(MODEL)
    assert share == pytest.approx(100 * least / 819e9 / 800e-9)
    obs["shapes"]["traced_steps"] = None
    assert span_host_share.reduce(obs, {"span": "serve.decode"}) is None
    assert decode_bytes_roofline.reduce(obs, {"span": "serve.decode"}) is None


def test_window_readers_on_counters_and_spans():
    from benchmark.reducers import forward_flops_share, span_share_of

    obs = {"spans": {"serve.prefill": [1.0, 1.0], "serve.decode": [6.0]}, "window_s": 10.0,
           "counters": {"forward.tokens": 1000, "forward.context_sum": 500000, "forward.logit_rows": 100},
           "model": MODEL, "chips": 1, "peaks": peaks.peaks_for("TPU v5e")}
    assert span_share_of.reduce(obs, {"span": "serve.prefill", "of": ["serve.prefill", "serve.decode"]}) == 25.0
    ops = flops_serve.forward_flops(MODEL, tokens=1000, context_sum=500000, logit_rows=100)
    assert forward_flops_share.reduce(obs, {}) == pytest.approx(100 * ops / 10.0 / 197e12)
    obs["peaks"] = None
    assert forward_flops_share.reduce(obs, {}) is None
    assert span_share_of.reduce({"spans": {}}, {"span": "serve.prefill", "of": ["serve.decode"]}) is None


# --------------------------------------------------------------- the lists
def test_the_new_cells_lists():
    cell = bench_run.find(MANIFEST["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3_0p6b", "serve_chat_over", 1)
    e2e = {m["name"]: m for m in bench_run.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert set(e2e) == {E2E, "setup_s"} and e2e[E2E]["workloads"] == [CELL]
    assert (e2e[E2E]["unit"], e2e[E2E]["better"], e2e[E2E]["source"]) == ("tokens/s", "higher", "host_clock")
    assert 0.01 <= e2e[E2E]["bound"] <= 0.1
    names = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", CELL)}
    serve = {f"{base}.serve" for base in (
        "decode_tick_ms", "prefill_ms_per_call", "prefill_share", "slots_busy_share",
        "queue_wait_p50_ms", "ttft_p50_ms", "tpot_p50_ms", "prefix_hit_share", "kv_utilization",
        "preemptions", "paged_gather_ms", "paged_attend_ms", "sampler_ms", "unattributed_ms",
        "device_idle_share", "peak_hbm_gb", "host_share_of_tick", "programs_built_in_window",
        "mfu_pct", "decode_hbm_roofline")}
    setup = {"engine_build_s.setup_serve", "compile_s.setup_serve"}
    assert names == serve | setup
    for m in MANIFEST["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == ("setup_s" if m["name"] in setup else E2E)
            with open(os.path.join(BENCH, "layer_metrics", f"{m['name']}.json")) as f:
                reader = json.load(f)
            assert (reader["layer"], reader["unit"], reader["source"], reader["moves"]) == \
                (m["layer"], m["unit"], m["source"], m["moves"])
            assert os.path.exists(os.path.join(BENCH, "reducers", reader["reducer"] + ".py"))
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not names & {m["name"] for m in bench_run.cell_metrics(
                MANIFEST, "per_layer", other["name"])}
            assert E2E not in {m["name"] for m in bench_run.cell_metrics(
                MANIFEST, "end_to_end", other["name"])}


# ------------------------------------------------------ the cell, end to end
def _drive(monkeypatch, capsys, *extra, seed=2 ** 31 + 4321, trace=0):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
                         "--trace", str(trace), "--rehearsal", *extra])
    said = capsys.readouterr()
    assert rc == 0
    return json.loads(said.out.strip().splitlines()[-1]), said.out + said.err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(monkeypatch, capsys, trace):
    line, out = _drive(monkeypatch, capsys, trace=trace, seed=2 ** 31 + 12345 + trace)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 20 and line["failed"] == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    want = {m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer" if trace else "end_to_end", CELL)}
    assert set(line["rehearsal_metric_names"]) <= want
    if not trace:
        assert set(line["rehearsal_metric_names"]) == want
    else:
        # all that needs no device: the spans, the counters, the outputs' timings
        assert {"decode_tick_ms.serve", "prefill_share.serve", "slots_busy_share.serve",
                "ttft_p50_ms.serve", "kv_utilization.serve", "prefix_hit_share.serve",
                "engine_build_s.setup_serve", "compile_s.setup_serve"} <= set(line["rehearsal_metric_names"])
    assert list(line)[-1] == "checks"
    checks = {c["name"]: c for c in line["checks"]}
    assert set(checks) == {"served_not_reference_best_share", "served_logit_gap_max",
                           "served_logit_gap_mean", "requests_failed", "outputs_of_another_length",
                           "tokens_lost_or_out_of_order", "clients_over_the_mix"}
    assert all(c["ok"] and c["value"] <= c["limit"] for c in checks.values())
    assert checks["served_logit_gap_max"]["request"] >= 0
    assert checks["requests_failed"]["sent"] == line["attempted"]
    assert checks["served_not_reference_best_share"]["positions"] >= 20
    # the loop: never more clients in flight than the mix has, every slot decoding
    assert "slots busy" in out and "requests sent" in out


# The 2-layer preset of 64 cannot tell fp8 from float32: its greedy token is
# the last token again, half a logit clear of the next (a margin's median is
# 0.60 against fp8's 0.008 of noise, one flip in 96 positions). At hidden 256
# over four layers and 4,096 words the margins are the full model's in kind
# (median 0.06 against 0.03), so the control is held there: the same job, the
# same mix, and the program beside it in the same run has to pass.
MIDDLE = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 4,
          "vocab_size": 4096, "head_dim": 32}


def _run_job(monkeypatch, control, seed):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    job = importlib.import_module("benchmark.jobs.serve_closed_loop")
    config = bench_run.overlay(bench_run.overlay(CONFIG, CONFIG["rehearsal"]), MIDDLE)
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    said = []
    ctx = bench_run.Ctx(
        cell=bench_run.find(MANIFEST["workloads"], CELL, "cell"), config=config,
        mix=bench_run.overlay(MIX, MIX["rehearsal"]), seed=seed, seconds=1.5, trace=False,
        rehearsal=True, control=control, limits=bench_run.overlay(limits, limits["rehearsal"]),
        device={"platform": "cpu", "kind": "cpu", "count": 1})
    ctx.log = said.append
    return job.run(ctx), said


def test_the_control_in_fp8_is_not_correct(monkeypatch):
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        control = json.load(f)["rehearsal_control"]
    result, said = _run_job(monkeypatch, control, seed=77)
    failed = {c["name"]: c for c in result["checks"] if not c["ok"]}
    assert {"served_not_reference_best_share", "served_logit_gap_max"} <= set(failed)
    assert set(failed) <= {"served_not_reference_best_share", "served_logit_gap_max",
                           "served_logit_gap_mean"}
    assert failed["served_logit_gap_max"]["value"] > 100 * failed["served_logit_gap_max"]["limit"]
    # the program's own readings, in the same run, are inside every limit
    program = [m for m in said if m.startswith("reading (program)")]
    assert len(program) == 3
    for m in program:
        value, limit = re.search(r": (\S+) \(limit (\S+)\)", m).groups()
        assert float(value) <= float(limit), m


def test_a_block_table_swapped_between_two_slots_is_not_correct(monkeypatch):
    from veomni_tpu.serving import engine as eng

    real = eng.InferenceEngine._fill_slot_arrays
    calls = {"n": 0}

    def swapped(self, running):
        out = real(self, running)
        calls["n"] += 1
        if len(running) >= 2 and calls["n"] % 3 == 0:
            a, b = running[0][0], running[1][0]
            out[0][[a, b]] = out[0][[b, a]]  # each reads, and writes, the other's blocks
        return out

    monkeypatch.setattr(eng.InferenceEngine, "_fill_slot_arrays", swapped)
    result, _ = _run_job(monkeypatch, None, seed=5)
    failed = {c["name"]: c for c in result["checks"] if not c["ok"]}
    assert "served_logit_gap_max" in failed
    assert failed["served_logit_gap_max"]["value"] > 100 * failed["served_logit_gap_max"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from veomni_tpu.serving import engine as eng

    real = eng.InferenceEngine._emit
    seen = {"n": 0}

    def altered(self, seq, token):
        seen["n"] += 1
        return real(self, seq, (token + 1) % 512 if seen["n"] % 50 == 0 else token)

    monkeypatch.setattr(eng.InferenceEngine, "_emit", altered)
    result, _ = _run_job(monkeypatch, None, seed=6)
    assert "served_logit_gap_max" in {c["name"] for c in result["checks"] if not c["ok"]}


def test_a_request_cut_short_is_not_correct(monkeypatch, capsys):
    from veomni_tpu.serving import engine as eng

    real = eng.InferenceEngine.submit

    def shorter(self, request, sampling=None):
        if request.sampling.max_new_tokens > 6:
            request.sampling = type(request.sampling)(
                max_new_tokens=request.sampling.max_new_tokens - 1,
                temperature=request.sampling.temperature, eos_id=request.sampling.eos_id)
        return real(self, request, sampling)

    monkeypatch.setattr(eng.InferenceEngine, "submit", shorter)
    line, _ = _drive(monkeypatch, capsys)
    assert line["correct"] is False
    assert "outputs_of_another_length" in {c["name"] for c in line["checks"] if not c["ok"]}
