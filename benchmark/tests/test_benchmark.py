"""Tests of the benchmark's own files (``python -m pytest benchmark/tests``).

They run on the CPU: the manifest against the contract's rules, every cell
end to end at its tiny rehearsal preset, the trace reduction on a recorded
trace, the peak table, ``flops.py`` against the program's counter, the
reference against the program, the control, a broken timed path, and a
fixture cell added purely as files. No topology is described here.

The tier-1 command collects ``tests/`` only, and a benchmark PR may add no
file there: whoever changes ``benchmark/`` runs this file (PERF.md, Open
questions).
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import compare, flops, peaks, trace as tr, traffic  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_KEY = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
                       r"expansion|num_experts_per_tok")


def _run(args, env_extra=None, root=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=root, timeout=timeout)


def _last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------------ manifest
def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in MANIFEST[g]]
    assert len(names) == len(set(names))
    for g in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MANIFEST[g]:
            assert NAME.match(e["name"]), e["name"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in MANIFEST["end_to_end"])
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_manifest_cells_and_configs():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = set()
    for c in MANIFEST["configs"]:
        assert c["name"] in used and c["file"].startswith("benchmark/") and len(c["why"]) <= 200
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not WIDTH_KEY.search(key), f"{key} is a width"
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits", w["name"] + ".json"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_the_contract_asks(cell):
    e2e = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "end_to_end", cell)}
    layer = bench_run.cell_metrics(MANIFEST, "per_layer", cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".json")
        with open(path) as f:
            reader = json.load(f)
        assert reader["layer"] == m["layer"] and reader["moves"] == m["moves"]
        assert reader["unit"] == m["unit"] and reader["source"] == m["source"]
        assert os.path.exists(os.path.join(BENCH, "reducers", reader["reducer"] + ".py"))


def test_layer_names_are_one_per_layer():
    by_file = {}
    for m in MANIFEST["per_layer"]:
        by_file.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_file.values()), by_file


# ------------------------------------------------------------------- cells
@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_the_cpu(cell, trace):
    line = _last_line(_run(["--workload", cell, "--seed", str(2 ** 31 + 12345 + trace),
                            "--seconds", "2", "--trace", str(trace), "--rehearsal"]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert line["device"]["platform"] == "cpu"
    assert {"busy_s", "window_s"}.isdisjoint(line["device"])
    want = {m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer" if trace else "end_to_end", cell)}
    assert set(line["rehearsal_metric_names"]) <= want
    if not trace:
        assert set(line["rehearsal_metric_names"]) == want
    # what was compared comes last in the line: every number beside its limit
    assert list(line)[-1] == "checks" and len(line["checks"]) >= 7
    for c in line["checks"]:
        assert NAME.match(c["name"]) and c["ok"] is True
        assert isinstance(c["value"], float) and isinstance(c["limit"], float)
        assert c["value"] <= c["limit"]
    leaves = {c["name"]: c.get("leaf") for c in line["checks"]}
    assert leaves["first_grad_norm_worst_leaf"] and leaves["loss_1_rel_gap"] is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_without_a_tpu_and_without_the_flag_fails(cell):
    proc = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{") and '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_lower_precision_is_not_correct(cell):
    """The reference, put in the program's place in the precision below the
    configuration's, at the tiny preset's own (tighter) limits."""
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        control = json.load(f)["rehearsal_control"]
    line = _last_line(_run(["--workload", cell, "--seed", "77", "--seconds", "1",
                            "--trace", "0", "--rehearsal", "--control", control]))
    assert line["correct"] is False
    failed = [c for c in line["checks"] if not c["ok"]]
    assert failed and all(c["value"] > c["limit"] for c in failed)
    assert {c["name"] for c in failed} & {"first_grad_norm_worst_leaf",
                                          "param_change_norm_2_steps_worst_leaf"}


# ------------------------------------------------------------- broken paths
def _drive_in_process(cell, monkeypatch, capsys, seed=5):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                         "--trace", "0", "--rehearsal"])
    said = capsys.readouterr()
    assert rc == 0
    # the result line is standard output's last; the checks are standard error's
    return json.loads(said.out.strip().splitlines()[-1]), said.out + said.err


def test_train_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    from veomni_tpu.trainer import base

    real = base.build_train_step

    def broken(*a, **k):
        step = real(*a, **k)

        def no_update(state, batch):
            new_state, metrics = step(state, batch)
            return state.replace(step=new_state.step), metrics

        return no_update

    monkeypatch.setenv("VEOMNI_DONATE_STATE", "0")
    monkeypatch.setattr(base, "build_train_step", broken)
    line, out = _drive_in_process("qwen3_0p6b.train_packed_4k", monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL param_change_norm", out)
    # the line names the failing check, with the gap of a state left unchanged
    # (1 by this measure) beside its limit and the leaf it was read on
    failed = {c["name"]: c for c in line["checks"] if not c["ok"]}
    gap = failed["param_change_norm_2_steps_worst_leaf"]
    assert gap["value"] == pytest.approx(1.0, abs=1e-3) and gap["limit"] < 0.01 and gap["leaf"]


def test_train_step_that_leaves_out_rows_is_not_correct(monkeypatch, capsys):
    from veomni_tpu.trainer import base

    real = base.BaseTrainer._ship_batch

    def half(self, batch_np):
        batch_np = dict(batch_np)
        labels = batch_np["labels"].copy()
        labels[:, labels.shape[1] // 2:] = -100  # the second half predicts nothing
        batch_np["labels"] = labels
        return real(self, batch_np)

    monkeypatch.setattr(base.BaseTrainer, "_ship_batch", half)
    line, out = _drive_in_process("qwen3_0p6b.train_packed_4k", monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm)", out)
    assert {c["name"] for c in line["checks"] if not c["ok"]} & {
        "loss_1_rel_gap", "loss_2_rel_gap", "first_grad_norm_worst_leaf"}


# ------------------------------------------------------------------- trace
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_trace_reduction_on_a_synthetic_trace():
    t = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["while.1", 1000, 600], ["fusion.1", 1000, 200], ["flash_fwd", 1300, 300],
            ["all-to-all.3", 1800, 100], ["fusion.2", 1850, 100]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 1000, 1000], ["data.wait", 1600, 150], ["$ignored", 0, 5000]]}]},
    ]}
    busy, window = tr.busy_and_window_s(t)
    assert window == pytest.approx(1000e-9) and busy == pytest.approx(750e-9)
    assert tr.op_seconds(t, "flash") == pytest.approx(300e-9)
    ops = dict(tr.top_device_ops(t))
    assert ops["while.1"] == pytest.approx(100e-9) and ops["flash_fwd"] == pytest.approx(300e-9)
    gaps = dict(tr.idle_gaps(t))
    assert gaps["data.wait"] == pytest.approx(200e-9) and gaps["(no span)"] == pytest.approx(50e-9)


def test_trace_reduction_on_the_recorded_trace(recorded):
    with open(os.path.join(HERE, "data", "trace_small.expected.json")) as f:
        want = json.load(f)
    busy, window = tr.busy_and_window_s(recorded)
    assert busy == pytest.approx(want["busy_s"]) and window == pytest.approx(want["window_s"])
    assert 0 < busy <= window
    for pattern, seconds in want["op_seconds"].items():
        assert tr.op_seconds(recorded, pattern) == pytest.approx(seconds)
    assert [n for n, _ in tr.top_device_ops(recorded, 3)] == want["top3"]
    assert sum(s for _, s in tr.idle_gaps(recorded, 100)) == pytest.approx(window - busy, rel=1e-6)


# ----------------------------------------------------------- peaks and flops
def test_peaks_raise_on_an_unlisted_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="not in benchmark/peaks.py"):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_flops_agree_with_the_programs_counter(config):
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.utils.count_flops import FlopsCounter

    entry = bench_run.find(MANIFEST["configs"], config, "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    model = {k: v for k, v in doc.items() if not isinstance(v, (dict, list))}
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "tie_word_embeddings",
            "num_experts", "num_experts_per_tok", "moe_intermediate_size")
    cfg = build_config(model["model_type"], **{k: model[k] for k in keys if k in model})
    counter = FlopsCounter.from_config(cfg)
    for seq in (512, 4096):
        assert flops.fwd_flops_per_token(model, seq) == pytest.approx(
            counter.flops_per_token_fwd(seq), rel=1e-12)
        assert flops.train_flops_per_token(model, seq) * 7 == pytest.approx(
            counter.batch_flops(7, seq), rel=1e-12)


def test_flash_attention_work_and_roofline():
    w = flops.flash_attention_ops_bytes(pairs=8, tokens=4, num_q_heads=2, num_kv_heads=1,
                                        head_dim=16, layers=3, backward=False)
    assert w["ops"] == 2 * (2 * 2 * 16) * 8 * 3
    assert w["bytes"] == (2 * 4 * 2 * 16 * 2 + 2 * 4 * 1 * 16 * 2) * 3
    r = flops.roofline_seconds({"ops": 197e12, "bytes": 1.0}, peaks.peaks_for("TPU v5e"))
    assert r["seconds"] == pytest.approx(1.0) and r["bound"] == "compute"


# ------------------------------------------------------------------ traffic
def test_traffic_same_seed_same_inputs_and_large_seeds():
    mix = traffic.load_mix("train_packed_4k")
    mix = bench_run.overlay(mix, mix["rehearsal"])
    a = traffic.packed_documents(mix, 512, 2 ** 31 + 9)
    b = traffic.packed_documents(mix, 512, 2 ** 31 + 9)
    c = traffic.packed_documents(mix, 512, 2 ** 31 + 10)
    assert all((x == y).all() for x, y in zip(a, b))
    assert [len(x) for x in a] == [len(x) for x in c], "every seed: the same sizes, same order"
    assert any((x != y).any() for x, y in zip(a, c)), "another seed: other ids"


def test_a_check_that_is_not_finite_still_makes_a_line_json_can_hold():
    c = compare.check("first_grad_norm_worst_leaf", float("inf"), 7e-3, leaf="layers.q_proj[3]")
    assert c["ok"] is False and "leaf layers.q_proj[3]" in compare.said(c)
    doc = json.loads(json.dumps(bench_run._jsonable_check(c), allow_nan=False))
    assert doc == {"name": "first_grad_norm_worst_leaf", "value": None, "value_said": "inf",
                   "limit": 7e-3, "ok": False, "leaf": "layers.q_proj[3]"}
    nan = bench_run._jsonable_check(compare.check("loss_1_rel_gap", float("nan"), 6e-5))
    assert nan["ok"] is False and nan["value"] is None and nan["value_said"] == "nan"
    fine = compare.check("loss_1_rel_gap", 1e-6, 6e-5)
    assert bench_run._jsonable_check(fine) == fine and fine["ok"] is True


def test_worst_leaf_gap_uses_the_median_floor():
    want = {"a": [1.0, 1.0, 1.0], "tiny": [1e-9]}
    got = {"a": [1.0, 1.1, 1.0], "tiny": [5e-9]}
    gap, where = compare.worst_leaf_gap(got, want)
    assert where == "a[1]" and gap == pytest.approx(0.1)


# ------------------------------------------------------------- fixture cell
def test_a_cell_added_as_files_only(tmp_path):
    """A fourth configuration, a mix, a per-layer metric and a reducer, added
    to a copy of the benchmark as new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root / "benchmark") for p in fs}
    with open(os.path.join(BENCH, "configs", "qwen3_0p6b.json")) as f:
        cfg = json.load(f)
    cfg = bench_run.overlay(cfg, cfg["rehearsal"])
    cfg.update(num_hidden_layers=3, source="https://example.org/fixture", reduced=[])
    (root / "benchmark/configs/fixture_cfg.json").write_text(json.dumps(cfg))
    mix = traffic.load_mix("train_packed_4k")
    mix = bench_run.overlay(mix, mix["rehearsal"])
    mix.update(seq_len=64, rows_per_chip=3)
    mix["doc_tokens"]["max"] = 64
    mix.pop("name")
    (root / "benchmark/traffic/fixture_mix.json").write_text(json.dumps(mix))
    shutil.copy(os.path.join(BENCH, "limits", "qwen3_0p6b.train_packed_4k.json"),
                root / "benchmark/limits/fixture_cfg.fixture_mix.json")
    (root / "benchmark/reducers/fixture_reducer.py").write_text(
        "def reduce(obs, args):\n    return obs['counters']['steps'] * args['times']\n")
    (root / "benchmark/layer_metrics/fixture_steps.json").write_text(json.dumps({
        "layer": "data (veomni_tpu/data)", "moves": "train_tokens_per_s", "unit": "steps",
        "source": "program_counter", "reducer": "fixture_reducer", "args": {"times": 2}}))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "fixture_cfg", "source": "https://example.org/fixture",
                                "file": "benchmark/configs/fixture_cfg.json", "reduced": [],
                                "why": "fixture"})
    manifest["workloads"].append({"name": "fixture_cfg.fixture_mix", "config": "fixture_cfg",
                                  "traffic": "fixture_mix", "chips": 1, "why": "fixture"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("fixture_cfg.fixture_mix")
    manifest["per_layer"].append({
        "name": "fixture_steps", "unit": "steps", "better": "higher", "source": "program_counter",
        "layer": "data (veomni_tpu/data)", "moves": "train_tokens_per_s",
        "workloads": ["fixture_cfg.fixture_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    line = _last_line(_run(["--workload", "fixture_cfg.fixture_mix", "--seed", "4", "--seconds",
                            "1", "--trace", "1", "--rehearsal"],
                           env_extra={"PYTHONPATH": ROOT}, root=str(root)))
    assert line["correct"] is True
    assert "fixture_steps" in line["rehearsal_metric_names"]
    assert "flash_attn_roofline" not in line["rehearsal_metric_names"]
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(root / "benchmark") for p in fs
             if "__pycache__" not in dp}
    assert all(after[p] == before[p] for p in before if p in after), "an existing file changed"


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    proc = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
                 "--rehearsal"], root=str(root))
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
