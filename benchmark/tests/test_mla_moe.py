"""Tests of what the JoyAI-LLM-Flash configuration added to the benchmark as
files: ``flops_mla_moe.py`` against the program's counter, the new reducers on
synthetic observations, the cell's limits against planted faults (a dropped
assignment, the MTP loss left out). That what the benchmark had stands is
``test_unchanged.py``'s, one case a cell.
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, flops_mla_moe, peaks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.reducers import kernel_roofline_mod, program_value, scope_cut_ms  # noqa: E402

CELL = "joyai_llm_flash.train_packed_8k"
MANIFEST = bench_run.load_manifest()


def _config():
    entry = bench_run.find(MANIFEST["configs"], "joyai_llm_flash", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


# ------------------------------------------------------------- configuration
def test_configuration_states_its_cut_and_keeps_every_width():
    entry, doc = _config()
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (5, 16, 16160)
    assert doc["n_routed_experts_published"] == 256 and doc["vocab_size"] * 8 == 129280
    # the published widths, letter for letter
    widths = dict(hidden_size=2048, intermediate_size=7168, moe_intermediate_size=768, head_dim=64,
                  q_lora_rank=1536, kv_lora_rank=512, qk_head_dim=192, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=32,
                  num_key_value_heads=32, num_experts_per_tok=8, n_shared_experts=1,
                  first_k_dense_replace=1, num_nextn_predict_layers=1, rope_theta=32000000,
                  routed_scaling_factor=2.5, n_group=1, topk_group=1)
    assert {k: doc[k] for k in widths} == widths
    over = doc["program_overrides"]
    assert over["num_experts"] == 256 and over["moe_experts_held"] == 16
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
        assert over[key] == doc[key]
    assert "16 chips" in doc["deployment"] and "mtp_loss_weight" in doc["assumed"]
    # the issue's cell: AdamW 3e-4 constant, the buffer an even routing's rows
    assert doc["train"]["lr"] == 3e-4 and doc["train"]["lr_decay_style"] == "constant"
    assert doc["moe_capacity_factor"] == over["moe_capacity_factor"] == 1.0
    assert "moe_capacity_factor" not in doc["rehearsal"].get("program_overrides", {})
    # the floors of a model_config cut: four expert layers, 8 experts, an eighth of the vocabulary
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 4
    assert doc["n_routed_experts"] >= 8 and doc["vocab_size"] * 8 >= 129280


def test_flops_agree_with_the_programs_counter_for_this_family():
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.utils.count_flops import FlopsCounter

    _, doc = _config()
    model = {k: v for k, v in doc.items() if not isinstance(v, (dict, list))}
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "tie_word_embeddings",
            "num_experts_per_tok", "moe_intermediate_size")
    cfg = build_config(model["model_type"], **{k: model[k] for k in keys},
                       **doc["program_overrides"])
    counter = FlopsCounter.from_config(cfg)
    for seq in (512, 8192):
        assert flops_mla_moe.fwd_flops_per_token(model, seq) == pytest.approx(
            counter.flops_per_token_fwd(seq), rel=1e-12)
        assert flops_mla_moe.train_flops_per_token(model, seq) * 7 == pytest.approx(
            counter.batch_flops(7, seq), rel=1e-12)
    # about 2.6 GFLOP a token of work done on this chip (the issue's figure, at
    # the cell's mean context of a few thousand positions)
    assert 2.2e9 < flops_mla_moe.train_flops_per_token(model, 2048) < 3.0e9
    # and a parameter count from the shapes: 680.4 M
    from benchmark.reference import mla_moe

    n = sum(int(__import__("numpy").prod(s)) for s in mla_moe.param_shapes(model).values())
    assert 680.0e6 < n < 681.0e6


def test_kernel_work_arithmetic():
    fwd = flops_mla_moe.mla_flash_ops_bytes(pairs=10, tokens=4, num_heads=2, qk_head_dim=24,
                                            v_head_dim=16)
    assert fwd["ops"] == (2 * 2 * 24 + 2 * 2 * 16) * 10
    assert fwd["bytes"] == 4 * 2 * (2 * 2 * 24 + 2 * 2 * 16)
    bwd = flops_mla_moe.mla_flash_ops_bytes(pairs=10, tokens=4, num_heads=2, qk_head_dim=24,
                                            v_head_dim=16, backward=True)
    assert bwd["ops"] == (3 * 2 * 2 * 24 + 2 * 2 * 2 * 16) * 10
    # equal widths: the dense family's count (fwd 2 of 7 units, bwd 5 of 7)
    same = flops.flash_attention_ops_bytes(pairs=10, tokens=4, num_q_heads=2, num_kv_heads=2,
                                           head_dim=16, layers=1, backward=False)
    assert flops_mla_moe.mla_flash_ops_bytes(pairs=10, tokens=4, num_heads=2, qk_head_dim=16,
                                             v_head_dim=16) == same
    g = flops_mla_moe.gmm_ops_bytes(tokens=100, top_k=8, held_share=0.0625, hidden_size=32,
                                    expert_width=8, experts=4)
    assert g["ops"] == 2 * 50 * 32 * 8 and g["bytes"] == 50 * 40 * 2 + 4 * 32 * 8 * 2
    gb = flops_mla_moe.gmm_ops_bytes(tokens=100, top_k=8, held_share=0.0625, hidden_size=32,
                                     expert_width=8, experts=4, backward=True)
    assert gb["ops"] == 2 * g["ops"] and gb["bytes"] == 2 * g["bytes"]


# ------------------------------------------------------------------ reducers
def _trace(events):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [["bench.window", 0, 10_000]]}]}]}


def _obs(trace, **shapes):
    return {"trace": trace, "peaks": peaks.peaks_for("TPU v5e"), "chips": 1, "log": lambda m: None,
            "shapes": dict({"traced_steps": 2}, **shapes),
            "model": {"num_attention_heads": 2, "qk_head_dim": 24, "v_head_dim": 16}}


def test_kernel_roofline_mod_counts_one_call_against_its_module():
    trace = _trace([["%flash_fwd.1 = bf16[] custom-call()", 100, 1000],
                    ["%flash_fwd.2 = bf16[] custom-call()", 2000, 3000],
                    ["%fusion.3 = f32[] fusion()", 6000, 500]])
    args = {"module": "flops_mla_moe", "ops_bytes": "mla_flash_ops_bytes",
            "pattern": "^%?flash_fwd\\.\\d+ = ", "fixed": {"backward": False},
            "per_step": {"pairs": "attention_pairs", "tokens": "attention_tokens"},
            "from_model": {"num_heads": "num_attention_heads", "qk_head_dim": "qk_head_dim",
                           "v_head_dim": "v_head_dim"}}
    obs = _obs(trace, attention_pairs=2e9, attention_tokens=2e4)
    one = flops_mla_moe.mla_flash_ops_bytes(pairs=1e9, tokens=1e4, num_heads=2, qk_head_dim=24,
                                            v_head_dim=16)
    least = flops.roofline_seconds(one, obs["peaks"])["seconds"]
    assert kernel_roofline_mod.reduce(obs, args) == pytest.approx(100 * least * 2 / 4000e-9)
    # no such kernel in the trace, no trace, or a model that routes nothing: left out
    assert kernel_roofline_mod.reduce(obs, dict(args, pattern="^%?gmm_fwd\\.\\d+ = ")) is None
    assert kernel_roofline_mod.reduce(dict(obs, trace=None), args) is None
    ratio = dict(args, shape_ratio={"held_share": ["moe_rows_multiplied", "moe_assignments"]})
    assert kernel_roofline_mod.reduce(obs, ratio) is None


def test_gmm_roofline_reads_the_rows_of_the_traced_steps():
    """The rows are the traced steps' own (held less dropped over all
    assignments), not the run's: routing moves over a run, and the program's
    counters cover all of it."""
    trace = _trace([["%gmm_fwd.1 = bf16[] custom-call()", 100, 1000],
                    ["%gmm_fwd.2 = bf16[] custom-call()", 2000, 3000]])
    with open(os.path.join(BENCH, "layer_metrics", "gmm_fwd_roofline.json")) as f:
        args = json.load(f)["args"]
    assert "program_ratio" not in args
    obs = _obs(trace, attention_tokens=2 * 16384.0, moe_assignments=2 * 5 * 16384 * 8.0,
               moe_rows_multiplied=2 * 5 * 4096.0)
    obs["model"] = {"num_experts_per_tok": 8, "hidden_size": 2048, "moe_intermediate_size": 768,
                    "n_routed_experts": 16}
    one = flops_mla_moe.gmm_ops_bytes(tokens=16384.0, top_k=8, held_share=4096 / (16384 * 8.0),
                                      hidden_size=2048, expert_width=768, experts=16)
    least = flops.roofline_seconds(one, obs["peaks"])["seconds"]
    assert kernel_roofline_mod.reduce(obs, args) == pytest.approx(100 * least * 2 / 4000e-9)
    fewer = dict(obs, shapes=dict(obs["shapes"], moe_rows_multiplied=2 * 5 * 1024.0))
    assert kernel_roofline_mod.reduce(fewer, args) < kernel_roofline_mod.reduce(obs, args)
    # steps in which the held experts got no row: an empty launch's time is
    # held against no work at all, so the metric is left out, never a share
    none = dict(obs, shapes=dict(obs["shapes"], moe_rows_multiplied=0.0))
    assert kernel_roofline_mod.reduce(none, args) is None


def test_program_value_reads_the_programs_registry_and_nothing_else():
    from veomni_tpu.observability.metrics import MetricsRegistry, set_registry

    old = set_registry(MetricsRegistry())
    try:
        assert program_value.reduce({}, {"counter": "moe.assignments_held",
                                         "over": "moe.assignments"}) is None
        from veomni_tpu.observability.metrics import get_registry

        get_registry().counter("moe.assignments").inc(1600)
        get_registry().counter("moe.assignments_held").inc(100)
        assert program_value.reduce({}, {"counter": "moe.assignments_held", "over":
                                         "moe.assignments", "scale": 100.0}) == pytest.approx(6.25)
        assert program_value.ratio("moe.assignments_dropped", "moe.assignments_held") is None
        get_registry().counter("moe.assignments_dropped").inc(20)
        assert program_value.ratio("moe.assignments_dropped", "moe.assignments_held") == 0.2
        assert program_value.reduce({}, {"counter": "moe.assignments_dropped", "over":
                                         "moe.assignments_held", "scale": 100.0}) == pytest.approx(20.0)
    finally:
        set_registry(old)


def test_scope_cut_sums_what_lies_under_the_module_scope(monkeypatch):
    trace = _trace([["%fusion.1 = f32[] fusion()", 0, 1000], ["%fusion.2 = f32[] fusion()", 1000, 3000],
                    ["%flash_fwd.4 = bf16[] custom-call()", 4000, 2000],
                    ["%fusion.5 = f32[] fusion()", 6000, 500]])
    scope_map = {"fusion.1": "jit(step_fn)/jvp(mtp)/attn.qkv/dot_general",
                 "fusion.2": "jit(step_fn)/while/body/attn.qkv/dot_general",
                 "flash_fwd.4": "jit(step_fn)/transpose(jvp(mtp))/attn.flash/pallas_call",
                 "fusion.5": "jit(step_fn)/mtptail/mul"}
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": scope_map)
    obs = _obs(trace)
    assert scope_cut_ms.reduce(obs, {"name": "mtp"}) == pytest.approx((1000 + 2000) * 1e-9 / 2 * 1e3)
    assert scope_cut_ms.reduce(obs, {"name": "nothing"}) is None
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": None)
    assert scope_cut_ms.reduce(obs, {"name": "mtp"}) is None


def test_every_new_metric_is_in_the_cells_traced_line_and_no_other_cells():
    names = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", CELL)}
    want = {"mla_flash_fwd_roofline", "mla_flash_bwd_roofline", "gmm_fwd_roofline", "gmm_bwd_roofline",
            "mla_proj_ms.train_mla_moe", "moe_ms.train_mla_moe", "mtp_ms.train_mla_moe",
            "moe_held_share.train_mla_moe", "moe_load_max_over_mean.train_mla_moe",
            "mfu_pct.train_mla_moe", "step_ms.train_mla_moe", "device_idle_share.train_mla_moe",
            "peak_hbm_gb.train_mla_moe", "padding_share.train_mla_moe"}
    # what the review of PR 29 asked for: the dropped share, and the qwen
    # cell's remaining readers under this cell's names
    want |= {"moe_dropped_share.train_mla_moe"}
    want |= {f"{base}.train_mla_moe" for base in (
        "recompute_ms", "lm_head_loss_ms", "optimizer_ms", "unattributed_ms", "data_wait_share",
        "host_busy_share", "attn_kernel_ms", "flash_tiles_live_share")}
    setup = {f"{base}.setup_mla_moe" for base in ("launch_to_trainer_s", "trainer_build_s", "compile_s")}
    assert names == want | setup
    assert {m["name"] for m in MANIFEST["per_layer"]
            if m["moves"] == "setup_s" and CELL in m["workloads"]} == setup
    qwen = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", "qwen3_0p6b.train_packed_4k")}
    assert not qwen & want and "mfu_pct.train" in qwen and len(qwen) == 19
    for m in MANIFEST["per_layer"]:
        assert "workloads" in m, f"{m['name']} would be asked of every later train cell"


# ------------------------------------------------------------ planted faults
def _drive_in_process(monkeypatch, capsys, seed=5):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                         "--trace", "0", "--rehearsal"])
    said = capsys.readouterr()
    assert rc == 0
    # the result line is standard output's last; the checks are standard error's
    return json.loads(said.out.strip().splitlines()[-1]), said.out + said.err


def test_a_dropped_assignment_is_not_correct(monkeypatch, capsys):
    """A buffer shorter than the rank's capacity the configuration states:
    the layer drops assignments the reference keeps (it visits every position
    with every held expert and zeroes only what comes after that capacity),
    and the comparison says so."""
    from veomni_tpu.models import transformer

    real = transformer.held_rows
    monkeypatch.setattr(transformer, "held_rows", lambda cfg, t: real(cfg, t) // 3 - 24)
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm)", out)
    # and the result line shows the failing check by name, its number past its limit
    failed = [c for c in line["checks"] if not c["ok"]]
    assert {c["name"] for c in failed} & {"loss_1_rel_gap", "loss_2_rel_gap",
                                          "first_grad_norm_worst_leaf"}
    assert all(c["value"] > c["limit"] for c in failed) and list(line)[-1] == "checks"


def test_the_mtp_loss_left_out_is_not_correct(monkeypatch, capsys):
    import jax.numpy as jnp

    from veomni_tpu.models import transformer

    monkeypatch.setattr(transformer, "mtp_labels",
                        lambda labels, segment_ids, depth: jnp.full_like(labels, -100))
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL loss", out)
