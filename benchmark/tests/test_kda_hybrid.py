"""Tests of what the Kimi-Linear configuration added to the benchmark as files:
the configuration's cut, ``flops_kda.py`` by hand, the recurrence's roofline
reader on a synthetic trace, which cell reads which metric, that what the
benchmark had at PR 35 is a PREFIX of what it has now, and the cell's limits
against two planted faults (a reset left out, rotary left on).
"""

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

from benchmark import flops, flops_kda, flops_mla_moe, peaks, traffic  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.reducers import scope_cut_ms, ssm_scan_roofline  # noqa: E402

CELL = "kimi_linear_48b_a3b.train_packed_8k_x1_doc4k"
MANIFEST = bench_run.load_manifest()


def _config():
    entry = bench_run.find(MANIFEST["configs"], "kimi_linear_48b_a3b", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _model(doc):
    return {k: v for k, v in doc.items() if not isinstance(v, (dict, list))}


# ------------------------------------------------------------- configuration
def test_configuration_states_its_cut_and_keeps_every_width():
    entry, doc = _config()
    assert entry["reduced"] == doc["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"]
    pub = doc["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"]) == (27, 256, 163840)
    assert pub["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (5, 8, 20480)
    assert doc["vocab_size"] * 8 == 163840 and doc["num_experts"] * 32 == 256
    # the published keys, letter for letter (config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct)
    published = dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024, head_dim=72,
        num_attention_heads=32, num_key_value_heads=32, kv_lora_rank=512, q_lora_rank=None,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
        num_experts_per_token=8, num_shared_experts=1, moe_router_activation_func="sigmoid",
        moe_renormalize=True, routed_scaling_factor=2.446, use_grouped_topk=True,
        num_expert_group=1, topk_group=1, first_k_dense_replace=1, moe_layer_freq=1,
        num_nextn_predict_layers=0, rms_norm_eps=1e-05, rope_theta=10000, rope_scaling=None,
        tie_word_embeddings=False, model_type="kimi_linear", model_max_length=1048576,
        hidden_act="silu")
    assert {k: doc[k] for k in published} == published
    # no width of the group is cut: the lists alone, to the layers that are run
    lac = doc["linear_attn_config"]
    assert lac == dict(pub["linear_attn_config"], kda_layers=[1, 2, 3, 5], full_attn_layers=[4])
    assert (lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"]) == (32, 128, 4)
    # the file's plain copies (what the reference and the readers are handed)
    assert doc["layer_kinds_run"].split(",") == ["kda", "kda", "kda", "mla", "kda"]
    assert (doc["kda_num_heads"], doc["kda_head_dim"], doc["kda_conv_kernel"], doc["kda_chunk"],
            doc["num_experts_published"], doc["first_expert_held"], doc["qk_head_dim"]) == (
        32, 128, 4, 64, 256, 0, 192)
    over = doc["program_overrides"]
    assert over["linear_attn_config"] == lac
    assert (over["num_experts"], over["moe_experts_held"], over["moe_experts_held_first"],
            over["moe_capacity_factor"]) == (256, 8, 0, 4.0) and doc["moe_capacity_factor"] == 4.0
    for key in ("mla_use_nope", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_token", "moe_router_activation_func", "moe_renormalize",
                "num_shared_experts", "use_grouped_topk", "num_expert_group", "topk_group",
                "routed_scaling_factor", "first_k_dense_replace"):
        assert over[key] == doc[key], key
    assert "32 chips" in doc["deployment"] and "pipeline stages" in doc["deployment"]
    assert {"A_log", "dt_bias", "conv", "l2norm eps", "q scale", "initializer_range",
            "e_score_correction_bias", "train dtype", "moe_capacity_factor",
            "module names"} <= set(doc["assumed"])
    # the issue's cell: AdamW 3e-4 constant, f32 state, bf16 compute, recompute `nothing`
    train = doc["train"]
    assert (train["optimizer"], train["lr"], train["lr_decay_style"]) == ("adamw", 3e-4, "constant")
    assert train["max_grad_norm"] == 1.0 and train["param_dtype"] == "float32" and train["bf16"]
    assert train["gradient_checkpointing_policy"] == "nothing"
    mix = traffic.load_mix("train_packed_8k_x1_doc4k")
    assert (mix["kind"], mix["seq_len"], mix["rows_per_chip"], mix["dyn_bsz"],
            mix["dyn_bsz_buffer_size"], mix["n_docs"], mix["size_seed"]) == (
        "train_packed", 8192, 1, True, 200, 4000, 20260930)
    assert mix["doc_tokens"] == {"median": 1024, "sigma": 1.2, "min": 64, "max": 4001}
    assert mix["rehearsal"]["doc_tokens"]["max"] < mix["rehearsal"]["seq_len"]
    cell = bench_run.find(MANIFEST["workloads"], CELL, "cell")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"]) and len(MANIFEST["workloads"]) >= 4


def test_the_mix_never_fills_a_row_with_one_document():
    """Why max 4001: every document is shorter than half a row, so the rows
    the loader fills longest first hold a boundary, off every power of two."""
    mix = traffic.load_mix("train_packed_8k_x1_doc4k")
    lengths = traffic.lognormal_lengths(traffic.rng_for(mix["size_seed"]), mix["n_docs"],
                                        mix["doc_tokens"])
    assert lengths.max() == 4001 and (lengths == 4001).sum() > 100
    assert 4001 % 2 == 1 and 2 * 4001 < mix["seq_len"]


def test_flops_and_the_count_of_parameters():
    from benchmark.reference import kda_hybrid

    model = _model(_config()[1])
    shapes = kda_hybrid.param_shapes(model)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 602_434_432
    mixer = {k: s for k, s in shapes.items() if k.startswith("kda_dense_layers.")
             and k.split(".")[-1] not in ("gate_proj", "up_proj", "down_proj",
                                          "input_layernorm", "post_attention_layernorm")}
    assert sum(int(np.prod(s[1:])) for s in mixer.values()) == 39_514_272
    assert shapes["kda_layers.experts.gate_proj"] == (3, 8, 2304, 1024)
    assert shapes["mla_layers.router"] == (1, 2304, 256)
    # forward operations a token at 8192: a KDA mixer's projections 79.0 M, its
    # recurrence 5.2 M; the MLA layer 58.2 M of projections and 83.9 M of
    # scores; an expert layer 18.9 M (8 x 8 / 256 of a token's experts, the shared
    # one, the router), the dense MLP 127.4 M; the head 94.4 M
    kda = flops_kda.kda_mixer_flops(model)
    assert kda["proj"] == 2 * 2304 * 3 * 4096 + 2 * (2 * 2304 * 128 + 2 * 128 * 4096) \
        + 2 * 2304 * 32 + 2 * 4096 * 2304
    assert kda["conv"] == 2 * 3 * 4096 * 4 and kda["scan"] == 32 * (8 * 64 * 128 + 6 * 128 * 128)
    assert flops_kda.expert_layer_flops(model) == pytest.approx(
        2 * 3 * 2304 * 1024 * (8 * 8 / 256 + 1) + 2 * 2304 * 256)
    assert flops_kda.mla_mixer_flops(model, 8192) == pytest.approx(58.2e6 + 83.9e6, rel=0.01)
    total = flops_kda.fwd_flops_per_token(model, 8192)
    assert total == pytest.approx(4 * sum(kda.values()) + flops_kda.mla_mixer_flops(model, 8192)
                                  + 2 * 3 * 2304 * 9216 + 4 * flops_kda.expert_layer_flops(model)
                                  + 2 * 2304 * 20480)
    assert flops_kda.train_flops_per_token(model, 8192) == 3 * total
    # the MLA layer's and the experts' counts are the joyai family's at these widths
    joyai = dict(model, q_lora_rank=0, num_experts_per_tok=8, n_shared_experts=1,
                 n_routed_experts=8, n_routed_experts_published=256)
    assert flops_mla_moe._expert_layer_flops(joyai) == flops_kda.expert_layer_flops(model)


def test_scan_work_arithmetic_by_hand():
    kw = dict(tokens=1000, heads=32, head_dim=128, chunk=64)
    fwd = flops_kda.kda_scan_ops_bytes(**kw)
    # a token and head: K K^T and Q K^T inside the chunk (2*64*128 each), the
    # triangular system applied and P U (2*64*128 each); the state read through k
    # and q and written (2*128*128 each)
    assert fwd["ops"] == 1000 * 32 * (4 * 16_384 + 3 * 32_768)
    # q, k, v, o in bf16 (128 a head each), the log-decay in f32 (128), beta in f32
    assert fwd["bytes"] == 1000 * 32 * (2 * 4 * 128 + 4 * 128 + 4)
    bwd = flops_kda.kda_scan_ops_bytes(**kw, backward=True)
    assert bwd["ops"] == 2 * fwd["ops"] + 1000 * 32 * 2 * 16_384
    # q, k, v, do in, dq, dk, dv out (bf16); g in and dg out (f32); beta and dbeta
    assert bwd["bytes"] == 1000 * 32 * (2 * 7 * 128 + 4 * 2 * 128 + 8)
    # at the cell's size a forward is bound by its bytes: 0.218 ms of operations, 0.492 of bytes
    p = peaks.peaks_for("TPU v5e")
    one = flops_kda.kda_scan_ops_bytes(**dict(kw, tokens=8192))
    assert one["ops"] / p["bf16_flops"] == pytest.approx(0.218e-3, rel=0.01)
    assert one["bytes"] / p["hbm_bytes_per_s"] == pytest.approx(0.492e-3, rel=0.01)
    assert flops_kda.kda_scan_flops(_model(_config()[1])) * 1000 == fwd["ops"]


# ------------------------------------------------------------------ reducers
def _trace(events):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [["bench.window", 0, 10_000]]}]}]}


def test_scan_roofline_reads_the_module_scope_against_the_least_work(monkeypatch):
    trace = _trace([["%fusion.1 = f32[] fusion()", 0, 1000], ["%fusion.2 = f32[] fusion()", 1000, 3000],
                    ["%fusion.3 = f32[] fusion()", 4000, 2000], ["%fusion.4 = f32[] fusion()", 6000, 500],
                    # three flash forward calls in a window of two steps: no
                    # reader counts steps by them (test_step_window.py)
                    ["%flash_fwd.7 = bf16[] custom-call()", 6500, 100],
                    ["%flash_fwd.7 = bf16[] custom-call()", 6600, 100],
                    ["%flash_fwd.8 = bf16[] custom-call()", 6700, 100]])
    scope_map = {"fusion.1": "jit(step_fn)/while/body/jvp(kda)/kda.scan/while/body/dot_general",
                 "fusion.2": "jit(step_fn)/transpose(jvp(kda))/kda.scan/exp",
                 "fusion.3": "jit(step_fn)/while/body/jvp(kda)/kda.proj/dot_general",
                 "fusion.4": "jit(step_fn)/kda.scanner/mul"}
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": scope_map)
    obs = {"trace": trace, "peaks": peaks.peaks_for("TPU v5e"), "chips": 1, "log": lambda m: None,
           "shapes": {"traced_steps": 2, "attention_tokens": 2 * 8192}, "model": _model(_config()[1])}
    with open(os.path.join(BENCH, "layer_metrics", "kda_scan_roofline.json")) as f:
        args = json.load(f)["args"]
    kw = dict(tokens=8192, heads=32, head_dim=128, chunk=64)
    fwd = flops.roofline_seconds(flops_kda.kda_scan_ops_bytes(**kw), obs["peaks"])["seconds"]
    bwd = flops.roofline_seconds(flops_kda.kda_scan_ops_bytes(**kw, backward=True), obs["peaks"])["seconds"]
    under_scope = (1000 + 3000) * 1e-9  # the trace's device time under kda.scan
    # four KDA layers, forward twice and backward once, for the 2 whole steps
    assert ssm_scan_roofline.reduce(obs, args) == pytest.approx(
        100 * 2 * 4 * (2 * fwd + bwd) / under_scope)
    with open(os.path.join(BENCH, "layer_metrics", "kda_ms.train_kda.json")) as f:
        cut = json.load(f)
    assert cut["reducer"] == "scope_cut_ms"
    assert scope_cut_ms.reduce(obs, cut["args"]) == pytest.approx((1000 + 3000 + 2000) * 1e-9 / 2 * 1e3)
    # nothing under the scope, no trace, or a program without a scope map (the parent): left out
    assert ssm_scan_roofline.reduce(obs, dict(args, name="kda.nothing")) is None
    assert ssm_scan_roofline.reduce(dict(obs, trace=None), args) is None
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": None)
    assert ssm_scan_roofline.reduce(obs, args) is None


def test_every_new_metric_is_in_the_cells_traced_line_and_no_other_cells():
    names = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", CELL)}
    want = {"kda_scan_roofline"} | {f"{base}.train_kda" for base in (
        "kda_ms", "kda_scan_ms", "kda_proj_ms", "kda_reset_chunk_share", "mfu_pct", "mla_proj_ms",
        "moe_ms", "moe_held_share", "moe_dropped_share", "moe_load_max_over_mean",
        "mla_flash_fwd_roofline", "mla_flash_bwd_roofline", "gmm_fwd_roofline", "gmm_bwd_roofline",
        "step_ms", "padding_share", "device_idle_share", "peak_hbm_gb", "recompute_ms",
        "lm_head_loss_ms", "optimizer_ms", "unattributed_ms", "data_wait_share", "host_busy_share",
        "attn_kernel_ms", "flash_tiles_live_share")}
    setup = {f"{base}.setup_kda" for base in ("launch_to_trainer_s", "trainer_build_s", "compile_s")}
    assert names == want | setup
    for m in MANIFEST["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == ("setup_s" if m["name"] in setup else "train_tokens_per_s")
            with open(os.path.join(BENCH, "layer_metrics", f"{m['name']}.json")) as f:
                reader = json.load(f)
            assert (reader["layer"], reader["unit"], reader["source"]) == (m["layer"], m["unit"], m["source"])
            assert os.path.exists(os.path.join(BENCH, "reducers", reader["reducer"] + ".py"))
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not names & {m["name"] for m in bench_run.cell_metrics(
                MANIFEST, "per_layer", other["name"])}
    e2e = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    # the shared readers' arguments are the joyai cell's, letter for letter
    for base in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline"):
        with open(os.path.join(BENCH, "layer_metrics", f"{base}.json")) as f, \
                open(os.path.join(BENCH, "layer_metrics", f"{base}.train_kda.json")) as g:
            assert json.load(f)["args"] == json.load(g)["args"]


# ------------------------------------------------------------ planted faults
def _drive_in_process(monkeypatch, capsys, seed=3000000007):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                         "--trace", "0", "--rehearsal"])
    said = capsys.readouterr()
    assert rc == 0
    # the result line is standard output's last; the checks are standard error's
    return json.loads(said.out.strip().splitlines()[-1]), said.out + said.err


def test_a_reset_left_out_is_not_correct(monkeypatch, capsys):
    """The recurrence's state carried across a document's start (the convs'
    taps still cut): the token-by-token reference starts every document from
    nothing, the rehearsal's rows hold boundaries inside a chunk, and the
    comparison says so."""
    from veomni_tpu.models import kimi_linear

    real = kimi_linear.ops.kda_scan
    monkeypatch.setattr(kimi_linear.ops, "kda_scan",
                        lambda q, k, v, g, beta, seg: real(q, k, v, g, beta, None))
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm|param_change_norm)", out)
    assert re.search(r"reference: step 1 follows rows whose documents start at \[\[\d+", out)


def test_rotary_left_on_is_not_correct(monkeypatch, capsys):
    """The NoPE switch: the MLA layer handed real rotary tables (``mla_use_nope``
    read as false) where the published layer turns nothing."""
    from veomni_tpu.models import kimi_linear

    real = kimi_linear._mla_tables

    def rotary(cfg, position_ids, shape):
        import dataclasses

        return real(dataclasses.replace(cfg, mla_use_nope=False), position_ids, shape)

    monkeypatch.setattr(kimi_linear, "_mla_tables", rotary)
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm|param_change_norm)", out)
