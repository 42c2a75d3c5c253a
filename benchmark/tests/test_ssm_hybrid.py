"""Tests of what the granite-4.0-h-micro configuration added to the benchmark
as files: the configuration's cut, ``flops_ssm.py`` against the program's
counter and by hand, the scan's roofline reducer on a synthetic trace, which
cell reads which metric, that nothing the benchmark had was changed, and the
cell's limits against planted faults (a reset left out, the gate applied
after the norm).
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

from benchmark import flops, flops_ssm, peaks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.reducers import scope_cut_ms, ssm_scan_roofline  # noqa: E402

CELL = "granite_4_0_h_micro.train_packed_8k_x1"
MANIFEST = bench_run.load_manifest()
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def _config():
    entry = bench_run.find(MANIFEST["configs"], "granite_4_0_h_micro", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _model(doc):
    return {k: v for k, v in doc.items() if not isinstance(v, (dict, list))}


# ------------------------------------------------------------- configuration
def test_configuration_states_its_cut_and_keeps_every_width():
    entry, doc = _config()
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert (doc["num_hidden_layers"], doc["vocab_size"]) == (10, 12544)
    assert doc["vocab_size"] * 8 == 100352
    # the published keys, letter for letter (config.json of ibm-granite/granite-4.0-h-micro)
    published = dict(
        hidden_size=2048, intermediate_size=8192, shared_intermediate_size=8192,
        num_attention_heads=32, num_key_value_heads=8, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256,
        mamba_conv_bias=True, mamba_proj_bias=False, attention_multiplier=0.015625,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        position_embedding_type="nope", num_local_experts=0, num_experts_per_tok=0,
        rms_norm_eps=1e-05, tie_word_embeddings=True, model_type="granitemoehybrid",
        max_position_embeddings=131072, hidden_act="silu", attention_bias=False)
    assert {k: doc[k] for k in published} == published
    # the published pattern whole, and the one period that is run: attention at place 5
    assert doc["layer_types"] == PERIOD * 4
    assert doc["layer_types_run"].split(",") == PERIOD
    over = doc["program_overrides"]
    assert over["layer_types"] == PERIOD
    for key in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_chunk_size", "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling", "position_embedding_type",
                "shared_intermediate_size"):
        assert over[key] == doc[key], key
    assert "pipeline stage" in doc["deployment"] and "8" in doc["deployment"]
    assert {"weights", "conv weights", "head_dim"} <= set(doc["assumed"])
    # the issue's cell: AdamW 3e-4 constant, f32 state, bf16 compute, recompute `nothing`
    train = doc["train"]
    assert (train["optimizer"], train["lr"], train["lr_decay_style"]) == ("adamw", 3e-4, "constant")
    assert train["max_grad_norm"] == 1.0 and train["param_dtype"] == "float32" and train["bf16"]
    assert train["gradient_checkpointing_policy"] == "nothing"
    from benchmark import traffic

    mix = traffic.load_mix("train_packed_8k_x1")
    assert (mix["seq_len"], mix["rows_per_chip"], mix["dyn_bsz"], mix["dyn_bsz_buffer_size"],
            mix["n_docs"], mix["size_seed"]) == (8192, 1, True, 200, 2500, 20260929)
    assert mix["doc_tokens"] == {"median": 1024, "sigma": 1.2, "min": 64, "max": 8192}
    cell = bench_run.find(MANIFEST["workloads"], CELL, "cell")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_flops_agree_with_the_programs_counter_and_the_count_of_parameters():
    from benchmark.reference import ssm_hybrid
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.utils.count_flops import FlopsCounter

    _, doc = _config()
    model = _model(doc)
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "tie_word_embeddings")
    cfg = build_config(model["model_type"], **{k: model[k] for k in keys},
                       **doc["program_overrides"])
    counter = FlopsCounter.from_config(cfg)
    for seq in (512, 8192):
        assert flops_ssm.fwd_flops_per_token(model, seq) == pytest.approx(
            counter.flops_per_token_fwd(seq), rel=1e-12)
        assert flops_ssm.train_flops_per_token(model, seq) * 7 == pytest.approx(
            counter.batch_flops(7, seq), rel=1e-12)
    # the issue's arithmetic: forward about 1.6 GFLOP a token at 8192 (MLPs 1.01,
    # the mixers' projections 0.47, head 0.05, scan 0.04; with the attention
    # layer's projections and scores 1.616), 4.85 in training
    mixer = flops_ssm.ssm_mixer_flops(model)
    assert 9 * mixer["scan"] == pytest.approx(0.0383e9, rel=0.01)
    assert 9 * mixer["proj"] == pytest.approx(0.465e9, rel=0.01)
    assert flops_ssm.train_flops_per_token(model, 8192) == pytest.approx(4.85e9, rel=0.01)
    shapes = ssm_hybrid.param_shapes(model)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 772_160_448
    per_mamba = sum(int(np.prod(s[2:])) for k, s in shapes.items() if k.startswith("mamba_layers."))
    assert per_mamba == 76_182_976 and shapes["mamba_layers.in_proj"] == (1, 9, 2048, 8512)


def test_scan_work_arithmetic_by_hand():
    kw = dict(tokens=1000, heads=64, head_dim=64, state=128, groups=1, chunk=256)
    fwd = flops_ssm.ssd_scan_ops_bytes(**kw)
    # a token: C B^T 2*256*128, times dt x 2*256*4096, the state read and written 2*2*4096*128
    assert fwd["ops"] == 1000 * (65_536 + 2_097_152 + 2_097_152)
    # x and y in bf16 (4096 each), B and C (128 each), dt in f32 (64)
    assert fwd["bytes"] == 1000 * (2 * (2 * 4096 + 2 * 128) + 4 * 64)
    bwd = flops_ssm.ssd_scan_ops_bytes(**kw, backward=True)
    assert bwd["ops"] == 2 * fwd["ops"] + 1000 * 65_536
    assert bwd["bytes"] == 1000 * (2 * (3 * 4096 + 4 * 128) + 4 * 2 * 64)
    # at the cell's size a forward is all but balanced: 0.177 ms of operations, 0.172 of bytes
    p = peaks.peaks_for("TPU v5e")
    one = flops_ssm.ssd_scan_ops_bytes(**dict(kw, tokens=8192))
    assert one["ops"] / p["bf16_flops"] == pytest.approx(0.177e-3, rel=0.01)
    assert one["bytes"] / p["hbm_bytes_per_s"] == pytest.approx(0.1716e-3, rel=0.01)
    # the flash kernels' count is the dense family's, a call at a time
    for back in (False, True):
        whole = flops.flash_attention_ops_bytes(pairs=10, tokens=4, num_q_heads=4, num_kv_heads=2,
                                                head_dim=16, layers=1, backward=True)
        part = flops.flash_attention_ops_bytes(pairs=10, tokens=4, num_q_heads=4, num_kv_heads=2,
                                               head_dim=16, layers=1, backward=False)
        want = {k: whole[k] - part[k] for k in whole} if back else part
        assert flops_ssm.gqa_flash_ops_bytes(pairs=10, tokens=4, num_q_heads=4, num_kv_heads=2,
                                             head_dim=16, backward=back) == want


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
    scope_map = {"fusion.1": "jit(step_fn)/while/body/jvp(ssm)/ssm.scan/while/body/dot_general",
                 "fusion.2": "jit(step_fn)/transpose(jvp(ssm))/ssm.scan/exp",
                 "fusion.3": "jit(step_fn)/while/body/jvp(ssm)/ssm.proj/dot_general",
                 "fusion.4": "jit(step_fn)/ssm.scanner/mul"}
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": scope_map)
    model = {"mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
             "mamba_chunk_size": 256, "layer_types_run": "mamba,attention,mamba"}
    obs = {"trace": trace, "peaks": peaks.peaks_for("TPU v5e"), "chips": 1, "log": lambda m: None,
           "shapes": {"traced_steps": 2, "attention_tokens": 2 * 8192}, "model": model}
    with open(os.path.join(BENCH, "layer_metrics", "ssm_scan_roofline.json")) as f:
        args = json.load(f)["args"]
    kw = dict(tokens=8192, heads=64, head_dim=64, state=128, groups=1, chunk=256)
    fwd = flops.roofline_seconds(flops_ssm.ssd_scan_ops_bytes(**kw), obs["peaks"])["seconds"]
    bwd = flops.roofline_seconds(flops_ssm.ssd_scan_ops_bytes(**kw, backward=True), obs["peaks"])["seconds"]
    under_scope = (1000 + 3000) * 1e-9  # the trace's device time under ssm.scan
    assert ssm_scan_roofline.reduce(obs, args) == pytest.approx(
        100 * 2 * 2 * (2 * fwd + bwd) / under_scope)  # 2 steps x 2 layers, over both steps' time
    assert scope_cut_ms.reduce(obs, {"name": "ssm"}) == pytest.approx((1000 + 3000 + 2000) * 1e-9 / 2 * 1e3)
    # nothing under the scope, no trace, or a program without a scope map (the parent): left out
    assert ssm_scan_roofline.reduce(obs, dict(args, name="ssm.nothing")) is None
    assert ssm_scan_roofline.reduce(dict(obs, shapes=dict(obs["shapes"], traced_steps=None)), args) is None
    assert ssm_scan_roofline.reduce(dict(obs, trace=None), args) is None
    monkeypatch.setattr(scope_cut_ms.sc, "program_scope_map", lambda site="train_step": None)
    assert ssm_scan_roofline.reduce(obs, args) is None


def test_every_new_metric_is_in_the_cells_traced_line_and_no_other_cells():
    names = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", CELL)}
    want = {"ssm_ms.train_ssm", "ssm_scan_ms.train_ssm", "ssm_proj_ms.train_ssm", "ssm_scan_roofline",
            "ssm_reset_chunk_share.train_ssm", "mfu_pct.train_ssm", "flash_fwd_roofline.train_ssm",
            "flash_bwd_roofline.train_ssm"}
    want |= {f"{base}.train_ssm" for base in (
        "step_ms", "padding_share", "device_idle_share", "peak_hbm_gb", "recompute_ms",
        "lm_head_loss_ms", "optimizer_ms", "unattributed_ms", "data_wait_share", "host_busy_share",
        "attn_kernel_ms", "flash_tiles_live_share")}
    setup = {f"{base}.setup_ssm" for base in ("launch_to_trainer_s", "trainer_build_s", "compile_s")}
    assert names == want | setup
    for m in MANIFEST["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == ("setup_s" if m["name"] in setup else "train_tokens_per_s")
            with open(os.path.join(BENCH, "layer_metrics", f"{m['name']}.json")) as f:
                reader = json.load(f)
            assert (reader["layer"], reader["unit"], reader["source"]) == (m["layer"], m["unit"], m["source"])
    for other in ("qwen3_0p6b.train_packed_4k", "joyai_llm_flash.train_packed_8k"):
        assert not names & {m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer", other)}
    e2e = {m["name"] for m in bench_run.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}


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
    """The scan's state carried across a document's start (the conv's taps
    still cut): the token-by-token reference starts every document from
    nothing, and the comparison says so."""
    from veomni_tpu.models import granite_hybrid

    real = granite_hybrid.ops.ssd_scan
    monkeypatch.setattr(granite_hybrid.ops, "ssd_scan",
                        lambda *a, segment_ids=None, **kw: real(*a, segment_ids=None, **kw))
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm|param_change_norm)", out)


def test_the_gate_applied_after_the_norm_is_not_correct(monkeypatch, capsys):
    """``rmsnorm(y) * silu(z)``, the order of the sibling family
    (``qwen3_next``), where this one norms ``y * silu(z)``."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import granite_hybrid

    def norm_then_gate(y, z, weight, cfg):
        y = y.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        return weight * (y * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)

    monkeypatch.setattr(granite_hybrid, "_gated_norm", norm_then_gate)
    line, out = _drive_in_process(monkeypatch, capsys)
    assert line["correct"] is False
    assert re.search(r"check FAIL (loss|first_grad_norm|param_change_norm)", out)
