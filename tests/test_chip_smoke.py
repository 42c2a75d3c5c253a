"""The flag and compile-cache channels, the peak tables, and
``chip_smoke.py``'s own behaviour off the chip: it must fail at its device
check, and its phase functions must pass at toy size.
"""

import json
import os
import subprocess
import sys

import jax
import pytest
from described_chip import v5e  # noqa: F401

import chip_smoke  # (repo root is on sys.path via conftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE_UNDER_FLAGS = """
import os, sys
os.environ["TPU_LOG_DIR"] = "disabled"
os.environ["VEOMNI_COMPILATION_CACHE"] = "0"
sys.path.insert(0, sys.argv[1])
from veomni_tpu.utils.xla_flags import apply_performance_flags
assert apply_performance_flags()
os.environ["LIBTPU_INIT_ARGS"] += sys.argv[2]
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
dev = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
x = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=SingleDeviceSharding(dev))
jax.jit(lambda a: a @ a).lower(x).compile()
print("COMPILED_UNDER", os.environ["LIBTPU_INIT_ARGS"])
"""


@pytest.mark.parametrize("extra,ok", [("", True), (" --xla_tpu_no_such_flag=true", False)],
                         ids=["ours", "bogus"])
def test_libtpu_takes_the_perf_flags(v5e, extra, ok):
    """The TPU compiler reads LIBTPU_INIT_ARGS when it is first asked for a
    topology and kills the process on a flag it does not know: so this runs
    in a child, and a flag the installed libtpu rejects fails here and not
    on the chip."""
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_UNDER_FLAGS, REPO, extra],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=120,
    )
    assert ("COMPILED_UNDER --xla_tpu_" in proc.stdout) is ok, proc.stderr[-2000:]
    if not ok:
        assert "xla_tpu_no_such_flag" in proc.stdout + proc.stderr


def test_perf_flags_go_to_libtpu_init_args_once(monkeypatch):
    from veomni_tpu.utils.xla_flags import _PERF_FLAGS, apply_performance_flags

    monkeypatch.setenv("VEOMNI_COMPILATION_CACHE", "0")  # flags only
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv(
        "LIBTPU_INIT_ARGS", "--xla_tpu_enable_latency_hiding_scheduler=false")
    assert apply_performance_flags() is True
    assert apply_performance_flags() is True  # idempotent
    assert "--xla_tpu_" not in os.environ["XLA_FLAGS"]
    toks = os.environ["LIBTPU_INIT_ARGS"].split()
    # the caller's own value stands; every flag is there exactly once
    assert "--xla_tpu_enable_latency_hiding_scheduler=false" in toks
    assert sorted(t.split("=")[0] for t in toks) == sorted(
        f.split("=")[0] for f in _PERF_FLAGS)
    monkeypatch.setenv("VEOMNI_XLA_PERF_FLAGS", "0")
    monkeypatch.delenv("LIBTPU_INIT_ARGS")
    assert apply_performance_flags() is False
    assert "LIBTPU_INIT_ARGS" not in os.environ


@pytest.mark.parametrize("from_env", [True, False], ids=["env_dir", "checkout_dir"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, from_env):
    from veomni_tpu.utils import xla_flags

    updates = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv("VEOMNI_COMPILATION_CACHE", raising=False)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert xla_flags.enable_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates  # JAX reads the variable
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert xla_flags.enable_compilation_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_peak_tables_raise_on_unlisted_accelerator(monkeypatch):
    from veomni_tpu.utils import device

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v99 mega"

    monkeypatch.setattr(device.jax, "devices", lambda: [FakeDevice()])
    device._device_peaks.cache_clear()
    try:
        for fn in (device.get_device_peak_flops, device.get_device_peak_bandwidth,
                   device.get_device_peak_interconnect_bandwidth):
            with pytest.raises(KeyError, match="TPU v99 mega"):
                fn()
        FakeDevice.device_kind = "TPU v5 lite"
        assert device.get_device_peak_flops() == 197e12
        assert device.get_device_peak_bandwidth() == 819e9
    finally:
        device._device_peaks.cache_clear()


def test_chip_smoke_fails_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "VEOMNI_COMPILATION_CACHE": "0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert chip_smoke.NO_CHIP_MSG.format(platform="cpu") in proc.stderr
    assert '"ok"' not in proc.stdout


TOY_DENSE = {
    "model_type": "qwen3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "qk_norm": True,
    "tie_word_embeddings": True,
}


@pytest.fixture
def toy_run(monkeypatch):
    """The phases call the entry points, which set up flags and the compile
    cache: keep both out of the test process."""
    monkeypatch.setenv("VEOMNI_COMPILATION_CACHE", "0")
    monkeypatch.setenv("VEOMNI_XLA_PERF_FLAGS", "0")
    monkeypatch.chdir(REPO)  # phase_train changes directory; undo it


def test_smoke_phase_kernels_at_toy_size(toy_run):
    doc = chip_smoke.phase_kernels(
        flash=dict(b=1, s=256, hq=2, hkv=1, d=64),
        gmm=(dict(m=256, k=128, n=128, e=4), dict(m=256, k=128, n=128, e=8)),
    )
    assert doc["flash"]["err"]["dq"] <= chip_smoke.KERNEL_TOL
    assert [g["groups"]["empty"] > 0 for g in doc["gmm"]] == [True, True]
    json.dumps(doc)  # a phase's result is one JSON line


def test_smoke_phase_train_at_toy_size(toy_run):
    doc = chip_smoke.phase_train(overrides=[
        "--model.config_overrides=" + json.dumps(TOY_DENSE),
        "--data.max_seq_len=256", "--train.micro_batch_size=2", "--train.lr=1e-2",
    ])
    assert doc["steps"] == 8 and doc["train_step_traces"] == 1
    assert doc["losses"][-1] < doc["losses"][0]
    assert doc["resolved"]["attention"] == "xla"  # the CPU's; no kernel claimed
    assert not os.path.exists(os.path.join(REPO, "output", "chip_smoke", "train"))
    json.dumps(doc)


def test_smoke_phase_serve_at_toy_size(toy_run):
    # preset "": the tiny demo model of scripts/serve.py
    doc = chip_smoke.phase_serve(preset="", prompt_lens=(20, 40), n_requests=6,
                                 shared_prefix=16, max_new=8)
    assert doc["completed"] == 6 and doc["prefix_hits"] > 0
    assert doc["tokens_equal_to_greedy_generate"] == doc["tokens_total"] == 48
    json.dumps(doc)


def test_smoke_phase_serve_catches_a_wrong_token(toy_run, monkeypatch):
    from veomni_tpu.models import decode

    real = decode.greedy_generate

    def off_by_one(params, cfg, prompt, **kw):
        ids = real(params, cfg, prompt, **kw)
        ids[len(prompt) + 3] = (ids[len(prompt) + 3] + 1) % cfg.vocab_size
        return ids

    monkeypatch.setattr(decode, "greedy_generate", off_by_one)
    with pytest.raises(AssertionError, match="logit gap"):
        chip_smoke.phase_serve(preset="", prompt_lens=(20,), n_requests=2,
                               shared_prefix=16, max_new=8)


def test_smoke_phase_multichip_on_four_virtual_devices(toy_run):
    moe = dict(chip_smoke.MOE_BLOCKS, vocab_size=512, hidden_size=64,
               intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=32)
    doc = chip_smoke.phase_multichip(dense_overrides=TOY_DENSE, dense_seq=64,
                                     dense_rows=2, dense_steps=2, moe=moe,
                                     moe_seq=64, moe_rows=4)
    assert doc["dense"]["four_devices"]["mesh"] == {"fsdp": 2, "ulysses": 2}
    assert doc["moe"]["four_devices"]["mesh"] == {"ep": 2, "fsdp": 2}
    assert doc["moe"]["four_devices"]["collectives"]["all-to-all"] > 0
    json.dumps(doc)
