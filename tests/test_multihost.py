"""Multi-process execution path: 2 processes x 4 virtual CPU devices.

Ports the reference's mp.spawn+gloo distributed test strategy (SURVEY §4):
jax.distributed.initialize via env vars, per-process data sharding,
make_array_from_process_local_data batch assembly, multihost Orbax
save/restore with exact loss-trajectory continuation after a restart.
"""

import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

DRIVER = os.path.join(os.path.dirname(__file__), "tools", "multihost_train.py")


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(nproc, data, out, steps, stop_at=0, timeout=600):
    port = _free_port()
    procs = []
    for pid in range(nproc):
        env = dict(
            os.environ,
            VEOMNI_COORDINATOR_ADDRESS=f"localhost:{port}",
            VEOMNI_NUM_PROCESSES=str(nproc),
            VEOMNI_PROCESS_ID=str(pid),
        )
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(subprocess.Popen(
            [sys.executable, DRIVER, data, out, str(steps), str(stop_at)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    results = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"rank failed:\n{stderr[-3000:]}"
        results.append(json.loads(stdout.strip().splitlines()[-1]))
    return sorted(results, key=lambda r: r["process"])


@pytest.fixture(scope="module")
def data_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("mh") / "data.jsonl"
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(512):
            ln = int(rng.integers(16, 100))
            f.write(json.dumps(
                {"input_ids": rng.integers(0, 256, ln).tolist()}) + "\n")
    return str(path)


LOAD_DRIVER = os.path.join(os.path.dirname(__file__), "tools", "multihost_load.py")
VLM_DRIVER = os.path.join(os.path.dirname(__file__), "tools", "multihost_vlm.py")


def test_two_process_vlm_matches_single_process(tmp_path):
    """Packed-VLM multihost data assembly: a 2-process run (per-row patch
    budgets, each process assembles only its rows) reproduces the
    1-process (global packed buffer) loss trajectory exactly. Dataset size
    == global batch, so every step sees the same sample set in both
    layouts. Reference: per-rank multimodal slicing,
    ``data/data_collator.py:317-431``."""
    rng = np.random.default_rng(0)
    data = tmp_path / "vlm.jsonl"
    with open(data, "w") as f:
        for i in range(8):  # == global micro-batch (mb 1 x dp 8)
            f.write(json.dumps({
                "input_ids": rng.integers(11, 256, int(rng.integers(8, 24))).tolist(),
                "images": [rng.random((8 + 4 * (i % 2), 8, 3)).tolist()],
            }) + "\n")

    def launch(nproc, local_devices, out):
        port = _free_port()
        procs = []
        for pid in range(nproc):
            env = dict(os.environ)
            if nproc > 1:
                env.update(
                    VEOMNI_COORDINATOR_ADDRESS=f"localhost:{port}",
                    VEOMNI_NUM_PROCESSES=str(nproc),
                    VEOMNI_PROCESS_ID=str(pid),
                )
            env.pop("PYTEST_CURRENT_TEST", None)
            procs.append(subprocess.Popen(
                # one shared output_dir: orbax multiprocess saves coordinate
                # via global barriers keyed on the path
                [sys.executable, VLM_DRIVER, str(data), "3",
                 str(local_devices), out],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            ))
        results = []
        for p in procs:
            stdout, stderr = p.communicate(timeout=900)
            assert p.returncode == 0, f"rank failed:\n{stderr[-3000:]}"
            results.append(json.loads(stdout.strip().splitlines()[-1]))
        return results

    single = launch(1, 8, str(tmp_path / "s"))[0]
    double = launch(2, 4, str(tmp_path / "d"))
    assert single["devices"] == 8 and double[0]["devices"] == 8
    assert not single["per_row"] and double[0]["per_row"]
    assert double[0]["losses"] == double[1]["losses"]
    np.testing.assert_allclose(
        double[0]["losses"], single["losses"], rtol=2e-4,
    )


def test_two_process_ep_sliced_weight_load(tmp_path):
    """Streamed HF load on a 2-process EP mesh: each process must read only
    the expert rows its local devices hold (reference EP-sliced per-rank
    reads, ``module_utils.py:530,867``), and every placed shard must match
    the on-disk tensor bit-for-bit."""
    import jax
    import numpy as np

    from veomni_tpu.models import TransformerConfig, build_foundation_model

    cfg = TransformerConfig(
        model_type="qwen3_moe", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, qk_norm=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
    )
    model = build_foundation_model(config=cfg)
    params = model.init(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "hf_ckpt")
    model.save_hf(ckpt, params=params)

    def run(extra):
        port = _free_port()
        procs = []
        for pid in range(2):
            env = dict(
                os.environ,
                VEOMNI_COORDINATOR_ADDRESS=f"localhost:{port}",
                VEOMNI_NUM_PROCESSES="2",
                VEOMNI_PROCESS_ID=str(pid),
            )
            env.pop("PYTEST_CURRENT_TEST", None)
            procs.append(subprocess.Popen(
                [sys.executable, LOAD_DRIVER, ckpt, "4"] + extra,
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            ))
        results = []
        for p in procs:
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, f"rank failed:\n{stderr[-3000:]}"
            results.append(json.loads(stdout.strip().splitlines()[-1]))
        return sorted(results, key=lambda r: r["process"])

    results = run([])
    # total expert bytes on disk: 3 tensors x L x E x (h x ffn) x f32
    total_expert = 3 * cfg.num_hidden_layers * cfg.num_experts * (
        cfg.hidden_size * cfg.moe_intermediate_size
    ) * 4
    for r in results:
        assert r["shards_match_disk"], r
        # ep=4 over 2 processes: each holds half the experts; a full-model
        # read (the failure mode this test exists to catch) would be ~2x
        assert r["expert_bytes"] <= 0.6 * total_expert, (
            r, total_expert,
        )
        assert r["expert_bytes"] >= 0.4 * total_expert, (
            r, total_expert,
        )

    # rank0-broadcast mode: replicated params are read once on process 0 and
    # shipped over the interconnect — rank 1's filesystem traffic drops
    bres = run(["broadcast"])
    for r in bres:
        assert r["shards_match_disk"], r
    assert bres[1]["other_bytes"] < results[1]["other_bytes"], (bres, results)


def test_two_process_training_and_resume(data_path, tmp_path):
    out = str(tmp_path / "out")
    # uninterrupted 8-step reference run
    ref = _launch(2, data_path, str(tmp_path / "ref"), steps=8)
    assert ref[0]["devices"] == 8
    assert ref[0]["global_step"] == 8
    # both processes observe the same (globally reduced) loss
    assert ref[0]["losses"] == ref[1]["losses"]

    # preempted run: stop after 4 (checkpoint at 4), restart to 8
    first = _launch(2, data_path, out, steps=8, stop_at=4)
    assert first[0]["global_step"] == 4
    second = _launch(2, data_path, out, steps=8)
    assert second[0]["global_step"] == 8
    # trajectory after resume continues the uninterrupted run exactly
    assert second[0]["losses"] == ref[0]["losses"][4:], (
        f"resumed {second[0]['losses']} != ref tail {ref[0]['losses'][4:]}"
    )
