"""What the benchmark had stands: one case a cell, against the one snapshot
PR 43 recorded (before it four tests hashed the benchmark's files against
three snapshots, and one of them pinned the list of cells to two).

A case holds that its cell's entry, its configuration's entry and file, its
limits and its traffic are as recorded, that each of its own per-layer
metrics still has its ``name``, ``unit``, ``better`` and ``moves``, that the
end-to-end metrics keep their bounds, and that the recorded START of every
``workloads`` list stands. Nothing here pins a list's end or a later entry:
the next cell appends itself to a reader's list and breaks no case.

Only a ``benchmark`` PR, which may change what the benchmark has, records
anew, and then names its tag in ``TAG``:

    python3 benchmark/tests/test_unchanged.py <tag>

writes ``data/<tag>_manifest.json`` (each cell's entry, its configuration's
entry, its own per-layer metrics' ``name``, ``unit``, ``better``, ``moves``
and ``workloads``, the end-to-end metrics with their bounds, ``command``,
``paths``, ``run_seconds``) and ``data/<tag>_files.sha256.json`` (the digests
of each cell's configuration, limits and traffic files).
"""

import hashlib

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

TAG = "pr43"
MANIFEST = bench_run.load_manifest()


def cell_files(config: dict, cell: dict) -> list:
    """The files that are one cell's: of its configuration's entry and its own."""
    return [config["file"], f"benchmark/limits/{cell['name']}.json",
            f"benchmark/traffic/{cell['traffic']}.json"]


def digest(rel: str) -> str:
    with open(os.path.join(ROOT, rel), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def pins(manifest: dict) -> dict:
    cells = {}
    for cell in manifest["workloads"]:
        own = [{k: m[k] for k in ("name", "unit", "better", "moves", "workloads")}
               for m in manifest["per_layer"] if cell["name"] in m.get("workloads", ())]
        cells[cell["name"]] = {
            "workload": cell,
            "config": bench_run.find(manifest["configs"], cell["config"], "configuration"),
            "metrics": own}
    return {**{k: manifest[k] for k in ("command", "paths", "run_seconds", "end_to_end")},
            "cells": cells}


def record(tag: str) -> None:
    doc = pins(MANIFEST)
    with open(os.path.join(HERE, "data", f"{tag}_manifest.json"), "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    files = sorted({rel for c in doc["cells"].values()
                    for rel in cell_files(c["config"], c["workload"])})
    with open(os.path.join(HERE, "data", f"{tag}_files.sha256.json"), "w") as f:
        json.dump({rel: digest(rel) for rel in files}, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    record(sys.argv[1])
    sys.exit(0)

with open(os.path.join(HERE, "data", f"{TAG}_manifest.json")) as f:
    RECORDED = json.load(f)
with open(os.path.join(HERE, "data", f"{TAG}_files.sha256.json")) as f:
    DIGESTS = json.load(f)


def _starts_with(now: list, was: list) -> bool:
    return now[:len(was)] == was


@pytest.mark.parametrize("cell", list(RECORDED["cells"]))
def test_what_the_benchmark_had_stands(cell):
    was = RECORDED["cells"][cell]
    assert bench_run.find(MANIFEST["workloads"], cell, "cell") == was["workload"]
    assert bench_run.find(MANIFEST["configs"], was["workload"]["config"],
                          "configuration") == was["config"]
    for rel in cell_files(was["config"], was["workload"]):
        assert digest(rel) == DIGESTS[rel], f"{rel} changed"
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert len(was["metrics"]) >= 19
    for m in was["metrics"]:
        now = by_name[m["name"]]
        assert {k: now[k] for k in ("unit", "better", "moves")} == \
            {k: m[k] for k in ("unit", "better", "moves")}, m["name"]
        assert _starts_with(now["workloads"], m["workloads"]), m["name"]
    for key in ("command", "paths", "run_seconds"):
        assert MANIFEST[key] == RECORDED[key]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in RECORDED["end_to_end"]:
        now = dict(e2e[m["name"]])
        if "workloads" in m:
            assert _starts_with(now["workloads"], m["workloads"]), m["name"]
            now["workloads"] = m["workloads"]
        assert now == m
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert _starts_with(cells, list(RECORDED["cells"]))
