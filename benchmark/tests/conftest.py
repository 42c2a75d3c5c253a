"""Two of ``test_benchmark.py``'s cases a cell are cases of a TRAIN cell: they
look for the train step's compared numbers by name (``loss_1_rel_gap``,
``first_grad_norm_worst_leaf``). A serving cell compares other numbers, and
its own file holds the same two things to them (``test_serve_closed_loop.py``:
the rehearsal of both kinds of run, the control that has to fail), so for a
cell of a serving traffic kind those two cases are not collected. A file that
is there is not edited (PERF.md, PR 45)."""

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_SHAPED = ("test_cell_rehearses_on_the_cpu[", "test_control_in_lower_precision_is_not_correct[")


def _serving_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = []
    for cell in manifest["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            if json.load(f)["kind"].startswith("serve_"):
                out.append(cell["name"])
    return out


def pytest_collection_modifyitems(config, items):
    serving = _serving_cells()
    drop = [it for it in items
            if any(t in it.nodeid for t in TRAIN_SHAPED) and any(c in it.nodeid for c in serving)]
    if drop:
        config.hook.pytest_deselected(items=drop)
        items[:] = [it for it in items if it not in drop]
