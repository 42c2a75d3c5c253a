"""A person's first look at a trace, not part of a run:

    python3 benchmark/tools/trace_summary.py <cell> [<out.json>]

reads the newest trace that a ``--trace 1`` run of ``<cell>`` left under
``.bench_work/<cell>/trace`` and writes, for every plane and line, the number
of events and the names that took most time.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace as tr  # noqa: E402


def summary(trace: dict, top: int = 40) -> dict:
    doc = {"planes": []}
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            tot = {}
            for name, _, dur in ln["events"]:
                tot[name] = tot.get(name, 0) + dur
            lines.append({"name": ln["name"], "events": len(ln["events"]),
                          "top": sorted(tot.items(), key=lambda kv: -kv[1])[:top]})
        doc["planes"].append({"name": p["name"], "lines": lines})
    return doc


if __name__ == "__main__":
    path = tr.newest_xplane(os.path.join(ROOT, ".bench_work", sys.argv[1], "trace"))
    text = json.dumps(summary(tr.load_xplane(path)), indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
