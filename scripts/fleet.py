"""Merge per-rank run artifacts into one cluster timeline + fleet verdict.

Every rank of a run leaves three artifact families in its output dir:
``metrics_rank<R>.jsonl`` (one line per sync step, wall-stamped),
``heartbeat-<R>.json`` (last progress marker, rewritten in place —
``observability/fleet.py``) and ``postmortem-<R>.json`` (flight-recorder
dump with a wall/perf anchor pair — ``observability/flight_recorder.py``).
Each alone is rank-local; this tool merges all three onto one shared wall
axis (post-mortem events via the PR 6 anchor-pair mapping, reused from
``scripts/postmortem.py``) and prints:

1. a per-rank summary — last metrics step, heartbeat age + phase,
   post-mortem reason;
2. a fleet verdict — which rank's heartbeat is stalest, which rank's last
   progress step lags the fleet, and (when the skew exchange ran) which
   rank the live telemetry already named slowest;
3. the merged, monotonically ordered timeline (``--tail N`` for the last
   N events).

One invocation answers "which rank is slow / wedged and what was it doing".

Usage:
  python scripts/fleet.py OUTPUT_DIR [--tail 80] [--json merged.json]
  python scripts/fleet.py out/ --now 1754300000   # pin "now" (tests)
"""

import argparse
import importlib.util
import json
import os
import re
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

_METRICS_RE = re.compile(r"^metrics_rank(\d+)\.jsonl$")
_POSTMORTEM_RE = re.compile(r"^postmortem-(\d+)\.json$")

#: metrics-JSONL keys carried into timeline events (the full line is huge)
_METRIC_KEYS = ("loss", "goodput_pct", "mfu_pct", "fleet.step_time_skew_s",
                "fleet.slowest_rank", "comm_est_frac")


def _load_postmortem_module():
    """The anchor-pair merge lives in scripts/postmortem.py (PR 6); scripts/
    is not a package, so load the sibling file directly."""
    spec = importlib.util.spec_from_file_location(
        "veomni_postmortem_cli", os.path.join(_HERE, "postmortem.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def collect_artifacts(dirpath):
    """-> (metrics_files {rank: path}, heartbeat dir, postmortem paths)."""
    metrics = {}
    postmortems = []
    for name in sorted(os.listdir(dirpath)):
        m = _METRICS_RE.match(name)
        if m:
            metrics[int(m.group(1))] = os.path.join(dirpath, name)
            continue
        if _POSTMORTEM_RE.match(name):
            postmortems.append(os.path.join(dirpath, name))
    return metrics, dirpath, postmortems


def merge_fleet(dirpath, now=None):
    """Merge one output dir's rank artifacts. Returns ``{"ranks": {...},
    "events": [...], "verdict": {...}}`` with events sorted ascending on
    the shared wall axis (``wall_s``) — one monotonic cluster timeline."""
    now = time.time() if now is None else now
    metrics_files, hb_dir, pm_paths = collect_artifacts(dirpath)
    events = []
    ranks = {}

    def rankdoc(r):
        # trainer ranks are ints; serving router pump heartbeats carry
        # their replica id (e.g. "r0") as the rank
        key = int(r) if str(r).lstrip("-").isdigit() else str(r)
        return ranks.setdefault(key, {"rank": key})

    # 1. metrics JSONL: already wall-stamped per line
    for rank, path in metrics_files.items():
        last = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue  # torn final line of a killed run
                payload = {k: doc[k] for k in _METRIC_KEYS if k in doc}
                payload["step"] = doc.get("step", 0)
                events.append({
                    "wall_s": float(doc.get("ts", 0.0)),
                    "rank": doc.get("rank", rank),
                    "kind": "metrics",
                    "payload": payload,
                })
                last = doc
        if last is not None:
            rankdoc(rank).update({
                "last_metrics_step": last.get("step", 0),
                "last_metrics_age_s": max(0.0, now - last.get("ts", now)),
            })

    # 2. heartbeats: freshness is the wedged-rank signal
    from veomni_tpu.observability.fleet import read_heartbeats

    for hb in read_heartbeats(hb_dir):
        rank = hb.get("rank", -1)
        wall = float(hb.get("wall_time_s", 0.0))
        events.append({
            "wall_s": wall, "rank": rank, "kind": "heartbeat",
            "payload": {"global_step": hb.get("global_step", 0),
                        "phase": hb.get("phase", "")},
        })
        rankdoc(rank).update({
            "heartbeat_age_s": max(0.0, now - wall),
            "heartbeat_step": hb.get("global_step", 0),
            "heartbeat_phase": hb.get("phase", ""),
        })

    # 3. post-mortems: anchor-pair merge (scripts/postmortem.py)
    if pm_paths:
        pm = _load_postmortem_module().merge_dumps(pm_paths)
        events.extend(pm["events"])
        for r in pm["ranks"]:
            rankdoc(r["rank"])["postmortem_reason"] = r["reason"]

    events.sort(key=lambda e: e["wall_s"])

    # fleet verdict: name the laggard instead of making the operator diff
    verdict = {}
    with_hb = [r for r in ranks.values() if "heartbeat_age_s" in r]
    if with_hb:
        stalest = max(with_hb, key=lambda r: r["heartbeat_age_s"])
        verdict["stalest_rank"] = stalest["rank"]
        verdict["stalest_age_s"] = stalest["heartbeat_age_s"]
        laggard = min(with_hb, key=lambda r: r.get("heartbeat_step", 0))
        verdict["lagging_rank"] = laggard["rank"]
        verdict["lagging_step"] = laggard.get("heartbeat_step", 0)
    # the live skew telemetry's own last word, if any rank exported it
    slowest = [e["payload"]["fleet.slowest_rank"] for e in events
               if e["kind"] == "metrics"
               and "fleet.slowest_rank" in e["payload"]]
    if slowest:
        verdict["telemetry_slowest_rank"] = int(slowest[-1])
    order = sorted(ranks, key=lambda r: (isinstance(r, str), r))
    return {"ranks": [ranks[r] for r in order], "events": events,
            "verdict": verdict}


def format_fleet(doc, tail=0):
    lines = []
    for r in doc["ranks"]:
        bits = [f"rank {r['rank']}:"]
        if "last_metrics_step" in r:
            bits.append(f"metrics@step {r['last_metrics_step']} "
                        f"({r['last_metrics_age_s']:.1f}s ago)")
        if "heartbeat_age_s" in r:
            bits.append(f"heartbeat {r['heartbeat_age_s']:.1f}s old "
                        f"(step {r.get('heartbeat_step', 0)}, "
                        f"{r.get('heartbeat_phase', '')})")
        if "postmortem_reason" in r:
            bits.append(f"postmortem: {r['postmortem_reason']}")
        lines.append("# " + " ".join(bits))
    v = doc["verdict"]
    if v:
        parts = []
        if "stalest_rank" in v:
            parts.append(f"stalest heartbeat: rank {v['stalest_rank']} "
                         f"({v['stalest_age_s']:.1f}s)")
        if "lagging_rank" in v:
            parts.append(f"least progress: rank {v['lagging_rank']} "
                         f"(step {v['lagging_step']})")
        if "telemetry_slowest_rank" in v:
            parts.append(
                f"telemetry slowest: rank {v['telemetry_slowest_rank']}")
        lines.append("# VERDICT — " + "; ".join(parts))
    events = doc["events"]
    if tail > 0:
        skipped = max(0, len(events) - tail)
        if skipped:
            lines.append(f"# ... {skipped} earlier events elided (--tail)")
        events = events[-tail:]
    t0 = events[0]["wall_s"] if events else 0.0
    for ev in events:
        extra = ""
        if ev.get("cid"):
            extra += f" cid={ev['cid']}"
        if ev.get("payload"):
            extra += " " + json.dumps(ev["payload"], sort_keys=True,
                                      default=str)
        lines.append(f"[+{ev['wall_s'] - t0:10.4f}s] rank{ev['rank']} "
                     f"{ev['kind']}{extra}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="run output dir (metrics_rank*.jsonl + "
                                "heartbeat-*.json + postmortem-*.json)")
    ap.add_argument("--tail", type=int, default=0,
                    help="print only the last N merged events")
    ap.add_argument("--json", default="",
                    help="also write the merged document here")
    ap.add_argument("--now", type=float, default=0.0,
                    help="wall time to age heartbeats against (default: "
                         "actual now; pin for reproducible output)")
    args = ap.parse_args()
    doc = merge_fleet(args.dir, now=args.now or None)
    print(format_fleet(doc, tail=args.tail))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, default=str)
        print(f"# merged {len(doc['ranks'])} ranks, {len(doc['events'])} "
              f"events -> {args.json}")


if __name__ == "__main__":
    main()
