"""One cell, once, in a new process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks what the timed path
produced against the plain reference, prints one JSON result line last and
exits. Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file that this program finds by the name in
``BENCHMARK.json`` (see ``benchmark/README.md``); nothing here names a cell.

Without a TPU that ``benchmark/peaks.py`` lists it prints no result and exits
2. ``--rehearsal`` is the one exception: it runs the cell's tiny rehearsal
preset on whatever JAX finds (the CPU, in the tests) and its result line
carries no metric at all, since a number from such a run is not a speed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP = 2


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def cell_metrics(manifest: dict, group: str, cell: str):
    """The metrics of ``group`` that ``cell`` reports: those that list it,
    and of those that list no cell every end-to-end metric, and every
    per-layer metric whose ``moves`` the cell reports."""
    e2e = [m for m in manifest["end_to_end"] if "workloads" not in m or cell in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


class Ctx:
    """What a job driver gets: the cell's data, the clock marks and the
    profiler switch."""

    def __init__(self, *, cell, config, mix, seed, seconds, trace, rehearsal, control,
                 limits, device):
        self.cell, self.config, self.mix = cell, config, mix
        self.chips = cell["chips"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rehearsal, self.control, self.limits = rehearsal, control, limits
        self.device = device
        self.platform = device["platform"]
        # the model's sizes are the configuration file's top-level keys
        self.model = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
        self.work_dir = os.path.join(ROOT, ".bench_work", cell["name"])
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_dir)
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self.window_t0 = None
        self._annotation = None
        self._built = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *_a, **_k) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self._built += 1

    def programs_built(self) -> int:
        """How many programs JAX has compiled, or loaded from its cache, in
        this process so far: jitted or eager, the program's or the
        benchmark's. It must not grow inside a measured window."""
        return self._built

    def log(self, msg: str) -> None:
        d = self.device
        print(f"[bench {self.cell['name']} platform={d['platform']} "
              f"device_kind={d['kind']!r} count={d['count']} t={time.perf_counter() - T_START:.1f}s] "
              f"{msg}", flush=True)

    def mark_window_start(self, t: float) -> None:
        self.window_t0 = t

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0 for d in jax.devices()]
        return int(max(peaks))

    def start_trace(self) -> None:
        import jax

        from veomni_tpu.observability import spans

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        spans.set_profiler_active(True)  # the program's spans join the trace
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        if self._annotation is None:
            return
        import jax

        from veomni_tpu.observability import spans

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        spans.set_profiler_active(False)
        jax.profiler.stop_trace()


def per_layer_values(manifest, ctx, result, device_doc) -> dict:
    from benchmark import peaks, trace as tr

    obs = result["obs"]
    obs.update({"values": result["values"], "model": ctx.model, "chips": ctx.chips,
                "memory_peak_bytes": result["memory_peak_bytes"], "log": ctx.log,
                "peaks": None if ctx.rehearsal else peaks.peaks_for(ctx.device["kind"]),
                "trace": None})
    if os.path.isdir(ctx.trace_dir):
        obs["trace"] = tr.load_xplane(tr.newest_xplane(ctx.trace_dir))
        if "on_trace" in obs:
            # the job cuts the window on its own program's executions and
            # fills in what goes with the steps it finds (trace.py)
            obs["on_trace"](obs["trace"])
    out = {}
    for m in cell_metrics(manifest, "per_layer", ctx.cell["name"]):
        with open(os.path.join(HERE, "layer_metrics", f"{m['name']}.json")) as f:
            reader = json.load(f)
        reducer = importlib.import_module(f"benchmark.reducers.{reader['reducer']}")
        value = reducer.reduce(obs, reader.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    if obs["trace"] is not None and tr.device_planes(obs["trace"]):
        busy, window = tr.busy_and_window_s(obs["trace"])
        device_doc["busy_s"], device_doc["window_s"] = busy, window
        result["breakdown"] = {"device_ops": tr.top_device_ops(obs["trace"]),
                               "idle_gaps": tr.idle_gaps(obs["trace"])}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny preset, any platform, no metric in the result")
    ap.add_argument("--control", default="",
                    help="put the reference in a lower precision (int8, fp8, bf16): "
                         "the run must then come out as not correct")
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell = find(manifest["workloads"], args.workload, "cell")
    cfg_entry = find(manifest["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    from benchmark import traffic

    mix = traffic.load_mix(cell["traffic"])
    limits_path = os.path.join(HERE, "limits", f"{cell['name']}.json")
    with open(limits_path) as f:
        limits = json.load(f)
    if args.rehearsal:
        config = overlay(config, config.get("rehearsal", {}))
        mix = overlay(mix, mix.get("rehearsal", {}))
        limits = overlay(limits, limits.get("rehearsal", {}))

    # as the program's entry points do, before the first use of a backend:
    # compiler flags, and the persistent compile cache at the fixed path
    # <checkout>/.jax_cache (or where JAX_COMPILATION_CACHE_DIR says)
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()
    # the driver of the mix's kind, and with it the program, is imported
    # before the first use of the chip (see the driver's own note)
    job = importlib.import_module(f"benchmark.jobs.{mix['kind']}")
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    head = (f"[bench {cell['name']} platform={device['platform']} "
            f"device_kind={device['kind']!r} count={device['count']}]")
    if not args.rehearsal:
        from benchmark import peaks

        if device["platform"] != "tpu":
            print(f"{head} JAX found no TPU; nothing was run", file=sys.stderr, flush=True)
            return EXIT_NO_CHIP
        try:
            peaks.peaks_for(device["kind"])
        except KeyError as e:
            print(f"{head} {e.args[0]}; nothing was run", file=sys.stderr, flush=True)
            return EXIT_NO_CHIP
    if device["count"] != cell["chips"]:
        print(f"{head} the cell needs {cell['chips']} chip(s), JAX found {device['count']}; "
              "nothing was run", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP

    ctx = Ctx(cell=cell, config=config, mix=mix, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), rehearsal=args.rehearsal, control=args.control or None,
              limits=limits, device=device)
    ctx.log(f"seed {args.seed}, window {args.seconds} s, trace {args.trace}"
            + (", REHEARSAL: tiny preset, no metric is reported" if args.rehearsal else ""))
    result = job.run(ctx)

    device_doc = dict(device, memory_peak_bytes=result["memory_peak_bytes"])
    values = dict(result["values"], setup_s=ctx.window_t0 - T_START)
    if args.trace:
        metrics = per_layer_values(manifest, ctx, result, device_doc)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(manifest, "end_to_end", cell["name"])}
    line = {"correct": all(c["ok"] for c in result["checks"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {} if args.rehearsal else metrics, "device": device_doc}
    if args.rehearsal:
        line["rehearsal"] = True
        line["rehearsal_metric_names"] = sorted(metrics)
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    # what was compared, last in the line: every check's number beside its
    # limit, so that a record of a run that was not correct says which
    line["checks"] = [_jsonable_check(c) for c in result["checks"]]
    ctx.log(f"wall {time.perf_counter() - T_START:.1f} s")
    print(json.dumps(line), flush=True)
    # and, as the driver's record of a run keeps the end of each stream, as the
    # last lines of standard error
    from benchmark import compare

    for c in result["checks"]:
        print(f"{head} check {'ok  ' if c['ok'] else 'FAIL'} {compare.said(c)}",
              file=sys.stderr, flush=True)
    return 0


def _jsonable_check(c: dict) -> dict:
    """A check for the result line: numbers as numbers; one that is not
    finite (JSON has no word for it) as null with what it was said beside."""
    out = dict(c)
    for key in ("value", "limit"):
        if not math.isfinite(out[key]):
            out[key], out[f"{key}_said"] = None, repr(c[key])
    return out


if __name__ == "__main__":
    sys.exit(main())
