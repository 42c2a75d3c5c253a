"""Device milliseconds a traced tick, inside the host's spans called ``span``,
by the program's scopes (``benchmark/scopes.py``): self time of the events
whose instruction the scope map puts under one of ``scopes``, or, with
``unattributed``, under no scope of the taxonomy.

A tick's window holds other programs than the decode step (prefills, their
scatter, the first token's sampler), whose instructions carry the same names,
so only what runs inside ``span`` is read: the host blocks on the decode
step's tokens inside ``serve.decode``, and nothing else runs there. The jit
``site`` compiles one program a table-width bucket, and their instructions'
names differ (compiled for a described v5e, buckets 128 and 512 share a fifth
of their names under another scope: PERF.md, PR 45), so every span is read
through the map of the program it ran: the job notes each tick's bucket from
the census's call counts and keeps each program's map from the moment it was
the site's newest (``obs["scope_maps"][site][bucket]``,
``obs["shapes"]["span_buckets"][span]``). None without a trace cut on whole
ticks, where the spans in the trace are not the ticks' own, or where a span's
program has no map."""

from benchmark import scopes as sc
from benchmark import trace_spans as ts


def table(obs, span: str, site: str):
    """Seconds inside the spans by scope; made once a run, logged."""
    key = f"span_scope_table.{site}.{span}"
    if key in obs:
        return obs[key]
    obs[key] = None
    spans = ts.span_intervals(obs["trace"], span)
    buckets = (obs["shapes"].get("span_buckets") or {}).get(span) or []
    maps = (obs.get("scope_maps") or {}).get(site) or {}
    if len(spans) != len(buckets) or any(not maps.get(b) for b in buckets):
        obs["log"](f"{len(spans)} {span} spans in the window for {len(buckets)} ticks' programs "
                   f"{sorted(set(map(str, buckets)))}, maps held for {sorted(maps)}: "
                   "no scope metric is reported")
        return None
    by_scope = {}
    planes = sc.tr.device_planes(obs["trace"])
    for interval, bucket in zip(spans, buckets):
        for p in planes:
            for name, ns in sc.tr.self_times(ts.device_events_in(p, [interval])).items():
                scope, _ = sc.classify(sc.instruction_name(name), maps[bucket])
                by_scope[scope] = by_scope.get(scope, 0.0) + ns * 1e-9 / len(planes)
    steps = obs["shapes"]["traced_steps"]
    rows = sorted(by_scope.items(), key=lambda kv: -kv[1])
    obs["log"](f"device time inside {span} by scope, ms a tick over {steps} traced ticks: "
               + ", ".join(f"{k} {v / steps * 1e3:.2f}" for k, v in rows))
    obs[key] = by_scope
    return by_scope


def reduce(obs, args):
    trace, steps = obs.get("trace"), obs["shapes"].get("traced_steps")
    if not trace or not steps or not sc.tr.device_planes(trace):
        return None
    by_scope = table(obs, args["span"], args.get("site", "paged_decode"))
    if by_scope is None:
        return None
    names = [sc.UNATTRIBUTED] if args.get("unattributed") else args["scopes"]
    seconds = sum(by_scope.get(s, 0.0) for s in names)
    return seconds / steps * 1e3 if seconds > 0 else None
