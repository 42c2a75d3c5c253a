"""A named kernel's share of its roofline, counted per call: the least time
the chip could take for ONE call of the kernel (``benchmark/flops.py::
<ops_bytes>`` on the job's shapes over layers x traced steps, with
``backward_only`` the backward's part alone) times the calls the trace holds,
over their device time. A call run again (a rematerialized forward) counts as
work done, so the number does not move with the remat policy.

``pattern`` matches the kernels' events; ``call_pattern`` (default: the same)
the one event there is per call: a call cut by the window's edge counts by
the part of it inside."""

import re

from benchmark import flops
from benchmark import trace as tr


def calls_in_window(trace, pattern: str) -> float:
    rx = re.compile(pattern)
    lo, hi = tr.window_ns(trace)
    planes = tr.device_planes(trace)
    total = 0.0
    for p in planes:
        for name, start, dur in tr.line_events(p, tr.OPS_LINE):
            if dur > 0 and rx.search(name):
                total += max(0, min(start + dur, hi) - max(start, lo)) / dur
    return total / max(len(planes), 1)


def reduce(obs, args):
    if not obs.get("trace") or not tr.device_planes(obs["trace"]) or not obs.get("peaks"):
        return None
    seconds = tr.op_seconds(obs["trace"], args["pattern"])
    calls = calls_in_window(obs["trace"], args.get("call_pattern", args["pattern"]))
    steps = obs["shapes"].get("traced_steps")
    if seconds <= 0 or calls <= 0 or not steps:
        return None
    kwargs = {k: obs["shapes"][v] for k, v in args.get("from_shapes", {}).items()}
    kwargs.update({k: obs["model"][v] for k, v in args.get("from_model", {}).items()})
    fn = getattr(flops, args["ops_bytes"])
    work = fn(**kwargs, backward=bool(args.get("backward_only")))
    if args.get("backward_only"):
        fwd = fn(**kwargs, backward=False)
        work = {k: work[k] - fwd[k] for k in work}
    # ``work`` is every layer's over the traced steps: one call is one layer's
    # of one step
    per_step = kwargs.get("layers", 1) * steps
    one = {k: v / per_step for k, v in work.items()}
    least = flops.roofline_seconds(one, obs["peaks"])
    least_s = least["seconds"] * calls / obs["chips"]
    obs["log"](f"kernel roofline {args['pattern']!r}: {calls:.2f} calls in the trace "
               f"({calls / steps:.2f} a step), one call {one['ops']:.4g} ops "
               f"{one['bytes']:.4g} bytes, least {least['seconds'] * 1e3:.4f} ms "
               f"({least['bound']}-bound), measured {seconds / calls * 1e3:.4f} ms a call")
    return 100.0 * least_s / seconds
