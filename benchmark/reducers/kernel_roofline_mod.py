"""A named kernel's share of its roofline, counted per call, with the
functions for operations and bytes in the module ``args["module"]`` beside
``flops.py`` (``kernel_roofline`` reads ``flops.py`` alone).

``ops_bytes`` gives the least work of ONE call from: ``per_step`` (entries of
the job's shapes that are summed over the traced steps, divided by them),
``from_shapes``, ``from_model``, ``fixed``, and ``shape_ratio`` (one entry of
the job's shapes over another, both summed over the very steps the trace holds:
the rows the held experts multiplied over all assignments). The share is that
least time x the calls the trace holds (``call_pattern``: the one event there
is per call; a call cut by the window's edge counts by its part inside) over
the device time of the events matching ``pattern``. A call run again (a
rematerialized forward) is work done. None without a trace cut on whole steps,
without the kernel in it, without the shapes (a model that routes nothing), or
where the ratio's upper entry is nought (the traced steps multiplied no row:
the kernel's launches then did no work to hold against their time)."""

import importlib

from benchmark import flops
from benchmark import trace as tr
from benchmark.reducers import kernel_roofline


def reduce(obs, args):
    if not obs.get("trace") or not tr.device_planes(obs["trace"]) or not obs.get("peaks"):
        return None
    seconds = tr.op_seconds(obs["trace"], args["pattern"])
    calls = kernel_roofline.calls_in_window(obs["trace"], args.get("call_pattern", args["pattern"]))
    steps = obs["shapes"].get("traced_steps")
    if seconds <= 0 or calls <= 0 or not steps:
        return None
    kwargs = {k: obs["shapes"][v] / steps for k, v in args.get("per_step", {}).items()}
    kwargs.update({k: obs["shapes"][v] for k, v in args.get("from_shapes", {}).items()})
    kwargs.update({k: obs["model"][v] for k, v in args.get("from_model", {}).items()})
    kwargs.update(args.get("fixed", {}))
    for k, (over, under) in args.get("shape_ratio", {}).items():
        if not obs["shapes"].get(under) or not obs["shapes"].get(over):
            return None  # nothing routed, or no row multiplied: an empty launch has no roofline
        kwargs[k] = obs["shapes"][over] / obs["shapes"][under]
    one = getattr(importlib.import_module(f"benchmark.{args['module']}"), args["ops_bytes"])(**kwargs)
    least = flops.roofline_seconds(one, obs["peaks"])
    obs["log"](f"kernel roofline {args['pattern']!r}: {calls:.2f} calls in the trace "
               f"({calls / steps:.2f} a step), one call {one['ops']:.4g} ops "
               f"{one['bytes']:.4g} bytes, least {least['seconds'] * 1e3:.4f} ms "
               f"({least['bound']}-bound), measured {seconds / calls * 1e3:.4f} ms a call")
    return 100.0 * least["seconds"] * calls / obs["chips"] / seconds
