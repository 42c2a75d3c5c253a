"""The host's share of the spans called ``span`` inside the traced window:
100 x (1 - seconds an operation ran on the device inside them / seconds they
lasted). None without a trace cut on whole ticks or without such a span."""

from benchmark import trace_spans as ts


def reduce(obs, args):
    trace = obs.get("trace")
    if not trace or not obs["shapes"].get("traced_steps") or not ts.tr.device_planes(trace):
        return None
    busy, lasted = ts.busy_and_span_s(trace, args["span"])
    if not lasted:
        return None
    obs["log"](f"spans {args['span']}: the device ran {busy:.4f} s of their {lasted:.4f} s")
    return 100.0 * (1.0 - busy / lasted)
