"""A decode tick's share of HBM's peak: the least bytes the traced ticks'
decode steps had to read (``benchmark/flops_serve.py::decode_tick_least_bytes``:
the weights once a tick, and each running request's own cached keys and
values once, the contexts the job counted for those very ticks) over the
chip's peak bandwidth, against the device time inside the ``span`` spans of
the traced window. It leaves out what the step writes and the logits, so it
is a floor of the work and the share cannot pass 100%. None without a trace
cut on whole ticks."""

from benchmark import flops_serve
from benchmark import trace_spans as ts


def reduce(obs, args):
    trace, shapes = obs.get("trace"), obs["shapes"]
    steps = shapes.get("traced_steps")
    if not trace or not steps or not obs.get("peaks") or not ts.tr.device_planes(trace):
        return None
    busy, _ = ts.busy_and_span_s(trace, args["span"])
    if busy <= 0 or not shapes.get("decode_tokens"):
        return None
    # ``decode_tick_least_bytes`` is linear in the contexts: the ticks' sum
    one = flops_serve.decode_tick_least_bytes(obs["model"], context_positions=0)
    least = steps * one + flops_serve.decode_tick_least_bytes(
        obs["model"], context_positions=shapes["decode_context_positions"]) - one
    least_s = least / obs["peaks"]["hbm_bytes_per_s"] / obs["chips"]
    obs["log"](f"decode roofline: {steps} ticks, least {least / steps * 1e-9:.3f} GB a tick "
               f"({least_s / steps * 1e3:.3f} ms at HBM's peak) against {busy / steps * 1e3:.3f} ms "
               f"of device time a tick inside {args['span']}")
    return 100.0 * least_s / busy
