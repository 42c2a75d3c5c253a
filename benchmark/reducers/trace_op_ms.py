"""Device milliseconds a traced step of the operations whose name matches
``pattern`` (``trace.py::op_seconds`` over the job's ``traced_steps``)."""

from benchmark import trace as tr


def reduce(obs, args):
    steps = obs["shapes"].get("traced_steps")
    if not obs.get("trace") or not tr.device_planes(obs["trace"]) or not steps:
        return None
    seconds = tr.op_seconds(obs["trace"], args["pattern"])
    if seconds <= 0:
        return None
    return seconds / steps * 1e3
