"""A counter's growth over the window: as it is, per second (``per_s``), or
over another counter's growth (``over``), times ``scale``."""


def reduce(obs, args):
    value = obs["counters"].get(args["counter"])
    if value is None:
        return None
    value = float(value)
    if args.get("over"):
        base = obs["counters"].get(args["over"])
        if not base:
            return None
        value /= float(base)
    if args.get("per_s"):
        value /= obs["window_s"]
    return value * args.get("scale", 1.0)
