"""Median of a host timer's readings inside the window (the job's own
timers first, then the program's spans of that name), times ``scale``."""

import statistics


def reduce(obs, args):
    durs = obs["timers"].get(args["timer"]) or obs["spans"].get(args["timer"])
    if not durs:
        return None
    return statistics.median(durs) * args.get("scale", 1.0)
