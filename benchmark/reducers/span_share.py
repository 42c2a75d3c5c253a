"""Sum of a named span's durations inside the window, as a share of it."""


def reduce(obs, args):
    durs = obs["spans"].get(args["span"])
    if durs is None or not obs.get("window_s"):
        return None
    return 100.0 * sum(durs) / obs["window_s"]
