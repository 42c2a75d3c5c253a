"""``memory_stats()["peak_bytes_in_use"]``, the largest over the cell's
devices, times ``scale``."""


def reduce(obs, args):
    if not obs.get("memory_peak_bytes"):
        return None
    return obs["memory_peak_bytes"] * args.get("scale", 1.0)
