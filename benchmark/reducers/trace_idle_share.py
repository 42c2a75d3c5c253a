"""1 minus the union of the device's operation intervals over the traced
window, averaged over the chips used."""

from benchmark import trace as tr


def reduce(obs, args):
    if not obs.get("trace") or not tr.device_planes(obs["trace"]):
        return None
    busy, window = tr.busy_and_window_s(obs["trace"])
    return 100.0 * (1.0 - busy / window)
