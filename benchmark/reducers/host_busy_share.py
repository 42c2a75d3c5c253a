"""The share of the traced window in which the loop was NOT waiting for the
device: 100 x (window - the ``wait_span`` spans of the trace's host planes,
clipped to the window) / window, on the trace's own clock. From the traced
steps and not from ``obs["spans"]``: after the trace the job's callback
blocks on every step inside another span, which would read as host work.
None where the trace holds no such span (a program without it)."""

from benchmark import trace as tr


def reduce(obs, args):
    trace = obs.get("trace")
    if not trace:
        return None
    waits = [ev for ev in tr.host_spans(trace) if ev[0] == args["wait_span"]]
    if not waits:
        return None
    lo, hi = tr.window_ns(trace)
    waited = tr.length(tr.union((s, s + d) for _, s, d in tr.clip(waits, lo, hi)))
    obs["log"](f"host: {len(waits)} {args['wait_span']} spans cover "
               f"{waited * 1e-9:.4f} s of the {(hi - lo) * 1e-9:.4f} s traced")
    return 100.0 * (1.0 - waited / (hi - lo))
