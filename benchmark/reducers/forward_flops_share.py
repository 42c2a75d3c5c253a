"""The whole window's share of the chip's peak, for a served model: the
forward passes the window made (``counters``: ``forward.tokens`` passes whose
contexts add up to ``forward.context_sum``, ``forward.logit_rows`` rows of
the head; a prefix served from the cache is not a pass) in
``benchmark/flops_serve.py::forward_flops``'s operations, over the window,
the chips and the chip's peak."""

from benchmark import flops_serve


def reduce(obs, args):
    c = obs["counters"]
    if not obs.get("peaks") or not c.get("forward.tokens") or not obs.get("window_s"):
        return None
    ops = flops_serve.forward_flops(obs["model"], tokens=c["forward.tokens"],
                                    context_sum=c["forward.context_sum"],
                                    logit_rows=c["forward.logit_rows"])
    return 100.0 * ops / obs["window_s"] / (obs["chips"] * obs["peaks"]["bf16_flops"])
