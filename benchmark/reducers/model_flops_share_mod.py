"""Model FLOP/s utilisation with the per-token count in the module
``args["module"]`` beside ``flops.py``: the end-to-end ``rate`` (tokens/s)
times the operations one token needs (nothing recomputed) over chips times
the chip's peak."""

import importlib


def reduce(obs, args):
    rate = obs["values"].get(args["rate"])
    if rate is None or not obs.get("peaks"):
        return None
    mod = importlib.import_module(f"benchmark.{args['module']}")
    per_token = getattr(mod, args["per_token"])(obs["model"], obs["shapes"]["seq_len"])
    return 100.0 * rate * per_token / (obs["chips"] * obs["peaks"]["bf16_flops"])
