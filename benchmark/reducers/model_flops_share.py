"""Model FLOP/s utilisation: the end-to-end ``rate`` (tokens/s) times the
operations one token needs (``benchmark/flops.py``, nothing recomputed)
over chips times the chip's peak."""

from benchmark import flops


def reduce(obs, args):
    rate = obs["values"].get(args["rate"])
    if rate is None or not obs.get("peaks"):
        return None
    per_token = getattr(flops, args["per_token"])(obs["model"], obs["shapes"]["seq_len"])
    return 100.0 * rate * per_token / (obs["chips"] * obs["peaks"]["bf16_flops"])
