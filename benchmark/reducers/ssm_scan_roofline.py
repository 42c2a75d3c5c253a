"""The state-space scans' share of their roofline in a traced step: the least
time the chip could take for a step's scans over the device time traced under
the module scope ``args["name"]`` (``ssm.scan``: the softplus of dt, the scan
op and its ``D`` skip).

The least time is ``forward_runs`` forwards and one backward of
``<module>.<ops_bytes>`` over the step's positions, for each layer of kind
``layer_kind`` that ``model[layers_from]`` (a comma list) names.
``forward_runs`` FOLLOWS THE REMAT POLICY: it is 2 under ``nothing``, the
cells' policy, which runs every layer's forward again in the backward pass; a
cell under a policy that keeps the scan's output through the backward states
1 in its reader's file. What an implementation recomputes beyond that (the
XLA form runs each chunk's forward a third time inside its own checkpoint) is
in the measured time and not in the least, so it lowers the share.

Both sides are a device step's: the device steps the trace holds are the
whole executions of the step program that the job cut its window on
(``shapes["traced_steps"]``, ``trace.py::cut_to_steps``), and the time under
the scope is summed over that same window. No kernel's call count stands in
for the steps. None without a trace cut on whole steps, without the program's
scope map, or where no instruction carries the name."""

import importlib

from benchmark import flops
from benchmark.reducers import scope_cut_ms


def reduce(obs, args):
    steps = obs["shapes"].get("traced_steps")
    if not steps or not obs.get("peaks"):
        return None
    ms = scope_cut_ms.reduce(obs, {"name": args["name"]})
    if not ms:
        return None
    fn = getattr(importlib.import_module(f"benchmark.{args['module']}"), args["ops_bytes"])
    kwargs = {k: obs["model"][v] for k, v in args["from_model"].items()}
    kwargs["tokens"] = obs["shapes"][args["tokens"]] / steps
    layers = obs["model"][args["layers_from"]].split(",").count(args["layer_kind"])
    fwd = flops.roofline_seconds(fn(**kwargs, backward=False), obs["peaks"])
    bwd = flops.roofline_seconds(fn(**kwargs, backward=True), obs["peaks"])
    least = layers * (args.get("forward_runs", 2) * fwd["seconds"] + bwd["seconds"])
    obs["log"](f"scan roofline under {args['name']!r}: {layers} layers, one forward least "
               f"{fwd['seconds'] * 1e3:.4f} ms ({fwd['bound']}-bound), one backward "
               f"{bwd['seconds'] * 1e3:.4f} ms ({bwd['bound']}-bound), a step's least "
               f"{least * 1e3:.3f} ms against {ms:.3f} ms under the scope a step, over "
               f"{steps} whole device steps")
    return 100.0 * least / obs["chips"] / (ms * 1e-3)
