"""The state-space scans' share of their roofline in a traced step: the least
time the chip could take for a step's scans over the device time traced under
the module scope ``args["name"]`` (``ssm.scan``: the softplus of dt, the scan
op and its ``D`` skip).

The least time is ``forward_runs`` forwards (2: the remat policy ``nothing``
runs every layer's forward again in the backward pass) and one backward of
``<module>.<ops_bytes>`` over the step's positions, for each layer of kind
``layer_kind`` that ``model[layers_from]`` (a comma list) names. What an
implementation recomputes beyond that (the XLA form runs each chunk's forward
a third time inside its own checkpoint) is in the measured time and not in the
least, so it lowers the share.

How many device steps the trace holds is NOT taken from the job's count: the
trace starts and stops on the host's steps while the device runs behind, so
a window counted as three steps has held 1.7 to 2.9 (PERF.md, section 7).
``step_proxy`` names a kernel that runs a known number of times a device step
(``flash_fwd``, twice: the cell has one attention layer and recomputes it);
the steps are that kernel's calls in the window over ``per_step``. None
without a trace, without the program's scope map, or where no instruction
carries the name or the proxy."""

import importlib

from benchmark import flops
from benchmark.reducers import kernel_roofline, scope_cut_ms


def reduce(obs, args):
    steps = obs["shapes"].get("traced_steps")
    if not steps or not obs.get("peaks"):
        return None
    ms = scope_cut_ms.reduce(obs, {"name": args["name"]})
    if not ms:
        return None
    proxy = args["step_proxy"]
    device_steps = kernel_roofline.calls_in_window(obs["trace"], proxy["pattern"]) / proxy["per_step"]
    if device_steps <= 0:
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
               f"{least * 1e3:.3f} ms; {ms * steps:.3f} ms under the scope in a trace of "
               f"{device_steps:.2f} device steps ({steps} counted by the job)")
    return 100.0 * least * device_steps / obs["chips"] / (ms * steps * 1e-3)
