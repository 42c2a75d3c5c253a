"""Device milliseconds a traced step of everything traced under the module
scope ``name`` (the program's ``observability/scopes.py::MODULE_SCOPES``):
self time of the instructions whose ``op_name`` path holds ``name`` as a
whole component, whatever taxonomy scope lies inside it. A cut across the
scopes, as ``recompute`` is, not a further part of their sum. None where the
program hands out no scope map, or no instruction carries the name."""

import re

from benchmark import scopes as sc


def reduce(obs, args):
    steps = obs["shapes"].get("traced_steps")
    trace = obs.get("trace")
    if not steps or not trace or not sc.tr.device_planes(trace):
        return None
    scope_map = sc.program_scope_map(args.get("site", "train_step"))
    if not scope_map:
        return None
    holds = re.compile(r"(?:^|[/(])" + re.escape(args["name"]) + r"(?=$|[/)])")
    ns = sum(t for inst, t in sc.self_ns_by_instruction(trace).items()
             if holds.search(scope_map.get(inst, "")))
    return ns * 1e-9 / steps * 1e3 if ns > 0 else None
