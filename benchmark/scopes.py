"""Device time by the program's own names: the join of a profiler trace with
the program's scope map, and the classes every scope metric sums.

The profiler names a device event by its instruction's whole HLO text,
``%fusion.12 = bf16[...] fusion(...)``: the instruction's name is what stands
between ``%`` and `` = ``. The program hands out ``{instruction name:
op_name}`` for a jit site (``CostCensus.scope_map``), and ``op_name`` carries
the ``jax.named_scope`` path the instruction was traced under, e.g.
``jit(step_fn)/while/body/closed_call/transpose(jvp())/while/body/closed_call/
checkpoint/rematted_computation/mlp/dot_general``. README_scopes.md says how
an event gets its scope and its phase from that.

The taxonomy is the benchmark's own copy of the program's
``veomni_tpu/observability/scopes.py`` (a test holds the two equal), so that a
later PR cannot move what a scope metric sums by editing the program's list:
the program's ``TRAIN_SCOPES``, and the mixers' scopes it lists under
``MODULE_SCOPES`` (``ssm*``, ``kda*``), which hold no other scope of the
taxonomy inside them and so are parts of its sum like any other.
Against a program that has no scope map (the parent of the PR that brought
this file) :func:`program_scope_map` returns None and every reader built on it
leaves its metric out.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark import trace as tr

# a state-space or a Kimi Delta Attention mixer, input norm to residual add:
# its leaf scopes, and the module's own name for what lies under it and under
# none of its leaves (the mixer's norm and residual). An event's scope is the
# innermost name of its path, so ``.../ssm/ssm.scan/...`` is ``ssm.scan``
MIXER_SCOPES = ("ssm", "ssm.proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                "kda", "kda.proj", "kda.conv", "kda.gate", "kda.scan")
TRAIN_SCOPES = ("embed", "attn.qkv", "attn.flash", "attn.out", "mlp", "moe.route",
                "moe.dispatch", "moe.experts", "moe.combine", "lm_head_loss", "grad_clip",
                "optimizer") + MIXER_SCOPES
SERVE_SCOPES = ("paged.gather", "paged.attend", "sampler")
SCOPES = TRAIN_SCOPES + SERVE_SCOPES
# a Pallas kernel's name is its instruction's name, so its scope needs no
# map; the phase beside it is the one it has where the map lacks it (with an
# op_name, a forward kernel may turn out to be the recomputed copy)
KERNELS = {"flash_fwd": ("attn.flash", "forward"), "flash_bwd_dkv": ("attn.flash", "backward"),
           "flash_bwd_dq": ("attn.flash", "backward"), "gmm_fwd": ("moe.experts", "forward"),
           "gmm_dlhs": ("moe.experts", "backward"), "gmm_drhs": ("moe.experts", "backward"),
           "qk_norm_rope_fwd": ("attn.qkv", "forward"), "qk_norm_rope_bwd": ("attn.qkv", "backward"),
           "mla_qkv_rope_fwd": ("attn.qkv", "forward"), "mla_qkv_rope_bwd": ("attn.qkv", "backward")}
PHASES = ("forward", "recompute", "backward", "optimizer")
UNATTRIBUTED = "(unattributed)"

# a scope is a whole component of the path, or stands alone inside a
# transformation's brackets: jvp(mlp), transpose(jvp(attn.out)); the longest
# name first, so that ``ssm.scan`` is never read as ``ssm``
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)) + r")(?=$|[/)])")
_NUMBERED = re.compile(r"^(.*?)\.\d+$")


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(instruction: str) -> Optional[str]:
    """The program's kernel name of an instruction (``flash_fwd.16`` ->
    ``flash_fwd``), None for anything else."""
    m = _NUMBERED.match(instruction)
    base = m.group(1) if m else instruction
    return base if base in KERNELS else None


def scope_of(op_name: str) -> Optional[str]:
    """The innermost taxonomy scope of an ``op_name``; None where it holds
    none."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def phase_of(op_name: str, scope: Optional[str]) -> str:
    """``optimizer`` by scope; else ``recompute`` where the path holds
    ``rematted_computation`` (the forward run again inside the backward),
    ``backward`` where it holds ``transpose(``, else ``forward``."""
    if scope in ("optimizer", "grad_clip"):
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def classify(instruction: str, scope_map: Dict[str, str]) -> Tuple[str, str]:
    """(scope or ``UNATTRIBUTED``, phase) of one instruction."""
    op_name = scope_map.get(instruction, "")
    kernel = kernel_of(instruction)
    if kernel:
        scope, phase = KERNELS[kernel]
        return scope, phase_of(op_name, scope) if op_name else phase
    scope = scope_of(op_name)
    return scope or UNATTRIBUTED, phase_of(op_name, scope)


def program_scope_map(site: str = "train_step") -> Optional[Dict[str, str]]:
    """The program's scope map for a jit site, None where the program has
    none to give (an older program, or a site that compiled nothing)."""
    try:
        from veomni_tpu.observability.cost import get_cost_census

        return get_cost_census().scope_map(site) or None
    except Exception:
        return None


def self_ns_by_instruction(trace: dict) -> Dict[str, float]:
    """Self time (``trace.py::self_times``: a ``while`` keeps only what its
    body's operations leave) by instruction name inside the traced window,
    averaged over the device planes."""
    lo, hi = tr.window_ns(trace)
    planes = tr.device_planes(trace)
    out: Dict[str, float] = {}
    for p in planes:
        for name, ns in tr.self_times(tr.clip(tr.line_events(p, tr.OPS_LINE), lo, hi)).items():
            key = instruction_name(name)
            out[key] = out.get(key, 0.0) + ns / len(planes)
    return out


def table(trace: dict, scope_map: Dict[str, str]) -> dict:
    """Device self time inside the traced window, in seconds: ``by_scope``
    (every scope that has any, and ``UNATTRIBUTED``), ``by_phase``,
    ``by_scope_phase`` and ``unattributed_top`` (the instructions that took
    most of the unattributed time, with their ``op_name``)."""
    by_scope: Dict[str, float] = {}
    by_phase: Dict[str, float] = {}
    by_both: Dict[str, Dict[str, float]] = {}
    loose: List[Tuple[str, float]] = []
    for instruction, ns in self_ns_by_instruction(trace).items():
        scope, phase = classify(instruction, scope_map)
        s = ns * 1e-9
        by_scope[scope] = by_scope.get(scope, 0.0) + s
        by_phase[phase] = by_phase.get(phase, 0.0) + s
        by_both.setdefault(scope, {})[phase] = by_both.get(scope, {}).get(phase, 0.0) + s
        if scope == UNATTRIBUTED:
            loose.append((instruction, s))
    loose.sort(key=lambda kv: -kv[1])
    return {"by_scope": by_scope, "by_phase": by_phase, "by_scope_phase": by_both,
            "unattributed_top": [[n, s, scope_map.get(n, "")] for n, s in loose[:8]]}


def seconds(tab: dict, scopes: Iterable[str] = (), phase: Optional[str] = None) -> float:
    """Seconds of ``tab`` in the given scopes (all phases), or in one phase
    (all scopes, the unattributed included), or in both."""
    scopes = tuple(scopes)
    if not scopes:
        return tab["by_phase"].get(phase, 0.0)
    rows = (tab["by_scope_phase"].get(s, {}) for s in scopes)
    return sum(sum(r.values()) if phase is None else r.get(phase, 0.0) for r in rows)


def table_for(obs: dict, site: str = "train_step") -> Optional[dict]:
    """The table of this run's trace, made once a run and kept in ``obs``
    (every scope metric asks), and logged beside the trace's busy time; None
    without a trace with device planes or without a scope map."""
    trace = obs.get("trace")
    if not trace or not tr.device_planes(trace):
        return None
    key = f"scope_table.{site}"
    if key not in obs:
        scope_map = program_scope_map(site)
        obs[key] = table(trace, scope_map) if scope_map else None
        if obs[key] is not None:
            log_table(obs, obs[key])
    return obs[key]


def log_table(obs: dict, tab: dict) -> None:
    """The component sum beside ``busy_s``: all scopes plus the unattributed
    have to come to the traced window's busy time."""
    busy, _ = tr.busy_and_window_s(obs["trace"])
    steps = obs["shapes"].get("traced_steps") or 1
    total = sum(tab["by_scope"].values())
    rows = sorted(tab["by_scope"].items(), key=lambda kv: -kv[1])
    obs["log"]("device time by scope, ms a step over %d traced steps: %s" % (
        steps, ", ".join(f"{k} {v / steps * 1e3:.1f}" for k, v in rows)))
    obs["log"]("device time by phase, ms a step: " + ", ".join(
        f"{k} {tab['by_phase'].get(k, 0.0) / steps * 1e3:.1f}" for k in PHASES))
    obs["log"](f"scope sum {total / steps * 1e3:.1f} ms a step against busy_s "
               f"{busy / steps * 1e3:.1f} ms a step: off by "
               f"{100.0 * (total - busy) / busy if busy else 0.0:+.2f}%")
    if tab["unattributed_top"]:
        obs["log"]("largest unattributed: " + "; ".join(
            f"{n} {s / steps * 1e3:.2f} ms [{op or 'no op_name'}]"
            for n, s, op in tab["unattributed_top"]))
