"""From a profiler trace to numbers: device busy and idle time, time by
operation name, idle gaps named by what the host was doing.

A trace is first brought into one plain form, which is also what the
recorded trace under ``tests/data`` holds::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [["fusion.12", start_ns, dur_ns], ...]}]}]}

Device planes are those named ``/device:TPU:<n>``; the line ``XLA Ops`` holds
one event per executed HLO operation (a ``while`` or ``conditional`` holds
its body's operations nested inside its own interval), ``XLA Modules`` one
per executed program. Host planes hold one line per thread, with the
program's spans (``TraceAnnotation``) among their events. All planes share
one clock, in nanoseconds.

**The window** every reduction below reads is :func:`window_ns`: where the job
has cut the trace on its step program's executions (:func:`cut_to_steps`),
from the start of the first execution the trace holds whole to the end of the
last; else the host's ``bench.window`` span; else the device operations'
extent.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the program's spans and the benchmark's own are dotted lower-case names
SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_0-9]+)+$")
WINDOW_SPAN = "bench.window"
STEP_WINDOW = "step_window_ns"  # the key cut_to_steps leaves in a trace


def short_name(op: str) -> str:
    """The trace names a device operation by its whole HLO text. For a
    breakdown: the instruction's own name, with the custom call's target or
    the fusion's kind beside it."""
    head = op.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', op)
    if m:
        return f"{head} [{m.group(1)}]"
    m = re.search(r"kind=(k\w+)", op)
    return f"{head} [{m.group(1)}]" if m else head


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path: str, keep_host: Optional[re.Pattern] = SPAN_NAME) -> dict:
    """Read an ``.xplane.pb`` with JAX alone. Host events are kept only
    where their name matches ``keep_host`` (host planes hold a great many)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events
                      if is_device or keep_host is None or keep_host.match(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------------ pieces
def device_planes(trace: dict) -> List[dict]:
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def line_events(plane: dict, line_name: str) -> List[list]:
    return [ev for ln in plane["lines"] if ln["name"] == line_name for ev in ln["events"]]


def host_spans(trace: dict) -> List[list]:
    """[name, start_ns, dur_ns] of every kept host event, by start."""
    out = [ev for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
           for ln in p["lines"] for ev in ln["events"] if SPAN_NAME.match(ev[0])]
    return sorted(out, key=lambda e: e[1])


def traced_span_ns(trace: dict) -> Tuple[int, int]:
    """The stretch in which the profiler was certainly on: the ``bench.window``
    span where the host plane has it (entered after the profiler started, left
    before it stopped), else from the first device operation's start to the
    last one's end."""
    marks = [ev for ev in host_spans(trace) if ev[0] == WINDOW_SPAN]
    if marks:
        return marks[0][1], marks[0][1] + marks[0][2]
    evs = [ev for p in device_planes(trace) for ev in line_events(p, OPS_LINE)]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def window_ns(trace: dict) -> Tuple[int, int]:
    """The window every reduction reads: the whole step executions
    :func:`cut_to_steps` found, where it was asked and found two or more;
    else :func:`traced_span_ns`."""
    return tuple(trace.get(STEP_WINDOW) or traced_span_ns(trace))


def program_executions(trace: dict, pattern: str) -> List[Tuple[int, int]]:
    """(start, end) of every execution of the programs whose name matches
    ``pattern`` (``re.search`` over the ``XLA Modules`` events' names, one
    event an execution), on the first device plane, by start."""
    planes = device_planes(trace)
    if not planes:
        return []
    rx = re.compile(pattern)
    return sorted((s, s + d) for n, s, d in line_events(planes[0], MODULES_LINE) if rx.search(n))


def whole_executions(trace: dict, pattern: str) -> List[Tuple[int, int]]:
    """The executions the trace holds WHOLE: those that start and end strictly
    inside :func:`traced_span_ns` AND strictly inside the device's own first
    and last event. One that was running when the profiler started, or still
    is when it stops, shows cut to the trace's edge (or with its true start
    outside) and is left out; the device's events can begin a hair after the
    host's span does, so the span alone does not tell (a cut execution read
    768 ms among whole ones of 810-857, PR 43)."""
    runs = program_executions(trace, pattern)
    if not runs:
        return []
    lo, hi = traced_span_ns(trace)
    device = [ev for name in (OPS_LINE, MODULES_LINE)
              for ev in line_events(device_planes(trace)[0], name)]
    lo = max(lo, min(s for _, s, _ in device))
    hi = min(hi, max(s + d for _, s, d in device))
    return [(a, b) for a, b in runs if a > lo and b < hi]


def cut_to_steps(trace: dict, pattern: str, at_most: Optional[int] = None, skip: int = 0) -> int:
    """Cut the trace's window on the step program's own executions: from the
    start of the first whole execution to the end of the last of those that
    follow it in a row (``at_most`` of them). ``skip`` leaves out the first
    executions the trace SHOWS, whole or cut, and the one after them has to be
    whole: a job that knows which step the trace's first execution is so names
    the steps of its window by number. Returns how many whole executions the
    window holds; with fewer than two the window is left as it was and the
    count is what was seen, so that a caller never divides by a step it did not
    see."""
    whole = set(whole_executions(trace, pattern))
    steps: List[Tuple[int, int]] = []
    for run in program_executions(trace, pattern)[skip:]:
        if run in whole:
            steps.append(run)
        elif steps or skip:
            break
    steps = steps[:at_most]
    trace.pop(STEP_WINDOW, None)
    if len(steps) >= 2:
        trace[STEP_WINDOW] = [steps[0][0], steps[-1][1]]
    return len(steps)


def clip(events: Iterable[list], lo: int, hi: int) -> List[list]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def busy_intervals(plane: dict, lo: int, hi: int) -> List[Tuple[int, int]]:
    return union((s, s + d) for _, s, d in clip(line_events(plane, OPS_LINE), lo, hi))


def self_times(events: List[list]) -> Dict[str, int]:
    """Time by operation name with what is nested inside an operation taken
    out of it (a ``while`` keeps only what none of its body's operations
    cover)."""
    out: Dict[str, int] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(until: int):
        while stack and stack[-1][1] <= until:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + self_ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


# ------------------------------------------------------------------ reduce
def busy_and_window_s(trace: dict) -> Tuple[float, float]:
    """(seconds in which an operation ran, averaged over the device planes;
    seconds of the traced window)."""
    lo, hi = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = sum(length(busy_intervals(p, lo, hi)) for p in planes) / len(planes)
    return busy * 1e-9, (hi - lo) * 1e-9


def op_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of operations whose name matches ``pattern``
    (``re.search``), averaged over the device planes. Matching operations
    nested in a matching operation are not counted twice."""
    rx = re.compile(pattern)
    lo, hi = window_ns(trace)
    planes = device_planes(trace)
    total = 0
    for p in planes:
        total += length(union(
            (s, s + d) for n, s, d in clip(line_events(p, OPS_LINE), lo, hi) if rx.search(n)))
    return total * 1e-9 / max(len(planes), 1)


def top_device_ops(trace: dict, n: int = 10) -> List[list]:
    """[[name, seconds], ...]: self time by operation name on the first
    device, largest first. Numbered copies of one operation
    (``fusion.12``, ``fusion.13``) stay apart: the names are the trace's."""
    lo, hi = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    times = self_times(clip(line_events(planes[0], OPS_LINE), lo, hi))
    top = sorted(times.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(name), ns * 1e-9] for name, ns in top]


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """[[host span, seconds], ...]: the first device's idle time inside the
    window, each gap charged to the host span that covers most of it
    (``(no span)`` where none does), summed by span, largest first."""
    lo, hi = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    busy = busy_intervals(planes[0], lo, hi)
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = b
    if hi > at:
        gaps.append((at, hi))
    spans = [ev for ev in host_spans(trace) if ev[0] != WINDOW_SPAN]
    by_name: Dict[str, int] = {}
    for a, b in gaps:
        best, best_ns = "(no span)", 0
        for name, s, d in spans:
            if s >= b:
                break
            ov = min(b, s + d) - max(a, s)
            if ov > best_ns:
                best, best_ns = name, ov
        by_name[best] = by_name.get(best, 0) + (b - a)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in top]
