"""Device time inside the host's spans of one name: what a serving cell's
trace readers share (``trace.py`` holds the window, the planes and the
self-time rule they build on).

A tick of the engine blocks the host on its decode step's tokens, so the
device work of a ``serve.decode`` span lies inside the span, and the span's
length less that work is the host's own share of the tick."""

from __future__ import annotations

from typing import List, Tuple

from benchmark import trace as tr


def span_intervals(trace: dict, name: str) -> List[Tuple[int, int]]:
    """(start, end) of the host spans called ``name``, clipped to the window."""
    lo, hi = tr.window_ns(trace)
    return [(a, b) for a, b in ((max(s, lo), min(s + d, hi))
                                for n, s, d in tr.host_spans(trace) if n == name) if b > a]


def device_events_in(plane: dict, intervals: List[Tuple[int, int]]) -> List[list]:
    """The plane's operation events, each cut to the part of it inside one of
    ``intervals`` (sorted, disjoint)."""
    out = []
    events = sorted(tr.line_events(plane, tr.OPS_LINE), key=lambda e: e[1])
    i = 0
    for a, b in intervals:
        while i < len(events) and events[i][1] + events[i][2] <= a:
            i += 1
        j = i
        while j < len(events) and events[j][1] < b:
            out.extend(tr.clip([events[j]], a, b))
            j += 1
    return out


def busy_and_span_s(trace: dict, name: str) -> Tuple[float, float]:
    """(seconds an operation ran on the device inside the spans, averaged over
    the device planes; seconds the spans lasted)."""
    spans = tr.union(span_intervals(trace, name))
    planes = tr.device_planes(trace)
    if not spans or not planes:
        return 0.0, 0.0
    busy = sum(tr.length(tr.union((s, s + d) for _, s, d in device_events_in(p, spans)))
               for p in planes) / len(planes)
    return busy * 1e-9, tr.length(spans) * 1e-9
