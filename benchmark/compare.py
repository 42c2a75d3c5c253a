"""The comparisons that decide ``correct``. Every number compared is
printed beside its limit, in every run."""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def check(name: str, value: float, limit: float, **where) -> dict:
    """One compared number under a short plain name: passes where
    ``value <= limit``. ``where`` says where it was read (the worst ``leaf``)
    and goes into the result line beside it."""
    ok = bool(np.isfinite(value) and value <= limit)
    return {"name": name, "value": float(value), "limit": float(limit), "ok": ok, **where}


def said(c: dict) -> str:
    """A check as every run prints it: the number beside its limit."""
    where = "".join(f" {k} {v}" for k, v in c.items() if k not in ("name", "value", "limit", "ok"))
    return f"{c['name']}: {c['value']!r} (limit {c['limit']!r}){where}"


def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> Tuple[float, str]:
    """Largest gap between the program's norm of a leaf and the reference's
    (not the norm of their difference), measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    median = float(np.median(np.concatenate([np.ravel(v) for v in want.values()])))
    worst, where = 0.0, ""
    for name in sorted(want):
        g, w = np.ravel(got[name]).astype(np.float64), np.ravel(want[name]).astype(np.float64)
        if g.shape != w.shape:
            raise ValueError(f"leaf {name}: {g.shape} vs {w.shape}")
        gaps = np.abs(g - w) / np.maximum(w, median)
        if not np.isfinite(gaps).all():
            return float("inf"), name
        i = int(np.argmax(gaps))
        if gaps[i] > worst:
            worst, where = float(gaps[i]), f"{name}[{i}]"
    return worst, where
