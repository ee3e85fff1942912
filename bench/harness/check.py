"""The comparison that decides ``correct``.

Every heat row the timed window returned is compared with the plain
reference (``reference.Reference``) evaluated over the events the index
holds. Numbers compared, each against a limit (``limits`` in the
configuration's file; a number not listed there has the limit 0):

``heat_gap``        max over returned rows of max|row - ref| / max|ref|.
``unanswered``      admitted requests that never got a response.
``engine_faults``   engine passes that raised inside the window.
``degradations``    trips of the server's executor ladder inside the
                    window (a degraded profile answers on the host: its
                    rows may be right, but they were not served by the
                    configured engine).
``engine_changed``  1 where the engine serving after the window is not the
                    one that served after warm-up.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Callable, Dict, List

import numpy as np

from .data import EventSet


def _gap(row: np.ndarray, ref: np.ndarray, scale: float) -> float:
    if not (np.isfinite(row).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.max(np.abs(row - ref)) / max(scale, 1e-300))


def served_numbers(c0: dict, c1: dict, engine0: str, engine1: str) -> dict:
    """What the server's own counters say about the window: it must have
    been served, start to end, by the engine warm-up set up."""
    return {"engine_faults": c1["n_engine_faults"] - c0["n_engine_faults"],
            "degradations": c1["n_degradations"] - c0["n_degradations"],
            "engine_changed": int(engine1 != engine0)}


def compare(answers, *, heat: Callable, events: EventSet, limits: dict,
            served: Dict[str, int] = None) -> dict:
    """``heat(events, ts) -> [len(ts), L]`` is the reference (or, for the
    control, whatever stands in the program's place for the reference)."""
    rows: Dict[float, List[np.ndarray]] = defaultdict(list)
    coverage = dict(rows=0, cache_hit_rows=0, padded_class_rows=0)
    unanswered = 0
    for a in answers:
        if a.done is None and a.error is None:
            unanswered += 1
        if not a.ok:
            continue
        for j, t in enumerate(a.ts):
            rows[float(t)].append(np.asarray(a.heat[j]))
            coverage["rows"] += 1
            coverage["cache_hit_rows"] += int(a.cache_hits > 0)
            coverage["padded_class_rows"] += int(a.windows_evaluated > 2)
    heat_gap = None
    if rows:
        ts = sorted(rows)
        heat_gap = 0.0
        for t, r in zip(ts, heat(events, ts)):
            scale = float(np.max(np.abs(r)))
            for row in rows[t]:
                gap = _gap(row, r, scale) if row.shape == r.shape else float("inf")
                heat_gap = max(heat_gap, gap)
    numbers = {"heat_gap": heat_gap, "unanswered": unanswered, **(served or {})}
    checks = {name: {"value": value, "limit": limits.get(name, 0)}
              for name, value in numbers.items() if value is not None}
    correct = bool(rows) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return dict(correct=correct, checks=checks, coverage=coverage)
