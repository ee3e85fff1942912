"""Reduction of a ``--trace 1`` profile to device busy time, per-program
device time and idle gaps labelled by the benchmark's host spans.

Two steps, so that the arithmetic can be checked on a small recorded trace
without the profiler: :func:`load` reads the profiler's ``.xplane.pb`` into
a compact dict of plain lists, and :func:`reduce` computes everything from
that dict.

Compact form::

    {"devices": [{"name": "/device:TPU:0",
                  "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[span name, start_ns, dur_ns], ...]}

``host`` keeps only the benchmark's own spans (``SPANS`` and ``window``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional

SPANS = ("admit", "pump", "drain", "sleep")
WINDOW = "window"
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """XLA module name without the ``jit_`` prefix and ``(id)`` suffix."""
    name = _SUFFIX.sub("", module.strip())
    return name[4:] if name.startswith("jit_") else name


def load(profile_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = [], []
    keep = set(SPANS) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
            if dev["modules"] or dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return {"devices": devices, "host": host}


def save(compact: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(compact, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(compact: dict, *, top: int = 10) -> Optional[dict]:
    """Busy and window seconds, per-program device seconds and the longest
    idle gaps, all inside the ``window`` host span. None when the trace has
    no window span or no device activity."""
    win = [(s, s + d) for n, s, d in compact["host"] if n == WINDOW]
    if not win or not compact["devices"]:
        return None
    lo, hi = win[0]
    window_ns = hi - lo
    busy_ns, programs = [], {}
    gaps: List[tuple] = []
    for dev in compact["devices"]:
        src = dev["ops"] or dev["modules"]
        busy = _union(_clip([(s, s + d) for _, s, d in src], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in dev["modules"]:
            for a, b in _clip([(s, s + d)], lo, hi):
                key = program_name(name)
                programs[key] = programs.get(key, 0) + (b - a)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n_dev = len(compact["devices"])
    spans = [(n, s, s + d) for n, s, d in compact["host"] if n in SPANS]
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        labelled.append([_label(a, b, spans), (b - a) * 1e-9])
    ranked = sorted(programs.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "window_s": window_ns * 1e-9,
        "program_s": {k: v / n_dev * 1e-9 for k, v in programs.items()},
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in ranked[:top]],
        "idle_gaps": labelled,
    }


def _label(a: int, b: int, spans) -> str:
    """The host span that overlaps the gap [a, b) most, the innermost where
    nested spans tie (``none`` if no span overlaps)."""
    cover: Dict[str, list] = {}
    for n, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            c = cover.setdefault(n, [0, 0])
            c[0] += ov
            c[1] += e - s
    if not cover:
        return "none"
    return max(cover, key=lambda n: (cover[n][0], -cover[n][1]))
