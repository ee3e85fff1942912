"""Shared benchmark harness: calibrated datasets, timing, CSV emission.

Every ``bench_*`` module maps to one figure of the paper (§8); scales are
reduced (C++/Xeon -> numpy/1 core) but the *relative* claims are what the
tables validate — see EXPERIMENTS.md §Paper-claims.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from repro.compat import enable_compile_cache
from repro.core import TNKDE
from repro.data.spatial import make_dataset

# the benchmark harness is an entry point: every process that imports it
# keeps its compiled programs in the persistent cache
enable_compile_cache()

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line, flush=True)


def timed(fn: Callable, repeats: int = 1):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn()
    return (time.perf_counter() - t0) / repeats, out


def dataset(name: str = "berkeley", scale: float = 0.08, seed: int = 0):
    return make_dataset(name, scale=scale, seed=seed)


def windows(ev, n: int, frac: float = 0.7, seed: int = 1):
    """n online query-window centers; each window holds ~frac of the span."""
    t0, t1 = float(ev.time.min()), float(ev.time.max())
    b_t = frac * (t1 - t0) / 2.0
    rng = np.random.default_rng(seed)
    ts = rng.uniform(t0 + b_t * 0.2, t1 - b_t * 0.2, size=n)
    return list(ts), b_t


def build_and_query(net, ev, *, solution, ts, b_t, g=50.0, b_s=800.0, **kw):
    """Returns (build_s, query_s, model, F)."""
    t0 = time.perf_counter()
    m = TNKDE(net, ev, g=g, b_s=b_s, b_t=b_t, solution=solution, **kw)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    F = m.query(ts)
    query_s = time.perf_counter() - t0
    return build_s, query_s, m, F
