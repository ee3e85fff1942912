"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``."""
from __future__ import annotations


def rows_computed(run) -> int:
    return run.counters1["n_rows_computed"] - run.counters0["n_rows_computed"]


def device_ms_per_row(run, programs) -> float | None:
    """Device milliseconds of the named programs per heat row computed, over
    the traced window; None without a trace or without a computed row."""
    if run.trace is None:
        return None
    n = rows_computed(run)
    if n <= 0:
        return None
    s = sum(v for k, v in run.trace["program_s"].items() if k in programs)
    return 1e3 * s / n


def idle_share(run) -> float | None:
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
