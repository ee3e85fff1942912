"""The trace reduction: busy union, per-program device time and labelled
idle gaps, on a hand-made trace and on a small trace recorded on a v5e."""
import os

import numpy as np
import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_on_a_hand_made_trace():
    ms = 1_000_000
    compact = {
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__flush(7)", 10 * ms, 30 * ms],
                        ["jit_dyn_window_tables(3)", 50 * ms, 20 * ms],
                        ["jit__flush(7)", 95 * ms, 10 * ms]],
            # ops overlap inside a module; the busy time is their union
            "ops": [["fusion.1", 10 * ms, 20 * ms], ["fusion.2", 25 * ms, 15 * ms],
                    ["gather", 50 * ms, 20 * ms], ["fusion.1", 95 * ms, 10 * ms]],
        }],
        "host": [["window", 0, 100 * ms], ["pump", 0, 45 * ms],
                 ["sleep", 70 * ms, 25 * ms], ["admit", 40 * ms, 10 * ms],
                 ["drain", 42 * ms, 5 * ms]],
    }
    r = trace.reduce(compact)
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [10, 40) + [50, 70) + [95, 100) inside the window
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["program_s"] == pytest.approx({"_flush": 0.035,
                                            "dyn_window_tables": 0.020})
    assert r["device_ops"][0] == ["_flush", pytest.approx(0.035)]
    # [0, 10) lies under pump; [40, 50) under admit (10 ms) more than
    # under pump (5 ms) or the nested drain (5 ms); [70, 95) under sleep
    got = sorted((name, round(s, 6)) for name, s in r["idle_gaps"])
    assert got == [("admit", 0.01), ("pump", 0.01), ("sleep", 0.025)]
    assert r["idle_gaps"][0][0] == "sleep"  # longest first


def test_reduce_without_a_window_or_a_device_reads_nothing():
    assert trace.reduce({"devices": [], "host": [["window", 0, 5]]}) is None
    assert trace.reduce({"devices": [{"name": "d", "modules": [], "ops": [
        ["x", 0, 1]]}], "host": []}) is None


def test_program_names():
    assert trace.program_name("jit__flush(123)") == "_flush"
    assert trace.program_name("jit_dyn_window_tables") == "dyn_window_tables"


def _grid_busy(compact, res_ns=1000):
    """Busy seconds per device by marking a time grid: a second, slower
    computation of the same union."""
    (lo, hi), = [(s, s + d) for n, s, d in compact["host"] if n == "window"]
    busy = []
    for dev in compact["devices"]:
        mask = np.zeros((hi - lo) // res_ns + 1, bool)
        for _, s, d in dev["ops"] or dev["modules"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                mask[(a - lo) // res_ns:(b - lo) // res_ns] = True
        busy.append(mask.sum() * res_ns * 1e-9)
    return sum(busy) / len(busy), (hi - lo) * 1e-9


def test_reduce_on_a_recorded_v5e_trace():
    compact = trace.read(os.path.join(DATA, "v5e_trace.json.gz"))
    r = trace.reduce(compact)
    busy, window = _grid_busy(compact)
    assert r["window_s"] == pytest.approx(window)
    assert r["busy_s"] == pytest.approx(busy, abs=2e-5 * len(compact["devices"][0]["ops"]) + 1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    # per-program time is the modules' time inside the window
    (lo, hi), = [(s, s + d) for n, s, d in compact["host"] if n == "window"]
    want = {}
    for name, s, d in compact["devices"][0]["modules"]:
        ov = min(s + d, hi) - max(s, lo)
        if ov > 0:
            key = trace.program_name(name)
            want[key] = want.get(key, 0) + ov * 1e-9
    assert r["program_s"] == pytest.approx(want)
    # gaps and busy time tile the window
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
