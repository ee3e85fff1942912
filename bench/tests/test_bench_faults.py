"""A whole run on the CPU at a small size, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, the
comparison turns ``correct`` false. One case per fault the cells can have:
half of a flush's windows left out, one answer altered where it is
produced, and an engine that faults until the server degrades it to the
host. (No cell inserts, so no insert can leave the state unchanged; no
cell spans chips, so there is no exchange between chips to leave out.)"""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = bench_run.load_spec(os.path.dirname(BENCH))


def small(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    with open(os.path.join(BENCH, "configs", f"{w['config']}.json")) as f:
        cfg = json.load(f)
    cfg["data"]["scale"] = 0.03
    cfg["server"]["window_cap"] = 4
    mix = traffic.Mix.load(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    return cfg, mix


def run(cell, seconds):
    cfg, mix = small(cell)
    out = bench_run.run_cell(SPEC, cell, 2**31 + 21, seconds, False,
                             config=cfg, mix=mix, platforms=("cpu",),
                             say=lambda msg: None)
    return out["line"]


def half_batch(monkeypatch):
    from repro.core.tnkde import PendingQuery

    orig = PendingQuery.result

    def result(self):
        F = orig(self)
        F[(len(F) + 1) // 2:] = 0.0
        return F

    monkeypatch.setattr(PendingQuery, "result", result)


def altered_answer(monkeypatch):
    from repro.core.tnkde import PendingQuery

    orig = PendingQuery.result

    def result(self):
        F = orig(self)
        F[0, int(np.argmax(F[0]))] *= 1.05
        return F

    monkeypatch.setattr(PendingQuery, "result", result)


def engine_fault(monkeypatch):
    """The device faults on the window's first two flushes; the server's
    ladder then degrades the profile, and the host answers the rest."""
    from repro.core.tnkde import PendingQuery
    from repro.serve.server import TNKDEServer

    warm, result = TNKDEServer.warmup, PendingQuery.result
    n = [0]

    def faulty(self):
        n[0] += 1
        if n[0] <= 2:
            raise RuntimeError("device fault")
        return result(self)

    def warmup(self, **kw):
        out = warm(self, **kw)
        monkeypatch.setattr(PendingQuery, "result", faulty)
        return out

    monkeypatch.setattr(TNKDEServer, "warmup", warmup)


CASES = [("berkeley-live.analyst", 3.0), ("johns_creek-static.analyst", 3.0)]
FAULTS = (half_batch, altered_answer, engine_fault)


@pytest.mark.parametrize("cell,seconds", CASES)
def test_a_sound_run_is_correct(cell, seconds):
    line = run(cell, seconds)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("cell,seconds,fault", [
    (cell, seconds, fault) for cell, seconds in CASES for fault in FAULTS
], ids=lambda v: getattr(v, "__name__", v))
def test_a_broken_timed_path_is_not_correct(cell, seconds, fault, monkeypatch):
    fault(monkeypatch)
    line = run(cell, seconds)
    assert not line["correct"], line["checks"]
