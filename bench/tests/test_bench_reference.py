"""The plain reference against a brute-force loop over every lixel, event
and route, and its bfloat16 control against the comparison's limits."""
import json
import os

import numpy as np
import pytest

from harness import data, reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def brute(net, ev, g, b_s, b_t, ts):
    edge, x = reference.lixels(net, g)
    D = reference.vertex_distances(net, np.inf)
    F = np.zeros((len(ts), len(edge)))
    for q, (a, xq) in enumerate(zip(edge, x)):
        va, vb, la = net.src[a], net.dst[a], net.length[a]
        for e, xp, tp in zip(ev.edge, ev.pos, ev.time):
            if e == a:
                d = abs(xq - xp)
            else:
                vc, vd, le = net.src[e], net.dst[e], net.length[e]
                dqc = min(xq + D[va, vc], la - xq + D[vb, vc])
                dqd = min(xq + D[va, vd], la - xq + D[vb, vd])
                d = min(dqc + xp, dqd + le - xp)
            ks = max(0.0, 1.0 - d / b_s)
            for w, t in enumerate(ts):
                F[w, q] += ks * max(0.0, 1.0 - abs(t - tp) / b_t)
    return F


@pytest.fixture(scope="module")
def world():
    net = data.make_network(12, 20, seed=3)
    ev = data.make_events(net, 300, 3, 2**31 + 9)
    b_t = 0.25 * float(ev.time.max() - ev.time.min())
    ts = list(np.random.default_rng(0).uniform(ev.time.min() + b_t,
                                                ev.time.max() - b_t, 3))
    return net, ev, b_t, ts


def test_reference_matches_a_brute_force_sum(world):
    net, ev, b_t, ts = world
    want = brute(net, ev, 50.0, 400.0, b_t, ts)
    got = reference.Reference(net, g=50.0, b_s=400.0, b_t=b_t).heat(ev, ts)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(want).max() > 0


@pytest.mark.parametrize("config", ["berkeley-live", "johns_creek-static"])
def test_bfloat16_control_fails_the_heat_limit(config):
    # a tenth of the configuration's events: the bfloat16 error grows with
    # the events summed, and at full size it reads higher still
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    net, ev = data.make_dataset(cfg["data"]["dataset"], 0.1, 0, 11)
    b_t = 0.25 * float(ev.time.max() - ev.time.min())
    ts = list(np.linspace(ev.time.min() + b_t, ev.time.max() - b_t, 4))
    kw = dict(g=cfg["g"], b_s=cfg["b_s"], b_t=b_t)
    ref = reference.Reference(net, **kw).heat(ev, ts)
    low = reference.Reference(net, **kw, dtype="bfloat16").heat(ev, ts)
    gap = (np.abs(low - ref).max(1) / np.abs(ref).max(1)).max()
    assert gap > cfg["limits"]["heat_gap"]
