"""Program spans and per-request wait stamps of the served path
(repro.spans, DESIGN.md §10), on the CPU at a small size.

A served query under ``jax.profiler.trace`` emits every span of
``repro.spans.SPANS``, nested where the work nests, with the serve spans
carrying the responses' ``flush_id``; the continuous core stamps each
response with its wait behind the flush in flight ahead; and tracing
changes no answer.
"""
import glob
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.core.events import Events
from repro.data.spatial import make_events, make_network
from repro.serve import ContinuousCore, ProfileConfig, TNKDEServer
from repro.spans import SPANS

DAY = 86400.0
KW = dict(g=40.0, b_s=600.0, b_t=2.0 * DAY)
# window_cap 2 makes each request its own flush: three flushes, two in
# flight at once (the core's default inflight_depth)
REQUESTS = [[2.5 * DAY, 6.0 * DAY], [3.5 * DAY], [4.5 * DAY, 7.0 * DAY]]
NESTED = {"tnkde.plan": "serve.dispatch", "tnkde.window_batch": "serve.dispatch",
          "tnkde.enqueue": "serve.dispatch", "tnkde.transfer": "serve.retire",
          "tnkde.ls_sweep": "serve.retire", "serve.assemble": "serve.retire"}


def _world():
    net = make_network(24, 40, seed=7)
    ev = make_events(net, 240, seed=8, span_days=9)
    order = np.argsort(ev.time, kind="stable")
    return net, Events(ev.edge_id[order], ev.pos[order], ev.time[order])


def _serve(solution, profile_dir=None):
    """Serve REQUESTS in one pump; returns the responses by tag, each
    request's submission time, the pump's end and the retired flushes'
    (flush_id, id of the flush ahead, t_dispatch, t_ready)."""
    net, ev = _world()
    prof = ProfileConfig(solution=solution, engine="jax", lixel_sharing=True,
                         drfs_depth=4, **KW)
    srv = TNKDEServer(net, ev, {"default": prof}, mode="continuous",
                      n_slots=8, window_cap=2)
    flushes = []
    retire = ContinuousCore._retire

    def spy(core, fl):
        ahead = None if fl.ahead is None else fl.ahead.flush_id
        out = retire(core, fl)
        flushes.append((fl.flush_id, ahead, fl.t_dispatch, fl.t_ready))
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(ContinuousCore, "_retire", spy)
    try:
        if profile_dir is not None:
            jax.profiler.start_trace(profile_dir)
        sent = {}
        for tag, ts in enumerate(REQUESTS):
            sent[tag] = time.perf_counter()
            srv.submit(ts, tag=tag)
        out = srv.pump(force=True)
        t_end = time.perf_counter()
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
        mp.undo()
    return {r.tag: r for r in out}, sent, t_end, flushes


def _spans(profile_dir):
    """(name, start_ns, end_ns, metadata) of every program span traced."""
    path, = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name in SPANS:
                    out.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module", params=["rfs", "drfs"])
def served(request, tmp_path_factory):
    d = str(tmp_path_factory.mktemp(f"profile_{request.param}"))
    traced = _serve(request.param, d)
    return request.param, traced, _spans(d), _serve(request.param)


def test_served_query_emits_every_span_nested(served):
    _, (responses, _, _, _), spans, _ = served
    assert {n for n, *_ in spans} == set(SPANS)
    for name, s, e, _ in spans:
        if name in NESTED:
            assert any(n == NESTED[name] and s0 <= s and e <= e0
                       for n, s0, e0, _ in spans), name
    # the serve spans carry the flush id the responses report
    ids = {r.stats.flush_id for r in responses.values()}
    assert ids == {0, 1, 2}
    for kind in ("serve.dispatch", "serve.retire", "serve.assemble"):
        assert sorted(m["flush"] for n, *_, m in spans if n == kind) == [0, 1, 2]


def test_answers_identical_with_and_without_the_profiler(served):
    _, (traced, *_), _, (plain, *_) = served
    assert traced.keys() == plain.keys() == {0, 1, 2}
    for tag in traced:
        assert traced[tag].ok and plain[tag].ok
        np.testing.assert_array_equal(traced[tag].heat, plain[tag].heat)


def test_inflight_wait_is_the_wait_for_the_flush_ahead(served):
    _, _, _, (responses, _, _, flushes) = served
    by_id = {f: (ahead, t_dispatch, t_ready)
             for f, ahead, t_dispatch, t_ready in flushes}
    assert by_id[0][0] is None  # into an empty pipeline
    assert by_id[1][0] == 0 and by_id[2][0] == 1
    for r in responses.values():
        ahead, t_dispatch, _ = by_id[r.stats.flush_id]
        if ahead is None:
            assert r.stats.inflight_wait_seconds == 0.0
        else:
            # the second flush was dispatched before the first landed
            want = by_id[ahead][2] - t_dispatch
            assert want > 0
            assert r.stats.inflight_wait_seconds == want


def test_queue_and_inflight_wait_fit_in_the_latency(served):
    _, _, _, (responses, sent, t_end, _) = served
    for tag, r in responses.items():
        st = r.stats
        assert st.queue_seconds >= 0 and st.inflight_wait_seconds >= 0
        assert st.queue_seconds + st.inflight_wait_seconds <= t_end - sent[tag]


def test_span_without_jax_imports_nothing_heavy():
    code = ("import sys, repro.serve\n"
            "from repro.spans import span\n"
            "with span('serve.dispatch', flush=0):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")]
        + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
