"""The served continuous path over a sharded live index, stated as data.

A deployment states its shard count in its profile (``ProfileConfig(
shards=4)``, as ``bench/configs/san_francisco-sharded.json`` does); the
model then builds a mesh over the first four local devices and serves
through the sharded DRFS engine (DESIGN.md §3). Checked on four forced host
devices, in a subprocess so the device-count override never leaks, at a
small San Francisco replica (the benchmark's own generator, scale 0.01):

* the continuous server answers like the single-device server (≤1e-12),
  and, with exact leaves, like the NumPy SPS oracle (≤1e-11);
* the benchmark's plain reference (float32 direct sum) agrees within the
  float32 tolerance, and the quantized profile within the cell's limit;
* no recompile after warmup; the slab layout's counters; the slab span;
* a stated shard count the devices cannot hold, or one that disagrees
  with an explicit mesh, is refused.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, glob, json, sys, tempfile
    root = sys.argv[1]
    sys.path[:0] = [os.path.join(root, "src"), os.path.join(root, "bench")]
    import numpy as np
    import jax
    from harness import data, reference
    from repro.compat import host_mesh
    from repro.core import TNKDE
    from repro.core.distributed import _owned_lists, assign_edges
    from repro.core.events import Events
    from repro.core.network import RoadNetwork
    from repro.serve import ProfileConfig, TNKDEServer, jit_entries
    from repro.spans import SHARD_SPANS

    with open(os.path.join(root, "bench", "configs",
                           "san_francisco-sharded.json")) as f:
        cfg = json.load(f)
    net, ev = data.make_dataset("san_francisco", 0.01, 0, 3)
    b_t = cfg["b_t_share_of_span"] * float(ev.time.max() - ev.time.min())
    base, _ = data.split_by_time(ev, cfg["sealed_share"])
    rn = RoadNetwork(net.n_vertices, net.src, net.dst, net.length)
    events = Events(base.edge, base.pos, base.time)
    kw = dict(g=cfg["g"], b_s=cfg["b_s"], b_t=b_t,
              spatial_kernel=cfg["spatial_kernel"],
              temporal_kernel=cfg["temporal_kernel"])
    t0, t1 = float(ev.time.min()) + b_t, float(ev.time.max()) - b_t
    REQS = [list(t0 + (t1 - t0) * np.array(f))
            for f in ([0.13], [0.31, 0.77], [0.05, 0.5, 0.95])]

    def rel(a, b):
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-300))

    def serve(prof):
        srv = TNKDEServer(rn, events, {"default": prof}, mode="continuous",
                          n_slots=8, window_cap=4)
        srv.warmup()
        j0 = jit_entries()
        for tag, ts in enumerate(REQS):
            srv.submit(ts, tag=tag)
        out = {}
        while len(out) < len(REQS):
            for r in srv.pump(force=True):
                assert r.ok, r.error
                out[r.tag] = r.heat
        return srv, out, jit_entries() - j0

    res = {"devices": len(jax.devices()), "modes": {}}
    ref = reference.Reference(net, g=cfg["g"], b_s=cfg["b_s"], b_t=b_t)
    sps = TNKDE(rn, events, solution="sps", **kw)
    for exact in (False, True):
        prof = ProfileConfig(**kw, **dict(cfg["profile"], drfs_exact_leaf=exact))
        srv, got, growth = serve(prof)
        _, one, _ = serve(dataclasses.replace(prof, shards=1))
        m = srv.models["default"]
        r = {"desc": m.engine_desc, "growth": growth,
             "vs_single": max(rel(got[t], one[t]) for t in got),
             "vs_reference": max(
                 rel(got[t], ref.heat(base, REQS[t])) for t in got)}
        if exact:
            r["vs_sps"] = max(rel(got[t], sps.query(REQS[t])) for t in got)
        # counters: the slab layout, and one [L, W] delta psum'd per block
        owned, El, _ = _owned_lists(
            assign_edges(np.bincount(base.edge, minlength=net.n_edges), 4), 4)
        p0 = m.stats.psum_bytes
        m.query(REQS[2])
        n_blocks = len(m._host_plan(m.snapshot()).blocks)
        r["counters"] = dict(
            slots=m.stats.shard_edge_slots, edges_max=m.stats.shard_edges_max,
            psum=m.stats.psum_bytes - p0,
            want_slots=4 * El, want_edges_max=max(len(o) for o in owned),
            want_psum=n_blocks * m.n_lixels * len(REQS[2]) * 8,
            n_edges=net.n_edges)
        res["modes"]["exact" if exact else "quantized"] = r

    # the slab build and upload runs under its span
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    TNKDE(rn, events, solution="drfs", engine="jax", drfs_depth=4, shards=4, **kw)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    res["spans"] = sorted({
        e.name.split("#", 1)[0]
        for p in jax.profiler.ProfileData.from_file(path).planes
        for line in p.lines for e in line.events
        if e.name.split("#", 1)[0] in SHARD_SPANS})

    # stated shards the devices cannot hold, or that disagree with a mesh
    refused = {}
    for name, kws in (("too_many", dict(shards=8)),
                      ("mesh_mismatch", dict(shards=4, mesh=host_mesh(2))),
                      ("zero", dict(shards=0))):
        try:
            TNKDE(rn, events, solution="drfs", engine="jax", **kws, **kw)
            refused[name] = None
        except ValueError as e:
            refused[name] = str(e)
    try:
        TNKDEServer(rn, events, {"default": ProfileConfig(**kw, shards=4)},
                    mesh=host_mesh(2))
        refused["server_mesh"] = None
    except ValueError as e:
        refused["server_mesh"] = str(e)
    # an explicit mesh of the stated extent is accepted
    res["mesh_and_shards"] = TNKDE(rn, events, solution="drfs", engine="jax",
                                   mesh=host_mesh(4), shards=4, **kw).engine_desc
    res["refused"] = refused
    res["limit"] = cfg["limits"]["heat_gap"]
    print(json.dumps(res))
    """
)


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    script = tmp_path_factory.mktemp("sharded_serve") / "sharded_serve.py"
    script.write_text(SCRIPT)
    out = subprocess.run([sys.executable, str(script), ROOT],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


MODES = ["quantized", "exact"]


@pytest.mark.parametrize("mode", MODES)
def test_sharded_server_answers_like_one_device(result, mode):
    r = result["modes"][mode]
    assert result["devices"] == 4
    assert r["desc"].endswith("@shards=4"), r["desc"]
    assert r["vs_single"] <= 1e-12, r


def test_exact_leaves_match_the_sps_oracle(result):
    assert result["modes"]["exact"]["vs_sps"] <= 1e-11, result["modes"]["exact"]


@pytest.mark.parametrize("mode", MODES)
def test_benchmark_reference_agrees(result, mode):
    # float32 direct sum against the f64 answer; the quantized leaves add
    # the index's own error, which the cell's heat_gap limit bounds
    bound = 1e-5 if mode == "exact" else result["limit"]
    assert result["modes"][mode]["vs_reference"] <= bound, result["modes"][mode]


@pytest.mark.parametrize("mode", MODES)
def test_no_recompile_after_warmup(result, mode):
    assert result["modes"][mode]["growth"] == 0


@pytest.mark.parametrize("mode", MODES)
def test_counters_read_the_slab_layout(result, mode):
    c = result["modes"][mode]["counters"]
    assert c["slots"] == c["want_slots"] >= c["n_edges"]
    assert c["edges_max"] == c["want_edges_max"]
    assert c["psum"] == c["want_psum"] > 0


def test_slab_build_runs_under_its_span(result):
    assert result["spans"] == ["shard.slab"]


@pytest.mark.parametrize("case", ["too_many", "mesh_mismatch", "zero",
                                  "server_mesh"])
def test_unholdable_or_mismatched_shards_are_refused(result, case):
    assert result["refused"][case] is not None, case


def test_explicit_mesh_of_the_stated_extent_wins(result):
    assert result["mesh_and_shards"] == "jax/packed@shards=4"
