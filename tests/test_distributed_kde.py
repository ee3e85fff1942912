"""Sharded packed-plan engines vs the single-host packed executor.

The sharded path shares the executor bodies verbatim (DESIGN.md §3), so the
acceptance bound is tight: ≤1e-12 relative against the single-host packed
engine across RFS + DRFS (quantized / exact_leaf) × kernel families ×
2/4/8 forced host devices, plus a streaming interleaving against the SPS
oracle and a jit_entry_count audit (zero steady-state recompiles; shard
count must not multiply compiles).

Device-count cases run in subprocesses so the XLA_FLAGS overrides never
leak into the other tests' single-device world. Host-side slabbing and
degenerate `assign_edges` cases are pinned in-process (no jax needed).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.distributed import assign_edges

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%DEV%"
    import sys, json
    sys.path.insert(0, sys.argv[1])
    import numpy as np
    import jax
    from repro.core import TNKDE
    from repro.core.events import Events
    from repro.core.rfs import jit_entry_count
    from repro.compat import host_mesh
    from repro.data.spatial import make_network, make_events

    DEV = %DEV%
    FULL = %FULL%
    net = make_network(36, 60, seed=31)
    ev = make_events(net, 420, seed=32, span_days=10)
    KW = dict(g=50.0, b_s=600.0, b_t=2.0 * 86400.0)
    TS = [2.5 * 86400.0, 6.0 * 86400.0]
    FAMILIES = [("triangular", "quartic"), ("epanechnikov", "cosine")]
    if not FULL:
        FAMILIES = FAMILIES[:1]
    mesh = host_mesh(DEV)
    res = {"devices": len(jax.devices()), "errs": {}}

    # ---- equivalence matrix: sharded vs single-host packed ----------------
    m_rfs = None
    for ks, kt in FAMILIES:
        kw = dict(KW, spatial_kernel=ks, temporal_kernel=kt)
        for mode in ("rfs", "quantized", "exact_leaf"):
            mkw = dict(kw)
            sol = "rfs" if mode == "rfs" else "drfs"
            if sol == "drfs":
                mkw.update(drfs_depth=4, drfs_exact_leaf=(mode == "exact_leaf"))
            single = TNKDE(net, ev, solution=sol, engine="jax", **mkw)
            ref = single.query(TS)
            m = TNKDE(net, ev, solution=sol, mesh=mesh, **mkw)
            got = m.query(TS)
            err = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-300))
            res["errs"]["%s/%s/%s" % (ks, kt, mode)] = err
            if mode == "rfs":
                m_rfs = m
                res["bytes_single"] = int(single._fe.bytes_per_shard)
                res["bytes_per_shard"] = int(m.stats.bytes_per_shard)
    res["engine_desc"] = m_rfs.engine_desc
    res["shard_loads"] = [int(x) for x in m_rfs._fe.sf.events_per_shard]

    # ---- dense window tables: each shard on the mesh vs its slab alone ----
    import jax.numpy as jnp
    from repro.compat import device_precision
    from repro.core.jax_engine import PackedForest, time_key
    from repro.core.rfs import _get_packed, feature_major
    fe, sf = m_rfs._fe, m_rfs._fe.sf
    wb = fe.window_batch(fe.rf.ctx, TS)
    mesh_tabs = np.asarray(fe.window_tables(wb, tuple(TS)))
    tables_fn = _get_packed()[0]
    slab_errs = []
    for s in range(DEV):
        with device_precision():
            pf = PackedForest(
                pm_pos=jnp.asarray(sf.pm_pos[s]), pos_base=jnp.asarray(sf.pos_base[s]),
                pm_time=jnp.asarray(time_key(sf.pm_time[s])),
                pm_phi=jnp.asarray(feature_major(sf.pm_phi[s])),
                n_pad=jnp.asarray(sf.n_pad[s]),
            )
            wb1 = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), wb)
            one = np.asarray(tables_fn(pf, wb1, level_nodes=sf.level_nodes,
                                       k_t=int(fe.rf.ctx.k_t)))
        slab_errs.append(float(np.abs(mesh_tabs[s] - one).max()
                               / max(np.abs(one).max(), 1e-300)))
    res["slab_errs"] = slab_errs

    # ---- zero steady-state recompiles -------------------------------------
    c0 = jit_entry_count()
    m_rfs.query(TS)
    res["steady_growth"] = (jit_entry_count() - c0) if c0 >= 0 else None

    # ---- shard count must not multiply compiles ---------------------------
    if DEV >= 4 and jit_entry_count() >= 0:
        growth = []
        for n in (2, 4):
            c0 = jit_entry_count()
            TNKDE(net, ev, solution="rfs", mesh=host_mesh(n), **KW).query(TS)
            growth.append(jit_entry_count() - c0)
        res["growth_by_shards"] = growth

    # ---- streaming interleaving vs the SPS oracle (exact mode) ------------
    order = np.argsort(ev.time, kind="stable")
    ev_s = Events(ev.edge_id[order], ev.pos[order], ev.time[order])
    def sub(lo, hi):
        return Events(ev_s.edge_id[lo:hi], ev_s.pos[lo:hi], ev_s.time[lo:hi])
    ms = TNKDE(net, sub(0, 140), solution="drfs", mesh=mesh, drfs_depth=3,
               drfs_exact_leaf=True, **KW)
    n_vis = 140
    stream_errs = []
    def check():
        got = ms.query(TS)
        oracle = TNKDE(net, sub(0, n_vis), solution="sps", **KW).query(TS)
        stream_errs.append(
            float(np.abs(got - oracle).max() / max(np.abs(oracle).max(), 1e-300))
        )
    for op, arg in (("insert", 60), ("query", None), ("insert", 80),
                    ("query", None), ("seal", None), ("query", None),
                    ("extend", None), ("insert", 70), ("query", None)):
        if op == "insert":
            ms.insert(sub(n_vis, n_vis + arg))
            n_vis += arg
        elif op == "seal":
            ms.index.seal()
        elif op == "extend":
            ms.index.extend()
        else:
            check()
    res["stream_errs"] = stream_errs

    # ---- sharded serve: epoch-pinned micro-batches from the sharded forest
    if FULL:
        from repro.serve import ProfileConfig, TNKDEServer
        cfg = {"default": ProfileConfig(
            g=60.0, b_s=KW["b_s"], b_t=KW["b_t"], solution="drfs", drfs_depth=3
        )}
        srv_s = TNKDEServer(net, sub(0, 200), profiles=cfg, mesh=mesh)
        srv_1 = TNKDEServer(net, sub(0, 200), profiles=cfg)
        serve_errs = []
        for srv in (srv_s, srv_1):
            srv.submit(TS[:1])
        # mutation between admission and pump: both must answer the PINNED epoch
        for srv in (srv_s, srv_1):
            srv.insert(sub(200, 240))
            srv.submit(TS)
        got = {}
        for name, srv in (("sharded", srv_s), ("single", srv_1)):
            got[name] = {r.id: r.heat for r in srv.pump(force=True)}
        for rid in got["single"]:
            a, b = got["sharded"][rid], got["single"][rid]
            serve_errs.append(
                float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-300))
            )
        res["serve_errs"] = serve_errs
        res["serve_desc"] = srv_s.models["default"].engine_desc
    print(json.dumps(res))
    """
)


def _run_matrix(tmp_path, devices: int, full: bool):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / f"dist_kde_{devices}.py"
    script.write_text(
        SCRIPT.replace("%DEV%", str(devices)).replace("%FULL%", str(full))
    )
    out = subprocess.run(
        [sys.executable, str(script), src],
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_matrix(res, devices: int):
    assert res["devices"] == devices
    assert res["engine_desc"] == f"jax/packed@shards={devices}"
    for key, err in res["errs"].items():
        assert err <= 1e-12, (key, err)
    for err in res["stream_errs"]:
        assert err <= 1e-11, res["stream_errs"]
    # one dense table build per shard, the same on the mesh as alone
    assert len(res["slab_errs"]) == devices
    for err in res["slab_errs"]:
        assert err <= 1e-12, res["slab_errs"]
    # greedy balancing: no shard holds more than 2x the mean event load
    loads = np.array(res["shard_loads"], float)
    assert loads.max() <= 2.0 * max(loads.mean(), 1.0), loads
    # per-shard slab ≈ 1/devices of the single-device index (padding slack)
    frac = res["bytes_per_shard"] / max(res["bytes_single"], 1)
    assert frac <= 1.0 / devices + 0.25, (res["bytes_per_shard"], res["bytes_single"])
    if res["steady_growth"] is not None:
        assert res["steady_growth"] == 0, res
    if res.get("growth_by_shards") is not None:
        g2, g4 = res["growth_by_shards"]
        # one program set per mesh — doubling the shard count must not add
        # compiles beyond the per-mesh set (it is the same program count)
        assert 0 < g4 <= g2, res["growth_by_shards"]
    for err in res.get("serve_errs", []):
        assert err <= 1e-12, res["serve_errs"]
    if "serve_desc" in res:
        assert res["serve_desc"] == f"jax/packed@shards={devices}"


def test_sharded_matrix_2dev(tmp_path):
    _check_matrix(_run_matrix(tmp_path, 2, full=True), 2)


def test_sharded_matrix_4dev(tmp_path):
    _check_matrix(_run_matrix(tmp_path, 4, full=False), 4)


@pytest.mark.slow
def test_sharded_matrix_8dev(tmp_path):
    _check_matrix(_run_matrix(tmp_path, 8, full=True), 8)


# --------------------------------------------------------------- host-side
def test_assign_edges_degenerate_cases():
    """More shards than edges / zero-event edges / no edges must all yield
    valid assignments (every edge assigned, zero-event edges spread)."""
    # more shards than edges: every edge still lands on exactly one shard
    out = assign_edges(np.array([5, 3]), 8)
    assert out.shape == (2,) and set(out) <= set(range(8))
    assert out[0] != out[1]  # two heavy edges never share while shards idle
    # zero-event edges spread round-robin instead of piling onto one shard
    out = assign_edges(np.zeros(12, np.int64), 4)
    assert np.bincount(out, minlength=4).max() == 3
    # empty network
    assert assign_edges(np.zeros(0, np.int64), 4).shape == (0,)
    # mixed: heavy edges balance by n log n work, light ones fill in
    counts = np.array([1000, 0, 0, 1000, 2, 2])
    out = assign_edges(counts, 2)
    heavy = out[[0, 3]]
    assert heavy[0] != heavy[1]


def test_sharded_slabs_degenerate_build():
    """Slabbing with more shards than edges yields valid (empty) slabs."""
    from repro.core.aggregation import build_event_moments
    from repro.core.distributed import build_sharded_packed
    from repro.core.events import group_events_by_edge
    from repro.core.kernels_math import get_kernel
    from repro.core.rfs import RangeForest
    from repro.data.spatial import make_events, make_network

    net = make_network(4, 4, seed=3)
    ev = make_events(net, 12, seed=4, span_days=5)
    ee = group_events_by_edge(net, ev)
    k = get_kernel("triangular")
    ctx, phi = build_event_moments(net, ee, k, k, 500.0, 86400.0)
    rf = RangeForest(net, ee, ctx, phi)
    S = net.n_edges + 3  # strictly more shards than edges
    sf = build_sharded_packed(rf, S)
    assert sf.n_shards == S
    assert sf.pm_pos.shape[0] == S and sf.pm_time.shape[0] == S
    # every edge owned exactly once, local slots dense per shard
    for s in range(S):
        own = np.nonzero(sf.shard_of_edge == s)[0]
        assert sorted(sf.edge_slot[own]) == list(range(len(own)))
    # empty shards have valid minimal slabs (uniform padded shapes)
    assert sf.pm_pos.shape[1] >= 1 and sf.pm_time.shape[1] >= 1
    assert int(sf.events_per_shard.sum()) == ee.n


def test_route_atoms_padding_invariants():
    """Padded routing rows are inert: valid=False, empty intervals, slot 0."""
    from repro.core.plan import AtomSet
    from repro.core.query_plan import route_atoms_by_shard

    m = 5
    atoms = AtomSet(
        lixel=np.arange(m),
        edge=np.array([0, 1, 1, 2, 3]),
        side_feat=np.zeros(m, np.int64),
        qs=np.ones((m, 2)),
        pos_hi=np.full(m, 10.0),
        pos_lo1=np.zeros(m),
        lo1_right=np.zeros(m, bool),
        pos_lo2=np.zeros(m),
    )
    shard_of = np.array([0, 1, 0, 1])
    edge_slot = np.array([0, 0, 1, 1])
    fields = route_atoms_by_shard(atoms, shard_of, edge_slot, 2, pad_to=4)
    assert fields["valid"].shape == (2, 4)
    assert fields["valid"].sum() == m
    # atoms landed on the shard owning their edge, with local ids
    assert list(fields["edge"][0][fields["valid"][0]]) == [0, 1]  # edges 0, 2
    assert list(fields["edge"][1][fields["valid"][1]]) == [0, 0, 1]  # 1, 1, 3
    pad = ~fields["valid"]
    assert np.all(fields["pos_hi"][pad] == -np.inf)
    assert np.all(fields["edge"][pad] == 0)
