"""Dense packed window tables vs a brute-force NumPy oracle.

``jax_engine.packed_node_tables`` masks the position-ordered leaves once per
window batch and sums pairwise up the tree over the descending-``n_pad``
layout of ``rfs.build_packed_host_tables``. The oracle here knows nothing of
that layout but the node ids: for every (edge, level, bucket) it takes the
edge's events in position order, sums the raw Φ of those whose time falls in
each half-window (left t_lo ≤ t ≤ t_mid, right t_mid < t ≤ t_hi), and
contracts with q_t. Event times sit on a coarse grid and window bounds on
the same grid, so bounds fall exactly on event times and pin the ≤ / <
sides. Edges hold 0 to 64 events (n_pad 1…64, and empty edges). The
sharded slabs (``distributed.build_sharded_packed``) run the same builder
per shard, as the shard_map body does, against the same oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import device_precision
from repro.core.aggregation import build_event_moments
from repro.core.distributed import build_sharded_packed
from repro.core.events import Events, group_events_by_edge
from repro.core.jax_engine import PackedForest, WindowBatch, time_key
from repro.core.kernels_math import get_kernel
from repro.core.rfs import (
    FlatForestEngine,
    RangeForest,
    _get_packed,
    feature_major,
    make_window_batch,
)
from repro.data.spatial import make_network

HOUR = 3600.0
B_T = 5 * HOUR
COUNTS = [0, 1, 2, 3, 4, 5, 7, 8, 9, 16, 31, 33, 64, 0, 6, 12, 17, 2, 1, 0]
N_SHARDS = 4
KERNELS = [("triangular", "triangular"), ("exponential", "exponential")]


def _forest(ks, kt):
    net = make_network(16, 24, seed=5)
    rng = np.random.default_rng(6)
    counts = np.resize(np.asarray(COUNTS), net.n_edges)
    edge = np.repeat(np.arange(net.n_edges), counts)
    pos = rng.uniform(0.0, 1.0, len(edge)) * net.edge_len[edge]
    time = rng.integers(0, 40, len(edge)) * HOUR  # ties, on the bounds' grid
    ee = group_events_by_edge(net, Events(edge, pos, time))
    ctx, phi = build_event_moments(
        net, ee, get_kernel(ks), get_kernel(kt), 500.0, B_T
    )
    return RangeForest(net, ee, ctx, phi)


def _centers(W):
    """W centers on the hour grid; t ± b_t land on event times too. The
    three-window batch repeats a center."""
    ts = [10 * HOUR, 20 * HOUR, 27 * HOUR, 8 * HOUR, 30 * HOUR, 15 * HOUR,
          22 * HOUR, 35 * HOUR][:W]
    if W == 3:
        ts[2] = ts[0]
    return ts


def _oracle(rf, ts, edges, node_id, R):
    """Brute-force node values [W·2k_s, 2R] of ``edges``; columns that no
    listed edge's node addresses stay NaN."""
    ctx, ee = rf.ctx, rf.ee
    k_s, k_t = ctx.k_s, ctx.k_t
    C = 2 * k_s
    t_lo, t_hi, _, _, qt = make_window_batch(ctx, ts)
    out = np.full((len(ts) * C, 2 * R), np.nan)
    for e in edges:
        lo, hi = int(ee.ptr[e]), int(ee.ptr[e + 1])
        if hi == lo:
            continue
        order = np.argsort(ee.pos[lo:hi], kind="stable")
        tm = ee.time[lo:hi][order]
        ph = rf.phi[lo:hi][order]  # [n, 4, K]
        for lev in range(int(rf.n_levels[e])):
            for b in range(int(rf.n_pad[e]) >> lev):
                nid = node_id(e, lev) + b
                t = tm[b << lev : (b + 1) << lev]
                p = ph[b << lev : (b + 1) << lev]
                for w in range(len(ts)):
                    halves = (
                        (t >= t_lo[2 * w]) & (t <= t_hi[2 * w]),
                        (t > t_lo[2 * w + 1]) & (t <= t_hi[2 * w + 1]),
                    )
                    for h, inside in enumerate(halves):
                        for c in (0, 1):
                            mom = p[inside, 2 * c + h].sum(axis=0).reshape(k_s, k_t)
                            rows = slice(w * C + h * k_s, w * C + (h + 1) * k_s)
                            out[rows, c * R + nid] = mom @ qt[2 * w + h]
    return out


def _check(got, want):
    on = ~np.isnan(want)
    assert on.any()
    scale = max(np.abs(want[on]).max(), 1e-300)
    np.testing.assert_allclose(got[on], want[on], rtol=1e-12, atol=1e-12 * scale)


@pytest.mark.parametrize("ks,kt", KERNELS)
@pytest.mark.parametrize("W", [1, 3, 8])
def test_packed_node_tables_match_oracle(ks, kt, W):
    rf = _forest(ks, kt)
    fe = FlatForestEngine(rf)
    pk = fe._get_packed_forest()
    nb = np.asarray(pk["node_base_lvl"]).T  # [E, Lmax]
    R = sum(pk["level_nodes"])
    # the layout: every node id in [0, R) exactly once
    ids = [nb[e, lev] + b for e in range(rf.net.n_edges)
           for lev in range(int(rf.n_levels[e]))
           for b in range(int(rf.n_pad[e]) >> lev)]
    assert sorted(ids) == list(range(R))
    ts = _centers(W)
    wb = fe.window_batch(rf.ctx, ts)
    got = np.asarray(fe.window_tables(wb, tuple(ts)))
    assert got.shape == (W * 2 * rf.ctx.k_s, 2 * R)
    _check(got, _oracle(rf, ts, range(rf.net.n_edges), lambda e, lev: nb[e, lev], R))
    assert fe.counters["table_leaves"] == W * int(rf.n_pad.sum())
    assert fe.counters["rank_searches"] == fe.counters["moment_gathers"] == 0
    if W == 3:  # a repeated center gives bitwise identical rows
        C = 2 * rf.ctx.k_s
        np.testing.assert_array_equal(got[:C], got[2 * C : 3 * C])


@pytest.mark.parametrize("shard", range(N_SHARDS))
def test_sharded_slab_tables_match_oracle(shard):
    rf = _forest("triangular", "triangular")
    sf = build_sharded_packed(rf, N_SHARDS)
    nb = sf.node_base_lvl[shard]  # [Lmax, El]
    owned = np.nonzero(sf.shard_of_edge == shard)[0]
    ts = _centers(8)
    t_lo, t_hi, lo_right, half, qt = make_window_batch(rf.ctx, ts)
    tables_fn, _, _ = _get_packed()
    with device_precision():
        pf = PackedForest(
            pm_pos=jnp.asarray(sf.pm_pos[shard]),
            pos_base=jnp.asarray(sf.pos_base[shard]),
            pm_time=jnp.asarray(time_key(sf.pm_time[shard])),
            pm_phi=jnp.asarray(feature_major(sf.pm_phi[shard])),
            n_pad=jnp.asarray(sf.n_pad[shard]),
        )
        wb = WindowBatch(
            t_lo=jnp.asarray(time_key(t_lo)), t_hi=jnp.asarray(time_key(t_hi)),
            lo_right=jnp.asarray(lo_right), half=jnp.asarray(half),
            qt=jnp.asarray(qt),
        )
        got = np.asarray(tables_fn(
            pf, wb, level_nodes=sf.level_nodes, k_t=int(rf.ctx.k_t)
        ))
    R = sum(sf.level_nodes)
    assert got.shape == (len(ts) * 2 * rf.ctx.k_s, 2 * R)
    slot = sf.edge_slot
    want = _oracle(rf, ts, owned, lambda e, lev: nb[lev, slot[e]], R)
    # the shard's real nodes have distinct ids inside the padded widths
    ids = [nb[lev, slot[e]] + b for e in owned
           for lev in range(int(rf.n_levels[e]))
           for b in range(int(rf.n_pad[e]) >> lev)]
    assert len(set(ids)) == len(ids) and max(ids, default=0) < R
    _check(got, want)
