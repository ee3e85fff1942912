"""Packed query plan: hoist invariants, op counters, caches, accounting.

The invariants the packed plan pins (DESIGN.md §7):
  * the window-table build scales with the index — the dense packed build
    folds W × every leaf and searches nothing — never with atoms × windows,
    and a warm (plan-hit) query pays ZERO;
  * the packed walk gathers one paired node row per (level, atom): strictly
    fewer moment rows than the legacy cascade executor moves;
  * plans are cached per (epoch, LS) and window tables per ts tuple, so
    steady state neither re-plans nor recompiles;
  * ``device_bytes`` counts index tables AND cached packed plans through the
    one shared helper.
"""
import numpy as np
import pytest

from repro.core import TNKDE
from repro.data.spatial import make_events, make_network

KW = dict(b_s=600.0, b_t=2.5 * 86400.0)
TS = [3 * 86400.0, 6 * 86400.0]


@pytest.fixture(scope="module")
def world():
    net = make_network(30, 50, seed=31)
    ev = make_events(net, 400, seed=32, span_days=12)
    return net, ev


def _query_deltas(m, ts):
    s0 = (m.stats.n_rank_searches, m.stats.n_moment_gathers, m.stats.n_table_leaves)
    m.query(ts)
    return (m.stats.n_rank_searches - s0[0], m.stats.n_moment_gathers - s0[1],
            m.stats.n_table_leaves - s0[2])


def test_rank_searches_scale_with_nodes_not_atoms(world):
    """Same index, 4x the lixel density -> identical table-build work: the
    dense packed build folds W x every padded leaf and searches nothing."""
    net, ev = world
    coarse = TNKDE(net, ev, g=80.0, solution="rfs", engine="jax", **KW)
    fine = TNKDE(net, ev, g=20.0, solution="rfs", engine="jax", **KW)
    d_coarse = _query_deltas(coarse, TS)
    d_fine = _query_deltas(fine, TS)
    assert fine.stats.n_atoms > 2 * coarse.stats.n_atoms  # the load differs
    assert d_fine[2] == d_coarse[2] > 0  # ... the table-build work does not
    # and the count is exactly W x the leaves, sum of n_pad
    assert d_fine[2] == len(TS) * int(fine._fe.rf.n_pad.sum())
    assert d_fine[0] == d_coarse[0] == 0  # the packed build has no search


def test_warm_query_pays_zero_searches(world):
    net, ev = world
    m = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax", **KW)
    cold = _query_deltas(m, TS)
    warm = _query_deltas(m, TS)
    assert cold[2] > 0 and warm[2] == 0  # plan hit: no leaves folded at all
    assert cold[0] == warm[0] == 0  # and no searches, cold or warm
    assert warm[1] > 0  # the walk still gathers node rows
    # one paired gather per (level, atom): 2 rows x levels x atoms, summed
    # over level classes -> bounded by 2 * max_levels * atoms per query
    atoms = m.stats.n_atoms // 2  # two queries accumulated so far
    assert warm[1] <= 2 * m._fe.max_levels * atoms


def test_packed_gathers_strictly_fewer_than_cascade(world):
    net, ev = world
    packed = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                   executor="packed", **KW)
    cascade = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                    executor="cascade", **KW)
    g_packed = _query_deltas(packed, TS)[1]
    g_cascade = _query_deltas(cascade, TS)[1]
    assert 0 < g_packed < g_cascade


def test_drfs_searches_atom_independent(world):
    net, ev = world
    coarse = TNKDE(net, ev, g=80.0, solution="drfs", engine="jax",
                   drfs_depth=5, **KW)
    fine = TNKDE(net, ev, g=20.0, solution="drfs", engine="jax",
                 drfs_depth=5, **KW)
    s_coarse = _query_deltas(coarse, TS)[0]
    s_fine = _query_deltas(fine, TS)[0]
    assert s_fine == s_coarse == 3 * len(TS) * net.n_edges * (1 << 5)


def test_plan_cache_reuse_and_epoch_invalidation(world):
    """Warm queries reuse the plan bitwise; inserts move the epoch key."""
    from repro.core.events import Events

    net, ev = world
    # exact mode: a streamed index answers identically to a fresh build
    # (quantized mode legitimately differs — pending events scan exactly)
    m = TNKDE(net, ev, g=40.0, solution="drfs", engine="jax", drfs_depth=5,
              drfs_exact_leaf=True, **KW)
    a = m.query(TS)
    key0 = (m.epoch, m.ls)
    assert m._plan_cache.get(key0) is not None
    b = m.query(TS)
    np.testing.assert_array_equal(a, b)
    # an insert bumps the epoch: the old plan no longer serves the live head
    extra = Events(
        np.array([0, 1], np.int64),
        np.array([1.0, 2.0]),
        np.array([4 * 86400.0, 4.1 * 86400.0]),
    )
    m.insert(extra)
    assert (m.epoch, m.ls) != key0
    c = m.query(TS)
    assert not np.array_equal(a, c)  # the new events are visible
    ref = TNKDE(net, Events(
        np.concatenate([ev.edge_id, extra.edge_id]),
        np.concatenate([ev.pos, extra.pos]),
        np.concatenate([ev.time, extra.time]),
    ), g=40.0, solution="drfs", engine="numpy", drfs_depth=5,
        drfs_exact_leaf=True, **KW).query(TS)
    np.testing.assert_allclose(c, ref, rtol=1e-9, atol=1e-12 * max(ref.max(), 1.0))


def test_device_bytes_counts_packed_plans(world):
    net, ev = world
    m = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax", **KW)
    before = m._fe.device_bytes
    assert before > 0  # index tables
    m.query(TS)
    after = m._fe.device_bytes
    assert after > before  # + window tables + atom packs (the cached plans)
    # the dynamic engine shares the same helper and property contract
    d = TNKDE(net, ev, g=40.0, solution="drfs", engine="jax", drfs_depth=5, **KW)
    b0 = d._fe.device_bytes
    d.query(TS)
    assert d._fe.device_bytes > b0 > 0


def test_steady_state_zero_recompiles(world):
    from repro.core.rfs import jit_entry_count

    net, ev = world
    m = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax", **KW)
    m.query(TS)
    n0 = jit_entry_count()
    for _ in range(3):
        m.query(TS)
    assert jit_entry_count() == n0  # warm queries never recompile


# ---------------------------------------------------------------------------
# Fused executor invariants (ISSUE 10): ONE kernel launch answers the whole
# multi-level climb of a flush block (the packed walk issues one gather chain
# per level instead), and the fused+codec tier moves measurably fewer bytes
# per warm query than the packed executor on the same world.


def test_fused_one_launch_per_flush(world):
    net, ev = world
    m = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
              executor="fused", **KW)
    m.query(TS)
    c0 = m._fe.counters["fused_launches"]
    m.query(TS)  # warm: plan, tables and groupings cached — only launches
    launches = m._fe.counters["fused_launches"] - c0
    plan = m._plan_cache.get((m.epoch, m.ls))
    packs = m._fe._atom_packs(plan)
    assert launches == len(packs) > 0
    # the climb is multi-level: a per-level gather chain would need this
    # many dispatches, the fused kernel folds them into len(packs) launches
    levels = sum(entry["max_levels"] for entry in packs)
    assert levels > launches


def test_fused_codec_bytes_moved_bound(world):
    """Warm bytes-moved of fused+f32 <= 0.55x the packed executor — the
    ISSUE 10 acceptance bound (same gather count, half the row bytes),
    measured through the engines' shared analytic traffic model."""
    net, ev = world
    packed = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                   executor="packed", **KW)
    fused = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                  executor="fused", table_codec="f32", **KW)
    assert fused._fe.codec.name == "f32"  # validated at build, no fallback
    ref = packed.query(TS)
    got = fused.query(TS)
    # f32 tables are the bench tier, not the exactness tier
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6 * ref.max())
    b0 = packed.stats.bytes_moved
    packed.query(TS)
    bytes_packed = packed.stats.bytes_moved - b0
    b0 = fused.stats.bytes_moved
    fused.query(TS)
    bytes_fused = fused.stats.bytes_moved - b0
    assert 0 < bytes_fused <= 0.55 * bytes_packed


def test_fused_f64_codec_stays_exact(world):
    """With the default identity codec the fused kernel is in the exactness
    tier: <= 1e-12 against the packed walk on the same window tables."""
    net, ev = world
    ref = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                executor="packed", **KW).query(TS)
    got = TNKDE(net, ev, g=40.0, solution="rfs", engine="jax",
                executor="fused", **KW).query(TS)
    assert np.abs(got - ref).max() <= 1e-12 * max(np.abs(ref).max(), 1.0)


def test_pack_nbytes_uses_device_accounting(world):
    """Sealed/pending pack nbytes go through _device_nbytes — the ONE
    accounting helper — so compressed table layouts show up in
    ``device_bytes`` and nothing (codec scratch, host staging) is counted
    twice. Pins the satellite fix of ISSUE 10."""
    from repro.core.rfs import _device_nbytes

    net, ev = world
    d = TNKDE(net, ev, g=40.0, solution="drfs", engine="jax", drfs_depth=5, **KW)
    d.query(TS)
    eng = d._fe
    assert eng._sealed_packs
    for pack in list(eng._sealed_packs.values()) + list(eng._pend_packs.values()):
        assert pack.nbytes == _device_nbytes(pack.tables)
    # the engine roll-up equals the sum of its parts, exactly once each
    total = _device_nbytes(
        [
            list(eng._sealed_packs.values()),
            list(eng._pend_packs.values()),
            list(eng._tab_cache.values()),
            list(eng._pack_cache.values()),
            list(eng._group_cache.values()),
        ]
    )
    assert eng.device_bytes == total == eng.bytes_per_shard
