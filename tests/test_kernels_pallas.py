"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.slow  # interpret-mode sweeps; scheduled CI job

from repro.compat import x64
from repro.kernels import ops, ref


# ------------------------------------------------------------------ minplus
@pytest.mark.parametrize("m,k,n", [(4, 4, 4), (16, 32, 8), (65, 33, 17), (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_minplus_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + n)
    a = jnp.asarray(rng.uniform(0, 10, (m, k)), dtype)
    b = jnp.asarray(rng.uniform(0, 10, (k, n)), dtype)
    # sprinkle infs (unreachable)
    a = a.at[rng.integers(0, m), rng.integers(0, k)].set(jnp.inf)
    got = ops.minplus_matmul(a, b, tm=32, tn=32, tk=32)
    want = ref.minplus_matmul(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_minplus_bellman_ford_distances():
    from repro.core.shortest_path import adjacency_csr, bounded_dijkstra, minplus_bellman_ford
    from repro.data.spatial import make_network

    net = make_network(30, 50, seed=7)
    adj = jnp.asarray(net.dense_adjacency())
    src = np.array([0, 3, 11])
    init = np.full((3, net.n_vertices), np.inf)
    init[np.arange(3), src] = 0.0
    d_ref = bounded_dijkstra(net, src, 1e18, adj=adjacency_csr(net))
    d_mp = minplus_bellman_ford(adj, jnp.asarray(init), rounds=net.n_vertices, use_pallas=True)
    np.testing.assert_allclose(np.asarray(d_mp), d_ref, rtol=1e-5)


# --------------------------------------------------------------- tree_query
def _random_forest(rng, G, n_events, K):
    """Build merge-tree tables directly (mirrors rfs.py construction)."""
    from repro.core.aggregation import next_pow2, segmented_cumsum

    npad = next_pow2(n_events)
    lvl = npad.bit_length()
    pos = np.full((G, lvl, npad), np.inf, np.float64)
    cum = np.zeros((G, lvl, npad, K))
    raw = []
    for g in range(G):
        p = np.sort(rng.uniform(0, 100, n_events))[rng.permutation(n_events)]
        f = rng.normal(size=(n_events, K))
        raw.append((p, f))
        pp = np.full(npad, np.inf)
        pp[:n_events] = p
        ff = np.zeros((npad, K))
        ff[:n_events] = f
        ranks = np.arange(npad)
        for lev in range(lvl):
            order = np.lexsort((pp, ranks >> lev))
            bptr = np.arange(0, npad + 1, 1 << lev)
            pos[g, lev] = pp[order]
            cum[g, lev] = segmented_cumsum(ff[order], bptr)
    return pos, cum, raw, npad


@pytest.mark.parametrize("n_events,K,Q,W", [(5, 2, 7, 1), (16, 4, 33, 3), (21, 3, 130, 2)])
def test_tree_query_matches_bruteforce(n_events, K, Q, W):
    rng = np.random.default_rng(n_events * 31 + Q)
    G = 3
    pos, cum, raw, npad = _random_forest(rng, G, n_events, K)
    # per-window rank intervals; position bounds shared across windows
    r_lo = rng.integers(0, n_events, (G, W, Q))
    r_hi = rng.integers(0, n_events + 1, (G, W, Q))
    r_hi = np.maximum(r_hi, r_lo)
    ph = rng.uniform(0, 110, (G, Q))
    pl1 = rng.uniform(-10, 100, (G, Q))
    l1r = rng.random((G, Q)) < 0.5
    pl2 = rng.uniform(-10, 60, (G, Q))
    qv = rng.normal(size=(G, W, Q, K))

    args = (pos, cum, r_lo, r_hi, ph, pl1, l1r, pl2, qv)
    got = np.asarray(ops.tree_query(*[jnp.asarray(x) for x in args], tq=32))
    want_ref = np.asarray(ref.tree_query(*[jnp.asarray(x) for x in args]))

    # brute force oracle over the raw events
    want = np.zeros((G, W, Q))
    for g in range(G):
        p, f = raw[g]
        for w in range(W):
            for q in range(Q):
                sel = np.arange(n_events)
                inrank = (sel >= r_lo[g, w, q]) & (sel < r_hi[g, w, q])
                lo1_ok = (p > pl1[g, q]) if l1r[g, q] else (p >= pl1[g, q])
                m = inrank & (p <= ph[g, q]) & lo1_ok & (p >= pl2[g, q])
                want[g, w, q] = f[m].sum(axis=0) @ qv[g, w, q]
    # ref/kernel run in fp32; oracle in fp64
    np.testing.assert_allclose(want_ref, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- DRFS packed layouts
@pytest.mark.parametrize("nleaf,K,Q,W", [(4, 2, 7, 1), (8, 4, 33, 3), (16, 3, 65, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dyn_leaf_query_matches_ref(nleaf, K, Q, W, dtype):
    """Leaf-prefix layout kernel (quantized DRFS tree phase) vs oracle."""
    rng = np.random.default_rng(nleaf * 100 + Q)
    G = 3
    R = (nleaf + 1) * 2
    tab = np.cumsum(rng.normal(size=(G, R, W * 2 * K)), axis=1)  # prefix-like
    tab = np.swapaxes(tab, 1, 2)  # feature-major [G, W·2K, R]
    leaf_lo = rng.integers(0, nleaf + 1, (G, Q))
    leaf_hi = np.maximum(rng.integers(0, nleaf + 1, (G, Q)), leaf_lo)
    side = rng.integers(0, 2, (G, Q))
    qv_l = rng.normal(size=(G, W, Q, K))
    qv_r = rng.normal(size=(G, W, Q, K))
    with x64(dtype == jnp.float64):
        args = [jnp.asarray(x, dtype) if np.issubdtype(np.asarray(x).dtype, np.floating)
                else jnp.asarray(x) for x in (tab, leaf_lo, leaf_hi, side, qv_l, qv_r)]
        got = np.asarray(ops.dyn_leaf_query(*args, tq=32))
        want = np.asarray(ref.dyn_leaf_query(*args))
    tol = 1e-12 if dtype == jnp.float64 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("hq,ks,Q,W", [(2, 2, 7, 1), (3, 3, 33, 2), (4, 2, 65, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dyn_node_walk_matches_ref(hq, ks, Q, W, dtype):
    """Node-value layout kernel (exact DRFS tree phase) vs oracle."""
    rng = np.random.default_rng(hq * 100 + Q)
    G = 3
    R2 = ((1 << (hq + 1)) - 1) * 2
    nv = rng.normal(size=(G, W * 2 * ks, R2))  # feature-major
    nleaf = 1 << hq
    r_lo = rng.integers(0, nleaf + 1, (G, Q))
    r_hi = np.maximum(rng.integers(0, nleaf + 1, (G, Q)), r_lo)
    side = rng.integers(0, 2, (G, Q))
    qs = rng.normal(size=(G, Q, ks))
    with x64(dtype == jnp.float64):
        args = [jnp.asarray(x, dtype) if np.issubdtype(np.asarray(x).dtype, np.floating)
                else jnp.asarray(x) for x in (nv, r_lo, r_hi, side, qs)]
        got = np.asarray(ops.dyn_node_walk(*args, hq=hq, tq=32))
        want = np.asarray(ref.dyn_node_walk(*args, hq=hq))
    tol = 1e-12 if dtype == jnp.float64 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


# ------------------------------------------------------------- fused layouts
def _rfs_offs(npad):
    """Level-major packed-forest row offsets: offs[lev] = sum_{j<lev} npad>>j."""
    offs, acc = [], 0
    for lev in range(npad.bit_length()):
        offs.append(acc)
        acc += npad >> lev
    return tuple(offs), acc


@pytest.mark.parametrize("layout,Q,W,ks", [
    ("rfs4", 7, 1, 2), ("rfs8", 33, 2, 3), ("rfs16", 65, 3, 2),
    ("tree2", 7, 1, 2), ("tree3", 33, 2, 3), ("tree4", 65, 2, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_walk_matches_ref(layout, Q, W, ks, dtype):
    """One-launch multi-level walk + contraction vs oracle, over BOTH static
    level layouts the engines feed it: the RFS packed-forest offsets and the
    DRFS complete tree."""
    n = int(layout.lstrip("rfstre"))
    if layout.startswith("rfs"):
        offs, R = _rfs_offs(n)
        rank_hi = n
    else:  # complete tree of height hq=n
        offs = tuple((1 << (n - lev)) - 1 for lev in range(n + 1))
        R = (1 << (n + 1)) - 1
        rank_hi = 1 << n
    rng = np.random.default_rng(R * 100 + Q)
    G = 3
    nv = rng.normal(size=(G, W * 2 * ks, R * 2))  # feature-major
    r_lo = rng.integers(0, rank_hi + 1, (G, Q))
    r_hi = np.maximum(rng.integers(0, rank_hi + 1, (G, Q)), r_lo)
    side = rng.integers(0, 2, (G, Q))
    qs = rng.normal(size=(G, Q, ks))
    with x64(dtype == jnp.float64):
        args = [jnp.asarray(x, dtype) if np.issubdtype(np.asarray(x).dtype, np.floating)
                else jnp.asarray(x) for x in (nv, r_lo, r_hi, side, qs)]
        got = np.asarray(ops.fused_walk(*args, offs=offs, tq=32))
        want = np.asarray(ref.fused_walk(*args, offs=offs))
    tol = 1e-12 if dtype == jnp.float64 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("nleaf,ks,kt,Q,W", [(4, 2, 2, 7, 1), (8, 3, 2, 33, 2), (16, 2, 3, 65, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_leaf_matches_ref(nleaf, ks, kt, Q, W, dtype):
    """Quantized leaf-prefix kernel with the q_s x q_t contraction fused
    in-kernel vs oracle (only raw q_s + [W, k_t] vectors cross the launch)."""
    rng = np.random.default_rng(nleaf * 100 + Q)
    G = 3
    R = (nleaf + 1) * 2
    K = ks * kt
    tab = np.swapaxes(np.cumsum(rng.normal(size=(G, R, W * 2 * K)), axis=1), 1, 2)
    leaf_lo = rng.integers(0, nleaf + 1, (G, Q))
    leaf_hi = np.maximum(rng.integers(0, nleaf + 1, (G, Q)), leaf_lo)
    side = rng.integers(0, 2, (G, Q))
    qs = rng.normal(size=(G, Q, ks))
    qtl = rng.normal(size=(W, kt))
    qtr = rng.normal(size=(W, kt))
    with x64(dtype == jnp.float64):
        args = [jnp.asarray(x, dtype) if np.issubdtype(np.asarray(x).dtype, np.floating)
                else jnp.asarray(x) for x in (tab, leaf_lo, leaf_hi, side, qs, qtl, qtr)]
        got = np.asarray(ops.fused_leaf(*args, tq=32))
        want = np.asarray(ref.fused_leaf(*args))
    tol = 1e-12 if dtype == jnp.float64 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,h,hkv,s,d", [(1, 2, 2, 64, 16), (2, 4, 2, 128, 32), (1, 8, 1, 256, 64)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, hkv, s, d, causal, dtype):
    rng = np.random.default_rng(h * s + d)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, tq=64, tk=64)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
