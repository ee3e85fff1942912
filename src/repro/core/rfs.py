"""Range Forest Solution (paper §4), TPU-adapted.

The paper's range forest is a *persistent* spatial range tree whose versions
are the time-sorted insertion prefixes; a time window is answered by
subtracting two versions while descending both roots in lockstep
(``DualDetect``, Algorithm 2).

Dense-array equivalent (see DESIGN.md §2): a **time-hierarchical merge tree**.
Per edge with n_e events (time-sorted = the version axis):

  level ℓ buckets 2^ℓ consecutive time-ranks; inside a bucket, events are
  position-sorted and carry inclusive prefix sums of the moment block Φ
  ([4 combos, K features], see aggregation.py).

A query (time-rank interval × position interval) decomposes canonically into
<= 2 buckets per level (exactly the nodes the paper's DualDetect touches);
each bucket contributes a difference of two prefix-sum rows located by binary
search. Identical outputs, O(n_e log n_e) space, zero data-dependent control
flow — every step is a masked gather, so the whole thing batches over
(lixels × edges × windows) and maps directly onto the Pallas ``tree_query``
kernel.

NumPy query engines, selectable with ``cascade``:
  * ``cascade=False`` — per-bucket binary searches: O(log² n_e) compare steps
    per query (a binary search inside each canonical bucket).
  * ``cascade=True``  — fractional cascading (beyond-paper §Perf
    optimization): the three position bounds are binary-searched **once** in
    the root bucket, then walked down the two boundary paths with O(1)
    precomputed bridge gathers per level — restoring the paper's O(log n_e)
    bound (their Lemma 4.1) and cutting the vectorized step count ~log n ×.

The device engines (``FlatForestEngine`` / ``FlatDynamicEngine``) run the
packed query plan (DESIGN.md §7): host plans cached per snapshot epoch,
window tables per ts tuple, and interchangeable executors — the gather-lean
jnp ``packed`` walk (default), the legacy ``cascade``/``search`` jnp paths,
and the Pallas kernels (``executor='pallas'``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from .aggregation import (
    MomentContext,
    N_COMBOS,
    next_pow2,
    segmented_cumsum,
    segmented_searchsorted,
    window_rank_ranges,
    window_rank_ranges_multi,
)
from .events import EdgeEvents
from .network import RoadNetwork
from .plan import AtomSet

__all__ = [
    "RangeForest",
    "FlatForestEngine",
    "FlatDynamicEngine",
    "make_window_batch",
    "jit_entry_count",
]


class RangeForest:
    """Static exact index over all edges (paper's RFS, Lemma 4.3)."""

    def __init__(
        self,
        net: RoadNetwork,
        ee: EdgeEvents,
        ctx: MomentContext,
        phi: np.ndarray,
        *,
        build_bridges: bool = True,
    ):
        self.net = net
        self.ee = ee
        self.ctx = ctx
        E = net.n_edges
        counts = np.diff(ee.ptr)
        self.n_pad = np.array([next_pow2(c) if c else 0 for c in counts], dtype=np.int64)
        self.n_levels = np.array(
            [int(p).bit_length() if p else 0 for p in self.n_pad], dtype=np.int64
        )
        self.max_levels = int(self.n_levels.max(initial=0))
        block = self.n_pad * self.n_levels
        self.edge_base = np.zeros(E + 1, dtype=np.int64)
        np.cumsum(block, out=self.edge_base[1:])
        T = int(self.edge_base[-1])
        K = ctx.K
        self.pos_flat = np.full(T, np.inf, dtype=np.float64)
        self.cum_flat = np.zeros((T, N_COMBOS, K), dtype=np.float64)
        self.has_bridges = build_bridges
        # bridge[slot] for slot i-1 within a bucket at level l>=1 gives
        # bl(i) = #(first i position-sorted elements) landing in the LEFT child
        self.bridge = np.zeros(T, dtype=np.int32) if build_bridges else None
        # O(1) whole-edge window aggregates for Lixel Sharing: inclusive
        # prefix sums of Φ in raw time order, per edge.
        self.time_cum = np.cumsum(phi, axis=0, dtype=np.float64) if len(phi) else phi
        # raw event moments, kept by reference: the packed-plan engine builds
        # its position-major tables from these (exact rows, not prefix diffs)
        self.phi = phi
        self._ptr = ee.ptr
        self.index_bytes = (
            self.pos_flat.nbytes
            + self.cum_flat.nbytes
            + (self.bridge.nbytes if build_bridges else 0)
            + self.time_cum.nbytes
            + self.phi.nbytes
        )

        for e in range(E):
            n = int(counts[e])
            if n == 0:
                continue
            npad = int(self.n_pad[e])
            nlev = int(self.n_levels[e])
            lo = int(ee.ptr[e])
            pos = np.full(npad, np.inf, dtype=np.float64)
            pos[:n] = ee.pos[lo : lo + n]
            ph = np.zeros((npad, N_COMBOS, K), dtype=np.float64)
            ph[:n] = phi[lo : lo + n]
            base = int(self.edge_base[e])
            ranks = np.arange(npad, dtype=np.int64)
            for lev in range(nlev):
                bucket = ranks >> lev
                order = np.lexsort((pos, bucket))
                bsize = 1 << lev
                bptr = np.arange(0, npad + 1, bsize)
                cs = segmented_cumsum(ph[order], bptr)
                sl = base + lev * npad
                self.pos_flat[sl : sl + npad] = pos[order]
                self.cum_flat[sl : sl + npad] = cs
                if build_bridges and lev >= 1:
                    to_left = (((ranks[order] >> (lev - 1)) & 1) == 0).astype(np.int64)
                    blc = segmented_cumsum(to_left, bptr)
                    self.bridge[sl : sl + npad] = blc.astype(np.int32)

    # ------------------------------------------------------------------ LS
    def window_edge_totals_multi(self, edges: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Whole-edge aggregates over W split windows: [W, n, 2(l/r), 4, K].

        O(1) per (edge, window) — the root-node shortcut Lixel Sharing relies
        on (§6), swept over all windows in one vectorized pass.
        """
        edges = np.asarray(edges, dtype=np.int64)
        lo, mid, hi = window_rank_ranges_multi(self.ee, edges, ts, self.ctx.b_t)
        base = self._ptr[edges][None, :]

        def prefix(c):
            # time_cum is a *global* inclusive cumsum; differences of two
            # prefixes within one edge cancel everything before the edge.
            idx = base + c - 1
            val = self.time_cum[np.maximum(idx, 0)]
            return np.where((idx >= 0)[..., None, None], val, 0.0)

        p_lo, p_mid, p_hi = prefix(lo), prefix(mid), prefix(hi)
        return np.stack([p_mid - p_lo, p_hi - p_mid], axis=2)

    def window_edge_totals(self, edges: np.ndarray, t: float) -> np.ndarray:
        """Single-window form of :meth:`window_edge_totals_multi`: [n, 2, 4, K]."""
        return self.window_edge_totals_multi(edges, np.array([float(t)]))[0]

    def dominated_moments_multi(self, edges: np.ndarray, ts: np.ndarray, side: int) -> np.ndarray:
        """LS root-node shortcut, window-batched: M [W, n, k_s] such that
        F_e(q) = Q_s(d(q, v_side)) · M[w] for a dominated edge (§6.2)."""
        ctx = self.ctx
        ts = np.asarray(ts, dtype=np.float64)
        totals = self.window_edge_totals_multi(edges, ts)  # [W, n, 2, 4, K]
        W, n = totals.shape[:2]
        qt = np.stack(
            [[ctx.qt_left(t) for t in ts], [ctx.qt_right(t) for t in ts]], axis=1
        )  # [W, 2, k_t]
        M = np.zeros((W, n, ctx.k_s))
        for w in (0, 1):
            A = totals[:, :, w, side * 2 + w].reshape(W, n, ctx.k_s, ctx.k_t)
            M += np.einsum("wnst,wt->wns", A, qt[:, w])
        return M

    def dominated_moments(self, edges: np.ndarray, t: float, side: int) -> np.ndarray:
        """Single-window form of :meth:`dominated_moments_multi`: [n, k_s]."""
        return self.dominated_moments_multi(edges, np.array([float(t)]), side)[0]

    # --------------------------------------------------------------- queries
    def eval_atoms(self, atoms: AtomSet, t: float, *, cascade: bool = True) -> np.ndarray:
        """Σ K_s·K_t per atom for the window [t-b_t, t+b_t]; float64 [M]."""
        M = atoms.m
        if M == 0:
            return np.zeros(0)
        ctx = self.ctx
        uniq, inv = np.unique(atoms.edge, return_inverse=True)
        lo_u, mid_u, hi_u = window_rank_ranges(self.ee, uniq, t, ctx.b_t)
        qt = (ctx.qt_left(t), ctx.qt_right(t))
        out = np.zeros(M)
        engine = self._decompose_cascade if (cascade and self.has_bridges) else self._decompose_search
        for w in (0, 1):
            r_lo = (lo_u if w == 0 else mid_u)[inv]
            r_hi = (mid_u if w == 0 else hi_u)[inv]
            q_full = (atoms.qs[:, :, None] * qt[w][None, :]).reshape(M, -1)
            combo = atoms.side_feat.astype(np.int64) * 2 + w
            out += engine(atoms, r_lo, r_hi, combo, q_full)
        return out

    # ---- shared: dot an interval of a bucket with the query vector --------
    def _interval_dot(self, idx, seg_lo, i_lo, i_hi, combo, q_full):
        c = combo[idx]
        i_hi = np.maximum(i_hi, i_lo)

        def pref(i):
            v = self.cum_flat[np.maximum(i - 1, 0), c]
            return np.where((i > seg_lo)[:, None], v, 0.0)

        mom = pref(i_hi) - pref(i_lo)
        return np.einsum("mk,mk->m", q_full[idx], mom)

    # ---- engine 1: per-bucket binary search --------------------------------
    def _decompose_search(self, atoms, r_lo, r_hi, combo, q_full):
        M = atoms.m
        eid = atoms.edge
        npad = self.n_pad[eid]
        base = self.edge_base[eid]
        out = np.zeros(M)
        l = r_lo.astype(np.int64).copy()
        r = r_hi.astype(np.int64).copy()
        for lev in range(self.max_levels):
            active = l < r
            if not active.any():
                break
            for side in (0, 1):
                if side == 0:
                    emit = active & ((l & 1) == 1)
                    b = l
                else:
                    emit = active & ((r & 1) == 1)
                    b = r - 1
                idx = np.nonzero(emit)[0]
                if len(idx):
                    seg_lo = base[idx] + lev * npad[idx] + (b[idx] << lev)
                    seg_hi = seg_lo + (1 << lev)
                    out[idx] += self._bucket_moment(atoms, idx, seg_lo, seg_hi, combo, q_full)
            l = np.where(active & ((l & 1) == 1), l + 1, l) >> 1
            r = np.where(active & ((r & 1) == 1), r - 1, r) >> 1
        return out

    def _bucket_moment(self, atoms, idx, seg_lo, seg_hi, combo, q_full):
        n = len(idx)
        i_hi = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_hi[idx], np.ones(n, bool)
        )
        i_lo1 = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_lo1[idx], atoms.lo1_right[idx]
        )
        i_lo2 = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_lo2[idx], np.zeros(n, bool)
        )
        i_lo = np.maximum(i_lo1, i_lo2)
        return self._interval_dot(idx, seg_lo, i_lo, i_hi, combo, q_full)

    # ---- engine 2: fractional cascading ------------------------------------
    # Top-down two-boundary-path walk. State per atom: current level, the two
    # path nodes (bucket ids), and for each path the three cascaded insertion
    # ranks (hi, lo1, lo2), each *local* to the path node. The three bounds
    # are binary-searched once, at the root; every further level is pure
    # gathers through the `bridge` table.
    def _decompose_cascade(self, atoms, r_lo, r_hi, combo, q_full):
        M = atoms.m
        eid = atoms.edge
        npad = self.n_pad[eid]
        nlev = self.n_levels[eid]
        base = self.edge_base[eid]
        out = np.zeros(M)

        top = np.maximum(nlev - 1, 0)
        seg_lo = base + top * npad
        seg_hi = seg_lo + npad
        j_hi = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_hi, np.ones(M, bool)
        )
        j_lo1 = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_lo1, atoms.lo1_right
        )
        j_lo2 = segmented_searchsorted(
            self.pos_flat, seg_lo, seg_hi, atoms.pos_lo2, np.zeros(M, bool)
        )
        root_loc = np.stack([j_hi, j_lo1, j_lo2]) - seg_lo[None, :]  # [3, M]

        l = r_lo.astype(np.int64)
        r = r_hi.astype(np.int64)
        lev = top.copy()  # per-atom current level
        node = np.zeros((2, M), np.int64)  # path node (bucket id at `lev`)
        loc = np.stack([root_loc, root_loc.copy()])  # [2, 3, M]
        merged = np.ones(M, bool)
        alive = (l < r) & (nlev > 0)
        # path p alive flags (after split, tracked separately)
        palive = np.stack([alive.copy(), alive.copy()])

        def emit(mask, which, at_lev, at_node, at_loc):
            idx = np.nonzero(mask)[0]
            if not len(idx):
                return
            s_lo = base[idx] + at_lev[idx] * npad[idx] + (at_node[idx] << at_lev[idx])
            i_hi = s_lo + at_loc[0][idx]
            i_lo = s_lo + np.maximum(at_loc[1][idx], at_loc[2][idx])
            out[idx] += self._interval_dot(idx, s_lo, i_lo, i_hi, combo, q_full)

        def cascade(mask, p, child_is_right):
            """Move path p's ranks from its node into a child; update node."""
            idx = np.nonzero(mask)[0]
            if not len(idx):
                return
            nf = base[idx] + lev[idx] * npad[idx] + (node[p][idx] << lev[idx])
            for k in range(3):
                i = loc[p, k][idx]
                bl = np.where(i > 0, self.bridge[nf + np.maximum(i - 1, 0)], 0)
                loc[p, k][idx] = np.where(child_is_right[idx], i - bl, bl)
            node[p][idx] = (node[p][idx] << 1) + child_is_right[idx]

        def sibling_loc(mask, p, sib_is_right):
            """Ranks for the sibling child of path p's node (before descent)."""
            idx = np.nonzero(mask)[0]
            res = np.zeros((3, M), np.int64)
            if not len(idx):
                return res
            nf = base[idx] + lev[idx] * npad[idx] + (node[p][idx] << lev[idx])
            for k in range(3):
                i = loc[p, k][idx]
                bl = np.where(i > 0, self.bridge[nf + np.maximum(i - 1, 0)], 0)
                res[k][idx] = np.where(sib_is_right[idx], i - bl, bl)
            return res

        for _ in range(self.max_levels):
            act = palive[0] | palive[1]
            if not act.any():
                break
            bs = np.int64(1) << lev
            half = bs >> 1
            a0 = node[0] * bs  # merged/left-path node range start
            # --- merged phase -------------------------------------------
            m_act = merged & palive[0]
            exact = m_act & (a0 == l) & (a0 + bs == r)
            emit(exact, 0, lev, node[0], loc[0])
            palive[0] &= ~exact
            palive[1] &= ~exact
            m_act &= ~exact
            can_desc = m_act & (lev > 0)
            go_left = can_desc & (r <= a0 + half)
            go_right = can_desc & (l >= a0 + half)
            split = can_desc & ~go_left & ~go_right
            # split: right path takes the right child; copy state then descend
            if split.any():
                idx = np.nonzero(split)[0]
                node[1][idx] = node[0][idx]
                for k in range(3):
                    loc[1, k][idx] = loc[0, k][idx]
                merged[idx] = False
            cascade(go_left | split, 0, np.zeros(M, bool))
            cascade(go_right, 0, np.ones(M, bool))
            cascade(split, 1, np.ones(M, bool))
            # un-merged right path mirrors node updates only where merged still
            node[1] = np.where(merged, node[0], node[1])
            # --- split phase: left boundary path (interval [l, node_end)) ---
            s_act = ~merged & palive[0] & ~split  # split handled next round
            if s_act.any():
                full = s_act & (a0 == l)
                emit(full, 0, lev, node[0], loc[0])
                palive[0] &= ~full
                rest = s_act & ~full & (lev > 0)
                in_left = rest & (l < a0 + half)
                # emit right child (fully covered) then descend left
                sl = sibling_loc(in_left, 0, np.ones(M, bool))
                emit(in_left, 0, lev - 1, (node[0] << 1) + 1, sl)
                cascade(in_left, 0, np.zeros(M, bool))
                in_right = rest & ~in_left
                cascade(in_right, 0, np.ones(M, bool))
            # --- split phase: right boundary path (interval [node_start, r)) -
            r_act = ~merged & palive[1] & ~split
            if r_act.any():
                a1 = node[1] * bs
                full = r_act & (a1 + bs == r)
                emit(full, 1, lev, node[1], loc[1])
                palive[1] &= ~full
                rest = r_act & ~full & (lev > 0)
                in_right = rest & (r > a1 + half)
                sl = sibling_loc(in_right, 1, np.zeros(M, bool))
                emit(in_right, 1, lev - 1, node[1] << 1, sl)
                cascade(in_right, 1, np.ones(M, bool))
                in_left = rest & ~in_right
                cascade(in_left, 1, np.zeros(M, bool))
            moved = (m_act & (lev > 0)) | (~merged & (palive[0] | palive[1]) & (lev > 0))
            lev = np.where(moved, lev - 1, lev)
        return out


# ===================================================================== JAX
# Flat-forest adapter: promotes the jit'd window-batched engine
# (jax_engine.eval_atoms_flat) to the default single-host query path.
# jax imports stay inside the class so the NumPy paths never pay them.

def _size_class(m: int, floor: int = 256) -> int:
    """Pad the ragged atom count to an ⅛-octave size class so the jit cache
    is keyed on O(log M) distinct shapes, never on the exact count. Above
    ~8·floor atoms the padding waste is bounded by ~12%; below that the
    ``floor`` granularity dominates (cache size matters more than waste
    for small batches)."""
    m = max(m, 1)
    if m <= floor:
        return floor
    gran = max(next_pow2(m) // 8, floor)
    return -(-m // gran) * gran


def make_window_batch(ctx: MomentContext, ts) -> Tuple[np.ndarray, ...]:
    """Host-side window tables for W centers → Wh = 2W half-window rows.

    Row order is (w0 left, w0 right, w1 left, ...) so engines can fold the
    two halves of a center with one reshape. Returns numpy arrays
    (t_lo, t_hi, lo_right, half, qt) ready to become a jax_engine.WindowBatch.
    """
    ts = [float(t) for t in ts]
    Wh = 2 * len(ts)
    t_lo = np.empty(Wh)
    t_hi = np.empty(Wh)
    lo_right = np.zeros(Wh, bool)
    half = np.zeros(Wh, np.int32)
    qt = np.empty((Wh, ctx.k_t))
    for w, t in enumerate(ts):
        # left half [t-b_t, t]: inclusive lower bound; right half (t, t+b_t]
        t_lo[2 * w], t_hi[2 * w] = t - ctx.b_t, t
        qt[2 * w] = ctx.qt_left(t)
        t_lo[2 * w + 1], t_hi[2 * w + 1] = t, t + ctx.b_t
        lo_right[2 * w + 1] = True
        half[2 * w + 1] = 1
        qt[2 * w + 1] = ctx.qt_right(t)
    return t_lo, t_hi, lo_right, half, qt


def feature_major(x: np.ndarray) -> np.ndarray:
    """[..., T, 4, K] host moment table → [..., 4K, T] for the device.

    The device keeps moment tables feature-major so the event axis is the
    minor (lane) axis: a TPU pads a trailing axis of K = 4 values to 128
    lanes, a 32x blow-up of the largest tables."""
    x = np.asarray(x)
    x = x.reshape(x.shape[:-2] + (-1,))
    return np.ascontiguousarray(np.swapaxes(x, -1, -2))


def _device_nbytes(obj) -> int:
    """Total bytes of every device array reachable from ``obj`` — the ONE
    accounting helper for engine tables, atom packs and packed plans
    (accepts arrays, NamedTuples, dicts, lists/tuples, and objects with a
    ``nbytes`` attribute)."""
    if obj is None:
        return 0
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):
        return int(np.prod(obj.shape)) * obj.dtype.itemsize
    if isinstance(obj, dict):
        return sum(_device_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_device_nbytes(v) for v in obj)
    nb = getattr(obj, "nbytes", None)
    if nb is not None and not callable(nb):
        return int(nb)
    return 0


def build_packed_host_tables(rf: RangeForest):
    """Position-major leaf tables for the packed-plan executor.

    Edges are laid out in descending ``n_pad`` order (a stable sort keeps
    ties in edge-id order), so every edge's leaf block starts at a multiple
    of its own power-of-two ``n_pad``. Level ℓ of an edge buckets 2^ℓ
    consecutive POSITION-ranks; with this layout the level-ℓ nodes of every
    edge with ``n_pad ≥ 2^ℓ`` are exactly the aligned 2^ℓ-blocks of the
    first P_ℓ leaves, ids ``[0, P_ℓ / 2^ℓ)`` within the level's block, and
    the edges whose tree ended at level ℓ−1 sort last there. So level ℓ is
    the pairwise sum of the first 2·NL_ℓ columns of level ℓ−1 — the dense
    build of ``jax_engine.packed_node_tables`` — with static per-level node
    counts (``level_nodes``) and no index arrays.

    Returns host arrays for ``jax_engine.PackedForest`` (position-sorted
    values, and the level-0 leaves' times and raw Φ rows in the same order;
    +inf / 0 pads), the per-edge ``pos_base`` and ``node_base`` [E, Lmax]
    (id = node_base[e, ℓ] + bucket, zero past an edge's tree), the static
    ``level_nodes`` (R = their sum), and ``n_leaves`` = Σ n_pad.
    """
    ee, phi = rf.ee, rf.phi
    E = rf.net.n_edges
    K = rf.ctx.K
    n_pad = rf.n_pad
    n_lev = rf.n_levels
    Lmax = max(rf.max_levels, 1)
    order = np.argsort(-n_pad, kind="stable")
    pos_base = np.zeros(E, dtype=np.int64)
    pos_base[order] = np.cumsum(n_pad[order]) - n_pad[order]
    P = int(n_pad.sum())
    # leaves: each edge's events by position (stable), at its block
    counts = np.diff(ee.ptr)
    eid = np.repeat(np.arange(E, dtype=np.int64), counts)
    by_pos = np.lexsort((ee.pos, eid))
    dst = pos_base[eid] + np.arange(len(eid)) - ee.ptr[eid]
    pm_pos = np.full(max(P, 1), np.inf)
    pm_time = np.full(max(P, 1), np.inf)
    pm_phi = np.zeros((max(P, 1), N_COMBOS, K))
    pm_pos[dst] = ee.pos[by_pos]
    pm_time[dst] = ee.time[by_pos]
    pm_phi[dst] = phi[by_pos]
    level_nodes = tuple(
        max(P, 1) if lev == 0 else int(n_pad[n_lev > lev].sum()) >> lev
        for lev in range(Lmax)
    )
    lev_base = np.concatenate([[0], np.cumsum(level_nodes)[:-1]]).astype(np.int64)
    levs = np.arange(Lmax)
    node_base = np.where(
        levs[None, :] < n_lev[:, None],
        lev_base[None, :] + (pos_base[:, None] >> levs[None, :]),
        0,
    )
    return dict(
        pm_pos=pm_pos,
        pos_base=pos_base,
        pm_time=pm_time,
        pm_phi=pm_phi,
        n_pad=n_pad,
        node_base=node_base.astype(np.int32),
        level_nodes=level_nodes,
        n_leaves=P,
    )


def packed_node_sums(pm_phi: np.ndarray, level_nodes: tuple) -> np.ndarray:
    """Raw-Φ sum of every packed node, level-major: [R, 4, K] — the moment
    magnitudes :func:`jax_engine.packed_node_tables` reaches, for the
    codec's build-time check."""
    lev = pm_phi[: level_nodes[0]]
    parts = [lev]
    for n in level_nodes[1:]:
        lev = lev[: 2 * n].reshape((n, 2) + lev.shape[1:]).sum(axis=1)
        parts.append(lev)
    return np.concatenate(parts)


_JIT_FLUSH = None  # persistent across FlatForestEngine instances: the jit
# cache under it is keyed on (size class, Wh, L) shapes plus the static
# (max_levels, search_steps, cascade) — repeated flushes never recompile.


def _get_flush():
    global _JIT_FLUSH
    if _JIT_FLUSH is None:
        import functools

        import jax

        from .jax_engine import eval_atoms_flat, rank_boundaries

        @functools.partial(
            jax.jit, static_argnames=("max_levels", "search_steps", "cascade"),
            donate_argnames=("heat",),
        )
        def _flush(forest, fa, wb, ranks, heat, *, max_levels, search_steps, cascade):
            vals = eval_atoms_flat(
                forest, fa, wb, ranks,
                max_levels=max_levels, search_steps=search_steps, cascade=cascade,
            )  # [Wh, Mpad]
            W = heat.shape[1]
            per_win = vals.reshape(W, 2, -1).sum(axis=1)  # fold window halves
            return heat.at[fa.lixel].add(per_win.T)  # scatter onto [L, W]

        ranks_fn = functools.partial(jax.jit, static_argnames=("search_steps",))(
            rank_boundaries
        )
        _JIT_FLUSH = (_flush, ranks_fn)
    return _JIT_FLUSH


_JIT_PACKED = None  # packed-plan executor jits: (node tables, root ranks,
# flush). Keyed on the (node count, W, size class) shapes plus the static
# trip counts — steady-state serving hits existing entries only.


def _get_packed():
    global _JIT_PACKED
    if _JIT_PACKED is None:
        import functools

        import jax

        from .jax_engine import (
            eval_atoms_packed,
            packed_node_tables,
            packed_root_ranks,
        )

        tables_fn = functools.partial(
            jax.jit, static_argnames=("level_nodes", "k_t", "out_dtype")
        )(packed_node_tables)
        roots_fn = functools.partial(jax.jit, static_argnames=("search_steps",))(
            packed_root_ranks
        )

        @functools.partial(
            jax.jit, static_argnames=("max_levels",), donate_argnames=("heat",)
        )
        def _flush(nodeval, node_base_lvl, fa, r_lo, r_hi, heat, *, max_levels):
            vals = eval_atoms_packed(
                nodeval, node_base_lvl, fa, r_lo, r_hi, max_levels=max_levels
            )  # [Wh, Mpad]
            W = heat.shape[1]
            per_win = vals.reshape(W, 2, -1).sum(axis=1)  # fold window halves
            return heat.at[fa.lixel].add(per_win.T)  # scatter onto [L, W]

        _JIT_PACKED = (tables_fn, roots_fn, _flush)
    return _JIT_PACKED


class _DeviceEngine:
    """Shared device plumbing for the flat query engines: window batches,
    the device-resident [L, W] heatmap, atom padding, and the final
    device->host transfer. Subclasses own the index packing and flush."""

    def _init_jax(self):
        import jax
        import jax.numpy as jnp

        from ..compat import device_precision, pallas_interpret
        from .query_plan import PlanCache

        self._jax = jax
        self._jnp = jnp
        # every upload and jit call runs in the device path's numeric scope
        # (f64 on CPU, f32 + full-precision matmuls on an accelerator);
        # Pallas kernels run interpreted only where the heatmap lives on CPU
        self._precision = device_precision
        self._interpret = pallas_interpret
        self._wb_cache = PlanCache(8)
        # op accounting for QueryStats (n_rank_searches / n_moment_gathers /
        # n_table_leaves / bytes_moved): time-boundary search problems
        # solved, prefix/node moment rows gathered, leaves folded by the
        # dense packed table build (W per leaf), and the bytes those read
        # (gather count × gathered-row bytes, so compressed codecs show up
        # directly; a dense build reads its leaf tables once) — host-side
        # formulas matching what the jits dispatch.
        # fused_launches counts tree-phase Pallas kernel launches (the fused
        # executor pays exactly ONE per flush; tests pin this).
        self.counters = {
            "rank_searches": 0,
            "moment_gathers": 0,
            "table_leaves": 0,
            "bytes_moved": 0,
            "fused_launches": 0,
        }

    def window_batch(self, ctx: MomentContext, ts):
        """Device WindowBatch for the ts tuple, LRU-cached — repeated queries
        over the same centers reuse one device object (and everything keyed
        on it downstream: rank tables, node values, leaf prefixes)."""
        from .jax_engine import WindowBatch, time_key

        ts_key = tuple(float(t) for t in ts)
        hit = self._wb_cache.get(ts_key)
        if hit is not None:
            return hit
        t_lo, t_hi, lo_right, half, qt = make_window_batch(ctx, ts)
        up = self._upload
        with self._precision():
            wb = WindowBatch(
                t_lo=up(time_key(t_lo)),
                t_hi=up(time_key(t_hi)),
                lo_right=up(lo_right),
                half=up(half),
                qt=up(qt),
            )
        self._wb_cache.put(ts_key, wb)
        return wb

    def new_heatmap(self, n_lixels: int, n_windows: int):
        """Fresh device [L, W] accumulator. The flush jits DONATE this
        argument (the input buffer is dead after each call; the output
        reuses its storage), so a multi-block flush recycles one buffer
        instead of allocating per block — callers must always rebind
        ``heat = flush(... heat ...)`` and never touch the old binding."""
        with self._precision():
            return self._upload(np.zeros((n_lixels, n_windows)))

    def _upload(self, x):
        """Host array → device array on this engine's placement: the
        default device here; the sharded engines replicate over their
        mesh, so no per-query input sits on the first device only."""
        return self._jnp.asarray(x)

    def _pad_atoms(self, atoms: AtomSet, sel: np.ndarray):
        """Pad the selected atoms to their ⅛-octave size class: FlatAtoms."""
        from .jax_engine import FlatAtoms

        jnp = self._jnp
        m = len(sel)
        mp = _size_class(m)

        def pad(x, fill=0):
            out = np.full((mp,) + x.shape[1:], fill, x.dtype)
            out[:m] = x[sel]
            return out

        valid = np.zeros(mp, bool)
        valid[:m] = True
        return FlatAtoms(
            lixel=jnp.asarray(pad(atoms.lixel)),
            edge=jnp.asarray(pad(atoms.edge)),
            side_feat=jnp.asarray(pad(atoms.side_feat.astype(np.int32))),
            qs=jnp.asarray(pad(atoms.qs)),
            pos_hi=jnp.asarray(pad(atoms.pos_hi, -np.inf)),
            pos_lo1=jnp.asarray(pad(atoms.pos_lo1, np.inf)),
            lo1_right=jnp.asarray(pad(atoms.lo1_right, False)),
            pos_lo2=jnp.asarray(pad(atoms.pos_lo2, np.inf)),
            valid=jnp.asarray(valid),
        )

    def to_numpy(self, heat) -> np.ndarray:
        """Device [L, W] heatmap → host [W, L] float64."""
        return np.asarray(heat, dtype=np.float64).T


class FlatForestEngine(_DeviceEngine):
    """Device-resident window-batched query engine over a built RangeForest.

    Solves the multiple-temporal-KDE hot loop (§8.2) on the accelerator with
    interchangeable executors over the packed query plan (DESIGN.md §7):

      executor='packed'   (default) gather-lean jnp executor: position-major
                          node tables with q_t folded in, built ONCE per
                          (snapshot, window batch) and LRU-cached; atoms
                          carry cached root rank intervals, so a steady-state
                          flush is one canonical walk with one paired gather
                          per level — no searches at all.
      executor='cascade'  the fractional-cascading prefix-path walk (legacy
                          jnp path; time-major tables, bridges required).
      executor='search'   per-bucket binary-search decomposition (legacy).
      executor='pallas'   the Pallas ``tree_query`` kernel over per-edge
                          grouped tables (TPU layout; interpret mode here).
      executor='fused'    ONE Pallas launch per flush: the whole canonical
                          walk + window contraction of the packed plan runs
                          in-kernel over per-edge grouped node values
                          (kernels/fused_walk.py) — the bytes-lean tier,
                          pairs with a compressed ``codec``.

    ``codec`` selects the device-table layout (jax_engine.TableCodec):
    ``'auto'``/``'f64'`` keeps today's bit-exact f64 tables; ``'f32'`` /
    ``'bf16'`` shrink the q_t-folded window tables the walk gathers from
    (validated against the f64 host tables at build time, falling back to
    f64 if the round-trip loses more than the preset tolerance).

    All executors answer all W windows per flush into a device-resident
    [L, W] heatmap (float64 — exactness is part of the paper's claim),
    transferred once per query.
    """

    def __init__(self, rf: RangeForest, *, executor: str = "packed",
                 codec: str = "auto"):
        self._init_jax()
        if executor in ("auto", None):
            executor = "packed"
        if executor not in ("packed", "cascade", "search", "pallas", "fused"):
            raise ValueError(f"unknown rfs executor {executor!r}")
        if executor == "cascade" and not rf.has_bridges:
            executor = "search"
        from .jax_engine import TableCodec
        from .query_plan import PlanCache

        jnp = self._jnp
        self.rf = rf
        self.executor = executor
        self.codec = TableCodec(codec)
        self.max_levels = max(rf.max_levels, 1)
        npmax = max(int(rf.n_pad.max(initial=1)), 1)
        nemax = max(int(np.diff(rf.ee.ptr).max(initial=1)), 1)
        self.search_steps = max(int(np.ceil(np.log2(max(npmax, nemax) + 1))) + 1, 1)
        self.cascade_ok = rf.has_bridges
        self._flat = None  # time-major FlatForest (legacy + pallas executors)
        self._packed = None  # PackedForest + node metadata (packed executor)
        self._tab_cache = PlanCache(2)  # ts_key -> window tables (plans)
        self._pack_cache = PlanCache(2)  # plan.key -> device atom packs
        # (ts_key, plan.key, block) -> per-edge grouped node values (fused)
        self._group_cache = PlanCache(8)
        if executor in ("packed", "fused"):
            self._get_packed_forest()
        else:
            self._get_flat_forest()

    # ------------------------------------------------------------- packing
    def _get_flat_forest(self):
        if self._flat is not None:
            return self._flat
        from .jax_engine import FlatForest, time_key

        rf, jnp = self.rf, self._jnp

        def pad1(x, fill):
            # gather-safe: flat tables must never be empty
            if x.shape[0]:
                return x
            return np.full((1,) + x.shape[1:], fill, x.dtype)

        bridge = rf.bridge if rf.bridge is not None else np.zeros(1, np.int32)
        with self._precision():
            self._flat = FlatForest(
                pos_flat=jnp.asarray(pad1(rf.pos_flat, np.inf)),
                cum_flat=jnp.asarray(pad1(rf.cum_flat, 0.0)),
                edge_base=jnp.asarray(rf.edge_base[:-1]),
                n_pad=jnp.asarray(rf.n_pad),
                n_lev=jnp.asarray(rf.n_levels),
                time_flat=jnp.asarray(time_key(pad1(rf.ee.time, np.inf))),
                time_ptr=jnp.asarray(rf.ee.ptr),
                bridge=jnp.asarray(pad1(bridge, 0)),
            )
        return self._flat

    def _get_packed_forest(self):
        if self._packed is not None:
            return self._packed
        from .jax_engine import PackedForest, time_key

        jnp = self._jnp
        host = build_packed_host_tables(self.rf)
        # build-time codec validation against the f64 per-node moment sums
        # the dense build reaches: a codec that can't round-trip this forest
        # degrades to f64 in place (the identity codec needs no check)
        if not self.codec.is_identity:
            self.codec.validate(packed_node_sums(host["pm_phi"], host["level_nodes"]))
        with self._precision():
            pf = PackedForest(
                pm_pos=jnp.asarray(host["pm_pos"]),
                pos_base=jnp.asarray(host["pos_base"]),
                pm_time=jnp.asarray(time_key(host["pm_time"])),
                pm_phi=jnp.asarray(feature_major(host["pm_phi"])),
                n_pad=jnp.asarray(host["n_pad"]),
            )
            # walk-level -> node base, transposed for dynamic level indexing
            node_base_lvl = jnp.asarray(host["node_base"].T.copy())
        self._packed = dict(
            pf=pf,
            node_base_lvl=node_base_lvl,
            level_nodes=host["level_nodes"],
            n_leaves=int(host["n_leaves"]),
        )
        return self._packed

    @property
    def device_bytes(self) -> int:
        """Index tables + cached packed plans (atom packs, window tables)."""
        return _device_nbytes(
            [
                self._flat,
                self._packed,
                list(self._tab_cache.values()),
                list(self._pack_cache.values()),
                list(self._group_cache.values()),
            ]
        )

    @property
    def bytes_per_shard(self) -> int:
        """Device bytes each participating device holds — the single-host
        engine IS one shard, so this equals :attr:`device_bytes`. The sharded
        engines (distributed.py) report their per-shard slab instead; the
        1/devices memory-scaling claim is measured via QueryStats, never
        asserted from a docstring."""
        return self.device_bytes

    # ----------------------------------------------------- plan-side caches
    def _atom_packs(self, plan):
        """Device atom packs for a HostPlan: per block, per LEVEL class
        (edge tree depth rounded up to multiples of 3, so shallow-edge atoms
        never walk the deepest edge's level count), the padded FlatAtoms —
        plus, for the packed executor, the cached window-independent root
        position-rank interval of every atom (searched once per plan, ever).
        """
        key = (plan.key, self.executor)
        hit = self._pack_cache.get(key)
        if hit is not None:
            return hit
        packs = []
        if self.executor == "fused":
            # one launch per npad class for the whole plan: per-block packs
            # would give every block its own (G, Q) launch shapes — one
            # compile each, hundreds at full Table-3 scale
            packs = self._fused_pack(AtomSet.concat(plan.blocks))
        for atoms in plan.blocks if self.executor != "fused" else ():
            if self.executor == "pallas":
                packs.extend(self._pallas_pack(atoms))
                continue
            nl = self.rf.n_levels[atoms.edge]
            cls = np.minimum(-(-nl // 3) * 3, self.max_levels).astype(np.int64)
            for c in np.unique(cls):
                sel = np.nonzero(cls == c)[0]
                with self._precision():
                    fa = self._pad_atoms(atoms, sel)
                    entry = dict(max_levels=int(c), fa=fa, m=len(sel))
                    if self.executor == "packed":
                        pk = self._get_packed_forest()
                        _, roots_fn, _ = _get_packed()
                        r_lo, r_hi = roots_fn(
                            pk["pf"], fa, search_steps=self.search_steps
                        )
                        entry["r_lo"], entry["r_hi"] = r_lo, r_hi
                packs.append(entry)
        self._pack_cache.put(key, packs)
        return packs

    def _pallas_pack(self, atoms):
        """Per-edge grouped kernel layout for one atom block: one entry per
        NPAD size class (every group in a call shares its table shape)."""
        from .query_plan import group_atoms_by_edge

        rf, jnp = self.rf, self._jnp
        K4 = N_COMBOS * rf.ctx.K
        entries = []
        npad_of = rf.n_pad[atoms.edge]
        for p in np.unique(npad_of):
            sel = np.nonzero(npad_of == p)[0]
            sub = atoms.take(sel)
            _, cnt = np.unique(sub.edge, return_counts=True)
            qp = _size_class(int(cnt.max(initial=1)), floor=16)
            edges, fields, _ = group_atoms_by_edge(sub, q_pad=qp)
            p_i, lvl = int(p), int(p).bit_length()
            G = len(edges)
            pos_g = np.empty((G, lvl, p_i))
            cum_g = np.empty((G, lvl, p_i, K4))
            for g, e in enumerate(edges):
                lo = int(rf.edge_base[e])
                hi = lo + lvl * p_i
                pos_g[g] = rf.pos_flat[lo:hi].reshape(lvl, p_i)
                cum_g[g] = rf.cum_flat[lo:hi].reshape(lvl, p_i, K4)
            with self._precision():
                entries.append(
                    dict(
                        kind="pallas",
                        edges=jnp.asarray(edges),
                        fields={k: jnp.asarray(v) for k, v in fields.items()},
                        pos=jnp.asarray(pos_g),
                        cum=jnp.asarray(cum_g),
                        tq=min(128, qp),
                        m=sub.m,
                        max_levels=lvl,
                    )
                )
        return entries

    def _fused_pack(self, atoms):
        """Per-edge grouped packed-plan layout for the fused executor over a
        plan's atoms: one entry per NPAD size class (so every group in a
        launch shares its node-row count), with the window-independent root
        rank intervals searched once per plan and cached on the entry — the
        fused kernel's only remaining inputs are the ts-keyed grouped node
        values."""
        from .jax_engine import FlatAtoms
        from .query_plan import group_atoms_by_edge

        rf, jnp = self.rf, self._jnp
        entries = []
        npad_of = rf.n_pad[atoms.edge]
        _, roots_fn, _ = _get_packed()
        pk = self._get_packed_forest()
        for p in np.unique(npad_of):
            sel = np.nonzero(npad_of == p)[0]
            sub = atoms.take(sel)
            _, cnt = np.unique(sub.edge, return_counts=True)
            qp = _size_class(int(cnt.max(initial=1)), floor=16)
            edges, fields, _ = group_atoms_by_edge(sub, q_pad=qp)
            p_i, nlev = int(p), int(p).bit_length()
            # walk level ℓ of an edge block holds npad >> ℓ node rows; the
            # kernel's static offs are their cumulative starts (node units)
            offs, o = [], 0
            for lev in range(nlev):
                offs.append(o)
                o += p_i >> lev
            G = len(edges)
            with self._precision():
                d_fields = {k: jnp.asarray(v) for k, v in fields.items()}
                edge2 = np.broadcast_to(
                    edges[:, None], fields["lixel"].shape
                ).copy()
                fa = FlatAtoms(
                    lixel=d_fields["lixel"].reshape(-1),
                    edge=jnp.asarray(edge2.reshape(-1)),
                    side_feat=d_fields["side_feat"].reshape(-1),
                    qs=d_fields["qs"].reshape(G * qp, -1),
                    pos_hi=d_fields["pos_hi"].reshape(-1),
                    pos_lo1=d_fields["pos_lo1"].reshape(-1),
                    lo1_right=d_fields["lo1_right"].reshape(-1),
                    pos_lo2=d_fields["pos_lo2"].reshape(-1),
                    valid=d_fields["valid"].reshape(-1),
                )
                r_lo, r_hi = roots_fn(pk["pf"], fa, search_steps=self.search_steps)
                entries.append(
                    dict(
                        kind="fused",
                        edges=jnp.asarray(edges),
                        fields=d_fields,
                        r_lo=r_lo.reshape(G, qp),
                        r_hi=r_hi.reshape(G, qp),
                        offs=tuple(offs),
                        npad=p_i,
                        tq=min(128, qp),
                        m=sub.m,
                        max_levels=nlev,
                    )
                )
        return entries

    def window_tables(self, wb, ts_key):
        """Per-(window batch) derived tables, LRU-cached by the ts tuple.

        packed: q_t-folded paired node values (the plan's core hoist — every
        time comparison and every node sum happens HERE, once per leaf and
        node, never per atom). legacy executors: the [3, W, E] time-rank
        boundary table shared by every flush of the query.
        """
        key = (ts_key, self.executor, self.codec.name)
        hit = self._tab_cache.get(key)
        if hit is not None:
            return hit
        W = len(ts_key)
        K = self.rf.ctx.K
        with self._precision():
            if self.executor in ("packed", "fused"):
                pk = self._get_packed_forest()
                tables_fn, _, _ = _get_packed()
                tabs = tables_fn(
                    pk["pf"], wb,
                    level_nodes=pk["level_nodes"],
                    k_t=int(self.rf.ctx.k_t),
                    out_dtype=self.codec.fold_name,
                )
                n = pk["n_leaves"]
                self.counters["table_leaves"] += W * n
                # the dense build reads every leaf's raw-Φ row and time key
                # once for all W windows, from the uncompressed leaf tables
                # (the codec shrinks only the DERIVED window tables the
                # per-atom walk gathers from)
                self.counters["bytes_moved"] += n * (
                    N_COMBOS * K * self.codec.float_itemsize + 2 * 4
                )
            else:
                _, ranks_fn = _get_flush()
                tabs = ranks_fn(
                    self._get_flat_forest(), wb, search_steps=self.search_steps
                )
                E = self.rf.net.n_edges
                self.counters["rank_searches"] += 3 * W * E
        self._tab_cache.put(key, tabs)
        return tabs

    # ------------------------------------------------------------ per query
    def flush_plan(self, heat, plan, wb, ts_key, **_):
        """heat[L, W] += every atom block of the plan, all W windows.

        One jit'd call per (block, level class); all window-dependent tables
        come from the ts-keyed cache, all atom-side state from the plan's
        pack cache — in steady state the only work left is the walks.
        """
        if plan.n_atoms == 0:
            return heat
        tabs = self.window_tables(wb, ts_key)
        packs = self._atom_packs(plan)
        W = len(ts_key)
        K = self.rf.ctx.K
        k_s = self.rf.ctx.k_s
        # the per-atom walk gathers q_t-folded node-value rows [W, 2k_s] in
        # the codec's fold dtype — the bytes-per-gather knob
        row_bytes = W * 2 * k_s * self.codec.fold_itemsize
        for bi, entry in enumerate(packs):
            c, m = entry["max_levels"], entry["m"]
            with self._precision():
                if self.executor == "packed":
                    pk = self._packed
                    _, _, flush_fn = _get_packed()
                    heat = flush_fn(
                        tabs, pk["node_base_lvl"], entry["fa"],
                        entry["r_lo"], entry["r_hi"], heat, max_levels=c,
                    )
                    self.counters["moment_gathers"] += 2 * c * m
                    self.counters["bytes_moved"] += 2 * c * m * row_bytes
                elif self.executor == "fused":
                    group_fn, fused_flush, _ = _get_fused()
                    gkey = (ts_key, self.codec.name, plan.key, bi)
                    nv_g = self._group_cache.get(gkey)
                    if nv_g is None:
                        pk = self._packed
                        nv_g = group_fn(
                            tabs, pk["node_base_lvl"], entry["edges"],
                            npad=entry["npad"], nlev=c,
                        )
                        self._group_cache.put(gkey, nv_g)
                    heat = fused_flush(
                        nv_g, entry["r_lo"], entry["r_hi"], entry["fields"],
                        heat, offs=entry["offs"], tq=entry["tq"],
                        interpret=self._interpret(heat),
                    )
                    # ONE kernel launch answered the whole flush; the walk
                    # still touches the same node rows, now codec-sized
                    self.counters["fused_launches"] += 1
                    self.counters["moment_gathers"] += 2 * c * m
                    self.counters["bytes_moved"] += 2 * c * m * row_bytes
                elif self.executor == "pallas":
                    rfs_flush, _, _ = _get_pallas()
                    heat = rfs_flush(
                        entry["pos"], entry["cum"], tabs, entry["edges"],
                        entry["fields"], wb, heat,
                        tq=entry["tq"], interpret=self._interpret(heat),
                    )
                    self.counters["moment_gathers"] += 4 * 2 * W * m * c
                    self.counters["bytes_moved"] += (
                        4 * 2 * W * m * c * N_COMBOS * K
                        * self.codec.float_itemsize
                    )
                else:
                    flush_fn, _ = _get_flush()
                    cascade = self.executor == "cascade"
                    heat = flush_fn(
                        self._get_flat_forest(), entry["fa"], wb, tabs, heat,
                        max_levels=c,
                        search_steps=self.search_steps,
                        cascade=cascade,
                    )
                    # paired hi/lo prefix rows: cascade pays one stacked
                    # gather per (boundary, level); search two buckets of
                    # two rows per (half-window, level)
                    gathers = (
                        2 * 3 * W * m * (c + 1) if cascade else 4 * 2 * W * m * c
                    )
                    self.counters["moment_gathers"] += gathers
                    self.counters["bytes_moved"] += (
                        gathers * 2 * K * self.codec.float_itemsize
                    )
        return heat


# ------------------------------------------------------------------- DRFS
_JIT_DYN = None  # persistent dynamic-engine jit cache: (tables, flush) pair.
# Keyed on the (size class, Wh, L, Np·Lv, Pp) shapes plus the static
# (n_levels, hq, exact) — steady-state streaming never recompiles: Np is
# padded to a size class, the pending capacity Pp is fixed per sealed epoch
# (pending_capacity), and trip counts are traced arguments.


def _get_dyn():
    global _JIT_DYN
    if _JIT_DYN is None:
        import functools

        import jax

        from .jax_engine import dyn_node_tables, dyn_window_tables, eval_atoms_dyn

        # trip counts (search_steps / scan_steps / pend_steps) are traced
        # arguments: occupancies that grow with the stream never recompile
        leaf_tables = functools.partial(
            jax.jit, static_argnames=("n_levels", "hq", "out_dtype")
        )(dyn_window_tables)
        node_tables = functools.partial(
            jax.jit, static_argnames=("n_levels", "hq", "steps_per_level", "out_dtype")
        )(dyn_node_tables)

        @functools.partial(
            jax.jit,
            static_argnames=("n_levels", "hq", "exact", "tree"),
            donate_argnames=("heat",),
        )
        def _flush(forest, fa, wb, tables, leaves, heat, *, n_levels, hq,
                   scan_steps, pend_steps, exact, tree=True):
            vals = eval_atoms_dyn(
                forest, fa, wb, tables, leaves,
                n_levels=n_levels, hq=hq,
                scan_steps=scan_steps, pend_steps=pend_steps, exact=exact,
                tree=tree,
            )  # [Wh, Mpad]
            W = heat.shape[1]
            per_win = vals.reshape(W, 2, -1).sum(axis=1)  # fold window halves
            return heat.at[fa.lixel].add(per_win.T)  # scatter onto [L, W]

        _JIT_DYN = (leaf_tables, node_tables, _flush)
    return _JIT_DYN


_JIT_PALLAS = None  # pallas executor wrappers: (rfs flush, dyn flush) — the
# table/q_vec assembly, kernel call and heat scatter in one jit each.


def _get_pallas():
    global _JIT_PALLAS
    if _JIT_PALLAS is None:
        import functools

        import jax
        import jax.numpy as jnp

        from ..kernels.dyn_query import dyn_leaf_query_pallas, dyn_node_walk_pallas
        from ..kernels.tree_query import tree_query_pallas

        @functools.partial(
            jax.jit, static_argnames=("tq", "interpret"),
            donate_argnames=("heat",),
        )
        def _rfs_flush(pos_g, cum_g, ranks, edges, f, wb, heat, *, tq, interpret):
            """Grouped tree_query kernel pass: [G, Wh, Qp] → heat[L, W]."""
            G = pos_g.shape[0]
            Wh = wb.qt.shape[0]
            W = Wh // 2
            Qp = f["qs"].shape[1]
            k_s = f["qs"].shape[-1]
            k_t = wb.qt.shape[1]
            k = ranks[:, :, edges]  # [3, W, G] (lo, mid, hi) per center
            r_lo = jnp.stack([k[0], k[1]], axis=1).reshape(Wh, G).T
            r_hi = jnp.stack([k[1], k[2]], axis=1).reshape(Wh, G).T
            r_lo = jnp.broadcast_to(r_lo[:, :, None], (G, Wh, Qp))
            r_hi = jnp.broadcast_to(r_hi[:, :, None], (G, Wh, Qp))
            # q_vec over the 4-combo axis: the atom's (side, half) slot holds
            # q_s ⊗ q_t, the rest zeros — the kernel stays combo-agnostic
            qfull = (
                f["qs"][:, None, :, :, None] * wb.qt[None, :, None, None, :]
            ).reshape(G, Wh, Qp, k_s * k_t)
            combo = f["side_feat"][:, None, :] * 2 + wb.half[None, :, None]
            oh = jnp.arange(4)[None, None, None] == combo[..., None]
            qvec = (oh[..., None] * qfull[..., None, :]).reshape(
                G, Wh, Qp, 4 * k_s * k_t
            )
            qvec = qvec * f["valid"][:, None, :, None]
            out = tree_query_pallas(
                pos_g, cum_g, r_lo, r_hi,
                f["pos_hi"], f["pos_lo1"], f["lo1_right"], f["pos_lo2"], qvec,
                # interpret mode keeps the engine's f64 tables (bit-comparable
                # to the oracle); a compiled TPU kernel must cast to f32
                tq=tq, interpret=interpret, precise=interpret,
            )  # [G, Wh, Qp]
            per_win = out.reshape(G, W, 2, Qp).sum(2)  # fold window halves
            flat = jnp.transpose(per_win, (0, 2, 1)).reshape(-1, W)
            return heat.at[f["lixel"].reshape(-1)].add(flat)

        @functools.partial(jax.jit, static_argnames=("hq", "exact", "E"))
        def _dyn_group(tables, edges, *, hq, exact, E):
            """Per-edge grouped kernel tables from the flat window tables.

            Depends only on (window tables, plan edges) — both stable across
            warm flushes — so the engine caches the result alongside the
            window tables instead of re-gathering it per flush.
            """
            if exact:
                (nodeval,) = tables  # [W·2k_s, 2·TN], level-major node ids
                TN = nodeval.shape[1] // 2
                parts = []
                for d in range(hq + 1):
                    q = jnp.arange(2 << d)[None, :]
                    base = E * ((1 << d) - 1) + edges * (1 << d)  # [G]
                    parts.append(base[:, None] + (q >> 1) + (q & 1) * TN)
                idx = jnp.concatenate(parts, axis=1)  # [G, R2]
                return jnp.transpose(nodeval[:, idx], (1, 0, 2))
            (lcum,) = tables  # [W·2K, 2·E·(nleaf+1)]
            EL = lcum.shape[1] // 2
            q = jnp.arange((1 << hq) * 2 + 2)[None, :]  # (leaf, side) pairs
            idx = edges[:, None] * ((1 << hq) + 1) + (q >> 1) + (q & 1) * EL
            return jnp.transpose(lcum[:, idx], (1, 0, 2))  # [G, W·2K, R]

        @functools.partial(
            jax.jit, static_argnames=("hq", "tq", "interpret", "exact"),
            donate_argnames=("heat",),
        )
        def _dyn_flush(grouped, leaves, f, wb, heat, *, hq, tq, interpret, exact):
            """Grouped DRFS kernel pass (tree phase only): scans ride the
            jnp flush with ``tree=False``. ``leaves`` [G, Qp, 4] holds the
            host-resolved leaf bounds in the grouped layout."""
            G, Qp = f["pos_hi"].shape
            W = wb.qt.shape[0] // 2
            k_s = f["qs"].shape[-1]
            k_t = wb.qt.shape[1]
            leaf_lo = leaves[..., 0]
            leaf_hi = jnp.maximum(leaves[..., 1], leaf_lo)
            qs_m = f["qs"] * f["valid"][..., None]
            if exact:
                out = dyn_node_walk_pallas(
                    grouped, leaf_lo, leaf_hi, f["side_feat"], qs_m,
                    hq=hq, tq=tq, interpret=interpret,
                )  # [G, W, Qp]
            else:
                qtl, qtr = wb.qt[0::2], wb.qt[1::2]  # [W, k_t]
                qv_l = (
                    qs_m[:, None, :, :, None] * qtl[None, :, None, None, :]
                ).reshape(G, W, Qp, k_s * k_t)
                qv_r = (
                    qs_m[:, None, :, :, None] * qtr[None, :, None, None, :]
                ).reshape(G, W, Qp, k_s * k_t)
                out = dyn_leaf_query_pallas(
                    grouped, leaf_lo, leaf_hi, f["side_feat"], qv_l, qv_r,
                    tq=tq, interpret=interpret,
                )  # [G, W, Qp]
            out = out * f["valid"][:, None, :]
            flat = jnp.transpose(out, (0, 2, 1)).reshape(-1, W)
            return heat.at[f["lixel"].reshape(-1)].add(flat)

        _JIT_PALLAS = (_rfs_flush, _dyn_flush, _dyn_group)
    return _JIT_PALLAS


_JIT_FUSED = None  # fused executor jits: (rfs node-value grouping, rfs
# flush, dyn flush). One Pallas launch per flush — the grouping runs once
# per (window tables, plan block) and is engine-cached like the pallas
# executor's, so a warm flush is exactly one kernel dispatch.


def _get_fused():
    global _JIT_FUSED
    if _JIT_FUSED is None:
        import functools

        import jax
        import jax.numpy as jnp

        from ..kernels.fused_walk import fused_leaf_pallas, fused_walk_pallas

        @functools.partial(jax.jit, static_argnames=("npad", "nlev"))
        def _rfs_group(nodeval, node_base_lvl, edges, *, npad, nlev):
            """Per-edge grouped node values from the flat packed tables.

            An edge's level-ℓ nodes are ids [node_base[e, ℓ], + npad >> ℓ)
            of the [W·C, 2R] table (column side·R + id), so the fused
            kernel's [G, W·C, R2] blocks are per-level column gathers with
            the two sides interleaved (block column = node·2 + side),
            stacked in walk-level order. Depends only on (window tables,
            plan edges) — both stable across warm flushes — so the engine
            caches the result alongside the window tables.
            """
            R = nodeval.shape[1] // 2
            parts = []
            for lev in range(nlev):
                q = jnp.arange(2 * (npad >> lev))[None, :]
                base = jax.lax.dynamic_index_in_dim(
                    node_base_lvl, lev, 0, keepdims=False
                )[edges]  # [G]
                parts.append(base[:, None] + (q >> 1) + (q & 1) * R)
            idx = jnp.concatenate(parts, axis=1)  # [G, R2]
            return jnp.transpose(nodeval[:, idx], (1, 0, 2))

        @functools.partial(
            jax.jit, static_argnames=("offs", "tq", "interpret"),
            donate_argnames=("heat",),
        )
        def _rfs_flush(nv_g, r_lo, r_hi, f, heat, *, offs, tq, interpret):
            """ONE fused kernel launch: walk + window contraction → heat."""
            W = heat.shape[1]
            qs_m = f["qs"] * f["valid"][..., None]
            out = fused_walk_pallas(
                nv_g, r_lo, r_hi, f["side_feat"], qs_m,
                # interpret mode keeps the table dtype (bit-comparable to
                # the oracle under the f64 codec); compiled TPU casts to f32
                offs=offs, tq=tq, interpret=interpret, precise=interpret,
            )  # [G, W, Qp]
            out = out * f["valid"][:, None, :]
            flat = jnp.transpose(out, (0, 2, 1)).reshape(-1, W)
            return heat.at[f["lixel"].reshape(-1)].add(flat.astype(heat.dtype))

        @functools.partial(
            jax.jit, static_argnames=("hq", "tq", "interpret", "exact"),
            donate_argnames=("heat",),
        )
        def _dyn_flush(grouped, leaves, f, wb, heat, *, hq, tq, interpret, exact):
            """Fused DRFS tree phase (scans ride the jnp flush, tree=False).

            Exact mode runs the complete-tree climb through the SAME fused
            walk kernel as the static forest (offs = the complete-tree row
            starts); quantized mode fuses the q_s ⊗ q_t contraction into the
            stacked leaf-prefix kernel, so only raw per-atom q_s and the
            tiny [W, k_t] temporal vectors cross the launch."""
            W = wb.qt.shape[0] // 2
            leaf_lo = leaves[..., 0]
            leaf_hi = jnp.maximum(leaves[..., 1], leaf_lo)
            qs_m = f["qs"] * f["valid"][..., None]
            if exact:
                offs = tuple((1 << (hq - lev)) - 1 for lev in range(hq + 1))
                out = fused_walk_pallas(
                    grouped, leaf_lo, leaf_hi, f["side_feat"], qs_m,
                    offs=offs, tq=tq, interpret=interpret, precise=interpret,
                )
            else:
                out = fused_leaf_pallas(
                    grouped, leaf_lo, leaf_hi, f["side_feat"], qs_m,
                    wb.qt[0::2], wb.qt[1::2],
                    tq=tq, interpret=interpret, precise=interpret,
                )
            out = out * f["valid"][:, None, :]
            flat = jnp.transpose(out, (0, 2, 1)).reshape(-1, W)
            return heat.at[f["lixel"].reshape(-1)].add(flat.astype(heat.dtype))

        _JIT_FUSED = (_rfs_group, _rfs_flush, _dyn_flush)
    return _JIT_FUSED


_EXTERNAL_JIT_FNS: list = []  # jitted callables registered by other modules
# (distributed.py's sharded programs) so the recompile audit covers them too


def register_jit_fns(fns) -> None:
    """Add jitted callables to the :func:`jit_entry_count` audit set."""
    _EXTERNAL_JIT_FNS.extend(fns)


def jit_entry_count() -> int:
    """Total compiled entries across the module-level jit caches.

    The serving subsystem's recompile audit: a steady-state load run must
    leave this number unchanged (every flush hits an existing entry).
    Returns -1 when the running jax version does not expose a cache-size
    probe on jitted callables.
    """
    fns = []
    if _JIT_FLUSH is not None:
        fns.extend(_JIT_FLUSH)
    if _JIT_PACKED is not None:
        fns.extend(_JIT_PACKED)
    if _JIT_DYN is not None:
        fns.extend(_JIT_DYN)
    if _JIT_PALLAS is not None:
        fns.extend(_JIT_PALLAS)
    if _JIT_FUSED is not None:
        fns.extend(_JIT_FUSED)
    fns.extend(_EXTERNAL_JIT_FNS)
    total = 0
    for f in fns:
        probe = getattr(f, "_cache_size", None)
        if probe is None:
            return -1
        total += int(probe())
    return total


def pending_capacity(snap, n_pending: int) -> int:
    """Rows of the device pending buffers for a snapshot: the size class of
    the auto-seal threshold (``drfs.needs_seal``: pending > sealed / 4), so
    the buffers keep one shape from one seal to the next and inserts never
    recompile; a longer backlog (background compaction behind) grows it."""
    cap = max(snap.n_sealed, 64) // 4 + 1
    return _size_class(max(int(n_pending), cap), floor=64)


def pending_by_position(csr):
    """Pending CSR (ptr, pos, time, phi) re-sorted by (edge, position): the
    row order the device pending phase (``jax_engine._pending_moments``)
    binary-searches. The host keeps (edge, time) order."""
    pptr, pp, pt, pf = csr
    edge_of = np.repeat(np.arange(len(pptr) - 1), np.diff(pptr))
    order = np.lexsort((pp, edge_of))
    return pptr, pp[order], pt[order], pf[order]


class _SealedPack:
    """Device tables for one sealed structure epoch (revision, depth)."""

    __slots__ = ("tables", "n_levels", "max_occ", "nbytes")


class _PendPack:
    """Device tables for one pending-buffer epoch (pend_revision)."""

    __slots__ = ("tables", "pend_steps", "nbytes")


class FlatDynamicEngine(_DeviceEngine):
    """Device-resident streaming query engine over a DynamicRangeForest.

    Promotes DRFS (§5) to the accelerator: the implicit position-bisection
    tree is packed level-major into flat device tables (DESIGN.md §5) and
    every flush answers all W windows in one jit'd call, exactly like
    :class:`FlatForestEngine` for the static forest. Streaming mutations stay
    on the host (drfs.py); this adapter packs **per snapshot**, keyed on the
    ``(revision, pend_revision)`` epochs (DESIGN.md §6):

      * every ``flush`` targets an explicit :class:`drfs.DrfsSnapshot` (the
        live head by default) — a long micro-batch pinned to an old epoch
        keeps answering from its own pack while inserts/seals move the live
        forest, so a batch never observes a torn re-pack (MVCC);
      * ``insert`` only bumps ``pend_revision`` — the next flush uploads the
        (small) pending CSR of the snapshot it serves and queries see new
        events through the device-side masked pending scan. No tree work.
      * ``seal`` / ``extend`` bump ``revision`` — the host repacks only the
        dirtied edges (drfs.seal is incremental) and the next flush on the
        new epoch uploads fresh level tables. Event capacity is padded to an
        ⅛-octave size class, so steady-state growth re-uploads but never
        recompiles.

    Packs live in small LRU caches (``max_snapshots`` sealed epochs, a few
    pending epochs); an evicted epoch re-packs on demand from the snapshot's
    host arrays, so pinning older revisions trades device memory for upload
    time, never correctness.

    Both the quantized-H₀ mode (partial boundary leaves dropped, paper §5.2)
    and the beyond-paper ``exact_leaf_scan`` mode run on device; work done by
    the pending and boundary-leaf scans is accounted into the forest's
    QueryStats counters host-side (same units as the NumPy path).
    """

    def __init__(self, df, *, max_snapshots: int = 2, executor: str = "packed",
                 codec: str = "auto"):
        self._init_jax()
        if executor in ("auto", None):
            executor = "packed"
        if executor not in ("packed", "pallas", "fused"):
            raise ValueError(f"unknown drfs executor {executor!r}")
        from .jax_engine import TableCodec

        self.df = df
        self.executor = executor
        self.codec = TableCodec(codec)
        self._codec_checked = False
        self.max_snapshots = max(int(max_snapshots), 1)
        from collections import OrderedDict

        from .query_plan import PlanCache

        self._sealed_packs = OrderedDict()  # (revision, depth) -> _SealedPack
        self._pend_packs = OrderedDict()  # pend_revision -> _PendPack
        # (ts_key, revision, depth, hq, exact) -> window tables (packed plans)
        self._tab_cache = OrderedDict()
        # plan.key -> device atom packs (epoch-independent: padded atoms and
        # the grouped kernel layout derive from the plan's host blocks only)
        self._pack_cache = PlanCache(2)
        # (table key, plan.key, block) -> per-edge grouped kernel tables
        self._group_cache = PlanCache(8)
        snap = df.snapshot()
        self._get_sealed(snap)
        self._get_pending(snap)

    # ----------------------------------------------------------- packing
    def _get_sealed(self, snap) -> _SealedPack:
        """Sealed level tables for the snapshot's structure epoch (LRU)."""
        key = (snap.revision, snap.depth)
        pack = self._sealed_packs.get(key)
        if pack is not None:
            self._sealed_packs.move_to_end(key)
            return pack
        jnp = self._jnp
        N = snap.n_sealed
        Lv = snap.depth + 1
        K = snap.ctx.K
        Np = _size_class(max(N, 1))
        time_lvl = np.full(Lv * Np, np.inf)
        pos_lvl = np.full(Lv * Np, np.inf)
        cum_lvl = np.zeros((Lv * Np, N_COMBOS, K))
        ptr_parts = []
        max_occ = np.zeros(Lv, np.int64)
        for d, (nptr, tms, cum, eidx) in enumerate(snap.levels):
            time_lvl[d * Np : d * Np + N] = tms
            pos_lvl[d * Np : d * Np + N] = snap.pos[eidx]
            cum_lvl[d * Np : d * Np + N] = cum
            ptr_parts.append(nptr)
            max_occ[d] = int(np.diff(nptr).max(initial=0))
        node_ptr = np.concatenate(ptr_parts).astype(np.int32)
        if not self._codec_checked:
            # build-time codec validation against the f64 host moments: a
            # codec that can't round-trip this forest degrades to f64
            self.codec.validate(cum_lvl)
            self._codec_checked = True
        from .jax_engine import time_key

        pack = _SealedPack()
        with self._precision():
            pack.tables = dict(
                time_lvl=jnp.asarray(time_key(time_lvl)),
                pos_lvl=jnp.asarray(pos_lvl),
                cum_lvl=jnp.asarray(feature_major(cum_lvl)),
                node_ptr=jnp.asarray(node_ptr),
            )
        pack.n_levels = Lv
        pack.max_occ = max_occ
        # account the DEVICE tables (shape × device dtype), not the host
        # staging arrays: the two can differ (codec layouts, index packing),
        # and _device_nbytes is the one accounting helper everywhere else —
        # this is also what keeps bytes_per_shard consistent with
        # device_bytes for compressed layouts (no codec scratch counted).
        pack.nbytes = _device_nbytes(pack.tables)
        self._sealed_packs[key] = pack
        while len(self._sealed_packs) > self.max_snapshots:
            old_key, _ = self._sealed_packs.popitem(last=False)
            # drop window tables derived from the evicted structure epoch
            for tk in [k for k in self._tab_cache if k[1:3] == old_key]:
                del self._tab_cache[tk]
        return pack

    def release_stale(self, epoch) -> int:
        """Drop device packs (and their derived window tables) for epochs
        strictly older than ``epoch = (revision, pend_revision)``.

        The compactor calls this right after a horizon eviction: the LRU
        would eventually rotate the pre-eviction packs out, but dropping
        them eagerly is what makes a horizon-bounded stream's
        ``device_bytes`` *plateau* instead of sawtoothing at LRU capacity.
        Safe with MVCC: a still-pinned snapshot that queries later simply
        re-packs from its own pinned arrays on the cache miss. Returns the
        number of packs dropped.
        """
        revision, pend_revision = epoch
        dropped = 0
        for key in [k for k in self._sealed_packs if k[0] < revision]:
            del self._sealed_packs[key]
            dropped += 1
            for tk in [k for k in self._tab_cache if k[1:3] == key]:
                del self._tab_cache[tk]
        for key in [k for k in self._pend_packs if k < pend_revision]:
            del self._pend_packs[key]
            dropped += 1
        return dropped

    @property
    def device_bytes(self) -> int:
        """Sealed + pending packs + cached packed plans (window tables and
        atom packs) — one shared accounting helper with the static engine."""
        return _device_nbytes(
            [
                list(self._sealed_packs.values()),
                list(self._pend_packs.values()),
                list(self._tab_cache.values()),
                list(self._pack_cache.values()),
                list(self._group_cache.values()),
            ]
        )

    @property
    def bytes_per_shard(self) -> int:
        """See :attr:`FlatForestEngine.bytes_per_shard` — one host, one shard."""
        return self.device_bytes

    def _get_pending(self, snap) -> _PendPack:
        """Pending-CSR tables for the snapshot's pending epoch (LRU)."""
        key = snap.pend_revision
        pack = self._pend_packs.get(key)
        if pack is not None:
            self._pend_packs.move_to_end(key)
            return pack
        jnp = self._jnp
        E = snap.net.n_edges
        K = snap.ctx.K
        csr = snap.pending_csr()
        pack = _PendPack()
        if csr is None:
            pptr = np.zeros(E + 1, np.int64)
            pp, pt, pf = np.zeros(0), np.zeros(0), np.zeros((0, N_COMBOS, K))
            pack.pend_steps = 0
        else:
            pptr, pp, pt, pf = pending_by_position(csr)
            pack.pend_steps = int(np.diff(pptr).max(initial=1))
        Pp = pending_capacity(snap, len(pp))
        pad = Pp - len(pp)
        pp = np.concatenate([pp, np.zeros(pad)])
        pt = np.concatenate([pt, np.full(pad, np.inf)])
        pf = np.concatenate([pf, np.zeros((pad,) + pf.shape[1:])])
        from .jax_engine import time_key

        with self._precision():
            pack.tables = dict(
                pend_ptr=jnp.asarray(pptr),
                pend_pos=jnp.asarray(pp),
                pend_time=jnp.asarray(time_key(pt)),
                pend_phi=jnp.asarray(feature_major(pf)),
            )
        pack.nbytes = _device_nbytes(pack.tables)
        self._pend_packs[key] = pack
        while len(self._pend_packs) > self.max_snapshots + 2:
            self._pend_packs.popitem(last=False)
        return pack

    def _forest(self, sealed: _SealedPack, pend: _PendPack):
        from .jax_engine import FlatDynamicForest

        return FlatDynamicForest(**sealed.tables, **pend.tables)

    # ------------------------------------------------------------ per query
    def window_tables(self, wb, ts_key, snap, sealed: _SealedPack, hq: int, exact: bool):
        """Window tables for (ts tuple, snapshot epoch, hq, mode), LRU-cached.

        The tables are the engine's core hoist: all per-node time searches
        (and the q_t contraction, in exact mode) are paid once per (window
        batch, structure epoch) at node-count scale, so every atom flush
        within — and every WARM QUERY over the same centers — costs O(1)
        table gathers per atom. Quantized mode reads the leaf prefix tables
        (jax_engine.dyn_window_tables), exact mode the packed node-value
        tables (jax_engine.dyn_node_tables) the shared canonical walk
        consumes. The tables depend only on the sealed structure (never the
        pending buffers), so the key is (ts, structure epoch, hq, mode) —
        re-keying from WindowBatch identity to the ts tuple is what lets
        repeated queries hit (the batch object is itself ts-cached).
        """
        key = (ts_key, snap.revision, snap.depth, int(hq), bool(exact),
               self.codec.name)
        hit = self._tab_cache.get(key)
        if hit is not None:
            self._tab_cache.move_to_end(key)
            return hit
        leaf_fn, node_fn, _ = _get_dyn()

        def steps(occ):
            return max(int(np.ceil(np.log2(int(occ) + 1))) + 1, 1)

        E = snap.net.n_edges
        W = len(ts_key)
        K = snap.ctx.K
        forest = self._forest(sealed, self._get_pending(snap))
        with self._precision():
            if exact:
                spl = tuple(steps(o) for o in sealed.max_occ[: hq + 1])
                tabs = (node_fn(
                    forest, wb,
                    n_levels=sealed.n_levels, hq=int(hq), steps_per_level=spl,
                    out_dtype=self.codec.fold_name,
                ),)
                nn = E * ((1 << (hq + 1)) - 1)
            else:
                tabs = (leaf_fn(
                    forest, wb,
                    n_levels=sealed.n_levels, hq=int(hq),
                    search_steps=steps(sealed.max_occ[hq]),
                    out_dtype=self.codec.moment_name,
                ),)
                nn = E * (1 << hq)
            self.counters["rank_searches"] += 3 * W * nn
            self.counters["moment_gathers"] += 3 * W * nn
            # table folds gather raw-Φ prefix rows from the level tables
            self.counters["bytes_moved"] += (
                3 * W * nn * N_COMBOS * K * self.codec.float_itemsize
            )
        self._tab_cache[key] = tabs
        while len(self._tab_cache) > 4 * self.max_snapshots:
            self._tab_cache.popitem(last=False)
        return tabs

    def _atom_packs(self, plan):
        """Padded device atom blocks for a HostPlan, LRU-cached per plan.

        The pallas executor additionally carries the per-edge grouped layout
        its kernels consume (the flat block still serves the scan phases).
        """
        hit = self._pack_cache.get(plan.key)
        if hit is not None:
            return hit
        from .query_plan import group_atoms_by_edge

        jnp = self._jnp
        packs = []
        for atoms in plan.blocks:
            with self._precision():
                entry = dict(fa=self._pad_atoms(atoms, np.arange(atoms.m)),
                             atoms=atoms, m=atoms.m)
                if self.executor in ("pallas", "fused"):
                    _, cnt = np.unique(atoms.edge, return_counts=True)
                    qp = _size_class(int(cnt.max(initial=1)), floor=16)
                    edges, fields, _ = group_atoms_by_edge(atoms, q_pad=qp)
                    entry["edges"] = jnp.asarray(edges)
                    entry["fields"] = {k: jnp.asarray(v) for k, v in fields.items()}
                    entry["qp"] = qp
                    entry["tq"] = min(128, qp)
                packs.append(entry)
        self._pack_cache.put(plan.key, packs)
        return packs

    def _leaf_pack(self, entry, snap, hq: int):
        """Host-resolved leaf bounds of one atom block at depth ``hq``
        (``drfs.leaf_bounds``, f64), uploaded once per (plan block, hq):
        ``flat`` [Mpad, 4] for the jnp flush and, for the kernel executors,
        ``grouped`` [G, Qp, 4] in the per-edge layout."""
        key = ("leaves", int(hq))
        hit = entry.get(key)
        if hit is not None:
            return hit
        from .query_plan import group_atoms_by_edge

        atoms = entry["atoms"]
        lb = snap.leaf_bounds(atoms, hq).astype(np.int32)  # [m, 4]
        flat = np.zeros((entry["fa"].valid.shape[0], 4), np.int32)
        flat[:, 2:] = -1  # padding rows: empty range, no boundary scans
        flat[: atoms.m] = lb
        with self._precision():
            pack = dict(flat=self._jnp.asarray(flat))
            if "edges" in entry:
                _, f, _ = group_atoms_by_edge(
                    atoms, q_pad=entry["qp"], extra={"leaves": lb}
                )
                pack["grouped"] = self._jnp.asarray(f["leaves"])
        entry[key] = pack
        return pack

    def flush_plan(self, heat, plan, wb, ts_key, *, h0=None, exact_leaf=False,
                   snapshot=None, **_):
        """heat[L, W] += every atom block of the plan, snapshot-consistent.

        Packs (or re-uses) the device tables of the targeted snapshot's
        epoch, then answers the fully-covered leaf ranges from the cached
        window tables plus boundary/pending scans, in one jit'd device call
        per atom block. ``snapshot=None`` pins the live head — the pre-MVCC
        behaviour.
        """
        if plan.n_atoms == 0:
            return heat
        snap = snapshot if snapshot is not None else self.df.snapshot()
        sealed = self._get_sealed(snap)
        pend = self._get_pending(snap)
        hq = snap.depth if h0 is None else min(int(h0), snap.depth)
        scan_steps = 0
        if exact_leaf:
            # next multiple of 8: bounds recompiles as occupancy drifts while
            # wasting at most 7 masked trips (pow-of-two rounding wastes ~2x)
            occ = int(sealed.max_occ[hq])
            scan_steps = -(-occ // 8) * 8 if occ else 0
        W = heat.shape[1]
        tables = self.window_tables(wb, ts_key, snap, sealed, hq, bool(exact_leaf))
        _, _, flush_fn = _get_dyn()
        forest = self._forest(sealed, pend)
        tab_key = (ts_key, snap.revision, snap.depth, int(hq), bool(exact_leaf),
                   self.codec.name)
        K = snap.ctx.K
        k_s = snap.ctx.k_s
        # exact mode walks codec-sized node-value rows [W, 2k_s]; quantized
        # mode differences two leaf-prefix rows [W, 2K] per atom
        row_bytes = (
            W * 2 * k_s * self.codec.fold_itemsize
            if exact_leaf
            else W * 2 * K * self.codec.moment_itemsize
        )
        for bi, entry in enumerate(self._atom_packs(plan)):
            atoms = entry["atoms"]
            leaves = self._leaf_pack(entry, snap, hq)
            # work accounting (same units as the NumPy scans: (atom, event)
            # pairs examined, per half-window for partials / window pending)
            snap.counters["pending"] += snap.pending_scan_pairs(atoms) * W
            if exact_leaf:
                snap.counters["partial"] += snap.partial_scan_pairs(atoms, hq) * 2 * W
            gathers = 2 * (hq + 1) * entry["m"] if exact_leaf else 2 * entry["m"]
            self.counters["moment_gathers"] += gathers
            self.counters["bytes_moved"] += gathers * row_bytes
            with self._precision():
                if self.executor in ("pallas", "fused"):
                    # tree phase on the kernels; scans stay in the jnp flush
                    _, dyn_flush, dyn_group = _get_pallas()
                    if self.executor == "fused":
                        _, _, dyn_flush = _get_fused()
                        self.counters["fused_launches"] += 1
                    gkey = (tab_key, plan.key, bi)
                    grouped = self._group_cache.get(gkey)
                    if grouped is None:
                        grouped = dyn_group(
                            tables, entry["edges"],
                            hq=int(hq), exact=bool(exact_leaf),
                            E=snap.net.n_edges,
                        )
                        self._group_cache.put(gkey, grouped)
                    heat = dyn_flush(
                        grouped, leaves["grouped"], entry["fields"], wb,
                        heat, hq=int(hq), tq=entry["tq"],
                        interpret=self._interpret(heat), exact=bool(exact_leaf),
                    )
                    if scan_steps or pend.pend_steps:
                        heat = flush_fn(
                            forest, entry["fa"], wb, (), leaves["flat"], heat,
                            n_levels=sealed.n_levels,
                            hq=int(hq),
                            scan_steps=int(scan_steps),
                            pend_steps=int(pend.pend_steps),
                            exact=bool(exact_leaf),
                            tree=False,
                        )
                else:
                    heat = flush_fn(
                        forest, entry["fa"], wb, tables, leaves["flat"], heat,
                        n_levels=sealed.n_levels,
                        hq=int(hq),
                        scan_steps=int(scan_steps),
                        pend_steps=int(pend.pend_steps),
                        exact=bool(exact_leaf),
                    )
        return heat
