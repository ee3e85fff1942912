"""JAX port of the RFS query engine (flat-table, ragged-atom, window-batched).

Same algorithm as rfs.RangeForest, expressed as pure jax.numpy on the flat
tables so it can run under jit / shard_map on TPU meshes. Scalar gathers only
— memory stays O(W·M) regardless of table size (the Pallas ``tree_query``
kernel is the size-classed VMEM-resident accelerator for the same math; this
engine is the general fallback, and the packed executor below is also the
distribution vehicle — distributed.py runs it verbatim per shard).

Window batching (the paper's multiple temporal KDE scenario, §8.2): one call
answers all W query windows. Each window center t contributes two *half
windows* ([t-b_t, t) and [t, t+b_t], the "doubled aggregations" of §3.3), so
the batch axis below has Wh = 2·W entries. Everything that does not depend on
the window — the atom's three position bounds, its spatial coefficient vector
q_s, its edge block — is stored once per atom; only the time-rank interval
and the temporal coefficient vector q_t vary along the Wh axis.

Three jnp executors. The default is the **packed-plan** executor
(:class:`PackedForest` / :func:`packed_walk`, DESIGN.md §7): a position-major
transpose of the merge tree whose per-node window values are q_t-folded once
per (snapshot, window batch) at node-count scale, leaving the per-atom walk
one paired gather per level with window-independent [M] state — the
gather-lean hot path — single-host and sharded (distributed.py slabs the
same layout and runs the same walk under shard_map). The two legacy
executors below share its hoisted :func:`rank_boundaries` table and remain
for the equivalence matrix; they are selected with the static ``cascade``
flag:

  * ``cascade=False`` — canonical bucket decomposition with a per-bucket
    binary search (the paper-faithful O(log²) path, identical to
    rfs._decompose_search). All Wh windows share one jit'd level loop; the
    time-rank searches run per EDGE, not per atom.
  * ``cascade=True``  — prefix-path walks over the fractional-cascading
    bridges (DESIGN.md §4): every half-window aggregate is a difference of
    two *prefix* aggregates G(k) = Σ over ranks [0, k), and the three rank
    boundaries of a window center (lo, mid, hi — mid shared by both halves)
    each walk one root-to-leaf path emitting the fully-covered left
    children. The position binary searches run **once per atom** in the
    root bucket, window-independent, and collapse to two ranks there (the
    bridge maps are monotone, so the max of the two lower bounds commutes
    with cascading) — this is the hoist that makes window batching
    sublinear in W: each boundary pays only two O(1) bridge gathers and one
    paired prefix-moment gather per level.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FlatForest",
    "FlatAtoms",
    "PackedForest",
    "WindowBatch",
    "FlatDynamicForest",
    "TableCodec",
    "eval_atoms_flat",
    "eval_atoms_dyn",
    "eval_atoms_packed",
    "packed_node_tables",
    "packed_root_ranks",
    "packed_walk",
    "rank_boundaries",
    "dyn_window_tables",
    "dyn_node_tables",
    "dyn_node_base",
    "time_key",
]


class FlatForest(NamedTuple):
    """Flat merge-tree tables for a set of edges (see rfs.RangeForest)."""

    pos_flat: jnp.ndarray  # [T] position-sorted bucket tables (+inf pad)
    cum_flat: jnp.ndarray  # [T, 4, K] inclusive per-bucket prefix moments
    edge_base: jnp.ndarray  # [E] flat offset of each edge's block
    n_pad: jnp.ndarray  # [E] padded event count (power of two; 0 = no events)
    n_lev: jnp.ndarray  # [E] level count (log2(n_pad) + 1; 0 = no events)
    time_flat: jnp.ndarray  # [2, N] per-edge time-sorted event time keys
    time_ptr: jnp.ndarray  # [E+1] event offsets
    bridge: jnp.ndarray  # [T] i32 left-child counts (zeros if not built)


class FlatAtoms(NamedTuple):
    """Flattened window-INDEPENDENT atoms (see plan.AtomSet)."""

    lixel: jnp.ndarray  # [M] output index
    edge: jnp.ndarray  # [M]
    side_feat: jnp.ndarray  # [M] i32 in {0, 1}: event features ψ_c / ψ_d
    qs: jnp.ndarray  # [M, k_s] spatial coefficient vector
    pos_hi: jnp.ndarray  # [M]
    pos_lo1: jnp.ndarray  # [M]
    lo1_right: jnp.ndarray  # [M] bool
    pos_lo2: jnp.ndarray  # [M]
    valid: jnp.ndarray  # [M] bool (padding mask)


class FlatDynamicForest(NamedTuple):
    """Flat position-bisection tree tables for DRFS (see drfs.DynamicRangeForest).

    Level-major packing: level d of the depth-(Lv-1) tree owns the slice
    [d·Np, d·Np + N) of every per-event table (Np = padded event capacity, so
    growth by < one size class never recompiles). ``node_ptr`` concatenates
    the per-level node CSRs (level d contributes E·2^d + 1 entries starting
    at offset E·(2^d − 1) + d; values are level-local in [0, N]). Events
    inside a node are time-sorted and carry inclusive prefix sums of Φ, so a
    query needs no position searches at all — the bisection structure
    resolves position, and only the *time* boundaries are binary-searched,
    once per (window, leaf node) in :func:`dyn_window_tables`.

    The pending (unsealed) buffers ride along as a per-edge CSR sorted by
    (edge, time); queries scan them with a masked fixed-trip loop so
    ``insert -> query`` never waits for a rebuild.
    """

    time_lvl: jnp.ndarray  # [2, Lv*Np] per-node time-sorted time keys (+inf pad)
    pos_lvl: jnp.ndarray  # [Lv*Np] event positions, same order
    cum_lvl: jnp.ndarray  # [4K, Lv*Np] per-node inclusive prefix moments (feature-major)
    node_ptr: jnp.ndarray  # [sum_d E*2^d + Lv] concatenated per-level node CSRs
    pend_ptr: jnp.ndarray  # [E+1] pending CSR by edge, position-sorted per edge
    pend_pos: jnp.ndarray  # [Pp]
    pend_time: jnp.ndarray  # [2, Pp] time keys
    pend_phi: jnp.ndarray  # [4K, Pp] (feature-major)


class PackedForest(NamedTuple):
    """Position-major merge-tree leaves — the packed-plan layout (DESIGN §7).

    The transpose of :class:`FlatForest`: level ℓ buckets 2^ℓ consecutive
    POSITION-ranks of an edge, and a node's window moment is the sum of raw
    Φ over its leaves whose time falls in the half-window. Only the leaves
    are stored: per leaf its time key and raw Φ row, in position order,
    edges laid out by descending ``n_pad`` (``rfs.build_packed_host_tables``)
    so that every level's nodes are aligned blocks of a leaf prefix.
    :func:`packed_node_tables` masks the leaves once per window batch and
    sums pairwise up the tree — no search, no gather — already contracted
    with q_t; an atom only converts its three position bounds to a rank
    interval at the root (:func:`packed_root_ranks`, window-independent,
    cached in the plan) and walks the canonical ≤2-nodes-per-level
    decomposition gathering finished per-node values (:func:`packed_walk`).
    The walk state is [M] ints (no window axis), and each level costs ONE
    paired gather — the gather-lean executor. The walk reads node ids only
    through the engine's ``node_base_lvl`` [Lmax, E]: id = base + bucket.
    """

    pm_pos: jnp.ndarray  # [P] per-edge position-sorted values (+inf pad)
    pos_base: jnp.ndarray  # [E] flat offset of each edge's leaf block
    pm_time: jnp.ndarray  # [2, P] leaf time keys, same order (+inf pad)
    pm_phi: jnp.ndarray  # [4K, P] leaf raw Φ rows, same order (feature-major)
    n_pad: jnp.ndarray  # [E] padded event count (power of two; 0 = empty)


class WindowBatch(NamedTuple):
    """Per-half-window query tables: Wh = 2 · n_window_centers entries."""

    t_lo: jnp.ndarray  # [2, Wh] window-half lower time bound (time_key)
    t_hi: jnp.ndarray  # [2, Wh] upper bound (always inclusive)
    lo_right: jnp.ndarray  # [Wh] bool: lower bound exclusive? (right halves)
    half: jnp.ndarray  # [Wh] i32 temporal orientation (0 = left, 1 = right)
    qt: jnp.ndarray  # [Wh, k_t] temporal coefficient vector


# ------------------------------------------------------------------- codec
_CODEC_PRESETS = {
    # fold   = dtype of the q_t-folded node-value tables (nodeval rows)
    # moment = dtype of the leaf-prefix moment tables (quantized DRFS lcum)
    # rtol   = build-time round-trip tolerance vs the f64 host tables; a
    #          table whose cast loses more than this falls back to f64
    "f64": dict(fold=None, moment=None, rtol=0.0, pack_index=False),
    "f32": dict(fold="float32", moment="float32", rtol=1e-5, pack_index=True),
    "bf16": dict(fold="bfloat16", moment="float32", rtol=2e-2, pack_index=True),
}


def _itemsize(name) -> int:
    """Bytes per stored value of a codec table dtype; ``None`` (identity)
    is the device path's float, which ``compat.device_x64`` decides."""
    if name is None:
        from ..compat import device_x64

        return 8 if device_x64() else 4
    return jnp.dtype(name).itemsize


class TableCodec:
    """Compressed device-table layout policy (DESIGN.md §12).

    Decides, per table family, the storage dtype of the window tables the
    executors gather from — the knob that shrinks bytes-per-gather without
    touching the walk itself:

      * **fold tables** (q_t-folded node values: :func:`packed_node_tables`,
        :func:`dyn_node_tables`) are stored in ``fold_dtype``. The fold
        itself always accumulates in f64 (the q_t contraction and the node
        sums or prefix differences run on the f64 host tables); only the
        finished values are cast.
      * **moment prefixes** (quantized DRFS leaf runs,
        :func:`dyn_window_tables`) are *delta-encoded*: the per-leaf window
        values are quantized to ``moment_dtype`` first and the running
        prefix is then accumulated in f64 over the quantized deltas, so a
        prefix difference recovers the quantized per-leaf value instead of
        amplifying big-prefix cancellation error.
      * **index packing**: node/bucket metadata (node bases, rank
        boundaries, CSR pointers) is pinned to int32 when the preset packs
        indices, so x64 mode doesn't silently double the metadata bytes.

    Exactness rule: the ``f64`` preset (the ``'auto'`` default) is the
    identity — the tables keep the device path's own float dtype
    (``compat.device_x64``): float64 on CPU, bit-identical to the
    uncompressed layout, so every exact mode keeps its ≤1e-12 cross-engine
    guarantee there; float32 on an accelerator, where it is the same layout
    as the ``f32`` preset without its build-time check. Compressed presets
    are validated at build time (:meth:`validate`) against the f64 host
    tables; a table family whose round-trip error exceeds the preset's
    tolerance falls back to f64 for that engine (``fallback_reason`` says
    why).
    """

    __slots__ = ("name", "fold_name", "moment_name", "rtol", "_pack_index",
                 "fallback_reason")

    def __init__(self, name="auto"):
        if isinstance(name, TableCodec):
            name = name.name
        name = "f64" if name in ("auto", None) else str(name)
        if name not in _CODEC_PRESETS:
            raise ValueError(
                f"unknown table codec {name!r}; pick from "
                f"{sorted(_CODEC_PRESETS)} or 'auto'"
            )
        p = _CODEC_PRESETS[name]
        self.name = name
        self.fold_name = p["fold"]
        self.moment_name = p["moment"]
        self.rtol = p["rtol"]
        self._pack_index = p["pack_index"]
        self.fallback_reason = None

    @property
    def is_identity(self) -> bool:
        return self.fold_name is None and self.moment_name is None

    @property
    def float_itemsize(self) -> int:
        """Bytes per value of the uncompressed level tables (the device
        path's float: 8 on CPU, 4 on an accelerator)."""
        return _itemsize(None)

    @property
    def fold_itemsize(self) -> int:
        return _itemsize(self.fold_name)

    @property
    def moment_itemsize(self) -> int:
        return _itemsize(self.moment_name)

    def pack_index(self, arr):
        """int32-pack node/bucket metadata (identity for the f64 preset)."""
        if self._pack_index and jnp.issubdtype(arr.dtype, jnp.integer):
            return arr.astype(jnp.int32)
        return arr

    def validate(self, host_moments) -> bool:
        """Build-time round-trip check of a moment table against f64.

        Casts the f64 host prefix moments through the narrowest storage
        dtype this codec uses and measures the relative round-trip error at
        the table's own scale. On failure (overflow to inf, or error above
        the preset tolerance) the codec degrades IN PLACE to the identity
        f64 layout and records ``fallback_reason`` — compressed modes must
        never silently change what an exact engine reports.
        """
        if self.is_identity:
            return True
        import numpy as np

        host = np.asarray(host_moments, dtype=np.float64)
        narrow = self.fold_name or self.moment_name
        # round-trip in NumPy (ml_dtypes supplies bfloat16) — going through
        # jnp here would silently truncate the f64 baseline outside an x64
        # scope and vacuously pass the check
        import ml_dtypes

        ndt = np.dtype(narrow) if narrow != "bfloat16" else ml_dtypes.bfloat16
        rt = host.astype(ndt).astype(np.float64)
        scale = float(np.max(np.abs(host), initial=0.0)) or 1.0
        err = float(np.max(np.abs(rt - host), initial=0.0)) / scale
        if not np.isfinite(rt).all():
            self.fallback_reason = f"{narrow} overflow in moment table"
        elif err > self.rtol:
            self.fallback_reason = (
                f"round-trip error {err:.3e} > rtol {self.rtol:.1e} for {narrow}"
            )
        else:
            return True
        self.name = "f64"
        self.fold_name = self.moment_name = None
        self.rtol = 0.0
        self._pack_index = False
        return False

    def __repr__(self):
        return f"TableCodec({self.name!r})"


def time_key(t) -> np.ndarray:
    """Order-preserving int32 pair encoding of float64 times: [2, ...].

    The device compares event times with window bounds only by order, and
    f32 cannot hold them (at 7.8e6 s it steps by 0.5 s; epoch seconds step
    by 128 s), so times cross to the device as the f64 bit pattern mapped
    to a signed 64-bit key with the same order, split into (high, biased
    low) int32 words — two leading planes, so the time axis stays the
    minor (lane) axis on a TPU. Comparing two keys lexicographically
    (:func:`_key_lt`) is exactly the float64 comparison on every backend,
    so no event ever changes sides of a window bound. ±inf pads map to the
    extreme keys.
    """
    t = np.ascontiguousarray(np.asarray(t, dtype=np.float64) + 0.0)  # -0 -> +0
    b = t.view(np.int64)
    key = np.where(b < 0, b ^ np.int64(0x7FFFFFFFFFFFFFFF), b)
    hi = (key >> 32).astype(np.int32)
    lo = ((key & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)
    return np.stack([hi, lo])


def _key_lt(a, b):
    """a < b for :func:`time_key` pairs (leading axis 2)."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _key_le(a, b):
    """a <= b for :func:`time_key` pairs."""
    return ~_key_lt(b, a)


def _bcast_key(t_b, shape):
    """[2, 3, W] boundary keys → ``(2,) + shape`` with a trailing node axis."""
    return jnp.broadcast_to(t_b[..., None], (2,) + tuple(shape))


def _seg_search(vals, seg_lo, seg_hi, q, right, steps: int):
    """Branch-free binary search of q within vals[seg_lo:seg_hi], batched
    over arbitrary leading dims (all args broadcast to a common shape).

    Float ``vals`` (positions) compare as numbers; int32 ``vals`` are
    :func:`time_key` pairs [2, T] searched with a [2, ...] key ``q``."""
    keyed = jnp.issubdtype(vals.dtype, jnp.integer)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        at = jnp.where(lo < hi, mid, 0)
        v = vals[:, at] if keyed else vals[at]
        if keyed:
            go = jnp.where(right, _key_le(v, q), _key_lt(v, q))
        else:
            go = jnp.where(right, v <= q, v < q)
        go = go & (lo < hi)
        return jnp.where(go, mid + 1, lo), jnp.where(go | (lo >= hi), hi, mid)

    lo, _ = jax.lax.fori_loop(0, steps, body, (seg_lo, seg_hi))
    return lo


def _pref_diff(table, combo, seg_lo, i_lo, i_hi, on):
    """Masked per-bucket moment difference prefix(i_hi) - prefix(i_lo): [..., C].

    table: [T, n_combo, C]; seg_lo/i_lo/i_hi/on broadcast to a common shape;
    combo broadcasts into the gather. The hi/lo prefix rows ride ONE stacked
    gather (gather dispatch count is what dominates on the CPU backend).
    Emits moment VECTORS — engines accumulate these across levels and
    contract with the factored query (q_s ⊗ q_t) exactly once at the end,
    so the level loop stays pure gathers and adds.
    """
    i_hi = jnp.maximum(i_hi, i_lo)
    ii = jnp.stack([jnp.broadcast_to(i_hi, i_lo.shape), i_lo])  # [2, ...]
    v = table[jnp.maximum(ii - 1, 0), combo[None]]  # [2, ..., C]
    v = jnp.where((ii > seg_lo[None])[..., None], v, 0.0)
    return jnp.where(on[..., None], v[0] - v[1], 0.0)


def _contract(mom, atoms, wb, qt=None):
    """Factored query contraction: Σ_st mom[..., s, t] q_s[m, s] q_t[w, t]."""
    k_s = atoms.qs.shape[1]
    k_t = wb.qt.shape[1]
    qt = wb.qt if qt is None else qt
    m4 = mom.reshape(mom.shape[:-1] + (k_s, k_t))
    return jnp.einsum("wmst,ms,wt->wm", m4, atoms.qs, qt)


def _mom0(forest, atoms, wb):
    # derive the accumulator init from (possibly shard_map-varying) inputs so
    # the fori_loop carry has consistent varying-manual-axes under shard_map
    K = forest.cum_flat.shape[-1]
    z = (atoms.qs[None, :, :1] * wb.qt[:, None, :1] * 0.0).astype(forest.cum_flat.dtype)
    return z * jnp.zeros((1, 1, K), forest.cum_flat.dtype)


# --------------------------------------------------------------------- search
def _engine_search(forest, atoms, wb, combo, r_lo, r_hi, *, max_levels, search_steps):
    """Canonical ≤2-buckets-per-level decomposition, binary search per bucket."""
    Wh, M = r_lo.shape
    eid = atoms.edge
    base = jnp.broadcast_to(forest.edge_base[eid].astype(jnp.int32), (Wh, M))
    npad = jnp.broadcast_to(forest.n_pad[eid].astype(jnp.int32), (Wh, M))
    ph = jnp.broadcast_to(atoms.pos_hi, (Wh, M))
    pl1 = jnp.broadcast_to(atoms.pos_lo1, (Wh, M))
    l1r = jnp.broadcast_to(atoms.lo1_right, (Wh, M))
    pl2 = jnp.broadcast_to(atoms.pos_lo2, (Wh, M))
    ones = jnp.ones((Wh, M), bool)

    def level_body(lev, state):
        l, r, mom = state
        lev = lev.astype(jnp.int32)

        def bucket_mom(b, on):
            seg_lo = base + lev * npad + (b << lev)
            seg_hi = seg_lo + (1 << lev)
            i_hi = _seg_search(forest.pos_flat, seg_lo, seg_hi, ph, ones, search_steps)
            i_l1 = _seg_search(forest.pos_flat, seg_lo, seg_hi, pl1, l1r, search_steps)
            i_l2 = _seg_search(forest.pos_flat, seg_lo, seg_hi, pl2, ~ones, search_steps)
            return _pref_diff(
                forest.cum_flat, combo, seg_lo, jnp.maximum(i_l1, i_l2), i_hi, on
            )

        active = l < r
        emit_l = active & ((l & 1) == 1)
        mom = mom + bucket_mom(l, emit_l)
        l = jnp.where(emit_l, l + 1, l)
        emit_r = (l < r) & ((r & 1) == 1)
        mom = mom + bucket_mom(r - 1, emit_r)
        r = jnp.where(emit_r, r - 1, r)
        return l >> 1, r >> 1, mom

    _, _, mom = jax.lax.fori_loop(
        0, max_levels, level_body,
        (r_lo.astype(jnp.int32), r_hi.astype(jnp.int32), _mom0(forest, atoms, wb)),
    )
    return _contract(mom, atoms, wb)


# -------------------------------------------------------------------- cascade
def _engine_cascade(forest, atoms, wb, ranks, *, max_levels, search_steps):
    """Prefix-path walks over the cascade bridges, one per window BOUNDARY.

    Requires the (left, right)-paired ``make_window_batch`` layout: window
    center w owns rows 2w/2w+1 and contributes three rank boundaries
    (lo, mid, hi) — the mid boundary is shared by both halves, so W centers
    walk 3W paths instead of 4W. Each half-window aggregate is a prefix
    difference: left = G(mid) - G(lo), right = G(hi) - G(mid).

    Hoists (DESIGN.md §4):
      * the position bounds are binary-searched once per atom in the ROOT
        bucket — window independent. The two lower bounds collapse into one
        rank there (bridge maps are monotone, so max commutes with
        cascading), leaving TWO ranks to carry down each path.
      * each walk step pays 2 bridge gathers + ONE paired prefix-moment
        gather (`cum` viewed as [T, side, 2K] serves both window halves of
        the boundary at once).
    G(k) emits the fully-covered left children along the path of rank k
    (plus the root when k == npad, hoisted before the loop; plus the leaf
    itself when the path bottoms out on an odd rank). Shared path prefixes
    of adjacent boundaries cancel exactly in floating point.
    """
    Wh = wb.qt.shape[0]
    W = Wh // 2
    M = atoms.edge.shape[0]
    E = forest.time_ptr.shape[0] - 1
    K = forest.cum_flat.shape[-1]
    eid = atoms.edge
    base = forest.edge_base[eid].astype(jnp.int32)  # [M]
    npad = forest.n_pad[eid].astype(jnp.int32)
    nlev = forest.n_lev[eid].astype(jnp.int32)
    top = jnp.maximum(nlev - 1, 0)

    # ---- per-(boundary, window, EDGE) time-rank boundaries (hoisted into
    # the plan via rank_boundaries), gathered per atom ----------------------
    k = ranks[:, :, eid].astype(jnp.int32)  # [3, W, M]

    # ---- hoisted, window-independent: root-bucket position searches --------
    root_lo = base + top * npad
    ones = jnp.ones((M,), bool)
    j_hi = _seg_search(forest.pos_flat, root_lo, root_lo + npad, atoms.pos_hi, ones, search_steps)
    j_l1 = _seg_search(forest.pos_flat, root_lo, root_lo + npad, atoms.pos_lo1, atoms.lo1_right, search_steps)
    j_l2 = _seg_search(forest.pos_flat, root_lo, root_lo + npad, atoms.pos_lo2, ~ones, search_steps)
    root_loc = (
        jnp.stack([j_hi, jnp.maximum(j_l1, j_l2)]) - root_lo[None, :]
    ).astype(jnp.int32)  # [2, M] (hi, lo) local ranks

    # paired-combo view: row [i, side] = [K left-half | K right-half] moments
    cum2 = forest.cum_flat.reshape(-1, 2, 2 * K)
    side = atoms.side_feat.astype(jnp.int32)[None, None]  # [1, 1, M]
    npb = npad[None, None]
    bsb = base[None, None]
    # root fully covered (k == npad): emit it with the hoisted root ranks
    full0 = (npb > 0) & (k == npb)
    s_root = root_lo[None, None]
    mom = _pref_diff(
        cum2, side, s_root,
        s_root + root_loc[1][None, None], s_root + root_loc[0][None, None], full0,
    )  # [3, W, M, 2K]
    zero = jnp.zeros((3, W, M), jnp.int32)
    state = (
        top[None, None] + zero,  # lev
        zero,  # node (bucket id at lev)
        root_loc[:, None, None, :] + zero[None],  # [2, 3, W, M] local ranks
        (npb > 0) & (k > 0) & ~full0,  # active
        mom,
    )

    def step(_, state):
        lev, node, loc, active, mom = state
        a0 = node << lev
        active = active & (k > a0)  # boundary landed on a node edge: done
        half = (jnp.int32(1) << lev) >> 1
        go_right = active & (lev > 0) & (k >= a0 + half)
        nf = bsb + lev * npb + a0  # parent bucket flat offset
        # both carried ranks cascade through ONE stacked bridge gather
        bl = jnp.where(
            loc > 0, forest.bridge[nf[None] + jnp.maximum(loc - 1, 0)], 0
        )
        # one emission per step: the fully-covered LEFT child when stepping
        # right, or the leaf itself when the path bottoms out on an odd rank
        emit_leaf = active & (lev == 0)  # invariant: a0 < k <= a0+1 here
        on = go_right | emit_leaf
        s_emit = jnp.where(emit_leaf, nf, nf - npb)  # left child starts at a0
        hi_loc = jnp.where(emit_leaf, loc[0], bl[0])
        lo_loc = jnp.where(emit_leaf, loc[1], bl[1])
        mom = mom + _pref_diff(cum2, side, s_emit, s_emit + lo_loc, s_emit + hi_loc, on)
        desc = active & (lev > 0)
        loc = jnp.where(desc[None], jnp.where(go_right[None], loc - bl, bl), loc)
        node = jnp.where(desc, (node << 1) + go_right.astype(jnp.int32), node)
        lev = jnp.where(desc, lev - 1, lev)
        active = active & ~emit_leaf
        return lev, node, loc, active, mom

    *_, mom = jax.lax.fori_loop(0, max_levels, step, state)
    # halves: left = G(mid) - G(lo) on the left-K block; right = G(hi) - G(mid)
    val_l = _contract((mom[1] - mom[0])[..., :K], atoms, wb, wb.qt[0::2])
    val_r = _contract((mom[2] - mom[1])[..., K:], atoms, wb, wb.qt[1::2])
    return jnp.stack([val_l, val_r], axis=1).reshape(Wh, M)


# ============================================================== packed plan
def rank_boundaries(forest: FlatForest, wb: WindowBatch, *, search_steps: int):
    """Per-(boundary, window, edge) time-rank boundaries: [3, W, E] i32.

    The (lo, mid, hi) ranks of every window center against every edge's
    time-sorted events — independent of atoms, so the plan computes them
    once per (snapshot, window batch) and every flush re-uses them (the
    hoist that makes per-flush time-search work zero in steady state).
    """
    W = wb.qt.shape[0] // 2
    E = forest.time_ptr.shape[0] - 1
    t_b, right_b = _dyn_boundaries(wb)
    s_lo = jnp.broadcast_to(forest.time_ptr[:-1][None, None, :], (3, W, E)).astype(jnp.int32)
    s_hi = jnp.broadcast_to(forest.time_ptr[1:][None, None, :], (3, W, E)).astype(jnp.int32)
    r_b = (
        _seg_search(
            forest.time_flat, s_lo, s_hi, _bcast_key(t_b, (3, W, E)),
            jnp.broadcast_to(right_b[..., None], (3, W, E)), search_steps,
        )
        - s_lo
    )
    return r_b.astype(jnp.int32)


def packed_root_ranks(pf: PackedForest, atoms: FlatAtoms, *, search_steps: int):
    """Window-independent position-rank interval [r_lo, r_hi) per atom: [M].

    The packed executor's only per-atom searches: the three position bounds
    are resolved against the edge's position-sorted root row in ONE batched
    search (stacked bound axis) and collapse to two ranks. Cached inside the
    plan's atom blocks, so steady-state flushes pay no searches at all.
    """
    M = atoms.edge.shape[0]
    eid = atoms.edge
    s_lo = pf.pos_base[eid].astype(jnp.int32)
    s_hi = s_lo + pf.n_pad[eid].astype(jnp.int32)
    q = jnp.stack([atoms.pos_hi, atoms.pos_lo1, atoms.pos_lo2])
    right = jnp.stack([jnp.ones((M,), bool), atoms.lo1_right, jnp.zeros((M,), bool)])
    j = (
        _seg_search(
            pf.pm_pos,
            jnp.broadcast_to(s_lo[None], (3, M)),
            jnp.broadcast_to(s_hi[None], (3, M)),
            q, right, search_steps,
        )
        - s_lo[None]
    )
    r_hi = j[0]
    r_lo = jnp.minimum(jnp.maximum(j[1], j[2]), r_hi)
    return r_lo.astype(jnp.int32), r_hi.astype(jnp.int32)


_FOLD_CHUNK = 32768  # nodes per step of a chunked table build


def _chunked(fold, s_lo, s_hi, chunk: int, out_len=None):
    """Run ``fold(lo, hi) -> [..., m]`` over chunks of ``chunk`` runs and
    concatenate the chunks' last axes: [..., out_len] (default len(s_lo)).

    A ``lax.map`` over fixed-size chunks (empty runs pad the tail): the TPU
    compiler then sees one chunk-sized body instead of a node-sized
    program, which cut a full-scale table build's compile from 27 s to 9 s
    and its temporaries from 4.0 to 1.5 GB (v5e compile rehearsal)."""
    n = s_lo.shape[0]
    ch = max(min(n, chunk), 1)
    nc = -(-n // ch)
    pad = nc * ch - n
    lo = jnp.pad(s_lo, (0, pad)).reshape(nc, ch)
    hi = jnp.pad(s_hi, (0, pad)).reshape(nc, ch)
    out = jax.lax.map(lambda lh: fold(*lh), (lo, hi))  # [nc, ..., m]
    out = jnp.moveaxis(out, 0, -2)
    out = out.reshape(out.shape[:-2] + (-1,))
    return out[..., : (n if out_len is None else out_len)]


def _fold_node_level(time_tab, cum_tab, s_lo, s_hi, t_b, right_b, qtl, qtr,
                     steps: int, k_t: int, out_dtype=None):
    """One level's q_t-folded paired node values: [W·2k_s, 2, NL].

    The fold of :func:`dyn_node_tables`: per (boundary, window, node) binary
    search in the node's time-sorted run [s_lo, s_hi), raw-Φ prefix
    difference (node-local rounding), combo slice per side/half, q_t
    contraction.
    Feature-major throughout (``cum_tab`` is [4K, T]): the node axis stays
    the minor axis of every intermediate, which a TPU stores unpadded —
    a trailing axis of 2k_s = 4 values would be padded to 128 lanes.
    Row w·2k_s + j of the result holds window w's [k_s left | k_s right]
    coefficient j; the column axes are (side, node).
    """
    W = qtl.shape[0]
    K = cum_tab.shape[0] // 4
    k_s = K // k_t

    def fold(lo, hi):
        n = lo.shape[0]
        i_b = _seg_search(
            time_tab,
            jnp.broadcast_to(lo[None, None], (3, W, n)),
            jnp.broadcast_to(hi[None, None], (3, W, n)),
            _bcast_key(t_b, (3, W, n)),
            jnp.broadcast_to(right_b[..., None], (3, W, n)),
            steps,
        )
        # prefix rows at the boundaries, node-local: [4K, 3, W, n]
        p = jnp.where(
            (i_b > lo[None, None])[None], cum_tab[:, jnp.maximum(i_b - 1, 0)], 0.0
        ).reshape(4, k_s, k_t, 3, W, n)  # combo, s, t (Φ's s-major features)
        left = p[0::2, :, :, 1] - p[0::2, :, :, 0]  # combos (ψ_c|ψ_d, left half)
        right = p[1::2, :, :, 2] - p[1::2, :, :, 1]  # combos (ψ_c|ψ_d, right)
        vl = jnp.einsum("csawn,wa->cswn", left, qtl)  # [2, k_s, W, n]
        vr = jnp.einsum("csawn,wa->cswn", right, qtr)
        vv = jnp.concatenate([vl, vr], axis=1)  # [2 side, 2k_s, W, n]
        return jnp.transpose(vv, (2, 1, 0, 3)).reshape(W * 2 * k_s, 2, n)

    out = _chunked(fold, s_lo, s_hi, _FOLD_CHUNK)
    # codec fold cast: search + prefix diff + q_t contraction all ran in the
    # host-table dtype; only the finished values shrink
    return out if out_dtype is None else out.astype(out_dtype)


def packed_node_tables(
    pf: PackedForest,
    wb: WindowBatch,
    *,
    level_nodes: tuple,
    k_t: int,
    out_dtype=None,
):
    """q_t-folded paired window values of EVERY position-rank node: [W·C, 2R].

    Dense: the leaves' time keys are compared once with every window's
    (lo, mid, hi) boundaries — left half t_lo ≤ t ≤ t_mid, right half
    t_mid < t ≤ t_hi — and each leaf's Φ, contracted with the half's q_t,
    is kept where it falls inside. Level ℓ is then the pairwise sum of the
    first 2·``level_nodes[ℓ]`` columns of level ℓ−1 (the descending-n_pad
    layout of ``rfs.build_packed_host_tables``), so the build reads the
    leaves once for all W windows, with no search and no gather. Column
    side·R + node holds, for every window w, the C = 2k_s values [k_s
    left-half | k_s right] at rows w·C + j: one walk gather moves every
    window's value for a node at once. Node ids are level-major, level ℓ
    starting at Σ_{ℓ'<ℓ} level_nodes[ℓ']. Every intermediate keeps the leaf
    (node) axis minor, which a TPU stores unpadded, and the q_t contraction
    is an elementwise multiply-add per window (no GEMM across the window
    axis), so duplicate window centers stay bitwise identical.
    """
    W = wb.qt.shape[0] // 2
    K = pf.pm_phi.shape[0] // 4
    k_s = K // k_t
    # the (lo, mid, hi) keys of every window center (the bounds of
    # :func:`_dyn_boundaries`), by reshape: no strided slice, which a TPU
    # lowers to a gather
    lo = wb.t_lo.reshape(2, W, 2)[:, :, 0, None]  # [2, W, 1]
    mid = wb.t_hi.reshape(2, W, 2)[:, :, 0, None]
    hi = wb.t_hi.reshape(2, W, 2)[:, :, 1, None]
    t = pf.pm_time[:, None, :]  # [2, 1, P]
    inside = (
        ~_key_lt(t, lo) & _key_le(t, mid),  # left half: [W, P]
        _key_lt(mid, t) & _key_le(t, hi),  # right half
    )
    # one row per (side, window, half, s), written straight into level 0:
    # Φ row (2·side + half)·K + s·k_t + j times q_t[j], the k_t sum
    # unrolled, kept where the leaf is inside the half
    lev = jnp.stack([
        jnp.stack([
            jnp.where(inside[h][w], sum(
                pf.pm_phi[(2 * c + h) * K + s * k_t + j] * wb.qt[2 * w + h, j]
                for j in range(k_t)
            ), 0.0)
            for w in range(W) for h in range(2) for s in range(k_s)
        ])
        for c in range(2)
    ])  # [side, W·C, leaf]: level 0, level_nodes[0] wide
    # the rows of a side fill whole (8, 128) tiles on a TPU, where a
    # [..., 2, node] array would fill a quarter of each
    parts = [lev]
    for n in level_nodes[1:]:
        lev = _pair_sum(lev, n)
        parts.append(lev)
    out = jnp.concatenate([p[c] for c in range(2) for p in parts], axis=1)
    # codec fold cast: mask, contraction and sums all ran in the leaf-table
    # dtype; only the finished values shrink
    return out if out_dtype is None else out.astype(out_dtype)


def _pair_sum(x, n: int):
    """x[..., 2j] + x[..., 2j+1] for j < n, over the minor axis: [..., n].

    A stride-2 window sum (``reduce_window``), which keeps the node axis
    minor; a strided slice of the minor axis lowers to a gather on a TPU,
    and a reshape to [..., n, 2] to a relayout with a trailing axis of 2."""
    nd = x.ndim
    return jax.lax.reduce_window(
        x[..., : 2 * n], jnp.zeros((), x.dtype), jax.lax.add,
        (1,) * (nd - 1) + (2,), (1,) * (nd - 1) + (2,),
    )


def packed_walk(nodeval, node_base_lvl, eid, side, r_lo, r_hi, *, max_levels: int):
    """Canonical ≤2-nodes-per-level walk over finished node values: [W·C, M].

    The shared executor core for the static packed forest AND the DRFS
    exact-mode node tables (``node_base_lvl`` [Lmax, E] maps walk levels to
    flat node bases; DRFS supplies the complete-tree arithmetic bases).
    ``nodeval`` is [W·C, 2R] (column side·R + node). State is [M] ints — no
    window axis — and each level pays exactly ONE paired gather ([W·C, 2, M]
    node columns, every window riding inside the column).
    """
    M = eid.shape[0]
    R = nodeval.shape[1] // 2
    acc0 = jnp.zeros((nodeval.shape[0], M), nodeval.dtype)

    def level_body(lev, state):
        l, r, acc = state
        nb = jax.lax.dynamic_index_in_dim(node_base_lvl, lev, 0, keepdims=False)[eid]
        active = l < r
        emit_l = active & ((l & 1) == 1)
        b_l = l
        l = jnp.where(emit_l, l + 1, l)
        emit_r = (l < r) & ((r & 1) == 1)
        b_r = r - 1
        r = jnp.where(emit_r, r - 1, r)
        on = jnp.stack([emit_l, emit_r])  # [2, M]
        idx = side[None] * R + nb[None] + jnp.stack([b_l, b_r])
        idx = jnp.clip(jnp.where(on, idx, 0), 0, 2 * R - 1)
        cols = nodeval[:, idx]  # [W·C, 2, M] — one paired gather per level
        acc = acc + jnp.where(on[None], cols, 0.0).sum(1)
        return l >> 1, r >> 1, acc

    _, _, acc = jax.lax.fori_loop(
        0, max_levels, level_body,
        (r_lo.astype(jnp.int32), r_hi.astype(jnp.int32), acc0),
    )
    return acc


def _walk_values(acc, qs):
    """[W·2k_s, M] walk accumulators · q_s → per-half values ([W, M], [W, M]).

    Elementwise multiply-reduce, NOT einsum: keeps duplicate window centers
    bitwise identical on CPU XLA (the GEMM an einsum lowers to is not
    row-deterministic across the window batch)."""
    k_s = qs.shape[1]
    a4 = acc.reshape(-1, 2, k_s, acc.shape[-1])
    qsT = qs.T[None]  # [1, k_s, M]
    return (a4[:, 0] * qsT).sum(1), (a4[:, 1] * qsT).sum(1)


def eval_atoms_packed(
    nodeval, node_base_lvl, atoms: FlatAtoms, r_lo, r_hi, *, max_levels: int
):
    """Packed-plan per-atom aggregate for every half-window: [Wh, M].

    Same output contract as :func:`eval_atoms_flat` (paired row layout;
    callers fold halves and scatter onto lixels), but consuming the packed
    plan: precomputed root rank intervals + q_t-folded node value tables.
    """
    acc = packed_walk(
        nodeval, node_base_lvl,
        atoms.edge.astype(jnp.int32), atoms.side_feat.astype(jnp.int32),
        r_lo, r_hi, max_levels=max_levels,
    )
    val_l, val_r = _walk_values(acc, atoms.qs)  # [W, M] each
    out = jnp.stack([val_l, val_r], axis=1).reshape(-1, atoms.edge.shape[0])
    return jnp.where(atoms.valid[None, :], out, 0.0)


# ===================================================================== DRFS
def _dyn_pos_mask(atoms, p):
    """Event-position acceptance against the atom's three bounds: [M] bool."""
    lo1_ok = jnp.where(atoms.lo1_right, p > atoms.pos_lo1, p >= atoms.pos_lo1)
    return (p <= atoms.pos_hi) & lo1_ok & (p >= atoms.pos_lo2)


def _dyn_boundaries(wb: WindowBatch):
    """(t_b [2, 3, W] time keys, right_b [3, W]): the (lo, mid, hi) time
    boundaries per window center — mid is shared by both halves, so W
    centers carry 3 rank boundaries instead of 4 (the paired
    ``make_window_batch`` layout)."""
    W = wb.qt.shape[0] // 2
    t_b = jnp.stack([wb.t_lo[:, 0::2], wb.t_hi[:, 0::2], wb.t_hi[:, 1::2]], axis=1)
    right_b = jnp.stack(
        [jnp.zeros((W,), bool), jnp.ones((W,), bool), jnp.ones((W,), bool)]
    )
    return t_b, right_b


def dyn_window_tables(
    forest: FlatDynamicForest,
    wb: WindowBatch,
    *,
    n_levels: int,
    hq: int,
    search_steps: int,
    out_dtype=None,
):
    """Per-(window, leaf-node) aggregates, prefix-summed along each edge.

    The key hoist of the dynamic engine (DESIGN.md §5): the time boundaries
    depend only on the *window*, and the bisection tree's leaves at depth hq
    partition every edge, so the window-restricted moment of each leaf can be
    resolved ONCE per query — per (boundary, window, leaf) binary search +
    prefix gather over the leaf's time-sorted run, already contracted with
    the temporal query vector q_t — and prefix-summed along the leaf axis of
    each edge. An atom's fully-covered range then costs two O(1) gathers
    (``Lcum[leaf_hi] − Lcum[leaf_lo]``) instead of a per-atom tree walk with
    per-node time searches: all O(log)-factor work scales with the *node
    count* E·2^hq, not with atoms × windows.

    Returns lcum [W·2K, 2·E·(nleaf+1)], feature-major: column
    side·E·(nleaf+1) + e·(nleaf+1) + leaf holds, for every window w, the
    raw paired moment vector [K left-half | K right-half] at rows w·2K + j
    (the W axis rides INSIDE the column, so an atom's two prefix lookups
    are one stacked gather serving all windows at once). Staying in raw Φ space
    (q_t applied only after the caller differences two prefixes) keeps the
    prefix magnitudes at the event scale — the same association the NumPy
    path's per-node prefix scheme uses — so the leaf-prefix shortcut costs
    no precision even for kernels with large alternating q_t entries.
    """
    Wh = wb.qt.shape[0]
    W = Wh // 2
    K = forest.cum_lvl.shape[0] // 4
    Np = forest.time_lvl.shape[1] // n_levels
    E = forest.pend_ptr.shape[0] - 1
    nleaf = 1 << hq
    NL = E * nleaf
    pb = E * (nleaf - 1) + hq  # node_ptr offset of level hq's CSR block
    s_lo = (hq * Np + forest.node_ptr[pb : pb + NL]).astype(jnp.int32)
    s_hi = (hq * Np + forest.node_ptr[pb + 1 : pb + NL + 1]).astype(jnp.int32)
    t_b, right_b = _dyn_boundaries(wb)

    def fold(lo, hi):  # whole edges: [W, 2K, 2, n // nleaf, nleaf + 1]
        n = lo.shape[0]
        i_b = _seg_search(
            forest.time_lvl,
            jnp.broadcast_to(lo[None, None], (3, W, n)),
            jnp.broadcast_to(hi[None, None], (3, W, n)),
            _bcast_key(t_b, (3, W, n)),
            jnp.broadcast_to(right_b[..., None], (3, W, n)),
            search_steps,
        )
        # prefix rows at the boundaries, feature-major: [4K, 3, W, n]
        p = jnp.where(
            (i_b > lo[None, None])[None], forest.cum_lvl[:, jnp.maximum(i_b - 1, 0)], 0.0
        ).reshape(4, K, 3, W, n)
        # per-leaf window moments, paired per side: [side, K left | K right]
        left = p[0::2, :, 1] - p[0::2, :, 0]  # [2, K, W, n] combos (ψ·left)
        right = p[1::2, :, 2] - p[1::2, :, 1]  # combos (ψ·right)
        lv = jnp.concatenate([left, right], axis=1)  # [2, 2K, W, n]
        # per-edge inclusive leaf prefix with a leading zero column; the leaf
        # axis stays minor (unpadded on a TPU)
        cum = jnp.transpose(lv, (2, 1, 0, 3)).reshape(W, 2 * K, 2, n // nleaf, nleaf)
        if out_dtype is not None:
            # delta-encoded compressed prefix: quantize the per-leaf DELTAS
            # to the storage dtype first, then accumulate the prefix in the
            # table dtype over the quantized deltas — a prefix difference
            # recovers the quantized per-leaf value exactly (up to the final
            # storage cast) instead of cancelling two large prefixes
            cum = cum.astype(out_dtype).astype(cum.dtype)
        cum = jnp.cumsum(cum, axis=-1)
        return jnp.concatenate([jnp.zeros_like(cum[..., :1]), cum], axis=-1)

    # chunks of whole edges (the prefix runs along each edge's leaves)
    ec = max(_FOLD_CHUNK // nleaf, 1)
    out = _chunked(
        lambda lo, hi: fold(lo, hi).reshape(W, 2 * K, 2, -1),
        s_lo, s_hi, ec * nleaf, out_len=E * (nleaf + 1),
    )  # [W, 2K, 2, E·(nleaf+1)] — each chunk's edges stay contiguous
    out = out.reshape(W * 2 * K, 2 * E * (nleaf + 1))
    return out if out_dtype is None else out.astype(out_dtype)


def dyn_node_tables(
    forest: FlatDynamicForest,
    wb: WindowBatch,
    *,
    n_levels: int,
    hq: int,
    steps_per_level: tuple,
    out_dtype=None,
):
    """q_t-contracted window moments of EVERY tree node up to depth hq.

    The exact-mode companion of :func:`dyn_window_tables`: instead of one
    leaf-level prefix, resolve each node's time window in its own run — per
    (boundary, window, node) binary search with per-level trip counts — and
    fold q_t immediately. The per-atom canonical walk then gathers these
    node-local values, so the floating-point association mirrors the NumPy
    node decomposition (node-scale rounding, not whole-edge-prefix scale) —
    that locality is what holds the ≤1e-12 cross-engine agreement even for
    kernels with large alternating q_t entries.

    Returns the packed node-value layout consumed by :func:`packed_walk`:
    nodeval [W·2k_s, 2·TN] with TN = E·(2^{hq+1}−1); node (d, e, i) is
    node id E·(2^d−1) + e·2^d + i, at column side·TN + id — the same
    executor layout the static packed forest uses.
    """
    Np = forest.time_lvl.shape[1] // n_levels
    E = forest.pend_ptr.shape[0] - 1
    k_t = wb.qt.shape[1]
    t_b, right_b = _dyn_boundaries(wb)
    qtl, qtr = wb.qt[0::2], wb.qt[1::2]
    parts = []
    for d in range(hq + 1):
        NL = E << d
        pb = E * ((1 << d) - 1) + d
        s_lo = (d * Np + forest.node_ptr[pb : pb + NL]).astype(jnp.int32)
        s_hi = (d * Np + forest.node_ptr[pb + 1 : pb + NL + 1]).astype(jnp.int32)
        parts.append(
            _fold_node_level(
                forest.time_lvl, forest.cum_lvl, s_lo, s_hi, t_b, right_b,
                qtl, qtr, int(steps_per_level[d]), k_t, out_dtype,
            )
        )
    out = jnp.concatenate(parts, axis=2)
    return out.reshape(out.shape[0], -1)


def dyn_node_base(E: int, hq: int) -> jnp.ndarray:
    """[hq+1, E] complete-tree node bases for :func:`packed_walk`: walk level
    ``lev`` reads depth d = hq − lev, whose edge-e block starts at
    E·(2^d − 1) + e·2^d in the :func:`dyn_node_tables` layout."""
    rows = []
    for lev in range(hq + 1):
        nb = 1 << (hq - lev)
        rows.append(E * (nb - 1) + jnp.arange(E, dtype=jnp.int32) * nb)
    return jnp.stack(rows)


def eval_atoms_dyn(
    forest: FlatDynamicForest,
    atoms: FlatAtoms,
    wb: WindowBatch,
    tables,
    leaves,
    *,
    n_levels: int,
    hq: int,
    scan_steps: int,
    pend_steps: int,
    exact: bool,
    tree: bool = True,
) -> jnp.ndarray:
    """DRFS per-atom aggregate for every half-window: [Wh, M].

    ``tree=False`` skips phase 1 (the Pallas executor answers the tree from
    its kernels; only the scan phases run here).

    Same contract as :func:`eval_atoms_flat` (callers fold the two halves of
    each window center and scatter onto lixels; requires the paired
    ``make_window_batch`` row layout). ``leaves`` [M, 4] i32 carries each
    atom's host-resolved (leaf_lo, leaf_hi, cl, cu) at depth ``hq``
    (``drfs.DynamicRangeForest.leaf_bounds``; -1 = no boundary leaf).
    Three phases, all window-batched:

      1. the fully-covered leaf range [leaf_lo, leaf_hi) at depth ``hq``.
         Quantized mode: two gathers into the per-edge leaf prefix tables
         (``tables`` = the :func:`dyn_window_tables` result). Exact mode:
         the canonical <= 2-nodes-per-level walk gathering the node-local
         values of :func:`dyn_node_tables` (``tables`` = (vl, vr)) — same
         node set and rounding locality as the NumPy decomposition;
      2. ``exact`` mode: the <= 2 partially covered boundary leaves are
         scanned with a masked loop (``scan_steps`` = max leaf occupancy)
         — the beyond-paper exactness path;
      3. pending (unsealed) events: :func:`_pending_moments` (two
         position searches per atom into its edge's pending run and one
         gather from per-window segmented prefix tables; ``pend_steps`` =
         max per-edge pending count), so streaming inserts are visible to
         queries without any rebuild.

    ``scan_steps`` and ``pend_steps`` may be traced: callers pass them as
    jit arguments, so a stream whose occupancies grow never recompiles.
    """
    Wh = wb.qt.shape[0]
    W = Wh // 2
    M = atoms.edge.shape[0]
    K = forest.cum_lvl.shape[0] // 4
    Np = forest.time_lvl.shape[1] // n_levels
    E = forest.pend_ptr.shape[0] - 1
    eid = atoms.edge.astype(jnp.int32)
    side = atoms.side_feat.astype(jnp.int32)
    nleaf = 1 << hq
    t_b, _ = _dyn_boundaries(wb)
    # [side, K left | K right, event]: combos (ψ_c·l, ψ_c·r, ψ_d·l, ψ_d·r)
    cum3 = forest.cum_lvl.reshape(2, 2 * K, -1)

    # ---- phase 1: fully-covered leaf range [leaf_lo, leaf_hi) -------------
    leaf_lo = leaves[:, 0]
    leaf_hi = jnp.maximum(leaves[:, 1], leaf_lo)
    # scan phases accumulate raw Φ moments (q_t applied at the end),
    # feature-major [K, W, M] so the atom axis stays minor
    mom_l = jnp.zeros((K, W, M), forest.cum_lvl.dtype)
    mom_r = jnp.zeros((K, W, M), forest.cum_lvl.dtype)
    k_s = atoms.qs.shape[1]
    acc = None
    if exact and tree:
        (nodeval,) = tables
        acc = packed_walk(
            nodeval, dyn_node_base(E, hq), eid, side, leaf_lo, leaf_hi,
            max_levels=hq + 1,
        )  # [W·2k_s, M]
    elif tree:
        (lcum,) = tables
        EL = E * (nleaf + 1)
        base = side * EL + eid * (nleaf + 1)
        idx = base[None] + jnp.stack([leaf_hi, leaf_lo])  # [2, M]
        cols = lcum[:, idx]  # one stacked gather: [W·2K, 2, M]
        tv = (cols[:, 0] - cols[:, 1]).reshape(W, 2, K, M)
        mom_l = mom_l + jnp.transpose(tv[:, 0], (1, 0, 2))  # paired halves
        mom_r = mom_r + jnp.transpose(tv[:, 1], (1, 0, 2))

    feat = jnp.arange(2 * K)[:, None, None]  # [2K, 1, 1]

    def masked_event_scan(mom_l, mom_r, s_lo, s_hi, on, steps):
        """Masked scan of the per-atom sealed-event runs [s_lo, s_hi),
        ``steps`` trips; Φ rows are differenced from the inclusive
        per-node prefix table."""

        def body(j, ms):
            ml, mr = ms
            i = s_lo + j
            valid = on & (i < s_hi)
            idx = jnp.where(valid, i, 0)
            te = forest.time_lvl[:, idx]  # [2, M] time keys
            p = forest.pos_lvl[idx]
            # per-event Φ from the inclusive prefix rows, both rows in ONE
            # stacked gather
            idx2 = jnp.stack([idx, jnp.maximum(idx - 1, 0)])
            rows2 = cum3[side[None, None], feat, idx2[None]]  # [2K, 2, M]
            prev = jnp.where(j > 0, rows2[:, 1], 0.0)
            row = rows2[:, 0] - prev
            keep = valid & _dyn_pos_mask(atoms, p)
            tw = t_b[:, :, :, None]  # [2, 3, W, 1] boundary keys
            te = te[:, None, :]  # [2, 1, M]
            m_l = _key_le(tw[:, 0], te) & _key_le(te, tw[:, 1])
            m_r = _key_lt(tw[:, 1], te) & _key_le(te, tw[:, 2])
            ml = ml + jnp.where((m_l & keep[None])[None], row[:K, None, :], 0.0)
            mr = mr + jnp.where((m_r & keep[None])[None], row[K:, None, :], 0.0)
            return ml, mr

        return jax.lax.fori_loop(0, steps, body, (mom_l, mom_r))

    # ---- phase 2 (exact mode): partially covered boundary leaves ----------
    if exact:
        pb = E * (nleaf - 1) + hq
        for leaf in (leaves[:, 2], leaves[:, 3]):
            pidx = pb + eid * nleaf + jnp.clip(leaf, 0, nleaf - 1)
            s_lo = (hq * Np + forest.node_ptr[pidx]).astype(jnp.int32)
            s_hi = (hq * Np + forest.node_ptr[pidx + 1]).astype(jnp.int32)
            mom_l, mom_r = masked_event_scan(
                mom_l, mom_r, s_lo, s_hi, leaf >= 0, scan_steps
            )

    # ---- phase 3: pending (unsealed) events -------------------------------
    pend_l, pend_r = jax.lax.cond(
        jnp.asarray(pend_steps) > 0,
        lambda: _pending_moments(forest, atoms, t_b, pend_steps),
        lambda: (jnp.zeros_like(mom_l), jnp.zeros_like(mom_r)),
    )
    mom_l = mom_l + pend_l
    mom_r = mom_r + pend_r

    # ---- contraction with the factored query ------------------------------
    k_t = wb.qt.shape[1]
    val_l = jnp.einsum(
        "stwm,ms,wt->wm", mom_l.reshape(k_s, k_t, W, M), atoms.qs, wb.qt[0::2]
    )
    val_r = jnp.einsum(
        "stwm,ms,wt->wm", mom_r.reshape(k_s, k_t, W, M), atoms.qs, wb.qt[1::2]
    )
    if acc is not None:
        acc_l, acc_r = _walk_values(acc, atoms.qs)
        val_l = val_l + acc_l
        val_r = val_r + acc_r
    out = jnp.stack([val_l, val_r], axis=1).reshape(Wh, M)
    return jnp.where(atoms.valid[None, :], out, 0.0)


def _bit_length(n):
    """Traced int32 ``n.bit_length()`` (0 for n <= 0)."""
    n = jnp.maximum(jnp.asarray(n, jnp.int32), 0)
    return 32 - jax.lax.clz(n)


def _pending_moments(forest: FlatDynamicForest, atoms: FlatAtoms, t_b, pend_steps):
    """Raw-Φ window moments of the pending events of every atom: ([K, W, M]
    left halves, [K, W, M] right halves), feature-major.

    The pending buffer is sorted by (edge, position), so the events an
    atom's position bounds accept are one run [j_lo, j_hi) of its edge's
    segment, found by binary searches (``pend_steps`` = max per-edge count
    bounds their trips). The run is summed as power-of-two blocks, one per
    set bit of its length, smallest first: level k of the loop holds every
    window's time-masked Φ rows summed over 2^k consecutive rows (built by
    doubling), and each atom gathers at most one block column per level.
    Blocks lie inside the run, so nothing is differenced and the rounding
    is that of summing the run's own events. The cost grows with log2 of
    the per-edge pending count, not with the count itself.
    """
    W = t_b.shape[2]
    K = forest.pend_phi.shape[0] // 4
    Pp = forest.pend_pos.shape[0]
    M = atoms.edge.shape[0]
    eid = atoms.edge.astype(jnp.int32)
    side = atoms.side_feat.astype(jnp.int32)
    ptr = forest.pend_ptr.astype(jnp.int32)
    nbits = _bit_length(pend_steps)

    # ---- each atom's run [j_lo, j_hi) within its edge ----------------------
    s_lo = ptr[eid]
    s_hi = ptr[eid + 1]
    steps = nbits + 1
    pos = forest.pend_pos
    j_lo = jnp.maximum(
        _seg_search(pos, s_lo, s_hi, atoms.pos_lo1, atoms.lo1_right, steps),
        _seg_search(pos, s_lo, s_hi, atoms.pos_lo2, jnp.zeros((M,), bool), steps),
    )
    j_hi = _seg_search(pos, s_lo, s_hi, atoms.pos_hi, jnp.ones((M,), bool), steps)
    length = jnp.maximum(j_hi - j_lo, 0)

    # ---- time-masked Φ rows per window half: [W·2·K, side, Pp] -------------
    te = forest.pend_time[:, None, :]  # [2, 1, Pp]
    tw = t_b[:, :, :, None]  # [2, 3, W, 1]
    m_l = _key_le(tw[:, 0], te) & _key_le(te, tw[:, 1])  # [W, Pp]
    m_r = _key_lt(tw[:, 1], te) & _key_le(te, tw[:, 2])
    phi = forest.pend_phi.reshape(2, 2, K, Pp)  # [side, half, K, Pp]
    mask = jnp.stack([m_l, m_r], axis=1)  # [W, half, Pp]
    rows = jnp.where(
        mask[:, :, None, None], jnp.transpose(phi, (1, 2, 0, 3))[None], 0.0
    ).reshape(W * 2 * K, 2, Pp)

    def level(k, carry):
        blocks, acc, at = carry  # blocks[..., j] = Σ rows[..., j - 2^k + 1 : j + 1]
        d = jnp.left_shift(jnp.int32(1), k)
        take = (jnp.right_shift(length, k) & 1) == 1
        col = side * Pp + jnp.clip(at + d - 1, 0, Pp - 1)
        acc = acc + jnp.where(take[None], blocks.reshape(-1, 2 * Pp)[:, col], 0.0)
        at = at + jnp.where(take, d, 0)
        padded = jnp.concatenate([jnp.zeros_like(blocks), blocks], axis=-1)
        prev = jax.lax.dynamic_slice_in_dim(padded, Pp - d, Pp, axis=-1)
        return blocks + prev, acc, at

    acc0 = jnp.zeros((W * 2 * K, M), rows.dtype)
    _, acc, _ = jax.lax.fori_loop(0, nbits, level, (rows, acc0, j_lo))
    run = acc.reshape(W, 2, K, M)
    return jnp.transpose(run[:, 0], (1, 0, 2)), jnp.transpose(run[:, 1], (1, 0, 2))


@functools.partial(jax.jit, static_argnames=("max_levels", "search_steps", "cascade"))
def eval_atoms_flat(
    forest: FlatForest,
    atoms: FlatAtoms,
    wb: WindowBatch,
    ranks,
    *,
    max_levels: int,
    search_steps: int,
    cascade: bool = False,
) -> jnp.ndarray:
    """Per-atom aggregated Q·A for every half-window: [Wh, M].

    Callers reduce the Wh axis (sum the two halves of each window center) and
    scatter the M axis onto lixels. Requires the (left, right)-paired row
    layout produced by ``make_window_batch`` (rows 2w / 2w+1 are the two
    halves of center w). ``ranks`` supplies the precomputed
    :func:`rank_boundaries` table [3, W, E] (the plan hoist) — every caller,
    including the sharded path, goes through the cached plan now.
    """
    if cascade:
        acc = _engine_cascade(
            forest, atoms, wb, ranks,
            max_levels=max_levels, search_steps=search_steps,
        )
    else:
        Wh = wb.qt.shape[0]
        W = Wh // 2
        eid = atoms.edge
        M = eid.shape[0]
        k = ranks[:, :, eid]  # [3, W, M] (lo, mid, hi) per center
        r_lo = jnp.stack([k[0], k[1]], axis=1).reshape(Wh, M)
        r_hi = jnp.stack([k[1], k[2]], axis=1).reshape(Wh, M)
        combo = atoms.side_feat.astype(jnp.int32)[None, :] * 2 + wb.half[:, None]
        acc = _engine_search(
            forest, atoms, wb, combo, r_lo, r_hi,
            max_levels=max_levels, search_steps=search_steps,
        )
    return jnp.where(atoms.valid[None, :], acc, 0.0)
