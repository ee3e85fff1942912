"""Sharded TN-KDE: the packed-plan executor with sharding as a first axis.

Distribution scheme (DESIGN.md §3): the *index* — not the query — dominates
memory at fleet scale, so the packed position-major tables are slabbed
across the mesh's data axes and the canonical executors run unchanged under
``shard_map``:

  * edges are assigned to shards by greedy balanced packing over
    n_e log n_e work (:func:`assign_edges`); each shard holds a **rebased,
    compacted slab** of the `jax_engine.PackedForest` layout — per-shard
    tables address shard-LOCAL edge slots, so every table (values *and*
    metadata) scales ~1/devices;
  * query atoms come from the same cached host plans every executor uses
    (`query_plan.py`); a plan block is routed once to the shard owning its
    edge (`query_plan.route_atoms_by_shard`) with local edge ids, and the
    window-independent root rank interval of every atom is resolved per
    shard and cached in the pack — exactly the single-host plan contract;
  * the per-(window batch) node tables (`packed_node_tables`), the canonical
    walk (`packed_walk` via `eval_atoms_packed`) and the DRFS table builders
    (`dyn_node_tables` / `dyn_window_tables` / `eval_atoms_dyn`) run
    **verbatim** inside the shard_map bodies — sharding adds only the slab
    unstacking and one ``psum`` of the per-shard [L, W] heatmap delta, so
    per-atom values are bitwise identical to the single-host packed executor
    and the full heatmaps agree to summation-order noise (≤1e-12, pinned by
    tests/test_distributed_kde.py);
  * DRFS snapshots slab the same way per (revision, depth) epoch — sealed
    level CSRs, leaf/node tables and the pending-event CSR are all
    shard-local, so streaming insert → seal → query works sharded with the
    same MVCC contract as `rfs.FlatDynamicEngine`.

The engines are mesh-agnostic: tests run them on 2/4/8 forced host devices;
``launch/dryrun.py --kde`` lowers the same programs for the production
16x16 and 2x16x16 meshes. Entry point: ``TNKDE(..., mesh=...)``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import OrderedDict
from typing import Sequence, Tuple

import numpy as np

from ..spans import span
from .aggregation import N_COMBOS
from .query_plan import PlanCache, route_atoms_by_shard
from .rfs import (
    _DeviceEngine,
    _device_nbytes,
    _size_class,
    feature_major,
    pending_by_position,
    pending_capacity,
)

__all__ = [
    "assign_edges",
    "ShardedPackedForest",
    "build_sharded_packed",
    "ShardedForestEngine",
    "ShardedDynamicEngine",
]


def assign_edges(counts: np.ndarray, n_shards: int) -> np.ndarray:
    """Greedy balanced edge→shard assignment by n log n work: [E] i64.

    Descending first-fit over the per-edge event counts. Degenerate cases
    yield valid (possibly empty) slabs: with more shards than edges some
    shards simply own nothing, and zero-event edges are given unit weight so
    they spread across shards instead of piling onto shard 0 (they carry no
    event tables, but they do occupy a local edge slot — round-robining them
    keeps the per-shard metadata width at ~E/S instead of E).
    """
    counts = np.asarray(counts, dtype=np.int64)
    n_shards = max(int(n_shards), 1)
    out = np.zeros(len(counts), np.int64)
    if len(counts) == 0:
        return out
    w = counts * np.maximum(np.log2(np.maximum(counts, 2)), 1.0)
    w = np.where(counts > 0, w, 1.0)
    order = np.argsort(-w, kind="stable")
    load = np.zeros(n_shards)
    for e in order:
        s = int(np.argmin(load))
        out[e] = s
        load[s] += w[e]
    return out


def _owned_lists(shard_of: np.ndarray, n_shards: int):
    """(owned edge-id list per shard, El = padded local edge capacity,
    edge_slot [E] global→local map). Owned lists are ascending, so local
    slot order matches global edge order within a shard."""
    owned = [np.nonzero(shard_of == s)[0] for s in range(n_shards)]
    El = max(max((len(o) for o in owned), default=0), 1)
    edge_slot = np.zeros(len(shard_of), np.int64)
    for o in owned:
        edge_slot[o] = np.arange(len(o))
    return owned, El, edge_slot


@dataclasses.dataclass
class ShardedPackedForest:
    """Stacked per-shard slabs of the packed position-major layout.

    Every array carries a leading shard axis; per-shard contents are the
    `jax_engine.PackedForest` leaf tables of that shard's edges, laid out by
    descending ``n_pad`` inside the shard (as `rfs.build_packed_host_tables`
    does for the whole forest) and addressed by shard-LOCAL edge slots
    (``edge_slot`` maps global edge ids; atoms are routed with local ids, so
    non-owned edges simply do not exist on a shard). Slabs are padded to the
    max across shards — shard_map requires uniform shapes — with +inf
    position/time pads and zero Φ; every level's width is the max of the
    shards' node counts (``level_nodes``), and since 2·NL_ℓ ≤ NL_{ℓ−1}
    holds for each shard it holds for the maxima, so the dense build's
    pairwise sums stay in bounds. Pad columns are never addressed by
    ``node_base_lvl``.
    """

    pm_pos: np.ndarray  # [S, Pmax]
    pos_base: np.ndarray  # [S, El]
    pm_time: np.ndarray  # [S, Pmax] leaf times, position order
    pm_phi: np.ndarray  # [S, Pmax, 4, K] leaf raw Φ rows, same order
    n_pad: np.ndarray  # [S, El]
    node_base_lvl: np.ndarray  # [S, Lmax, El] walk level → local node base
    shard_of_edge: np.ndarray  # [E]
    edge_slot: np.ndarray  # [E] global edge → local slot on its shard
    events_per_shard: np.ndarray  # [S]
    max_levels: int
    search_steps: int
    level_nodes: tuple  # padded per-level node widths (uniform)
    n_shards: int
    # per-shard byte accounting lives on the engines (_ShardedBase.
    # bytes_per_shard over the actual device arrays) — one accounting path


def build_sharded_packed(rf, n_shards: int) -> ShardedPackedForest:
    """Slab a built RangeForest's packed tables into per-shard rebased slabs.

    Builds the position-major host tables once (`rfs.build_packed_host_tables`
    — the identical leaves the single-host engine uploads) and relocates
    each edge's leaf block into its shard's slab, owned edges by descending
    ``n_pad``; node ids are re-assigned level-major within the shard with
    per-level blocks padded to the max across shards, so
    `packed_node_tables`'s concatenated nodeval layout and ``node_base_lvl``
    agree on every shard.
    """
    from .rfs import build_packed_host_tables

    host = build_packed_host_tables(rf)
    counts = np.diff(rf.ee.ptr)
    shard_of = assign_edges(counts, n_shards)
    S = max(int(n_shards), 1)
    owned, El, edge_slot = _owned_lists(shard_of, S)
    n_pad_g = np.asarray(host["n_pad"], np.int64)
    n_lev_g = np.asarray(rf.n_levels, np.int64)
    K = rf.ctx.K
    Lmax = max(rf.max_levels, 1)
    nl_cnt = np.zeros((S, Lmax), np.int64)
    for s, o in enumerate(owned):
        for lev in range(Lmax):
            nl_cnt[s, lev] = int(n_pad_g[o[n_lev_g[o] > lev]].sum()) >> lev
    NL = np.maximum(nl_cnt.max(axis=0, initial=0), 1)  # [Lmax] padded widths
    lev_base = np.concatenate([[0], np.cumsum(NL)])
    Pmax = int(NL[0])

    pm_pos = np.full((S, Pmax), np.inf)
    pm_time = np.full((S, Pmax), np.inf)
    pm_phi = np.zeros((S, Pmax, N_COMBOS, K))
    pos_base = np.zeros((S, El), np.int64)
    n_pad = np.zeros((S, El), np.int64)
    node_base_lvl = np.zeros((S, Lmax, El), np.int32)
    for s, o in enumerate(owned):
        n_pad[s, : len(o)] = n_pad_g[o]
        p_off = 0
        for j in np.argsort(-n_pad_g[o], kind="stable"):
            e = o[j]
            npd = int(n_pad_g[e])
            if npd == 0:
                continue
            gp = int(host["pos_base"][e])
            pm_pos[s, p_off : p_off + npd] = host["pm_pos"][gp : gp + npd]
            pm_time[s, p_off : p_off + npd] = host["pm_time"][gp : gp + npd]
            pm_phi[s, p_off : p_off + npd] = host["pm_phi"][gp : gp + npd]
            pos_base[s, j] = p_off
            for lev in range(int(n_lev_g[e])):
                node_base_lvl[s, lev, j] = lev_base[lev] + (p_off >> lev)
            p_off += npd
    ev_per_shard = np.bincount(shard_of, weights=counts.astype(np.float64), minlength=S)
    return ShardedPackedForest(
        pm_pos=pm_pos,
        pos_base=pos_base,
        pm_time=pm_time,
        pm_phi=pm_phi,
        n_pad=n_pad,
        node_base_lvl=node_base_lvl,
        shard_of_edge=shard_of,
        edge_slot=edge_slot,
        events_per_shard=ev_per_shard.astype(np.int64),
        max_levels=Lmax,
        search_steps=max(int(np.ceil(np.log2(max(int(n_pad_g.max(initial=1)), 1) + 1))) + 1, 1),
        level_nodes=tuple(int(n) for n in NL),
        n_shards=S,
    )


def _slab_keys(t: np.ndarray) -> np.ndarray:
    """[S, N] host times → [S, 2, N] time keys (``jax_engine.time_key``),
    shard axis first so each slab carries its own key planes."""
    from .jax_engine import time_key

    return np.ascontiguousarray(np.moveaxis(time_key(t), 0, 1))


# ------------------------------------------------------------- programs
_PROGRAMS: dict = {}  # (mesh, axes) -> dict of jitted shard_map programs
# Module-level cache: every engine instance on the same mesh reuses one
# program set, so the jit caches underneath are keyed on shapes + statics
# only (shard count never multiplies compiles — one program per mesh, not
# per shard; tests/test_distributed_kde.py audits this via jit_entry_count).


def _get_programs(mesh, axes: Tuple[str, ...]):
    key = (mesh, tuple(axes))
    hit = _PROGRAMS.get(key)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    from .jax_engine import (
        dyn_node_tables,
        dyn_window_tables,
        eval_atoms_dyn,
        eval_atoms_packed,
        packed_node_tables,
        packed_root_ranks,
    )
    from .rfs import register_jit_fns

    spec = P(tuple(axes))
    rep = P()
    ax = tuple(axes)

    def _local(t):
        return jax.tree.map(lambda x: x[0], t)

    def _smap(body, in_specs, out_specs):
        return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)

    def _psum_delta(vals, fa_l, heat):
        """Fold half-windows, scatter the shard's atoms, psum the delta.

        ``heat`` rides in replicated so multi-block flushes accumulate
        across calls — only the shard-local delta is reduced.
        """
        W = heat.shape[1]
        per_win = vals.reshape(W, 2, -1).sum(axis=1)
        delta = jnp.zeros_like(heat).at[fa_l.lixel].add(per_win.T)
        return heat + jax.lax.psum(delta, ax)

    # ---- static RFS: node tables, root ranks, flush ------------------------
    @functools.partial(jax.jit, static_argnames=("level_nodes", "k_t"))
    def rfs_tables(pf, wb, *, level_nodes, k_t):
        def body(pf, wb):
            out = packed_node_tables(
                _local(pf), wb, level_nodes=level_nodes, k_t=k_t
            )
            return out[None]

        return _smap(body, (spec, rep), spec)(pf, wb)

    @functools.partial(jax.jit, static_argnames=("search_steps",))
    def rfs_roots(pf, fa, *, search_steps):
        def body(pf, fa):
            r_lo, r_hi = packed_root_ranks(
                _local(pf), _local(fa), search_steps=search_steps
            )
            return r_lo[None], r_hi[None]

        return _smap(body, (spec, spec), (spec, spec))(pf, fa)

    @functools.partial(jax.jit, static_argnames=("max_levels",))
    def rfs_flush(nodeval, node_base_lvl, fa, r_lo, r_hi, heat, *, max_levels):
        def body(nodeval, node_base_lvl, fa, r_lo, r_hi, heat):
            fa_l = _local(fa)
            vals = eval_atoms_packed(
                nodeval[0], node_base_lvl[0], fa_l, r_lo[0], r_hi[0],
                max_levels=max_levels,
            )
            return _psum_delta(vals, fa_l, heat)

        return _smap(body, (spec, spec, spec, spec, spec, rep), rep)(
            nodeval, node_base_lvl, fa, r_lo, r_hi, heat
        )

    # ---- DRFS: window tables + flush ---------------------------------------
    @functools.partial(
        jax.jit,
        static_argnames=("n_levels", "hq", "steps_per_level", "exact"),
    )
    def dyn_tables(forest, wb, *, n_levels, hq, search_steps, steps_per_level, exact):
        def body(forest, wb):
            f = _local(forest)
            if exact:
                out = dyn_node_tables(
                    f, wb, n_levels=n_levels, hq=hq, steps_per_level=steps_per_level
                )
            else:
                out = dyn_window_tables(
                    f, wb, n_levels=n_levels, hq=hq, search_steps=search_steps
                )
            return out[None]

        return _smap(body, (spec, rep), spec)(forest, wb)

    @functools.partial(
        jax.jit,
        static_argnames=("n_levels", "hq", "exact"),
    )
    def dyn_flush(forest, fa, wb, tables, leaves, heat, *, n_levels, hq,
                  scan_steps, pend_steps, exact):
        def body(forest, fa, wb, tables, leaves, heat):
            fa_l = _local(fa)
            vals = eval_atoms_dyn(
                _local(forest), fa_l, wb, tuple(t[0] for t in tables), leaves[0],
                n_levels=n_levels, hq=hq, scan_steps=scan_steps,
                pend_steps=pend_steps, exact=exact,
            )
            return _psum_delta(vals, fa_l, heat)

        return _smap(body, (spec, spec, rep, spec, spec, rep), rep)(
            forest, fa, wb, tables, leaves, heat
        )

    progs = dict(
        rfs_tables=rfs_tables,
        rfs_roots=rfs_roots,
        rfs_flush=rfs_flush,
        dyn_tables=dyn_tables,
        dyn_flush=dyn_flush,
    )
    register_jit_fns(progs.values())
    _PROGRAMS[key] = progs
    return progs


class _ShardedBase(_DeviceEngine):
    """Shared plumbing for the sharded engines: the single-host device
    plumbing (window batches, heatmap, device->host transfer, counters)
    plus mesh bookkeeping, atom routing/upload and per-shard accounting —
    subclassing `_DeviceEngine` keeps the two engine families from
    drifting apart."""

    def _init_mesh(self, mesh, axes: Sequence[str]):
        self.mesh = mesh
        self.axes = tuple(axes)
        missing = [a for a in self.axes if a not in mesh.shape]
        if missing:
            raise ValueError(f"mesh has no axes {missing}; got {dict(mesh.shape)}")
        self.n_shards = int(math.prod(mesh.shape[a] for a in self.axes))
        self._progs = _get_programs(mesh, self.axes)
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._slab_sharding = NamedSharding(mesh, P(self.axes))
        self._replicated = NamedSharding(mesh, P())
        # shard_edge_slots / shard_edges_max describe the slab layout (set
        # once, by _count_slots); psum_bytes adds the heat-delta bytes every
        # collective flush reduces
        self.counters.update(shard_edge_slots=0, shard_edges_max=0, psum_bytes=0)

    def _count_slots(self, shard_of: np.ndarray, El: int):
        """Padded local edge slots over all shards, and the largest shard's
        own edges: slabs are padded to it, so the table build of every
        shard runs over ``El`` edge slots."""
        self.counters["shard_edge_slots"] = int(El) * self.n_shards
        self.counters["shard_edges_max"] = int(
            np.bincount(shard_of, minlength=self.n_shards).max(initial=0)
        )

    def _psum_flush(self, prog, *args, **statics):
        """Run one collective flush program; ``args[-1]`` is the [L, W]
        heatmap whose per-shard delta it psums."""
        heat = args[-1]
        self.counters["psum_bytes"] += heat.size * heat.dtype.itemsize
        with self._precision():
            return prog(*args, **statics)

    def _upload(self, x):
        """Replicated upload (window batches, heatmaps): every device of
        the mesh holds its own copy from the start."""
        return self._jax.device_put(x, self._replicated)

    def _shard_put(self, x):
        """Upload a stacked [S, ...] host array with its shard axis placed
        over the mesh. Plain ``jnp.asarray`` would commit the WHOLE stack to
        the default device and reshard inside every collective — on a real
        multi-device mesh that is both a device-0 memory hot spot and a
        per-flush transfer; placing at upload time is what actually realizes
        the 1/devices scaling on hardware (callers must hold the x64
        context so float64 tables survive canonicalization)."""
        return self._jax.device_put(x, self._slab_sharding)

    def _upload_fa(self, fields: dict):
        """Host-routed [S, Mp] atom fields → a device FlatAtoms, sharded."""
        from .jax_engine import FlatAtoms

        with self._precision():
            return FlatAtoms(**{k: self._shard_put(v) for k, v in fields.items()})

    @property
    def bytes_per_shard(self) -> int:
        """Per-shard device bytes: stacked arrays divided by the shard count
        (slabs are padded to the max, so this is within padding of the
        heaviest shard). The measured counterpart of the 1/devices
        memory-scaling claim — surfaced as ``QueryStats.bytes_per_shard``."""
        return self.device_bytes // max(self.n_shards, 1)


class ShardedForestEngine(_ShardedBase):
    """Sharded packed-plan query engine over a built RangeForest.

    The :class:`rfs.FlatForestEngine` contract (window_batch / new_heatmap /
    flush_plan / to_numpy / counters / device_bytes) over per-shard slabs of
    the same position-major layout. Every flush is ONE collective program:
    per shard the canonical `eval_atoms_packed` walk — verbatim the
    single-host executor — followed by a psum of the [L, W] heatmap delta.
    Cache structure mirrors the single-host engine exactly: window tables
    per ts tuple, atom packs (with cached per-shard root rank intervals)
    per host plan, both keyed with the mesh so two meshes never alias.
    """

    executor = "packed"

    def __init__(self, rf, mesh, axes: Sequence[str] = ("data",)):
        self._init_jax()
        self._init_mesh(mesh, axes)
        self.rf = rf
        from .jax_engine import PackedForest

        with span("shard.slab"):
            self.sf = build_sharded_packed(rf, self.n_shards)
            with self._precision():
                self._nbl = self._shard_put(self.sf.node_base_lvl)
                self._pf = PackedForest(
                    pm_pos=self._shard_put(self.sf.pm_pos),
                    pos_base=self._shard_put(self.sf.pos_base),
                    pm_time=self._shard_put(_slab_keys(self.sf.pm_time)),
                    pm_phi=self._shard_put(feature_major(self.sf.pm_phi)),
                    n_pad=self._shard_put(self.sf.n_pad),
                )
        self.max_levels = self.sf.max_levels
        self.search_steps = self.sf.search_steps
        self._count_slots(self.sf.shard_of_edge, self.sf.pos_base.shape[1])
        self._tab_cache = PlanCache(2)
        self._pack_cache = PlanCache(2)
        self._mesh_key = (tuple(sorted(mesh.shape.items())), self.axes)

    @property
    def device_bytes(self) -> int:
        return _device_nbytes(
            [
                self._pf,
                self._nbl,
                list(self._tab_cache.values()),
                list(self._pack_cache.values()),
            ]
        )

    def window_tables(self, wb, ts_key):
        """Sharded q_t-folded node values [S, W·2k_s, 2R], LRU per ts.

        Same hoist, same builder (`packed_node_tables`), run per shard over
        the slab's leaves — one dense fold per shard, no searches.
        """
        key = (ts_key, self._mesh_key)
        hit = self._tab_cache.get(key)
        if hit is not None:
            return hit
        W = len(ts_key)
        with self._precision():
            tabs = self._progs["rfs_tables"](
                self._pf, wb,
                level_nodes=self.sf.level_nodes,
                k_t=int(self.rf.ctx.k_t),
            )
        # every shard folds its padded slab of leaves
        self.counters["table_leaves"] += W * self.sf.level_nodes[0] * self.n_shards
        self._tab_cache.put(key, tabs)
        return tabs

    def _atom_packs(self, plan):
        """Per-block sharded atom packs with cached root rank intervals."""
        key = (plan.key, self._mesh_key)
        hit = self._pack_cache.get(key)
        if hit is not None:
            return hit
        packs = []
        for atoms in plan.blocks:
            fields = route_atoms_by_shard(
                atoms, self.sf.shard_of_edge, self.sf.edge_slot, self.n_shards
            )
            fa = self._upload_fa(fields)
            with self._precision():
                r_lo, r_hi = self._progs["rfs_roots"](
                    self._pf, fa, search_steps=self.search_steps
                )
            packs.append(dict(fa=fa, r_lo=r_lo, r_hi=r_hi, m=atoms.m))
        self._pack_cache.put(key, packs)
        return packs

    def flush_plan(self, heat, plan, wb, ts_key, **_):
        """heat[L, W] += every atom block, all shards, one collective each."""
        if plan.n_atoms == 0:
            return heat
        tabs = self.window_tables(wb, ts_key)
        for entry in self._atom_packs(plan):
            heat = self._psum_flush(
                self._progs["rfs_flush"],
                tabs, self._nbl, entry["fa"], entry["r_lo"], entry["r_hi"],
                heat, max_levels=self.max_levels,
            )
            self.counters["moment_gathers"] += 2 * self.max_levels * entry["m"]
        return heat

    def lower_flush(self, wb, plan, n_lixels: int):
        """Lower (never execute) the sharded flush collective — the dry-run
        hook ``launch/dryrun.py --kde`` uses to compile-prove the packed
        program on the production meshes. Table and root-rank shapes come
        from ``jax.eval_shape`` over the real programs, so what is lowered
        is exactly what :meth:`flush_plan` would dispatch.
        """
        import functools as ft

        jax, jnp = self._jax, self._jnp
        atoms = plan.blocks[0]
        fields = route_atoms_by_shard(
            atoms, self.sf.shard_of_edge, self.sf.edge_slot, self.n_shards
        )
        with self._precision():
            fa = self._upload_fa(fields)
            tabs_s = jax.eval_shape(
                ft.partial(
                    self._progs["rfs_tables"],
                    level_nodes=self.sf.level_nodes,
                    k_t=int(self.rf.ctx.k_t),
                ),
                self._pf, wb,
            )
            r_s = jax.eval_shape(
                ft.partial(self._progs["rfs_roots"], search_steps=self.search_steps),
                self._pf, fa,
            )
            heat_s = jax.ShapeDtypeStruct(
                (n_lixels, wb.qt.shape[0] // 2),
                jax.dtypes.canonicalize_dtype(jnp.float64),
            )
            return self._progs["rfs_flush"].lower(
                tabs_s, self._nbl, fa, r_s[0], r_s[1], heat_s,
                max_levels=self.max_levels,
            )


class _ShardedSealed:
    """Stacked device tables for one sealed structure epoch, all shards."""

    __slots__ = ("tables", "n_levels", "max_occ", "nbytes")


class _ShardedPend:
    """Stacked device tables for one pending-buffer epoch, all shards."""

    __slots__ = ("tables", "pend_steps", "nbytes")


class ShardedDynamicEngine(_ShardedBase):
    """Sharded streaming DRFS engine — `rfs.FlatDynamicEngine` over slabs.

    Mutations stay on the host (`drfs.py`); this engine slabs **per snapshot
    epoch**: sealed level CSRs and event tables are compacted to each
    shard's owned edges (shard-local node_ptr over El local edge slots, so
    `eval_atoms_dyn` and the `dyn_*` table builders run verbatim per shard),
    and the pending CSR is sliced the same way — insert → query never
    rebuilds, exactly the single-host MVCC contract. Shard assignment is
    fixed at construction from the initial per-edge event counts; streamed
    events follow their edge's shard.
    """

    executor = "packed"

    def __init__(self, df, mesh, axes: Sequence[str] = ("data",), *,
                 max_snapshots: int = 2):
        self._init_jax()
        self._init_mesh(mesh, axes)
        self.df = df
        self.max_snapshots = max(int(max_snapshots), 1)
        counts = np.diff(df.ptr)
        self.shard_of = assign_edges(counts, self.n_shards)
        self._owned, self.El, self.edge_slot = _owned_lists(self.shard_of, self.n_shards)
        self._own_mask = [
            np.zeros(df.net.n_edges, bool) for _ in range(self.n_shards)
        ]
        for s, o in enumerate(self._owned):
            self._own_mask[s][o] = True
        self._count_slots(self.shard_of, self.El)
        self._sealed_packs: "OrderedDict" = OrderedDict()
        self._pend_packs: "OrderedDict" = OrderedDict()
        self._tab_cache: "OrderedDict" = OrderedDict()
        self._pack_cache = PlanCache(2)
        self._mesh_key = (tuple(sorted(mesh.shape.items())), self.axes)
        snap = df.snapshot()
        self._get_sealed(snap)
        self._get_pending(snap)

    @property
    def device_bytes(self) -> int:
        return _device_nbytes(
            [
                list(self._sealed_packs.values()),
                list(self._pend_packs.values()),
                list(self._tab_cache.values()),
                list(self._pack_cache.values()),
            ]
        )

    # ------------------------------------------------------------- packing
    def _get_sealed(self, snap) -> _ShardedSealed:
        """Stacked sealed level tables for the snapshot's structure epoch."""
        key = (snap.revision, snap.depth)
        pack = self._sealed_packs.get(key)
        if pack is not None:
            self._sealed_packs.move_to_end(key)
            return pack
        with span("shard.slab", revision=snap.revision):
            pack = self._slab_sealed(snap)
        self._sealed_packs[key] = pack
        while len(self._sealed_packs) > self.max_snapshots:
            old_key, _ = self._sealed_packs.popitem(last=False)
            for tk in [k for k in self._tab_cache if k[1:3] == old_key]:
                del self._tab_cache[tk]
        return pack

    def _slab_sealed(self, snap) -> _ShardedSealed:
        """Slab the snapshot's sealed level CSRs on the host and upload."""
        S, El = self.n_shards, self.El
        E = snap.net.n_edges
        Lv = snap.depth + 1
        K = snap.ctx.K
        edge_of_event = np.repeat(np.arange(E, dtype=np.int64), np.diff(snap.ptr))
        n_s = np.bincount(self.shard_of[edge_of_event], minlength=S) if len(
            edge_of_event
        ) else np.zeros(S, np.int64)
        Np = _size_class(max(int(n_s.max(initial=1)), 1))
        time_lvl = np.full((S, Lv * Np), np.inf)
        pos_lvl = np.full((S, Lv * Np), np.inf)
        cum_lvl = np.zeros((S, Lv * Np, N_COMBOS, K))
        ptr_len = sum(El * (1 << d) + 1 for d in range(Lv))
        node_ptr = np.zeros((S, ptr_len), np.int64)
        max_occ = np.zeros(Lv, np.int64)
        for d, (nptr, tms, cum, eidx) in enumerate(snap.levels):
            cnt = np.diff(nptr).reshape(E, 1 << d)
            eos = edge_of_event[eidx] if len(eidx) else eidx
            off_d = El * ((1 << d) - 1) + d
            for s, o in enumerate(self._owned):
                sel = np.nonzero(self._own_mask[s][eos])[0] if len(eos) else eos
                k = len(sel)
                time_lvl[s, d * Np : d * Np + k] = tms[sel]
                pos_lvl[s, d * Np : d * Np + k] = snap.pos[eidx[sel]]
                cum_lvl[s, d * Np : d * Np + k] = cum[sel]
                cl = np.zeros((El, 1 << d), np.int64)
                cl[: len(o)] = cnt[o]
                np.cumsum(cl.ravel(), out=node_ptr[s, off_d + 1 : off_d + El * (1 << d) + 1])
                max_occ[d] = max(max_occ[d], int(cl.max(initial=0)))
        pack = _ShardedSealed()
        with self._precision():
            pack.tables = dict(
                time_lvl=self._shard_put(_slab_keys(time_lvl)),
                pos_lvl=self._shard_put(pos_lvl),
                cum_lvl=self._shard_put(feature_major(cum_lvl)),
                node_ptr=self._shard_put(node_ptr),
            )
        pack.n_levels = Lv
        pack.max_occ = max_occ
        # account DEVICE tables (shape × device dtype), not host staging:
        # the two differ under index packing / canonicalization, and
        # _device_nbytes is the one accounting helper everywhere else
        pack.nbytes = _device_nbytes(pack.tables)
        return pack

    def _get_pending(self, snap) -> _ShardedPend:
        """Stacked pending-CSR tables for the snapshot's pending epoch."""
        key = snap.pend_revision
        pack = self._pend_packs.get(key)
        if pack is not None:
            self._pend_packs.move_to_end(key)
            return pack
        with span("shard.slab", pend_revision=key):
            pack = self._slab_pending(snap)
        self._pend_packs[key] = pack
        while len(self._pend_packs) > self.max_snapshots + 2:
            self._pend_packs.popitem(last=False)
        return pack

    def _slab_pending(self, snap) -> _ShardedPend:
        """Slab the snapshot's pending CSR on the host and upload."""
        S, El = self.n_shards, self.El
        E = snap.net.n_edges
        K = snap.ctx.K
        csr = snap.pending_csr()
        pack = _ShardedPend()
        if csr is None:
            Pp = pending_capacity(snap, 0)
            pptr = np.zeros((S, El + 1), np.int64)
            pp = np.zeros((S, Pp))
            pt = np.full((S, Pp), np.inf)
            pf = np.zeros((S, Pp, N_COMBOS, K))
            pack.pend_steps = 0
        else:
            gptr, gp, gt, gf = pending_by_position(csr)
            counts = np.diff(gptr)
            edge_of = np.repeat(np.arange(E, dtype=np.int64), counts)
            per_shard = np.bincount(self.shard_of[edge_of], minlength=S)
            Pp = pending_capacity(snap, int(per_shard.max(initial=1)))
            pptr = np.zeros((S, El + 1), np.int64)
            pp = np.zeros((S, Pp))
            pt = np.full((S, Pp), np.inf)
            pf = np.zeros((S, Pp, N_COMBOS, K))
            for s, o in enumerate(self._owned):
                sel = np.nonzero(self._own_mask[s][edge_of])[0]
                k = len(sel)
                pp[s, :k] = gp[sel]
                pt[s, :k] = gt[sel]
                pf[s, :k] = gf[sel]
                cl = np.zeros(El, np.int64)
                cl[: len(o)] = counts[o]
                np.cumsum(cl, out=pptr[s, 1:])
            pack.pend_steps = int(counts.max(initial=1))
        with self._precision():
            pack.tables = dict(
                pend_ptr=self._shard_put(pptr),
                pend_pos=self._shard_put(pp),
                pend_time=self._shard_put(_slab_keys(pt)),
                pend_phi=self._shard_put(feature_major(pf)),
            )
        pack.nbytes = _device_nbytes(pack.tables)
        return pack

    def _forest(self, sealed: _ShardedSealed, pend: _ShardedPend):
        from .jax_engine import FlatDynamicForest

        return FlatDynamicForest(**sealed.tables, **pend.tables)

    # ------------------------------------------------------------ per query
    def window_tables(self, wb, ts_key, snap, sealed: _ShardedSealed, hq: int,
                      exact: bool):
        """Sharded window tables for (ts, structure epoch, hq, mode), LRU.

        Same builders (`dyn_node_tables` / `dyn_window_tables`) as the
        single-host engine, run per shard over the shard-local CSRs."""
        key = (ts_key, snap.revision, snap.depth, int(hq), bool(exact), self._mesh_key)
        hit = self._tab_cache.get(key)
        if hit is not None:
            self._tab_cache.move_to_end(key)
            return hit

        def steps(occ):
            return max(int(np.ceil(np.log2(int(occ) + 1))) + 1, 1)

        W = len(ts_key)
        forest = self._forest(sealed, self._get_pending(snap))
        with self._precision():
            # only the active branch's trip counts enter the jit key — a
            # seal that moves an occupancy the other mode reads must not
            # recompile this one (mirrors the single-host engine, which
            # passes each builder only its own static)
            tabs = (self._progs["dyn_tables"](
                forest, wb,
                n_levels=sealed.n_levels, hq=int(hq),
                search_steps=1 if exact else steps(sealed.max_occ[hq]),
                steps_per_level=(
                    tuple(steps(o) for o in sealed.max_occ[: hq + 1])
                    if exact else ()
                ),
                exact=bool(exact),
            ),)
        nn = self.El * (((1 << (hq + 1)) - 1) if exact else (1 << hq)) * self.n_shards
        self.counters["rank_searches"] += 3 * W * nn
        self.counters["moment_gathers"] += 3 * W * nn
        self._tab_cache[key] = tabs
        while len(self._tab_cache) > 4 * self.max_snapshots:
            self._tab_cache.popitem(last=False)
        return tabs

    def _atom_packs(self, plan):
        """Sharded device atom blocks for a HostPlan (local edge ids)."""
        key = (plan.key, self._mesh_key)
        hit = self._pack_cache.get(key)
        if hit is not None:
            return hit
        packs = []
        for atoms in plan.blocks:
            fields = route_atoms_by_shard(
                atoms, self.shard_of, self.edge_slot, self.n_shards
            )
            packs.append(dict(fa=self._upload_fa(fields), atoms=atoms, m=atoms.m))
        self._pack_cache.put(key, packs)
        return packs

    def _leaf_pack(self, entry, snap, hq: int):
        """Host-resolved leaf bounds of one atom block at depth ``hq``
        (``drfs.leaf_bounds``), routed like its atoms: [S, Mp, 4], cached
        per (plan block, hq)."""
        key = ("leaves", int(hq))
        hit = entry.get(key)
        if hit is None:
            atoms = entry["atoms"]
            lb = snap.leaf_bounds(atoms, hq).astype(np.int32)
            routed = route_atoms_by_shard(
                atoms, self.shard_of, self.edge_slot, self.n_shards,
                pad_to=entry["fa"].valid.shape[1], extra={"leaves": lb},
            )["leaves"]
            with self._precision():
                hit = entry[key] = self._shard_put(routed)
        return hit

    def flush_plan(self, heat, plan, wb, ts_key, *, h0=None, exact_leaf=False,
                   snapshot=None, **_):
        """heat[L, W] += every atom block, snapshot-consistent, collective."""
        if plan.n_atoms == 0:
            return heat
        snap = snapshot if snapshot is not None else self.df.snapshot()
        sealed = self._get_sealed(snap)
        pend = self._get_pending(snap)
        hq = snap.depth if h0 is None else min(int(h0), snap.depth)
        scan_steps = 0
        if exact_leaf:
            occ = int(sealed.max_occ[hq])
            scan_steps = -(-occ // 8) * 8 if occ else 0
        W = heat.shape[1]
        tables = self.window_tables(wb, ts_key, snap, sealed, hq, bool(exact_leaf))
        forest = self._forest(sealed, pend)
        for entry in self._atom_packs(plan):
            atoms = entry["atoms"]
            snap.counters["pending"] += snap.pending_scan_pairs(atoms) * W
            if exact_leaf:
                snap.counters["partial"] += snap.partial_scan_pairs(atoms, hq) * 2 * W
            self.counters["moment_gathers"] += (
                2 * (hq + 1) * entry["m"] if exact_leaf else 2 * entry["m"]
            )
            leaves = self._leaf_pack(entry, snap, hq)
            heat = self._psum_flush(
                self._progs["dyn_flush"],
                forest, entry["fa"], wb, tables, leaves, heat,
                n_levels=sealed.n_levels, hq=int(hq),
                scan_steps=int(scan_steps), pend_steps=int(pend.pend_steps),
                exact=bool(exact_leaf),
            )
        return heat
