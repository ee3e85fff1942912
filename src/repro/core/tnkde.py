"""TN-KDE driver (paper Algorithm 1 + Algorithm 5).

Ties the pieces together: lixelization, SPS shortest-path sharing, candidate
pruning, Lixel Sharing classification, atom planning, and one of the four
solutions:

  solution='sps'   index-free direct evaluation            (§3.2 baseline)
  solution='ada'   per-window linear index                 (§3.2, SOTA)
  solution='rfs'   range forest (static, exact)            (§4)
  solution='drfs'  dynamic range forest (streaming, ~exact) (§5)

``query(ts)`` answers a *batch* of online time windows (the paper's multiple
temporal KDE scenario, §8.2): build once, query many.

The per-edge loop batches atoms across query edges and flushes them through
the index in large vectorized blocks — the same batching the distributed
(shard_map) and Pallas paths use.

``engine`` selects the flush backend for the forest solutions (DESIGN.md
§4/§5/§7):

  engine='jax'    window-batched jit'd flat engine, all W windows per flush,
                  device-resident [W, L] heatmap (the default when available).
                  rfs -> rfs.FlatForestEngine (static merge tree);
                  drfs -> rfs.FlatDynamicEngine (streaming bisection tree:
                  insert/seal/extend re-pack lazily, pending events are
                  scanned on device so insert -> query never rebuilds)
  engine='pallas' same engines, tree phase routed through the Pallas kernels
  engine='numpy'  the host reference path (one eval_atoms pass per window)
  engine='auto'   'jax' for rfs/drfs; 'numpy' otherwise, or when JAX is not
                  installed (any other engine failure raises)

``executor`` picks the jnp executor flavour over the packed query plan:
'packed' (gather-lean default), 'cascade' / 'search' (the legacy rfs
decompositions), 'pallas' (same as engine='pallas'), 'fused' (ONE Pallas
launch per flush: the whole canonical walk + window contraction in-kernel,
DESIGN.md §12 — pairs with ``table_codec``). Every query reuses the plan
cached for its (epoch, LS) pair — warm queries skip planning entirely —
and window-side tables cached by the ts tuple (DESIGN.md §7).

``table_codec`` picks the device window-table layout (jax_engine.TableCodec):
'auto'/'f64' keeps the device float (f64 on CPU, f32 on a TPU —
``compat.device_x64``); 'f32'/'bf16' shrink the rows the
per-atom walk gathers (build-time validated, falls back to f64 when the
round-trip exceeds the preset tolerance). QueryStats.bytes_moved measures
the effect in the same units for every executor.

``mesh`` shards the forest index across the mesh's ``shard_axes``
(DESIGN.md §3): the same packed executors run per shard under shard_map
with a psum of the heatmap, so sharded == single-host to summation-order
noise, index memory per device scales ~1/shards (``QueryStats.
bytes_per_shard``), and streaming DRFS mutation (insert/seal/extend)
works unchanged. rfs/drfs only; the packed executor only. ``shards`` states
the same as a number, for a deployment that cannot carry a ``Mesh``:
``shards > 1`` without a mesh builds ``compat.host_mesh(shards)`` over the
first ``shards`` local devices; with a mesh it must be 1 or the mesh's
extent over ``shard_axes``.
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import List, Optional, Sequence

import numpy as np

from .ada import AggregateDistanceIndex
from .aggregation import build_event_moments
from .drfs import DynamicRangeForest
from .events import (
    EventCountsView,
    Events,
    group_events_by_edge,
    ragged_arange,
    validate_events,
)
from .kernels_math import get_kernel
from .lixel_sharing import dominated_sweep
from .network import RoadNetwork, build_lixels
from .plan import build_edge_geometry
from .rfs import RangeForest
from .shortest_path import adjacency_csr, bounded_dijkstra
from .sps import sps_eval_edge
from . import wal as _wal
from ..spans import span

__all__ = ["TNKDE", "PendingQuery", "QueryStats"]


@dataclasses.dataclass
class QueryStats:
    build_seconds: float = 0.0
    query_seconds: float = 0.0
    n_atoms: int = 0
    n_pairs_dominated: int = 0
    n_pairs_out: int = 0
    n_pairs_normal: int = 0
    index_bytes: int = 0
    # DRFS streaming work that the index answers *outside* the tree walk —
    # (atom, event) pairs examined by the pending-buffer scans and by the
    # exact-mode partial-leaf scans. Without these the reported work of a
    # streaming query is misleadingly low (the scans are the O(n) fallback
    # the geometric seal keeps amortized).
    n_pending_scanned: int = 0
    n_partial_scanned: int = 0
    # device-engine op accounting (the packed-plan hoist invariants,
    # DESIGN.md §7): time-boundary binary-search problems solved, prefix/node
    # moment rows gathered, and leaves folded by the dense packed table
    # build (W per leaf and window batch). Table work scales with the index
    # (leaves or nodes; zero on a warm plan hit), never with atoms; the
    # packed build searches nothing, and the packed walk gathers one paired
    # node row per (level, atom).
    n_rank_searches: int = 0
    n_moment_gathers: int = 0
    n_table_leaves: int = 0
    # analytic memory-traffic model of the gathers above: gather count ×
    # gathered-row bytes, same units for every engine/executor, so the
    # fused+codec tier's bytes-per-query claim is measured, not asserted.
    # Compressed TableCodec layouts show up directly (smaller row bytes).
    bytes_moved: int = 0
    # device bytes each participating device holds (index tables + cached
    # packed plans). Single-host engines report their full device footprint
    # (one shard); the sharded engines report one slab — the MEASURED form
    # of the 1/devices memory-scaling claim (DESIGN.md §3).
    bytes_per_shard: int = 0
    # sharded engines only (DESIGN.md §3): padded local edge slots over all
    # shards and the largest shard's own edges (the slab layout; every
    # shard's table build runs over the largest's slots), and the heat-delta
    # bytes the collective flushes psum'd
    shard_edge_slots: int = 0
    shard_edges_max: int = 0
    psum_bytes: int = 0


def _resolve_mesh(mesh, shard_axes: Sequence[str], shards: int):
    """The mesh a model shards over: ``mesh`` as given (``shards`` must then
    be 1 or its extent over ``shard_axes``), else one over the first
    ``shards`` local devices when ``shards > 1``, else None. Never falls
    back to fewer devices than stated."""
    shards = int(shards)
    if shards < 1:
        raise ValueError(f"shards must be at least 1, got {shards}")
    if mesh is not None:
        extent = int(np.prod([mesh.shape.get(a, 1) for a in shard_axes]))
        if shards not in (1, extent):
            raise ValueError(
                f"shards={shards} disagrees with the mesh's {extent} shards "
                f"over {tuple(shard_axes)}"
            )
        return mesh
    if shards == 1:
        return None
    from ..compat import host_mesh

    return host_mesh(shards, shard_axes)


class TNKDE:
    def __init__(
        self,
        net: RoadNetwork,
        events: Events,
        *,
        g: float = 10.0,
        b_s: float = 1000.0,
        b_t: float = 86400.0,
        spatial_kernel: str = "triangular",
        temporal_kernel: str = "triangular",
        solution: str = "rfs",
        engine: str = "auto",
        executor: str = "auto",
        table_codec: str = "auto",
        mesh=None,
        shard_axes: Sequence[str] = ("data",),
        shards: int = 1,
        lixel_sharing: bool = False,
        cascade: bool = True,
        drfs_depth: int = 8,
        drfs_h0: Optional[int] = None,
        drfs_exact_leaf: bool = False,
        auto_seal: bool = True,
        horizon_s: Optional[float] = None,
        edge_block: int = 128,
        atom_flush: int = 400_000,
    ):
        if solution not in ("sps", "ada", "rfs", "drfs"):
            raise ValueError(f"unknown solution {solution!r}")
        if engine not in ("auto", "numpy", "jax", "pallas"):
            raise ValueError(f"unknown engine {engine!r}")
        if engine in ("jax", "pallas") and solution not in ("rfs", "drfs"):
            raise ValueError(
                "engine='jax'/'pallas' accelerates the forest flush "
                "(solution='rfs'/'drfs')"
            )
        if executor not in ("auto", "packed", "search", "cascade", "pallas", "fused"):
            raise ValueError(f"unknown executor {executor!r}")
        if solution == "drfs" and executor in ("search", "cascade"):
            raise ValueError("search/cascade executors are rfs-only")
        if table_codec not in ("auto", "f64", "f32", "bf16"):
            raise ValueError(f"unknown table_codec {table_codec!r}")
        mesh = _resolve_mesh(mesh, tuple(shard_axes), shards)
        if mesh is not None:
            if solution not in ("rfs", "drfs"):
                raise ValueError("mesh= shards the forest indexes (rfs/drfs)")
            if engine in ("numpy", "pallas") or executor in (
                "search", "cascade", "pallas", "fused"
            ):
                raise ValueError(
                    "the sharded path runs the packed jnp executor "
                    "(engine='jax'/'auto', executor='packed'/'auto')"
                )
            if table_codec not in ("auto", "f64"):
                raise ValueError(
                    "the sharded path keeps uncompressed slabs "
                    "(table_codec='auto'/'f64')"
                )
        if lixel_sharing and solution == "sps":
            raise ValueError("lixel sharing needs an aggregation index (ada/rfs/drfs)")
        if horizon_s is not None:
            if solution != "drfs":
                raise ValueError("horizon_s= (sliding time horizon) requires solution='drfs'")
            horizon_s = float(horizon_s)
            if not horizon_s > 0.0:
                raise ValueError(f"horizon_s must be positive, got {horizon_s!r}")
        if not auto_seal and solution != "drfs":
            raise ValueError("auto_seal=False requires solution='drfs'")
        t0 = _time.perf_counter()
        self.net = net
        self.g = g
        self.solution = solution
        self.ls = lixel_sharing
        self.cascade = cascade
        self.drfs_h0 = drfs_h0
        self.drfs_exact_leaf = drfs_exact_leaf
        self.auto_seal = bool(auto_seal)
        self.horizon_s = horizon_s
        self.edge_block = edge_block
        self.atom_flush = atom_flush
        self.lix = build_lixels(net, g)
        self.ee = group_events_by_edge(net, events)
        ks = get_kernel(spatial_kernel)
        kt = get_kernel(temporal_kernel)
        self.ctx, phi = build_event_moments(net, self.ee, ks, kt, b_s, b_t)
        self.index = None
        if solution == "rfs":
            self.index = RangeForest(net, self.ee, self.ctx, phi, build_bridges=cascade)
        elif solution == "drfs":
            self.index = DynamicRangeForest(
                net, self.ee, self.ctx, phi, depth=drfs_depth, auto_seal=auto_seal
            )
        elif solution == "ada":
            self.index = AggregateDistanceIndex(net, self.ee, self.ctx)
        self._phi_dim = phi.shape[-1] if phi.size else self.ctx.K
        # ---- engine resolution: promote the jit'd flat engines -------------
        # engine='pallas' (or executor='pallas') routes the tree phase of
        # every flush through the Pallas kernels; the jnp executors are the
        # packed-plan default (DESIGN.md §7). The requested pair is kept so
        # the engine can be REBUILT over a mutated index (restore()) or
        # tripped down the degradation ladder (degrade(), DESIGN.md §8).
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes)
        self._engine_req = engine
        self._executor_req = executor
        self.table_codec = table_codec
        self._build_engine()
        # cumulative consumption cursors over the index/engine work counters
        # (see _consume_counters); survives engine rebuilds — the consume
        # guard resets a cursor whose counter object was replaced
        self._counter_cursor: dict = {}
        # ---- durability: WAL hookup + config identity (DESIGN.md §8) -------
        self._wal = None  # attach_wal(); logged-before-mutation when set
        self._replaying = False  # replay must not re-log its own records
        self._ckpt_step = 0
        self._fingerprint = dict(
            solution=solution,
            g=float(g),
            b_s=float(b_s),
            b_t=float(b_t),
            spatial_kernel=spatial_kernel,
            temporal_kernel=temporal_kernel,
            drfs_depth=int(drfs_depth),
            drfs_h0=drfs_h0,
            drfs_exact_leaf=bool(drfs_exact_leaf),
            # replay determinism: auto-seal timing and the eviction cutoff
            # both depend on these, so a restore under different settings
            # must be rejected, not silently diverge
            auto_seal=bool(auto_seal),
            horizon_s=horizon_s,
            n_edges=int(net.n_edges),
            n_lixels=int(self.lix.n_lixels),
            n_base_events=int(self.ee.n),
        )
        self._adj = adjacency_csr(net)
        # per-edge event extremes for window-independent LS classification
        E = net.n_edges
        self.ev_min_pos = np.full(E, np.inf)
        self.ev_max_pos = np.full(E, -np.inf)
        counts = np.diff(self.ee.ptr)
        eo = np.repeat(np.arange(E), counts)
        if self.ee.n:
            np.minimum.at(self.ev_min_pos, eo, self.ee.pos)
            np.maximum.at(self.ev_max_pos, eo, self.ee.pos)
        self.stats = QueryStats(build_seconds=_time.perf_counter() - t0)
        if self.index is not None and hasattr(self.index, "index_bytes"):
            self.stats.index_bytes = self.index.index_bytes

    def _build_engine(self) -> None:
        """(Re)bind the flush engine + plan cache for the current
        ``(engine, executor)`` request. Used at construction, by ``restore``
        (fresh device/pack caches over the restored index state) and by
        ``degrade`` (ladder trips); always leaves ``engine``/``_fe``/
        ``_plan_cache`` consistent."""
        engine, executor = self._engine_req, self._executor_req
        solution = self.solution
        self.engine = "numpy"
        self._fe = None
        if engine == "pallas":
            executor = "pallas"
        if self.mesh is not None:
            # sharding is explicit: never fall back silently to one host
            from .distributed import ShardedDynamicEngine, ShardedForestEngine

            self._fe = (
                ShardedForestEngine(self.index, self.mesh, self.shard_axes)
                if solution == "rfs"
                else ShardedDynamicEngine(self.index, self.mesh, self.shard_axes)
            )
            self.engine = "jax"
        elif solution in ("rfs", "drfs") and engine != "numpy":
            try:
                import jax  # noqa: F401
            except ImportError:
                if engine != "auto":
                    raise
                # engine='auto' without JAX installed: the host path is the
                # only one there is. Every other failure raises — a fallback
                # that hides a broken device path reports host numbers as
                # device ones.
                import warnings

                warnings.warn("jax is not installed, using the numpy path")
            else:
                from .rfs import FlatDynamicEngine, FlatForestEngine

                self._fe = (
                    FlatForestEngine(
                        self.index, executor=executor, codec=self.table_codec
                    )
                    if solution == "rfs"
                    else FlatDynamicEngine(
                        self.index,
                        executor=executor
                        if executor in ("pallas", "fused")
                        else "packed",
                        codec=self.table_codec,
                    )
                )
                self.engine = "pallas" if executor == "pallas" else "jax"
        from .query_plan import PlanCache

        self._plan_cache = PlanCache(2)

    def degrade(self) -> Optional[str]:
        """Trip one rung down the executor degradation ladder
        ``pallas → jax/packed → numpy`` (DESIGN.md §8).

        Returns the new ``engine_desc``, or ``None`` when already at the
        numpy floor. The serve tier calls this after repeated engine
        faults: queries keep answering on the next rung (the host path
        consumes the same packed plans and MVCC snapshots), trading speed
        for availability instead of failing the profile outright. Sharded
        engines fall back to the single-host packed executor first.
        """
        if self._fe is None:
            return None
        if self.mesh is not None:
            self.mesh = None
            self._engine_req, self._executor_req = "jax", "packed"
        elif self.engine == "pallas" or getattr(self._fe, "executor", None) == "fused":
            # kernel-launch rungs (pallas, fused) fall back to the jnp
            # packed walk before abandoning the device entirely
            self._engine_req, self._executor_req = "jax", "packed"
        else:
            self._engine_req, self._executor_req = "numpy", "auto"
        try:
            self._build_engine()
        except Exception:
            # a fallback rung that cannot even build lands on the floor
            self._engine_req, self._executor_req = "numpy", "auto"
            self._build_engine()
        return self.engine_desc

    # ------------------------------------------------------------------ API
    @property
    def n_lixels(self) -> int:
        return self.lix.n_lixels

    @property
    def engine_desc(self) -> str:
        """Human-readable backend/executor that actually answers queries,
        e.g. ``'jax/packed'``, ``'pallas/pallas'`` or ``'numpy'`` — what
        benchmarks and examples print so auto-resolution is never silent.
        Sharded engines append ``@shards=N`` (the mesh data-axis extent)."""
        if self._fe is None:
            return "numpy"
        desc = f"{self.engine}/{self._fe.executor}"
        n_shards = getattr(self._fe, "n_shards", 1)
        if self.mesh is not None:
            desc += f"@shards={n_shards}"
        return desc

    @property
    def epoch(self):
        """(revision, pend_revision) of the index — (0, 0) for static ones."""
        if self.index is not None and hasattr(self.index, "epoch"):
            return self.index.epoch
        return (0, 0)

    def snapshot(self):
        """Pin the current index state as an immutable read handle (MVCC).

        For the streaming DRFS index this returns a :class:`drfs.DrfsSnapshot`
        that ``query(ts, at=snap)`` evaluates against, so inserts and seals
        issued after the pin are invisible to the query — the serving
        subsystem (``repro.serve``) pins one per request at admission.
        Static indices (rfs/ada) are immutable, so the handle is ``None``
        and ``at=None`` reads the index directly.
        """
        if self.index is not None and hasattr(self.index, "snapshot"):
            return self.index.snapshot()
        return None

    # ------------------------------------------------- planner event view
    @property
    def ee(self):
        """The planner's per-edge event view (candidate pruning, self-edge
        flags). Construction and restore bind full payload views
        (:class:`EdgeEvents`); streaming inserts/evictions only dirty the
        per-edge *counts*, and the view is lazily refreshed in O(E) as a
        :class:`EventCountsView` — never the O(N log N) full re-merge that
        made a T-insert stream O(T²). Payloads live in the index; LS
        extremes live in ``ev_min_pos``/``ev_max_pos``."""
        if self._ee_dirty:
            ptr = np.zeros(self.net.n_edges + 1, np.int64)
            np.cumsum(self._ev_counts, out=ptr[1:])
            self._ee = EventCountsView(ptr=ptr, t_min=self._ee_tmin, t_max=self._ee_tmax)
            self._ee_dirty = False
        return self._ee

    @ee.setter
    def ee(self, value) -> None:
        self._ee = value
        self._ev_counts = np.diff(value.ptr).astype(np.int64)
        self._ee_tmin = float(value.t_min)
        self._ee_tmax = float(value.t_max)
        self._ee_dirty = False

    @property
    def stream_t_max(self) -> float:
        """Largest event timestamp seen so far — the stream clock
        ``compact()`` resolves the horizon cutoff against when the caller
        does not supply wall time."""
        return self._ee_tmax

    def insert(self, events: Events) -> None:
        """Streaming insertion (DRFS only, §5), vectorized over the batch.

        The whole batch is one O(batch) step: validation, a single WAL
        append, one φ-moment pass, one DRFS pending append, and incremental
        per-dirty-edge planner updates (count bumps + extreme min/max) —
        no per-event host work and no full planner rebuild.

        Invalid batches (bad edge id, out-of-range position, non-finite
        time) raise :class:`EventValidationError` **before** the WAL append
        and before any in-memory mutation, so a rejected batch leaves the
        log, the index and the planner untouched. With a WAL attached, the
        validated batch is fsync'd to the log before any in-memory
        mutation — a crash at any later instant replays it (DESIGN.md §8).
        """
        if self.solution != "drfs":
            raise ValueError("insert() requires solution='drfs'")
        validate_events(self.net, events)
        if self._wal is not None and not self._replaying:
            self._wal.append_insert(events)
        net = self.net
        pos = events.pos  # validated in [0, edge_len] — no silent clipping
        from .aggregation import MomentContext  # noqa: F401 (doc pointer)

        ctx = self.ctx
        lens = net.edge_len[events.edge_id]
        u_c = pos / lens
        sig = lens / ctx.b_s
        psi_c = ctx.ks.e_vec(u_c, sig)
        psi_d = ctx.ks.e_vec(1.0 - u_c, sig)
        v_l = (ctx.t_max - events.time) / ctx.t_span
        v_r = (events.time - ctx.t_min) / ctx.t_span
        tau_l = ctx.kt.e_vec(v_l, ctx.sigma_t)
        tau_r = ctx.kt.e_vec(v_r, ctx.sigma_t)
        n = events.n

        def outer(a, b):
            return (a[:, :, None] * b[:, None, :]).reshape(n, -1)

        phi = np.stack(
            [outer(psi_c, tau_l), outer(psi_c, tau_r), outer(psi_d, tau_l), outer(psi_d, tau_r)],
            axis=1,
        )
        self.index.insert(events.edge_id.astype(np.int64), pos, events.time, phi)
        # incremental planner update: O(batch) count/extreme bumps on the
        # dirty edges only — the counts view refreshes lazily in O(E)
        if n:
            np.add.at(self._ev_counts, events.edge_id, 1)
            tmin = float(events.time.min())
            tmax = float(events.time.max())
            if int(self._ev_counts.sum()) == n:  # first events ever seen
                self._ee_tmin, self._ee_tmax = tmin, tmax
            else:
                self._ee_tmin = min(self._ee_tmin, tmin)
                self._ee_tmax = max(self._ee_tmax, tmax)
            self._ee_dirty = True
            np.minimum.at(self.ev_min_pos, events.edge_id, pos)
            np.maximum.at(self.ev_max_pos, events.edge_id, pos)

    # --------------------------------------------- background compaction
    @property
    def needs_compaction(self) -> bool:
        """True when a ``compact()`` would do useful work: the geometric
        pending/sealed ratio crossed the seal threshold, or (with a
        horizon) events have expired. Cheap — the serve tier polls this
        between batches to schedule compaction off the insert/query path."""
        if self.solution != "drfs":
            return False
        if self.index.needs_seal:
            return True
        if self.horizon_s is not None and self.index.n_sealed + self.index.n_pending:
            return self._ee_tmin < self._ee_tmax - self.horizon_s
        return False

    def compact(self, t_now: Optional[float] = None) -> dict:
        """One background-compaction step: evict expired events (sliding
        horizon), then seal the pending buffers into the tree.

        Runs *off* the insert path (with ``auto_seal=False`` insert never
        seals) and off the query path (MVCC: pinned snapshots keep
        answering over the pre-compaction arrays). ``t_now`` resolves the
        horizon cutoff ``t_now - horizon_s``; default is the stream clock
        ``stream_t_max``. Eviction is NOT a pure function of event counts,
        so — unlike the count-triggered auto-seal — it is WAL-logged as an
        explicit EVICT record (carrying the resolved ``t_now``) before it
        applies; the seal is logged as usual. Returns
        ``{"evicted": n, "sealed": n}``.
        """
        if self.solution != "drfs":
            raise ValueError("compact() requires solution='drfs'")
        out = {"evicted": 0, "sealed": 0}
        if self.horizon_s is not None:
            t_now = self._ee_tmax if t_now is None else float(t_now)
            # log only evictions that remove something: _ee_tmin is exact
            # (recomputed after every eviction), so this never misses — and
            # a logged record always replays to the identical state
            if self._ee_tmin < t_now - self.horizon_s and (
                self.index.n_sealed + self.index.n_pending
            ):
                if self._wal is not None and not self._replaying:
                    self._wal.append_evict(t_now)
                out["evicted"] = self._apply_evict(t_now)
        if self.index.n_pending:
            out["sealed"] = self.index.n_pending
            self.seal()
        if out["evicted"] and self._fe is not None and hasattr(self._fe, "release_stale"):
            # drop device packs for pre-eviction epochs promptly so a
            # horizon-bounded run's device footprint plateaus
            self._fe.release_stale(self.index.epoch)
        return out

    def _apply_evict(self, t_now: float) -> int:
        """Apply (never log) the eviction for resolved stream time
        ``t_now`` — called by ``compact`` after logging, and by WAL replay
        for each EVICT record. Updates the planner's counts and per-edge
        extremes exactly for the touched edges, so post-eviction LS
        classification stays exact (stale-wide extremes would only be
        conservative, but exact keeps replay state identical)."""
        cutoff = float(t_now) - self.horizon_s
        idx = self.index
        removed = idx.evict_before(cutoff)
        if removed is None:
            return 0
        self._ev_counts -= removed
        self._ee_dirty = True
        # recompute extremes for touched edges from the surviving events
        touched = np.nonzero(removed)[0]
        self.ev_min_pos[touched] = np.inf
        self.ev_max_pos[touched] = -np.inf
        cnts = np.diff(idx.ptr)
        sl = ragged_arange(idx.ptr[touched], cnts[touched])
        eo = np.repeat(touched, cnts[touched])
        np.minimum.at(self.ev_min_pos, eo, idx.pos[sl])
        np.maximum.at(self.ev_max_pos, eo, idx.pos[sl])
        t_lo = float(idx.time.min()) if idx.n_sealed else np.inf
        pcsr = idx.pending_csr()
        if pcsr is not None:
            pptr, pp, pt, _ = pcsr
            pe = np.repeat(np.arange(self.net.n_edges, dtype=np.int64), np.diff(pptr))
            m = removed[pe] > 0
            np.minimum.at(self.ev_min_pos, pe[m], pp[m])
            np.maximum.at(self.ev_max_pos, pe[m], pp[m])
            t_lo = min(t_lo, float(pt.min()))
        # advance the exact lower stream bound so needs_compaction / the
        # next compact() gate correctly (never stale-high)
        self._ee_tmin = t_lo if np.isfinite(t_lo) else self._ee_tmax
        return int(removed.sum())

    # ------------------------------------------- durability (DESIGN.md §8)
    def attach_wal(self, wal) -> None:
        """Log every subsequent mutation (``insert``/``seal``/``extend``) to
        ``wal`` before it takes effect in memory. DRFS only — the static
        solutions have no mutations to log."""
        if self.solution != "drfs":
            raise ValueError("attach_wal() requires solution='drfs'")
        self._wal = wal

    def seal(self) -> None:
        """Explicit seal, durably logged when a WAL is attached. The
        *automatic* geometric seal inside ``index.insert`` is intentionally
        not logged: its trigger is a pure function of event counts, so
        replaying the logged inserts re-fires it at the same points."""
        if self.solution != "drfs":
            raise ValueError("seal() requires solution='drfs'")
        if self._wal is not None and not self._replaying:
            self._wal.append_marker(_wal.KIND_SEAL)
        self.index.seal()

    def extend(self) -> None:
        """Add one index depth level (Algorithm 4), durably logged."""
        if self.solution != "drfs":
            raise ValueError("extend() requires solution='drfs'")
        if self._wal is not None and not self._replaying:
            self._wal.append_marker(_wal.KIND_EXTEND)
        self.index.extend()

    def checkpoint(
        self,
        ckpt_dir: str,
        *,
        step: Optional[int] = None,
        keep_last: int = 3,
        blocking: bool = True,
    ) -> int:
        """Persist the sealed index through the atomic-COMMIT checkpoint
        layout (``repro.ckpt``); returns the step written.

        Seals first (logged, so a crash *during* the save still replays
        consistently from the previous checkpoint), snapshots the index
        state tree plus the planner's per-edge extremes, then — once the
        save committed — rotates the WAL and prunes segments the new
        checkpoint fully covers. With ``blocking=False`` the arrays are
        captured by reference (safe: MVCC rebinds, never overwrites) and
        written on a worker thread; rotation still happens now, pruning is
        deferred to the next blocking checkpoint.
        """
        if self.solution != "drfs":
            raise ValueError("checkpoint() requires solution='drfs'")
        from ..ckpt import save_checkpoint

        th = getattr(self, "_ckpt_thread", None)
        if th is not None:
            th.join()
            self._ckpt_thread = None
        self.seal()
        if step is not None:
            seq = int(step)  # coordinated checkpoint: the server picks the seq
        elif self._wal is not None:
            seq = self._wal.last_seq
        else:
            seq = self._ckpt_step + 1
        tree = self.index.state_tree()
        extras = {
            "seq": int(seq),
            "depth": int(self.index.depth),
            "revision": int(self.index.revision),
            "pend_revision": int(self.index.pend_revision),
            "ee_t_min": float(self._ee_tmin),
            "ee_t_max": float(self._ee_tmax),
            "n_events": int(self.index.n_sealed),
            "fingerprint": self._fingerprint,
        }
        self._ckpt_thread = save_checkpoint(
            ckpt_dir, seq, tree, extras=extras, blocking=blocking, keep_last=keep_last
        )
        self._ckpt_step = seq
        if self._wal is not None:
            self._wal.rotate()
            if blocking:
                self._wal.prune(seq)
        return seq

    def restore(self, ckpt_dir=None, *, wal=None, attach: bool = True):
        """Crash recovery: rebind the latest committed checkpoint (if any),
        then replay the WAL suffix past its sequence number.

        Call on a freshly-constructed model with the *same* configuration
        and base events as the crashed process — enforced via a config
        fingerprint stored in the checkpoint. With no committed checkpoint
        the whole log replays against the seed state. ``attach=True`` keeps
        logging to ``wal`` afterwards, so the recovered process is itself
        durable. Returns a :class:`repro.core.wal.RecoveryReport`.
        """
        if self.solution != "drfs":
            raise ValueError("restore() requires solution='drfs'")
        t0 = _time.perf_counter()
        step = None
        seq0 = 0
        arrays = None
        if ckpt_dir is not None:
            from ..ckpt import load_checkpoint_arrays

            try:
                arrays, step, extras = load_checkpoint_arrays(ckpt_dir)
            except FileNotFoundError:
                arrays = None  # crashed before the first checkpoint committed
        if arrays is not None:
            fp = extras.get("fingerprint")
            if fp != self._fingerprint:
                raise ValueError(
                    "checkpoint fingerprint mismatch: the checkpoint was taken "
                    f"under a different configuration ({fp!r} != "
                    f"{self._fingerprint!r})"
                )
            # load_checkpoint_arrays keys by jax keystr: "['ptr']" -> "ptr"
            tree = {k[2:-2]: v for k, v in arrays.items()}
            self.index.load_state(
                tree,
                depth=extras["depth"],
                revision=extras["revision"],
                pend_revision=extras["pend_revision"],
            )
            # the sealed index arrays ARE the canonical (edge, time)-sorted
            # event set — rebind the planner's view from them by reference
            from .events import EdgeEvents

            self.ee = EdgeEvents(
                ptr=self.index.ptr,
                pos=self.index.pos,
                time=self.index.time,
                t_min=float(extras["ee_t_min"]),
                t_max=float(extras["ee_t_max"]),
            )
            E = self.net.n_edges
            self.ev_min_pos = np.full(E, np.inf)
            self.ev_max_pos = np.full(E, -np.inf)
            eo = np.repeat(np.arange(E), np.diff(self.index.ptr))
            if self.index.n_sealed:
                np.minimum.at(self.ev_min_pos, eo, self.index.pos)
                np.maximum.at(self.ev_max_pos, eo, self.index.pos)
            seq0 = int(extras["seq"])
            self._ckpt_step = step
            self._build_engine()  # fresh pack/plan caches over restored state
        report = _wal.RecoveryReport(
            restored_step=step,
            from_seq=seq0,
            to_seq=seq0,
            n_truncated_bytes=wal.truncated_bytes if wal is not None else 0,
            restore_seconds=_time.perf_counter() - t0,
        )
        if wal is not None:
            t1 = _time.perf_counter()
            self._replaying = True
            try:
                for rec in wal.records(after_seq=seq0):
                    if rec.kind == _wal.KIND_INSERT:
                        self.insert(rec.events)
                        report.n_events += rec.events.n
                    elif rec.kind == _wal.KIND_SEAL:
                        self.index.seal()
                    elif rec.kind == _wal.KIND_EVICT:
                        # the record carries the resolved stream time; each
                        # model applies its own horizon cutoff (a server-level
                        # log serves heterogeneous per-profile horizons, and
                        # horizon-less models no-op deterministically)
                        if self.horizon_s is not None:
                            report.n_evicted += self._apply_evict(rec.t_now)
                    else:
                        self.index.extend()
                    report.n_records += 1
                    report.to_seq = rec.seq
            finally:
                self._replaying = False
            report.replay_seconds = _time.perf_counter() - t1
            if attach:
                self._wal = wal
        return report

    def edge_geometries(self):
        """Yield the window-independent EdgeGeometry of every query edge with
        at least one lixel — the planning loop shared by the single-host and
        distributed paths (SPS rows are computed per edge block)."""
        net, lix, ee, ctx = self.net, self.lix, self.ee, self.ctx
        E = net.n_edges
        radius = ctx.b_s + float(net.edge_len.max()) + 1.0
        for blk_lo in range(0, E, self.edge_block):
            blk = np.arange(blk_lo, min(blk_lo + self.edge_block, E))
            verts = np.unique(
                np.concatenate([net.edge_src[blk], net.edge_dst[blk]])
            )
            rows = bounded_dijkstra(net, verts, radius, adj=self._adj)
            vmap = {int(v): i for i, v in enumerate(verts)}
            for a in blk:
                ra = rows[vmap[int(net.edge_src[a])]]
                rb = rows[vmap[int(net.edge_dst[a])]]
                geom = build_edge_geometry(
                    net, lix, ee, int(a), ctx.b_s, np.stack([ra, rb])
                )
                if geom.x.shape[0]:
                    yield geom

    def _host_plan(self, snap):
        """The window-independent packed query plan for the pinned epoch.

        One planning walk (Dijkstra + geometry + atoms + LS classification)
        per (epoch, LS-mode), LRU-cached — a warm query, and every serve
        batch pinned to a live epoch, skips planning entirely (DESIGN.md §7).
        """
        from .query_plan import build_host_plan

        epoch = snap.epoch if snap is not None else self.epoch
        key = (epoch, self.ls)
        plan = self._plan_cache.get(key)
        if plan is None:
            cap = (
                self.atom_flush
                if self._fe is None
                # device blocks are capped so the walk state (O(W · M) per
                # flush) stays within device memory
                else min(self.atom_flush, 200_000)
            )
            with span("tnkde.plan"):
                plan = build_host_plan(self, key, flush_cap=cap, ls=self.ls)
            self._plan_cache.put(key, plan)
        return plan

    def dispatch(self, ts: Sequence[float], *, at=None) -> "PendingQuery":
        """Begin a query asynchronously; returns a :class:`PendingQuery`.

        The host-side work — planning, window tables, atom packs — runs now,
        and the device flush is *enqueued* (jax dispatch is asynchronous), but
        the device→host transfer and the Lixel-Sharing dominated sweep are
        deferred to :meth:`PendingQuery.result`. The continuous-batching serve
        tier (DESIGN.md §10) exploits this split for double-buffered flushes:
        while the device runs batch N, the host packs and dispatches batch
        N+1, then blocks on batch N's result.

        ``at`` pins a :meth:`snapshot` exactly as in :meth:`query`; the
        pinned epoch is captured before this call returns, so overlapping
        mutations stay invisible (MVCC). Host-only paths (numpy/sps) have no
        device queue to overlap with and evaluate eagerly here; ``result()``
        is then a no-op returning the stored array.
        """
        if at is not None and self.solution != "drfs":
            raise ValueError("query(at=snapshot) requires solution='drfs'")
        ts = list(map(float, ts))
        t0 = _time.perf_counter()
        W = len(ts)
        L = self.lix.n_lixels
        F = np.zeros((W, L))
        if W == 0:
            return PendingQuery(self, ts, F)
        snap = at
        if snap is None and self.solution == "drfs":
            snap = self.index.snapshot()
        idx = snap if snap is not None else self.index
        ee, ctx = self.ee, self.ctx
        if self.solution == "sps":
            for geom in self.edge_geometries():
                sl = slice(geom.lix_base, geom.lix_base + geom.x.shape[0])
                for w, t in enumerate(ts):
                    F[w, sl] += sps_eval_edge(geom, ee, ctx, t)
            self.stats.query_seconds += _time.perf_counter() - t0
            return PendingQuery(self, ts, F)
        # ---- packed plan: atoms + dominated work, cached per epoch ---------
        plan = self._host_plan(snap)
        self.stats.n_atoms += plan.n_atoms
        self.stats.n_pairs_dominated += plan.pairs[0]
        self.stats.n_pairs_out += plan.pairs[1]
        self.stats.n_pairs_normal += plan.pairs[2]
        use_jax = self.engine in ("jax", "pallas") and self._fe is not None
        heat = None
        if use_jax:
            # all W windows ride one device pass per block; the heatmap stays
            # device-resident (and the flush merely *enqueued*) until result()
            with span("tnkde.window_batch"):
                wb = self._fe.window_batch(ctx, ts)
            with span("tnkde.enqueue"):
                heat = self._fe.new_heatmap(L, W)
                heat = self._fe.flush_plan(
                    heat, plan, wb, tuple(ts),
                    h0=self.drfs_h0,
                    exact_leaf=self.drfs_exact_leaf,
                    snapshot=snap,
                )
        else:
            for atoms in plan.blocks:
                for w, t in enumerate(ts):
                    vals = idx.eval_atoms(
                        atoms,
                        t,
                        cascade=self.cascade,
                        h0=self.drfs_h0,
                        exact_leaf_scan=self.drfs_exact_leaf,
                    ) if self.solution == "drfs" else self.index.eval_atoms(
                        atoms, t, cascade=self.cascade
                    ) if self.solution == "rfs" else self.index.eval_atoms(atoms, t)
                    np.add.at(F[w], atoms.lixel, vals)
        self.stats.query_seconds += _time.perf_counter() - t0
        return PendingQuery(self, ts, F, heat=heat, idx=idx, plan=plan,
                            use_jax=use_jax)

    def _consume_counters(self, use_jax: bool) -> None:
        """Fold the index/engine work counters into ``stats`` via cumulative
        cursors. Cursor-based (not bracketing snapshots) so overlapping
        in-flight dispatches never double-count scans into the roll-ups —
        each unit of work is consumed by exactly one ``result()``; a counter
        that *shrank* means its owner was rebuilt (restore/degrade) and the
        cursor resets with it."""
        scan = getattr(self.index, "counters", None)
        if scan is not None:
            for name, stat in (("pending", "n_pending_scanned"),
                               ("partial", "n_partial_scanned")):
                cur = int(scan[name])
                prev = self._counter_cursor.get(name, 0)
                if cur < prev:
                    prev = 0
                setattr(self.stats, stat, getattr(self.stats, stat) + cur - prev)
                self._counter_cursor[name] = cur
        if use_jax and self._fe is not None:
            eng = self._fe.counters
            for name, stat in (("rank_searches", "n_rank_searches"),
                               ("moment_gathers", "n_moment_gathers"),
                               ("table_leaves", "n_table_leaves"),
                               ("bytes_moved", "bytes_moved"),
                               ("psum_bytes", "psum_bytes")):
                if name not in eng:
                    continue
                cur = int(eng[name])
                prev = self._counter_cursor.get(name, 0)
                if cur < prev:
                    prev = 0
                setattr(self.stats, stat, getattr(self.stats, stat) + cur - prev)
                self._counter_cursor[name] = cur
            self.stats.bytes_per_shard = self._fe.bytes_per_shard
            for name in ("shard_edge_slots", "shard_edges_max"):
                if name in eng:
                    setattr(self.stats, name, int(eng[name]))

    def query(self, ts: Sequence[float], *, at=None) -> np.ndarray:
        """KDE values for every lixel, for each window center in ts: [W, L].

        ``at`` pins the query to a :meth:`snapshot` handle (DRFS only): the
        result reflects exactly the event set visible when the snapshot was
        taken, regardless of inserts/seals issued since (MVCC, DESIGN.md §6).
        Planning still walks the live event view — a superset of the
        snapshot's events, which is conservative: extra candidate atoms
        evaluate to zero against the pinned index, and the Lixel-Sharing
        domination bounds only tighten as events accrue. ``at=None`` reads
        the latest revision (one snapshot is pinned per query internally so
        a single query can never straddle a mutation).

        Equivalent to ``dispatch(ts, at=at).result()`` — the synchronous
        convenience over the async split (DESIGN.md §10).
        """
        return self.dispatch(ts, at=at).result()


class PendingQuery:
    """Handle to an in-flight :meth:`TNKDE.dispatch` (DESIGN.md §10).

    Holds the device-resident [L, W] heatmap whose flush is enqueued but not
    necessarily finished; :meth:`result` blocks on the device, applies the
    host-side Lixel-Sharing dominated sweep, folds the work counters into
    ``TNKDE.stats`` and returns the [W, L] array. Idempotent — repeated calls
    return the same materialized array. Host-path dispatches arrive here
    already evaluated and ``result()`` just returns them.
    """

    __slots__ = ("_model", "_ts", "_F", "_heat", "_idx", "_plan",
                 "_use_jax", "_done")

    def __init__(self, model, ts, F, *, heat=None, idx=None, plan=None,
                 use_jax=False):
        self._model = model
        self._ts = ts
        self._F = F
        self._heat = heat
        self._idx = idx
        self._plan = plan
        self._use_jax = use_jax
        self._done = plan is None  # W==0 / sps dispatches need no finalize

    @property
    def ts(self) -> List[float]:
        return list(self._ts)

    @property
    def done(self) -> bool:
        return self._done

    def result(self) -> np.ndarray:
        if self._done:
            return self._F
        model = self._model
        t0 = _time.perf_counter()
        if self._heat is not None:
            # the blocking device->host transfer (everything enqueued by
            # dispatch completes before the bytes land)
            with span("tnkde.transfer"):
                self._F += model._fe.to_numpy(self._heat)
            self._heat = None
        # ---- Lixel Sharing: dominated edges, batched across the network ----
        if self._plan.dominated:
            with span("tnkde.ls_sweep"):
                dominated_sweep(self._F, self._idx, model.ctx,
                                self._plan.dominated, self._ts)
        model._consume_counters(self._use_jax)
        model.stats.query_seconds += _time.perf_counter() - t0
        if model.index is not None and hasattr(model.index, "index_bytes"):
            model.stats.index_bytes = model.index.index_bytes  # ADA lazy build
        self._done = True
        self._idx = self._plan = None  # drop the snapshot/plan pins
        return self._F
