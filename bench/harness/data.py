"""Seeded replicas of the TN-KDE paper's Table-3 datasets (arXiv:2501.07106).

A copy of the program's ``repro.data.spatial`` generator, kept with the
benchmark so that no later change to the program can move the benchmark's
inputs. One change from the copy: the events' edges, their positions and
the order of their times come from the configuration's seed, the time
values from the run's (see :func:`make_events`). The OSM networks and
municipal feeds of Table 3 cannot be fetched here, so networks are
grid-perturbed graphs with Table 3's |V| and |E| (~150 m blocks, edges 100-200 m), and events cluster on hotspot edges and
around two daily rush-hour peaks over 90 days.

Arrays only: ``Network`` and ``EventSet`` are plain NumPy records. The
harness wraps them in the program's types where it hands them over.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Table 3 of the paper: |V|, |E|, N
TABLE3 = {
    "berkeley": (1576, 4378, 735_366),
    "johns_creek": (3074, 3471, 979_072),
    "san_francisco": (9700, 16008, 5_379_023),
    "new_york": (55765, 92229, 38_400_730),
}


@dataclasses.dataclass(frozen=True)
class Network:
    n_vertices: int
    src: np.ndarray  # int64 [E]
    dst: np.ndarray  # int64 [E]
    length: np.ndarray  # float64 [E], metres

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])


@dataclasses.dataclass(frozen=True)
class EventSet:
    edge: np.ndarray  # int64 [N]
    pos: np.ndarray  # float64 [N], metres from the edge's src
    time: np.ndarray  # float64 [N], seconds

    @property
    def n(self) -> int:
        return int(self.edge.shape[0])

    def take(self, idx) -> "EventSet":
        return EventSet(self.edge[idx], self.pos[idx], self.time[idx])


def make_network(n_vertices: int, n_edges: int, seed: int) -> Network:
    """Grid-perturbed connected network with ~n_edges edges: a spanning grid,
    then random grid edges dropped (keeping rows and column 0) or random
    chords between nearby grid nodes added until the edge budget is met."""
    rng = np.random.default_rng(seed)
    side = int(np.ceil(np.sqrt(n_vertices)))
    n = n_vertices
    xy = np.stack(
        np.meshgrid(np.arange(side, dtype=np.float64),
                    np.arange(side, dtype=np.float64)),
        axis=-1,
    ).reshape(-1, 2)[:n]
    xy = xy * 150.0 + rng.normal(0, 25.0, size=(n, 2))

    src, dst = [], []
    for r in range(side):
        for c in range(side):
            v = r * side + c
            if v >= n:
                continue
            if c + 1 < side and v + 1 < n:
                src.append(v)
                dst.append(v + 1)
            if r + 1 < side and v + side < n:
                src.append(v)
                dst.append(v + side)
    src = np.array(src, np.int64)
    dst = np.array(dst, np.int64)
    have = len(src)
    if have > n_edges:
        keep = np.ones(have, bool)
        is_tree = (dst == src + 1) | ((src % side == 0) & (dst % side == 0))
        droppable = np.nonzero(~is_tree)[0]
        n_drop = min(have - n_edges, len(droppable))
        keep[rng.choice(droppable, size=n_drop, replace=False)] = False
        src, dst = src[keep], dst[keep]
    else:
        extra = n_edges - have
        if extra > 0:
            a = rng.integers(0, n, size=extra * 3)
            off = rng.integers(1, 4, size=extra * 3) * np.where(
                rng.random(extra * 3) < 0.5, 1, side
            )
            b = (a + off) % n
            ok = a != b
            src = np.concatenate([src, a[ok][:extra]])
            dst = np.concatenate([dst, b[ok][:extra]])
    lens = np.linalg.norm(xy[src] - xy[dst], axis=1)
    lens = np.maximum(lens * rng.uniform(1.0, 1.3, size=len(lens)), 30.0)
    return Network(n, src, dst, lens)


def make_events(net: Network, n_events: int, layout_seed: int, seed: int, *,
                n_hotspots: int = 8, span_days: float = 90.0) -> EventSet:
    """Hotspot-clustered, rush-hour-peaked events.

    The hotspots, the edge and position of every event and the rank of its
    time come from ``layout_seed``, the configuration's: how many events
    each edge holds, where they lie on it and which of them come first fix
    the index's shapes (an RFS edge's size, a DRFS leaf's, the sealed
    share's). The time values come from ``seed`` and are handed out in that
    fixed rank order, so every seed gives the same sizes and new data.
    """
    rng = np.random.default_rng(layout_seed + 1)
    E = net.n_edges
    hotspots = rng.integers(0, E, size=max(n_hotspots, 1))
    idx = np.arange(E)
    w = np.full(E, 1.0)
    for h in hotspots:
        w += 40.0 * np.exp(-((idx - h) ** 2) / (2 * (E * 0.01 + 1) ** 2))
    w /= w.sum()
    eid = rng.choice(E, size=n_events, p=w)
    pos = rng.random(n_events) * net.length[eid]
    rank = rng.permutation(n_events)
    rng = np.random.default_rng([seed, 0])
    day = rng.integers(0, max(int(span_days), 1), size=n_events).astype(np.float64)
    peak = np.where(rng.random(n_events) < 0.5, 8.5, 17.5)
    tod = rng.normal(peak, 1.5) % 24.0
    time = np.empty(n_events)
    time[rank] = np.sort(day * 86400.0 + tod * 3600.0)
    return EventSet(eid.astype(np.int64), pos, time)


def make_dataset(name: str, scale: float, network_seed: int, event_seed: int):
    """(network, events) of a Table-3 replica: the network and the events'
    layout from the configuration's fixed ``network_seed``, their time
    values from ``event_seed``."""
    v, e, n = TABLE3[name]
    nv = max(int(v * scale), 16)
    net = make_network(nv, max(int(e * scale), nv), network_seed)
    return net, make_events(net, max(int(n * scale), 64), network_seed, event_seed)


def split_by_time(ev: EventSet, sealed_share: float):
    """(first ``sealed_share`` of events by time, the rest in time order)."""
    order = np.argsort(ev.time, kind="stable")
    cut = int(ev.n * sealed_share)
    return ev.take(order[:cut]), ev.take(order[cut:])
