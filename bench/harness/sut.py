"""The system under test: the program's served path, built from a
configuration file. The only module of the benchmark that imports the
program; everything it hands over is data the benchmark generated."""
from __future__ import annotations

from .data import EventSet, Network


def program_events(ev: EventSet):
    from repro.core.events import Events

    return Events(ev.edge, ev.pos, ev.time)


def build_server(cfg: dict, net: Network, base: EventSet, b_t: float):
    """``TNKDEServer`` in continuous mode with one profile: the
    configuration's ``profile`` group, passed whole as ``ProfileConfig``
    fields, with its bandwidths and kernels."""
    from repro.core.network import RoadNetwork
    from repro.serve import ProfileConfig, TNKDEServer

    prof = ProfileConfig(g=float(cfg["g"]), b_s=float(cfg["b_s"]),
                         b_t=float(b_t), spatial_kernel=cfg["spatial_kernel"],
                         temporal_kernel=cfg["temporal_kernel"],
                         **cfg["profile"])
    s = cfg["server"]
    rn = RoadNetwork(net.n_vertices, net.src, net.dst, net.length)
    return TNKDEServer(rn, program_events(base), {"default": prof},
                       mode="continuous", n_slots=int(s["n_slots"]),
                       window_cap=int(s["window_cap"]))


def engine_desc(server) -> str:
    return next(iter(server.models.values())).engine_desc


def jit_entries() -> int:
    from repro.serve import jit_entries as probe

    return probe()


def counters(server) -> dict:
    """The served path's own counts (``ServerStats``)."""
    st = server.stats
    return dict(n_windows_requested=st.n_windows_requested,
                n_rows_computed=st.n_rows_computed,
                n_windows_evaluated=st.n_windows_evaluated,
                n_flushes=st.n_flushes, n_batches=st.n_batches,
                n_errors=st.n_errors, n_engine_faults=st.n_engine_faults,
                n_degradations=st.n_degradations,
                occupancy_sum=st.occupancy_sum)
