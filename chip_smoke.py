"""On-chip smoke test: the TN-KDE engine and query server on one TPU.

Drives the main path once, through the entry points a user calls, at the
full Table-3 berkeley scale (``make_dataset("berkeley", scale=1.0)``:
1,576 vertices, 4,378 edges, 735,366 events, seed 0), on the chip's default
device and with no CPU fallback:

  a. static index     ``TNKDE(solution="rfs", engine="jax")``: 8 window
                      centers, cold then warm; NumPy oracle on 2 of them.
  b. streaming index  ``solution="drfs"`` over 90% of the events (by
                      time); then the held-back 10% in 4 insert batches,
                      ``compact()``, and the same checks again.
  c. compiled kernels the phase-a index with ``executor="fused"``: the
                      Pallas kernel runs compiled and agrees with phase a.
  d. served path      ``TNKDEServer`` over the deployment
                      ``repro.launch.serve.tnkde_world`` builds (DRFS
                      profile, ``engine="jax"``): warmup, 32 requests of
                      1-3 windows with periodic inserts; every response ok,
                      no engine fault or ladder trip, no recompile after
                      warmup, spot checks against a NumPy twin at each
                      response's pinned epoch.

``--chips 4`` runs only the sharded path: RFS and DRFS over a 4-device
mesh against the single-device answer in the same process.

Phases run in the order d, a, b, c. Heatmaps are f32 on the chip
(``repro.compat.device_x64``); agreement is the max absolute error over the
max |oracle| value, held to ``TOL``. The NumPy oracles run only after every
device phase has finished, so they never compete with the device phases for
the host: three host-only worker processes (``JAX_PLATFORMS=cpu``, never the
chip) rebuild the same data from the seed, and the workers are stopped when
the run ends. The last line of stdout is
``{"ok": true, "device": {...}}``; any failed check or exception exits
non-zero without it. Run from the repository root:

    python chip_smoke.py             # one chip, phases a-d
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 host
"""
from __future__ import annotations

import argparse
import gc
import json
import multiprocessing
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: max |device − oracle| / max |oracle| admitted for the chip's f32 path:
#: 8x the worst a full-scale v5e run read (1.2e-6; PERF.md). The ≤1e-12
#: contract is the CPU/f64 one.
TOL = 1e-5
SCALE = 1.0  # Table-3 berkeley at full size
KW = dict(g=50.0, b_s=1000.0)
N_WINDOWS = 8
N_ORACLE = 2  # windows the host oracle answers (it is the slow side)
N_REQUESTS = 32
ORACLE_WAIT_S = 600  # an oracle process that takes longer fails the run
_T0 = time.perf_counter()


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.perf_counter() - _T0:7.1f} s] {msg}", flush=True)


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"shape {got.shape} != oracle {ref.shape}")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-300))


def agree(name: str, got, ref) -> float:
    err = rel_err(got, ref)
    log(f"{name}: worst error {err:.3e} (tolerance {TOL:.0e})")
    check(err <= TOL, f"{name}: error {err:.3e} > {TOL:.0e}")
    return err


def timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def world(scale: float, seed: int = 0):
    from repro.data.spatial import make_dataset

    net, ev, meta = make_dataset("berkeley", scale=scale, seed=seed)
    t0, t1 = float(ev.time.min()), float(ev.time.max())
    span = t1 - t0
    ts = [t0 + (i + 1) * span / (N_WINDOWS + 1) for i in range(N_WINDOWS)]
    return net, ev, meta, ts, 0.25 * span


def query_cold_warm(m, ts, name: str):
    """Query twice: the cold pass compiles, the warm one hits every cache."""
    F_cold, t_cold = timed(lambda: m.query(ts))
    F, t_warm = timed(lambda: m.query(ts))
    check(np.isfinite(F).all(), f"{name}: non-finite heat values")
    check(np.array_equal(F, F_cold), f"{name}: warm answer differs from cold")
    log(f"{name}: [{len(ts)}, {F.shape[1]}] heat, cold query {t_cold:.2f} s "
        f"(compiles), warm query {t_warm:.3f} s, "
        f"bytes_per_shard {m.stats.bytes_per_shard}")
    return F, dict(cold_s=t_cold, warm_s=t_warm)


def _oracle_init():
    """Worker-process setup: the oracles compute on the host only."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def oracle_static(scale: float):
    from repro.core import TNKDE

    net, ev, _, ts, b_t = world(scale)
    return timed(lambda: TNKDE(net, ev, solution="rfs", engine="numpy",
                               b_t=b_t, **KW).query(ts[:N_ORACLE]))


def phase_static(net, ev, ts, b_t, scale, checks, report):
    from repro.core import TNKDE

    m, t_build = timed(lambda: TNKDE(net, ev, solution="rfs", engine="jax",
                                     b_t=b_t, **KW))
    check(m.engine_desc == "jax/packed", f"rfs engine is {m.engine_desc}")
    log(f"a. static: {m.engine_desc}, build {t_build:.1f} s")
    F, times = query_cold_warm(m, ts, "a. static")
    report["a_static"] = dict(engine=m.engine_desc, build_s=t_build,
                              bytes_per_shard=m.stats.bytes_per_shard, **times)

    def verify(result):
        ref, t_ref = result
        log(f"a. static: numpy oracle {t_ref:.1f} s (host process)")
        return agree("a. static vs NumPy oracle", F[:N_ORACLE], ref)

    checks.append(("a_static", oracle_static, (scale,), verify))
    return F


def _split_by_time(ev, frac: float = 0.9):
    from repro.core.events import Events

    order = np.argsort(ev.time, kind="stable")
    cut = int(ev.n * frac)

    def take(idx):
        return Events(ev.edge_id[idx], ev.pos[idx], ev.time[idx])

    return take(order[:cut]), take(order[cut:])


def _insert_batches(held, n: int = 4):
    from repro.core.events import Events

    bounds = np.linspace(0, held.n, n + 1).astype(int)
    return [Events(held.edge_id[lo:hi], held.pos[lo:hi], held.time[lo:hi])
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def oracle_streaming(scale: float):
    """The NumPy twin of phase b: same base, inserts and compaction."""
    from repro.core import TNKDE

    t = time.perf_counter()
    net, ev, _, ts, b_t = world(scale)
    base, held = _split_by_time(ev)
    twin = TNKDE(net, base, solution="drfs", engine="numpy", b_t=b_t, **KW)
    before = twin.query(ts[:N_ORACLE])
    for batch in _insert_batches(held):
        twin.insert(batch)
    twin.compact()
    after = twin.query(ts[:N_ORACLE])
    return before, after, twin.epoch, time.perf_counter() - t


def phase_streaming(net, ev, ts, b_t, scale, checks, report):
    from repro.core import TNKDE

    base, held = _split_by_time(ev)
    m, t_build = timed(lambda: TNKDE(net, base, solution="drfs", engine="jax",
                                     b_t=b_t, **KW))
    check(m.engine_desc == "jax/packed", f"drfs engine is {m.engine_desc}")
    log(f"b. streaming: {m.engine_desc}, build {t_build:.1f} s, "
        f"{base.n} sealed events, {held.n} held back")
    F, times = query_cold_warm(m, ts, "b. streaming")
    t_ins = time.perf_counter()
    for batch in _insert_batches(held):
        m.insert(batch)
    m.compact()
    t_ins = time.perf_counter() - t_ins
    F2, t_q2 = timed(lambda: m.query(ts))
    check(np.isfinite(F2).all(), "b. streaming: non-finite after inserts")
    check(not np.array_equal(F2, F), "b. streaming: inserts changed nothing")
    epoch = m.epoch
    log(f"b. streaming: 4 inserts + compact {t_ins:.1f} s, epoch {epoch}, "
        f"query after {t_q2:.2f} s")
    report["b_streaming"] = dict(engine=m.engine_desc, build_s=t_build,
                                 epoch=list(epoch), query_after_inserts_s=t_q2,
                                 **times)

    def verify(result):
        ref0, ref1, twin_epoch, t_ref = result
        log(f"b. streaming: numpy twin {t_ref:.1f} s (host process)")
        check(epoch == twin_epoch, f"epochs diverged: {epoch} vs twin {twin_epoch}")
        return max(
            agree("b. streaming vs NumPy twin", F[:N_ORACLE], ref0),
            agree("b. streaming after inserts vs NumPy twin", F2[:N_ORACLE], ref1),
        )

    checks.append(("b_streaming", oracle_streaming, (scale,), verify))


def phase_kernels(net, ev, ts, b_t, F_static, report):
    import jax.numpy as jnp

    from repro.compat import pallas_interpret
    from repro.core import TNKDE

    m, t_build = timed(lambda: TNKDE(net, ev, solution="rfs", engine="jax",
                                     executor="fused", b_t=b_t, **KW))
    check(m.engine_desc == "jax/fused", f"fused engine is {m.engine_desc}")
    check(not pallas_interpret(jnp.zeros(1)),
          "Pallas kernels would run interpreted on this device")
    F, times = query_cold_warm(m, ts, "c. kernels")
    launches = m._fe.counters["fused_launches"]
    check(launches > 0, "the fused executor launched no kernel")
    log(f"c. kernels: {m.engine_desc}, compiled Pallas, {launches} launches")
    err = agree("c. kernels vs phase a", F, F_static)
    report["c_kernels"] = dict(engine=m.engine_desc, build_s=t_build, err=err,
                               fused_launches=launches, interpret=False,
                               **times)


def served_world(scale: float):
    from repro.launch.serve import tnkde_world

    return tnkde_world(scale=scale, engine="jax", n_requests=N_REQUESTS)


def oracle_served(scale: float, spots):
    """The NumPy twin of phase d: the served profile on the host, fed the
    workload's inserts in order, answering each (ts, epoch) spot check at
    its pinned epoch. Returns (answers, twin epochs, seconds)."""
    from repro.core import TNKDE
    from repro.serve.loadgen import InsertItem

    t = time.perf_counter()
    net, base, _, prof, workload, _ = served_world(scale)
    twin = TNKDE(net, base, **dict(prof.to_kwargs(), engine="numpy"))
    snaps = {twin.epoch: twin.snapshot()}
    for item in workload:
        if isinstance(item, InsertItem):
            twin.insert(item.events)
            snaps[twin.epoch] = twin.snapshot()
    refs = [twin.query(ts, at=snaps[epoch]) for ts, epoch in spots]
    return refs, sorted(snaps), time.perf_counter() - t


def phase_served(scale: float, checks, report):
    from repro.serve import TNKDEServer, jit_entries
    from repro.serve.loadgen import InsertItem

    net, base, _, prof, workload, _ = served_world(scale)
    server, t_build = timed(lambda: TNKDEServer(
        net, base, {"default": prof}, mode="continuous", batch_cap=8, n_slots=32
    ))
    model = server.models["default"]
    desc0 = model.engine_desc
    check(desc0 == "jax/packed", f"served engine is {desc0}")
    w, t_warm = timed(server.warmup)
    log(f"d. served: {desc0}, build {t_build:.1f} s, warmup {t_warm:.1f} s "
        f"(classes {w.get('window_classes')})")
    epochs = [model.epoch]
    j0 = jit_entries()
    check(j0 >= 0, "this JAX exposes no jit cache probe")
    responses, ts_of = [], {}
    pumps = []

    def pump(force: bool):
        n, t = len(responses), time.perf_counter()
        responses.extend(server.pump(force=force))
        pumps.append(time.perf_counter() - t)
        log(f"d. served: pump {len(pumps)} answered {len(responses) - n} "
            f"at epoch {model.epoch} in {pumps[-1]:.2f} s")

    t_run = time.perf_counter()
    for i, item in enumerate(workload):
        if isinstance(item, InsertItem):
            server.insert(item.events)
            epochs.append(model.epoch)
            continue
        ts_of[i] = item.ts
        server.submit(item.ts, tag=i)
        if server.has_ready_batch:
            pump(False)
    while server.n_queued:
        pump(True)
    t_run = time.perf_counter() - t_run
    recompiles = jit_entries() - j0
    st = server.stats
    check(len(responses) == len(ts_of),
          f"{len(responses)} responses for {len(ts_of)} requests")
    bad = [(r.tag, r.error) for r in responses if not r.ok]
    check(not bad, f"error responses: {bad[:3]}")
    check(st.n_engine_faults == 0, f"{st.n_engine_faults} engine faults")
    check(st.n_degradations == 0, f"{st.n_degradations} ladder trips")
    check(model.engine_desc == desc0, f"engine now {model.engine_desc}")
    check(recompiles == 0, f"{recompiles} recompiles after warmup")
    check(model.epoch == epochs[-1], "the server mutated outside the inserts")
    log(f"d. served: {len(responses)} ok responses in {t_run:.2f} s, "
        f"{st.n_batches} flushes, 0 faults, 0 ladder trips, 0 recompiles")
    report["d_served"] = dict(engine=desc0, build_s=t_build, warmup_s=t_warm,
                              n_requests=len(responses), run_s=t_run,
                              pump_s=pumps,
                              recompiles=recompiles,
                              n_engine_faults=st.n_engine_faults,
                              n_degradations=st.n_degradations)
    # spot checks: the first, middle and last one-window request, answered
    # by the twin at each response's pinned epoch (in a host process)
    by_tag = sorted(responses, key=lambda r: r.tag)
    one = [r for r in by_tag if len(ts_of[r.tag]) == 1] or by_tag
    spot = [one[0], one[len(one) // 2], one[-1]]
    spots = [(ts_of[r.tag], tuple(r.stats.epoch)) for r in spot]
    served_epochs = sorted(set(epochs))

    def verify(result):
        refs, twin_epochs, t_ref = result
        log(f"d. served: numpy twin {t_ref:.1f} s (host process)")
        check(twin_epochs == served_epochs,
              f"twin epochs {twin_epochs} != served {served_epochs}")
        return max(
            agree(f"d. served request {r.tag} @ epoch {r.stats.epoch} "
                  "vs NumPy twin", r.heat, ref)
            for r, ref in zip(spot, refs)
        )

    checks.append(("d_served", oracle_served, (scale, spots), verify))


def phase_sharded(net, ev, ts, b_t, n_dev: int, report):
    """RFS and DRFS over an n_dev-device mesh vs one device, same process.

    The single-device answer comes from the same index: ``degrade()`` moves
    a sharded model onto the single-device packed executor (its first
    fallback rung) without rebuilding the host index."""
    import jax

    from repro.compat import make_mesh
    from repro.core import TNKDE

    mesh = make_mesh((n_dev,), ("data",))
    devices = set(mesh.devices.flat)
    for sol in ("rfs", "drfs"):
        m, t_build = timed(lambda: TNKDE(net, ev, mesh=mesh, solution=sol,
                                         b_t=b_t, **KW))
        check(m.engine_desc == f"jax/packed@shards={n_dev}",
              f"sharded engine is {m.engine_desc}")
        F, times = query_cold_warm(m, ts, f"{sol} sharded")
        per_dev = {d: 0 for d in devices}
        for leaf in jax.tree_util.tree_leaves(_device_arrays(m._fe)):
            check(set(leaf.sharding.device_set) == devices,
                  f"{sol}: an upload of shape {leaf.shape} sits on "
                  f"{len(leaf.sharding.device_set)} of {n_dev} devices")
            for sh in leaf.addressable_shards:
                per_dev[sh.device] += sh.data.nbytes
        log(f"{sol} sharded: bytes per device "
            f"{[per_dev[d] for d in sorted(devices, key=lambda d: d.id)]}")
        bytes_per_shard = m.stats.bytes_per_shard
        check(m.degrade() == "jax/packed", f"single device is {m.engine_desc}")
        gc.collect()
        ref, _ = query_cold_warm(m, ts, f"{sol} single device")
        err = agree(f"{sol} sharded vs single device", F, ref)
        report[f"sharded_{sol}"] = dict(
            engine=f"jax/packed@shards={n_dev}", build_s=t_build, err=err,
            bytes_per_shard=bytes_per_shard,
            bytes_per_device=sorted(per_dev.values()), **times)
        del m
        gc.collect()


def _device_arrays(fe):
    """Every device array a sharded engine holds (tables, packs, caches)."""
    import jax

    found = []

    def walk(x):
        if isinstance(x, jax.Array):
            found.append(x)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif hasattr(x, "values") and callable(x.values):
            for v in x.values():
                walk(v)
        elif hasattr(x, "__slots__"):
            for s in x.__slots__:
                walk(getattr(x, s, None))

    for name in ("_pf", "_nbl", "_tab_cache", "_pack_cache",
                 "_sealed_packs", "_pend_packs", "_wb_cache"):
        walk(getattr(fe, name, None))
    return found


def run(chips: int, scale: float) -> dict:
    report: dict = {}
    net, ev, meta, ts, b_t = world(scale)
    log(f"berkeley x{scale}: |V|={meta['V']} |E|={meta['E']} N={meta['N']}, "
        f"b_t={b_t:.0f} s, {len(ts)} window centers")
    if chips > 1:
        phase_sharded(net, ev, ts, b_t, chips, report)
        return report
    # (report key, oracle function, its arguments, verify(oracle result))
    checks: list = []
    phase_served(scale, checks, report)
    gc.collect()
    F_static = phase_static(net, ev, ts, b_t, scale, checks, report)
    gc.collect()
    phase_streaming(net, ev, ts, b_t, scale, checks, report)
    gc.collect()
    phase_kernels(net, ev, ts, b_t, F_static, report)
    del net, ev, F_static
    gc.collect()
    log("device phases done; NumPy oracles start")
    pool = multiprocessing.get_context("spawn").Pool(
        len(checks), initializer=_oracle_init
    )
    try:
        jobs = [pool.apply_async(fn, args) for _, fn, args, _ in checks]
        for (key, _, _, verify), job in zip(checks, jobs):
            report[key]["err"] = verify(job.get(ORACLE_WAIT_S))
    finally:
        pool.terminate()
        pool.join()
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path over a 4-device mesh")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {dev.platform} devices",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    from repro.compat import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    log(f"device {dev.device_kind} x{len(devs)}, compile cache {cache}")
    t = time.perf_counter()
    report = run(args.chips, SCALE)
    mem = dev.memory_stats() or {}
    log(f"total {time.perf_counter() - t:.1f} s, peak device bytes "
        f"{mem.get('peak_bytes_in_use')}")
    log("report " + json.dumps(report, default=float))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
