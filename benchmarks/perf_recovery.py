"""Crash-recovery benchmark — the BENCH_recovery.json emitter (DESIGN.md §8).

Prices the durability layer end to end on a real dataset:

* **durable ingest** — streaming inserts with the fsync'd WAL attached vs
  the same stream bare, reported as events/s each plus the overhead
  fraction (the cost of the "logged before applied" contract);
* **checkpoint** — one mid-stream atomic checkpoint (seal + state tree +
  COMMIT + WAL rotate/prune), wall-clock;
* **recovery** — the process "dies" (state abandoned, WAL tail torn the
  way a crash mid-append leaves it), then a fresh process restores the
  committed checkpoint and replays the WAL suffix; restore/replay seconds
  and replay events/s come straight off the :class:`RecoveryReport`;
* **equivalence** — the recovered index must match an uncrashed reference
  run to 1e-12 with identical epochs (the same property the tier-1 tests
  assert, here at benchmark scale);
* **degraded floor** — query throughput on the primary engine vs after
  :meth:`TNKDE.degrade` walks to the numpy floor: what a ladder trip
  actually costs while the fallback keeps answering;
* **failover** — a 3-replica router fleet (DESIGN.md §11) under a
  mid-stream replica kill: steady-state vs kill-window pump latency, the
  failover count, zero-lost verification, and the WAL-based ``resync``
  that readmits the killed replica (seconds, replayed events/s, post-resync
  epoch agreement, and the jit entries its warmup added — 0 when the fleet
  warmup already covered the stream state, the compile bill otherwise).

None of the emitted metric names contain "speedup": recovery timings are
capacity/latency telemetry, not accelerated-vs-baseline ratios, so the
perf gate's speedup floor must not apply to them.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")
import numpy as np

import benchmarks.common  # noqa: F401,E402 (persistent compile cache)
from repro.core import TNKDE, WriteAheadLog
from repro.core.events import Events
from repro.data.spatial import make_dataset
from repro.ft.faults import tear_wal_tail


def _split_stream(ev, frac=0.5):
    order = np.argsort(ev.time, kind="stable")
    cut = int(ev.n * frac)
    base = Events(ev.edge_id[order[:cut]], ev.pos[order[:cut]], ev.time[order[:cut]])
    rest = Events(ev.edge_id[order[cut:]], ev.pos[order[cut:]], ev.time[order[cut:]])
    return base, rest


def _batches(stream, n_batches):
    edges = np.linspace(0, stream.n, n_batches + 1).astype(int)
    return [
        Events(stream.edge_id[a:b], stream.pos[a:b], stream.time[a:b])
        for a, b in zip(edges[:-1], edges[1:])
        if b > a
    ]


def _failover_section(net, ev, *, depth, b_t, work, n_queries=18):
    """Price the fleet's self-healing: kill one of three replicas with a
    full queue, fail everything over exactly, then resync it from the
    router WAL. Emits latency/throughput telemetry (no 'speedup' keys —
    exempt from the perf gate like the rest of this file)."""
    from repro.ft.faults import kill_replica
    from repro.serve import ProfileConfig, ReplicaRouter

    base, stream = _split_stream(ev, frac=0.5)
    t0v, t1v = float(ev.time.min()), float(ev.time.max())
    prof = ProfileConfig(g=50.0, b_s=600.0, b_t=b_t, solution="drfs",
                         drfs_depth=depth)
    router = ReplicaRouter(net, base, {"default": prof}, replicas=3,
                           n_slots=16)
    router.attach_wal(WriteAheadLog(os.path.join(work, "fleet_wal")))
    router.warmup()
    batches = _batches(stream, 6)
    rng = np.random.default_rng(0)

    def wave(n):
        for i in range(n):
            router.submit([float(t) for t in
                           rng.uniform(t0v + b_t, t1v - b_t, 2)])
        t0 = time.perf_counter()
        rs = router.pump(force=True)
        return rs, time.perf_counter() - t0

    # steady phase: stream + query waves on the intact fleet
    steady_s = []
    for b in batches[:3]:
        router.insert(b)
        rs, dt = wave(n_queries)
        assert all(r.ok for r in rs)
        steady_s.append(dt / n_queries)

    # kill window: a third of the queue dies with its replica
    for i in range(n_queries):
        router.submit([float(t) for t in
                       rng.uniform(t0v + b_t, t1v - b_t, 2)])
    kill_replica(router.servers[1])
    t0 = time.perf_counter()
    rs = router.pump(force=True)
    kill_pump_s = time.perf_counter() - t0
    zero_lost = len(rs) == n_queries and all(r.ok for r in rs)
    n_failovers = router.n_failovers

    # stream moves on without the dead replica, then resync readmits it
    for b in batches[3:]:
        router.insert(b)
    t0 = time.perf_counter()
    rep = router.resync(1)
    resync_s = time.perf_counter() - t0
    ladders = {
        tuple(s.models["default"].epoch) for s in router.servers
    }
    rs, dt_post = wave(n_queries)
    section = dict(
        replicas=3,
        steady_pump_s_per_query=round(float(np.mean(steady_s)), 6),
        kill_window_pump_seconds=round(kill_pump_s, 4),
        kill_window_s_per_query=round(kill_pump_s / n_queries, 6),
        n_failovers=int(n_failovers),
        zero_lost=bool(zero_lost),
        resync_seconds=round(resync_s, 4),
        resync_replay_events_per_s=round(
            rep["n_events"] / max(rep["replay_seconds"], 1e-9), 1),
        resync_jit_entries_added=rep["jit_entries_added"],
        epochs_match_post_resync=len(ladders) == 1,
        post_resync_pump_s_per_query=round(dt_post / n_queries, 6),
    )
    assert section["zero_lost"], "failover dropped or errored a request"
    assert section["epochs_match_post_resync"], "resync ladder diverged"
    print(f"failover: kill-window {kill_pump_s*1e3:.1f}ms for {n_queries} "
          f"queries ({n_failovers} failovers, zero_lost={zero_lost}); "
          f"resync {resync_s*1e3:.1f}ms "
          f"({section['resync_replay_events_per_s']:,.0f} ev/s replay, "
          f"+{rep['jit_entries_added']} jit entries)")
    return section


def run_recovery_bench(scale=0.04, depth=7, n_batches=8, ckpt_after=4,
                       repeats=2, seed=0, out_json=None):
    print(f"=== TN-KDE crash-recovery bench (berkeley x{scale}) ===")
    net, ev, meta = make_dataset("berkeley", scale=scale, seed=seed)
    base, stream = _split_stream(ev, frac=0.5)
    t0v, t1v = float(ev.time.min()), float(ev.time.max())
    b_t = 0.25 * (t1v - t0v)
    kw = dict(g=50.0, b_s=600.0, b_t=b_t, solution="drfs", drfs_depth=depth)
    batches = _batches(stream, n_batches)
    ts = list(np.linspace(t0v + b_t, t1v - b_t, 4))
    print(f"|V|={meta['V']} |E|={meta['E']} N={meta['N']} base={base.n} "
          f"stream={stream.n} in {len(batches)} batches, ckpt after "
          f"{ckpt_after}")

    work = tempfile.mkdtemp(prefix="bench_recovery_")
    wal_dir = os.path.join(work, "wal")
    ckpt_dir = os.path.join(work, "ckpt")
    try:
        # ---- bare ingest baseline (no WAL): what durability is priced against
        bare = TNKDE(net, base, **kw)
        t0 = time.perf_counter()
        for b in batches:
            bare.insert(b)
        bare_s = time.perf_counter() - t0
        ingest_eps = stream.n / max(bare_s, 1e-9)

        # ---- durable run: WAL'd inserts, mid-stream checkpoint, then "crash"
        model = TNKDE(net, base, **kw)
        model.attach_wal(WriteAheadLog(wal_dir))
        t0 = time.perf_counter()
        for b in batches[:ckpt_after]:
            model.insert(b)
        t_ck = time.perf_counter()
        ckpt_seq = model.checkpoint(ckpt_dir, keep_last=2)
        checkpoint_s = time.perf_counter() - t_ck
        for b in batches[ckpt_after:]:
            model.insert(b)
        durable_s = (time.perf_counter() - t0) - checkpoint_s
        durable_eps = stream.n / max(durable_s, 1e-9)
        wal_bytes = sum(
            os.path.getsize(os.path.join(wal_dir, n))
            for n in os.listdir(wal_dir)
        )
        n_segments = len(model._wal.segments())
        crashed_heat = model.query(ts)
        crashed_epoch = model.epoch
        model._wal.close()
        del model  # the crash: in-memory state is gone, disk remains

        # a crash mid-append leaves a torn final record; recovery truncates
        # it, so the reference below must exclude the torn batch too
        tear_wal_tail(wal_dir, nbytes=12)

        # ---- recovery: fresh process restores ckpt + replays the WAL suffix
        best = None
        for _ in range(max(repeats, 1)):
            fresh = TNKDE(net, base, **kw)
            rep = fresh.restore(ckpt_dir, wal=WriteAheadLog(wal_dir),
                                attach=False)
            if best is None or (rep.restore_seconds + rep.replay_seconds) < (
                best[1].restore_seconds + best[1].replay_seconds
            ):
                best = (fresh, rep)
        recovered, rep = best
        replay_eps = rep.n_events / max(rep.replay_seconds, 1e-9)

        # ---- equivalence vs an uncrashed reference applying the same ops:
        # the checkpoint's logged seal at the same point, minus the torn batch
        ref = TNKDE(net, base, **kw)
        for i, b in enumerate(batches[:-1]):
            ref.insert(b)
            if i == ckpt_after - 1:
                ref.seal()
        max_abs_err = float(np.abs(recovered.query(ts) - ref.query(ts)).max())
        epochs_match = recovered.epoch == ref.epoch
        assert max_abs_err <= 1e-12, f"recovered heat off by {max_abs_err:.3e}"
        assert epochs_match, "recovered epoch diverged from reference"
        # sanity: the crashed run itself only differs by the torn batch
        assert crashed_epoch is not None and crashed_heat is not None

        # ---- degraded floor: primary engine vs numpy rung, same queries
        def qps(m, n_calls=3):
            m.query(ts)  # warm
            t0 = time.perf_counter()
            for _ in range(n_calls):
                m.query(ts)
            return (n_calls * len(ts)) / max(time.perf_counter() - t0, 1e-9)

        primary_desc = recovered.engine_desc
        primary_rps = qps(recovered)
        while recovered.degrade() is not None:
            pass
        assert recovered.engine_desc == "numpy"
        floor_rps = qps(recovered)
        # cross-engine check (numpy floor vs the reference's jit engine):
        # summation order differs, so the tolerance is 1e-9, like the
        # cross-engine assertions in the tier-1 suite
        floor_err = float(np.abs(recovered.query(ts) - ref.query(ts)).max())
        assert floor_err <= 1e-9, "numpy floor diverged after degrade"

        out = dict(
            section="recovery", dataset="berkeley", scale=scale,
            V=meta["V"], E=meta["E"], N=meta["N"], depth=depth,
            n_batches=len(batches), ckpt_seq=ckpt_seq,
            ingest_events_per_s=round(ingest_eps, 1),
            durable_ingest_events_per_s=round(durable_eps, 1),
            durability_overhead_frac=round(
                max(0.0, 1.0 - durable_eps / max(ingest_eps, 1e-9)), 3),
            wal_bytes=wal_bytes, wal_segments=n_segments,
            checkpoint_seconds=round(checkpoint_s, 4),
            recovery=dict(rep.as_dict(),
                          replay_events_per_s=round(replay_eps, 1)),
            max_abs_err=max_abs_err, epochs_match=bool(epochs_match),
            degraded=dict(
                primary_engine=primary_desc,
                primary_windows_per_s=round(primary_rps, 2),
                floor_windows_per_s=round(floor_rps, 2),
                floor_throughput_frac=round(
                    floor_rps / max(primary_rps, 1e-9), 3),
            ),
        )
        print(f"ingest {ingest_eps:,.0f} ev/s bare vs {durable_eps:,.0f} ev/s "
              f"durable (overhead {out['durability_overhead_frac']:.1%}); "
              f"checkpoint {checkpoint_s*1e3:.1f}ms @ seq {ckpt_seq}")
        print(f"recovery: restore {rep.restore_seconds*1e3:.1f}ms + replay "
              f"{rep.replay_seconds*1e3:.1f}ms ({rep.n_records} records, "
              f"{rep.n_events} events, {replay_eps:,.0f} ev/s, torn "
              f"{rep.n_truncated_bytes}B); max_abs_err={max_abs_err:.1e} "
              f"epochs_match={epochs_match}")
        print(f"degraded floor: {primary_desc} {primary_rps:.1f} win/s -> "
              f"numpy {floor_rps:.1f} win/s "
              f"({out['degraded']['floor_throughput_frac']:.2f}x)")
        out["failover"] = _failover_section(net, ev, depth=depth, b_t=b_t,
                                            work=work)
        if out_json:
            with open(out_json, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {out_json}")
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.04)
    ap.add_argument("--json", default="BENCH_recovery.json")
    ap.add_argument("--smoke", action="store_true", help="tiny CI-sized run")
    args = ap.parse_args()
    if args.smoke:
        run_recovery_bench(scale=0.02, depth=5, n_batches=6, ckpt_after=3,
                           repeats=1, out_json=args.json)
    else:
        run_recovery_bench(scale=args.scale, out_json=args.json)
