"""Sharded packed-engine benchmark — BENCH_dist.json.

Runs a 2-shard rung against the single-host packed engine on the same
world, in this process, over the first two devices JAX sees:

  * ``shard2_speedup`` — warm W-window query, sharded / single-host. On one
    physical CPU two host "devices" time-slice the same cores, so this
    measures collective overhead, not a speedup — it is tracked for
    trajectory (a regression means the sharded path got heavier), not
    gated on an absolute floor.
  * ``bytes_per_shard_frac`` — per-shard device bytes / single-device
    bytes. THE load-bearing number: the 1/devices memory-scaling claim of
    DESIGN.md §3, measured (≈0.5 + padding slack at 2 shards; the CI gate
    fails above 0.65).

Both modes run: static RFS and streaming DRFS (quantized), warm. Run as a
script on a CPU host, it asks XLA for two host devices before JAX starts
(``--xla_force_host_platform_device_count=2``); any other caller must
already have two devices.
"""
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")
if __name__ == "__main__":
    from benchmarks.host_devices import request_host_devices

    request_host_devices(2)
import benchmarks.common  # noqa: F401,E402 (persistent compile cache)


def _timed(m, ts) -> float:
    m.query(ts)  # warm: compile + populate the plan/table caches
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        m.query(ts)
        best = min(best, time.perf_counter() - t)
    return best


def run_dist_bench(scale: float = 0.04, n_windows: int = 5,
                   out_json: str = "BENCH_dist.json") -> dict:
    import jax

    from repro.compat import host_mesh
    from repro.core import TNKDE
    from repro.data.spatial import make_dataset

    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"the 2-shard rung needs two devices, JAX sees {len(jax.devices())}"
        )
    net, ev, _ = make_dataset("berkeley", scale=scale, seed=0)
    span = float(ev.time.max() - ev.time.min())
    t0 = float(ev.time.min())
    ts = [t0 + (i + 1) * span / (n_windows + 1) for i in range(n_windows)]
    b_t = span / 4
    mesh = host_mesh(2)
    rec = {"scale": scale, "W": n_windows, "N": int(ev.n), "rungs": []}
    for mode, kw in (
        ("rfs", dict(solution="rfs")),
        ("drfs_quantized", dict(solution="drfs", drfs_depth=6)),
    ):
        base = dict(g=50.0, b_s=400.0, b_t=b_t, **kw)
        single = TNKDE(net, ev, engine="jax", **base)
        t_single = _timed(single, ts)
        sharded = TNKDE(net, ev, mesh=mesh, **base)
        t_shard = _timed(sharded, ts)
        rec["rungs"].append(dict(
            mode=mode,
            engine=sharded.engine_desc,
            t_single=round(t_single, 4),
            t_shard2=round(t_shard, 4),
            shard2_speedup=round(t_single / max(t_shard, 1e-9), 3),
            bytes_single=int(single._fe.bytes_per_shard),
            bytes_per_shard=int(sharded.stats.bytes_per_shard),
            bytes_per_shard_frac=round(
                sharded.stats.bytes_per_shard / max(single._fe.bytes_per_shard, 1), 3
            ),
        ))
    for r in rec["rungs"]:
        print(
            f"dist/{r['mode']},0.0,engine={r['engine']};"
            f"shard2_speedup={r['shard2_speedup']};"
            f"bytes_frac={r['bytes_per_shard_frac']}"
        )
        # the measured memory-scaling claim: one slab must be roughly half
        # of the single-device index (padding + replicated window batches
        # allow slack, but 2 shards must never approach a full copy each)
        assert r["bytes_per_shard_frac"] <= 0.75, r
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CI-sized run")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--json", default="BENCH_dist.json")
    args = ap.parse_args()
    scale = args.scale if args.scale is not None else (0.02 if args.smoke else 0.04)
    run_dist_bench(scale=scale, out_json=args.json)
