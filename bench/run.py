"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and each metric's reader in
``bench/metrics/<metric>.py``. In order, a run loads the configuration,
generates the data from the seed, builds the server, warms the cell's own
shapes (all of that is ``setup_s``), drives the measured window through
``submit`` / ``pump``, drains it, frees the server, compares
the answers with the plain reference and prints one JSON line last on
stdout: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), and the numbers compared beside
their limits under ``checks``. It refuses to run without a TPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import check, data, drivers, reference, sut, traffic  # noqa: E402
from harness import trace as tracing  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


class NoDevice(RuntimeError):
    pass


def log(device_tag: str, msg: str) -> None:
    print(f"[bench {device_tag} {time.perf_counter() - T_PROCESS:7.1f} s] {msg}",
          file=sys.stderr, flush=True)


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_metric(name: str, bench: str = BENCH):
    path = os.path.join(bench, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, traced: bool) -> list:
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def setup_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set, else
    a fixed directory in the checkout (the path is part of the cache key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, platforms=("tpu",)):
    import jax

    devs = jax.devices()
    if devs[0].platform not in platforms:
        raise NoDevice(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, bench: str = BENCH, config: dict = None,
             mix: "traffic.Mix" = None, platforms=("tpu",), say=None) -> dict:
    """One run of one cell; returns the result line as a dict (the last key
    is ``checks``) with ``info`` beside it for the earlier output line."""
    import jax

    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    if config is None:
        with open(os.path.join(bench, "configs", f"{cell['config']}.json")) as f:
            config = json.load(f)
    if mix is None:
        mix = traffic.Mix.load(os.path.join(bench, "traffic", f"{cell['traffic']}.json"))
    devs = devices(int(cell["chips"]), platforms)
    dinfo = device_info(devs)
    tag = f"{dinfo['platform']} {dinfo['kind']} x{dinfo['count']}"
    say = say or (lambda msg: log(tag, msg))

    d = config["data"]
    net, ev = data.make_dataset(d["dataset"], float(d["scale"]),
                                int(d["network_seed"]), seed)
    b_t = float(config["b_t_share_of_span"]) * float(ev.time.max() - ev.time.min())
    base, _ = data.split_by_time(ev, float(config["sealed_share"]))
    say(f"{cell_name}: |V|={net.n_vertices} |E|={net.n_edges} N={ev.n} "
        f"(indexed {base.n}), b_t={b_t:.0f} s")
    server = sut.build_server(config, net, base, b_t)
    warm = server.warmup()
    engine0 = sut.engine_desc(server)
    say(f"built {engine0}, warmed window classes "
        f"{warm['window_classes']} in {warm['seconds']} s")

    draws = traffic.closed_stream(mix, seed, t_min=float(ev.time.min()),
                                  t_max=float(ev.time.max()), b_t=b_t)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    annotate = (jax.profiler.TraceAnnotation if traced
                else lambda name: contextlib.nullcontext())
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0, jit0 = sut.counters(server), sut.jit_entries()
    setup_s = time.perf_counter() - T_PROCESS
    with annotate(tracing.WINDOW):
        rec = drivers.drive_closed(server, lambda: next(draws), mix.clients,
                                   seconds=seconds, span=annotate)
    c1, jit1 = sut.counters(server), sut.jit_entries()
    trace = None
    if traced:
        jax.profiler.stop_trace()
        trace = tracing.reduce(tracing.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak(devs)
    engine1 = sut.engine_desc(server)
    del server
    gc.collect()

    run = types.SimpleNamespace(record=rec, setup_s=setup_s, counters0=c0,
                                counters1=c1, trace=trace)
    metrics = {}
    for m in metrics_for(spec, cell_name, traced):
        value = load_metric(m["name"], bench).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference.Reference(net, g=float(config["g"]), b_s=float(config["b_s"]),
                              b_t=b_t, dtype=config["precision"])
    verdict = check.compare(rec.answers, heat=ref.heat, events=base,
                            limits=config["limits"],
                            served=check.served_numbers(c0, c1, engine0, engine1))
    ref_s = time.perf_counter() - t_ref

    info = {
        "engine": engine1, "setup_s": setup_s, "warmup_s": warm["seconds"],
        "reference_s": ref_s, "window_s": rec.t_close - rec.t_open,
        "drain_s": max(rec.t_drained - rec.t_close, 0.0),
        "jit_entries_growth": jit1 - jit0,
        "memory_peak_bytes": peak,
        "counters": {k: c1[k] - c0[k] for k in c0},
        "coverage": verdict["coverage"],
    }
    device = dict(dinfo, memory_peak_bytes=peak)
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    line = {"correct": verdict["correct"], "attempted": len(rec.answers),
            "failed": sum(1 for a in rec.answers if not a.ok),
            "metrics": metrics, "device": device}
    if trace is not None:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["checks"] = verdict["checks"]
    return {"line": line, "info": info}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    spec = load_spec()
    if not any(w["name"] == args.workload for w in spec["workloads"]):
        ap.error(f"unknown workload {args.workload!r}")
    setup_compile_cache()
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoDevice as e:
        print(f"[bench] {e}; no result", file=sys.stderr)
        return 3
    line, info = out["line"], out["info"]
    dev = line["device"]
    tag = f"{dev['platform']} {dev['kind']} x{dev['count']}"
    print(json.dumps({"info": info, "device": device_info_short(dev)}), flush=True)
    for name, c in line["checks"].items():
        log(tag, f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


def device_info_short(dev: dict) -> dict:
    return {k: dev[k] for k in ("platform", "kind", "count")}


if __name__ == "__main__":
    sys.exit(main())
