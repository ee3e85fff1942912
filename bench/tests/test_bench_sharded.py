"""The sharded cell's pieces: its two per-layer readers on a hand-made
four-device trace, a whole run of the cell on four forced host devices
(sound, it is correct; with the exchange between chips left out, it is
not), and the bfloat16 control against its heat limit."""
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import run as bench_run
from harness import data, reference, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "san_francisco-sharded.analyst"
MS = 1_000_000


def _trace():
    """Four chips, each running one ``dyn_tables`` and two ``dyn_flush``
    modules inside the window, for different lengths."""
    devices = []
    for d in range(4):
        tables, flush = (40 + 10 * d) * MS, (5 + d) * MS
        devices.append({
            "name": f"/device:TPU:{d}",
            "modules": [[f"jit_dyn_tables({d})", 10 * MS, tables],
                        ["jit_dyn_flush(9)", 60 * MS, flush],
                        ["jit_dyn_flush(9)", 80 * MS, flush]],
            "ops": [],
        })
    return {"devices": devices, "host": [["window", 0, 100 * MS]]}


@pytest.mark.parametrize("metric,per_chip_ms", [
    # mean over the chips: tables 40, 50, 60, 70 ms; flushes 2 x 5..8 ms
    ("shard_tables_ms_per_window.analyst", 55.0),
    ("shard_walk_ms_per_window.analyst", 13.0),
])
def test_readers_average_the_chips_and_divide_by_rows(metric, per_chip_ms):
    rows = 5
    run = types.SimpleNamespace(
        trace=trace.reduce(_trace()),
        counters0={"n_rows_computed": 7}, counters1={"n_rows_computed": 7 + rows})
    got = bench_run.load_metric(metric).read(run)
    assert got == pytest.approx(per_chip_ms / rows)
    assert bench_run.load_metric(metric).read(
        types.SimpleNamespace(trace=None)) is None


def test_readers_are_reported_in_the_sharded_cell_alone():
    spec = bench_run.load_spec(os.path.dirname(BENCH))
    names = {"shard_tables_ms_per_window.analyst", "shard_walk_ms_per_window.analyst"}
    for w in spec["workloads"]:
        got = {m["name"] for m in bench_run.metrics_for(spec, w["name"], True)}
        assert (names <= got) if w["name"] == CELL else not (names & got)


RUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    bench = sys.argv[1]
    sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
    import jax
    if sys.argv[2] == "no_psum":
        # each chip keeps its own shard's heat delta: the exchange between
        # chips is left out
        jax.lax.psum = lambda x, axes: x
    import run
    spec = run.load_spec(os.path.dirname(bench))
    with open(os.path.join(bench, "configs", "san_francisco-sharded.json")) as f:
        cfg = json.load(f)
    cfg["data"]["scale"] = 0.01
    cfg["server"]["window_cap"] = 4
    out = run.run_cell(spec, "san_francisco-sharded.analyst", 2**31 + 21, 3.0,
                       False, config=cfg, platforms=("cpu",),
                       say=lambda msg: None)
    print(json.dumps(dict(out["line"], engine=out["info"]["engine"])))
    """
)


@pytest.mark.parametrize("fault", ["sound", "no_psum"])
def test_the_cell_on_four_host_devices(tmp_path, fault):
    script = tmp_path / "cell.py"
    script.write_text(RUN)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(script), BENCH, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["engine"] == "jax/packed@shards=4"
    assert line["device"]["count"] == 4
    assert line["correct"] == (fault == "sound"), line["checks"]


def test_bfloat16_control_fails_the_heat_limit():
    with open(os.path.join(BENCH, "configs", "san_francisco-sharded.json")) as f:
        cfg = json.load(f)
    net, ev = data.make_dataset(cfg["data"]["dataset"], 0.05, 0, 11)
    b_t = 0.25 * float(ev.time.max() - ev.time.min())
    ts = list(np.linspace(ev.time.min() + b_t, ev.time.max() - b_t, 4))
    kw = dict(g=cfg["g"], b_s=cfg["b_s"], b_t=b_t)
    ref = reference.Reference(net, **kw).heat(ev, ts)
    low = reference.Reference(net, **kw, dtype="bfloat16").heat(ev, ts)
    gap = (np.abs(low - ref).max(1) / np.abs(ref).max(1)).max()
    assert gap > cfg["limits"]["heat_gap"]
