"""The control of the comparison that decides ``correct``: the plain
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the rows a run of the cell compares (the same data
and traffic: the first ``--requests`` requests of the seed's stream),
evaluates them with the bfloat16 control and with the float32 reference,
and prints the numbers the comparison reads beside the configuration's
limits: the control must fail at least one of them. It does not run the program, and the benchmark's own runs do
not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import check, data, drivers, reference, traffic  # noqa: E402


def control_checks(spec: dict, cell_name: str, seed: int, requests: int, *,
                   bench: str = BENCH, config: dict = None) -> dict:
    """The comparison's verdict on the bfloat16 control answering the first
    ``requests`` requests of the seed's stream on the seed's data."""
    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    if config is None:
        with open(os.path.join(bench, "configs", f"{cell['config']}.json")) as f:
            config = json.load(f)
    mix = traffic.Mix.load(os.path.join(bench, "traffic", f"{cell['traffic']}.json"))
    d = config["data"]
    net, ev = data.make_dataset(d["dataset"], float(d["scale"]),
                                int(d["network_seed"]), seed)
    b_t = float(config["b_t_share_of_span"]) * float(ev.time.max() - ev.time.min())
    base, _ = data.split_by_time(ev, float(config["sealed_share"]))
    draws = traffic.closed_stream(mix, seed, t_min=float(ev.time.min()),
                                  t_max=float(ev.time.max()), b_t=b_t)
    answers = [drivers.Answer(tag=i, ts=next(draws), sched=0.0, done=0.0, ok=True)
               for i in range(requests)]
    kw = dict(g=float(config["g"]), b_s=float(config["b_s"]), b_t=b_t)
    ts = sorted({t for a in answers for t in a.ts})
    # the control answers in the program's place
    low = reference.Reference(net, **kw, dtype="bfloat16").heat(base, ts)
    rows = dict(zip(ts, low))
    for a in answers:
        a.heat = np.stack([rows[t] for t in a.ts])
    return check.compare(answers, heat=reference.Reference(net, **kw).heat,
                         events=base, limits=config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=200,
                    help="requests compared per seed")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[control] no TPU: JAX sees {dev.platform}", file=sys.stderr)
        return 3
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for seed in [int(s) for s in args.seeds.split(",")]:
        v = control_checks(spec, args.workload, seed, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": not v["correct"],
                          "checks": v["checks"], "coverage": v["coverage"],
                          "device": {"platform": dev.platform,
                                     "kind": dev.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
