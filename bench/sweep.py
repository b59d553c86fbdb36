"""Find the knee of an open-loop mix: several offered rates, one set-up.

    python bench/sweep.py --config vgg16 --traffic poisson --rates 60,80,100 --seconds 20 --seed 3200000003

Builds the configuration (``bench/configs/<config>.json``) with the
traffic's buckets once, whether or not a cell of ``BENCHMARK.json`` pairs
them, then measures one window per rate (the traffic with ``rate_per_s``
replaced) and prints, per rate, p50/p95, the requests expired, and the
backlog left when the window closed.  The knee is the highest rate whose
backlog does not grow across the window (no more than one largest bucket
pending at its close) and whose p95 stays within the traffic's budget; a
cell runs at 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True, help="a name in BENCHMARK.json's configs")
    ap.add_argument("--traffic", required=True, help="a file bench/traffic/<name>.json")
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    c = run.assemble(spec, {"name": f"{args.config}.{args.traffic}",
                            "config": args.config, "traffic": args.traffic})
    run.devices_for(1, True)
    ctx = run.setup(c, args.seed)
    budget_ms = 1e3 * float(c["traffic"].get("budget_s") or np.inf)
    biggest = max(c["traffic"]["buckets"])
    for rate in (float(r) for r in args.rates.split(",")):
        ci = dict(c, traffic=dict(c["traffic"], rate_per_s=rate))
        m = run.measure(ci, ctx, args.seed, args.seconds)
        lat = m["latencies_ms"]
        w = m["window"]
        row = {"rate_per_s": rate, "attempted": m["attempted"],
               "failed": m["failed"],
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "backlog_end": w.backlog_end, "pending_peak": w.pending_peak,
               "bucket_steps": m["counters"]["bucket_steps"],
               "late_s": w.late_s}
        row["sustained"] = (w.backlog_end <= biggest and row["p95_ms"] <= budget_ms
                            and not m["failed"])
        print("sweep " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
