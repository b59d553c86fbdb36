"""Readings for the correctness limit: the program and its control, many seeds.

    python bench/calibrate.py --workload vgg16.offline --seeds 3200000001-3200000012 --seconds 3
    python bench/calibrate.py --workload vgg16.offline --seeds 3200000001-3200000003 --control

In one process, runs the cell (``bench/run.py``'s ``run_cell``) once per
seed at the configuration's policy, or with ``--control`` at its
``control_policy`` (the program's own lower-precision path), each with a
short window at the cell's own load.  Prints one JSON
line per run with the compared number; the limit in ``bench/configs`` is
set between the largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="e.g. 3200000001-3200000012 or 3,5,9")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    config = run.cell(spec, args.workload)["config"]
    policy = config["control_policy"] if args.control else config["policy"]
    for s in seeds(args.seeds):
        try:
            line, info = run.run_cell(spec, args.workload, s, args.seconds,
                                      False, policy=policy)
            row = {"seed": s, "policy": policy,
                   "max_rel_err": line["checks"]["max_rel_err"]["value"],
                   "correct": line["correct"], "metrics": line["metrics"],
                   "memory_peak_bytes": line["device"]["memory_peak_bytes"],
                   "info": info}
        except run.NoChip:
            raise
        except Exception as e:      # a control that crashes has failed
            row = {"seed": s, "policy": policy,
                   "error": f"{type(e).__name__}: {e}"}
        print("calibrate " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
