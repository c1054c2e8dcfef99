#!/usr/bin/env python3
"""Runs a cell with the control (or a planted fault) patched into the
program, and prints the numbers compared beside their limits. The
benchmark's own runs never run it; it sets the upper readings of the
limits in PERF.md.

    python3 benchmark/control.py --workload store.push2m --seed 9 \
        --seconds 5 --fault control
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faults  # noqa: E402
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--fault", choices=faults.FAULTS, default="control")
    args = ap.parse_args()
    cell = run.cell_spec(run.lib.benchmark_spec(), args.workload)
    traffic = run.lib.load_json(os.path.join(
        run.BENCH_DIR, "traffic", args.workload + ".json"))
    with faults.patched(cell, traffic, args.fault):
        result, _ = run.run_cell(args.workload, args.seed, args.seconds,
                                 False)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "fault": args.fault, "correct": result["correct"],
                      "checks": result["checks"]}), flush=True)


if __name__ == "__main__":
    main()
