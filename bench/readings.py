#!/usr/bin/env python3
"""Readings that the limits of the check are set from: for each seed, the
program's compared numbers and the control's, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5

Each seed is one run of the cell at its own size and load with a short
window (``--seconds``); after the window, the same answers that the run
compares are also produced by the control (the reference computed in
bfloat16, put in the program's place) and compared the same way.  Prints one
JSON line per seed, then the largest reading of the program and the smallest
of the control for each number.  The benchmark's own runs never run the
control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell: str, seeds, seconds: float, rows=None, require_chip=True,
             log=None):
    from bench import harness
    out = []
    for seed in seeds:
        control = {}

        def keep(stmts, host):
            control.update(harness.compare(stmts, host, lowp=True))

        r = harness.run_cell(cell, seed, seconds, False, rows=rows,
                             require_chip=require_chip, log=log, keep=keep)
        out.append({"seed": seed, "correct": r["correct"],
                    "attempted": r["attempted"], "failed": r["failed"],
                    "program": {k: v["value"] for k, v in r["checks"].items()},
                    "control": {k: v["value"] for k, v in control.items()},
                    "limits": {k: v["limit"] for k, v in r["checks"].items()}})
        print(json.dumps(out[-1]), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    names = sorted({k for r in rows for k in r["program"]})
    for n in names:
        lo = max(r["program"].get(n, 0.0) for r in rows)
        up = min(r["control"].get(n, float("inf")) for r in rows)
        print(json.dumps({"number": n, "lower": lo, "upper": up,
                          "limit": rows[0]["limits"].get(n)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
