#!/usr/bin/env python3
"""The spreads that a cell's bounds are set from.

    python3 bench/spread.py --workload <cell> --seconds 51 \
        --seeds 1,2,3,4,5,6 --sets 2 --trace-seeds 7,8,9 --out <dir>

Runs the cell once for each seed of each set, then once traced for each of
``--trace-seeds``; every run is a process of its own, one at a time (a run
holds the chip), and the sets use the same seeds.  Each run's output and
errors go to ``<dir>/<tag>.out`` and ``.err``.  Prints one line per run
(its seed, exit code, ``correct``, metrics and compared numbers), then for
each end-to-end metric each set's median and spread: (Q3 - Q1) / median, by
``statistics.quantiles(values, n=4)``, over all of the set's runs and
without its run farthest from the median.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def run(args, tag, seed, trace):
    base = os.path.join(args.out, tag)
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    with open(base + ".out", "w") as out, open(base + ".err", "w") as err:
        rc = subprocess.run(cmd, stdout=out, stderr=err, cwd=os.path.dirname(HERE)).returncode
    with open(base + ".out") as f:
        lines = f.read().strip().splitlines()
    res = json.loads(lines[-1]) if rc == 0 and lines else None
    print(json.dumps({"run": tag, "seed": seed, "rc": rc,
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "correct": res and res["correct"],
                      "metrics": res and {k: v["value"] for k, v in res["metrics"].items()},
                      "device": res and res["device"],
                      "checks": res and {k: v["value"] for k, v in res["checks"].items()}}),
          flush=True)
    return res


def spread(v):
    q1, _, q3 = statistics.quantiles(v, n=4)
    return (q3 - q1) / statistics.median(v)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = [[run(args, f"s{k + 1}_{i + 1}", s, 0) for i, s in enumerate(seeds)]
            for k in range(args.sets)]
    for i, s in enumerate(filter(None, args.trace_seeds.split(","))):
        run(args, f"t{i + 1}", int(s), 1)
    names = sorted({n for rs in sets for r in rs if r for n in r["metrics"]})
    for n in names:
        for k, rs in enumerate(sets):
            v = [r["metrics"][n]["value"] for r in rs if r and n in r["metrics"]]
            if len(v) < 2:
                continue
            med = statistics.median(v)
            trimmed = sorted(v, key=lambda x: abs(x - med))[:-1]
            print(json.dumps({"metric": n, "set": k + 1, "runs": len(v), "median": med,
                              "spread": spread(v),
                              "spread_without_farthest":
                                  spread(trimmed) if len(trimmed) > 1 else None}), flush=True)
    ok = all(r and r["correct"] for rs in sets for r in rs)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
