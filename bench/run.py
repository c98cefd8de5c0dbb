#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; see
``bench/harness.py`` for what a run does.  With ``--trace 0`` the result
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.  The run needs a TPU with the chips the cell asks for; without one
it exits 2 and prints no result.  ``--rows N`` rehearses the run with every
table cut to N rows, on any backend (``JAX_PLATFORMS=cpu``): it prints the
checks and exits 1, with no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        from bench import harness
        import repro  # noqa: F401 - the system under test must be there
    except ImportError as e:
        print(f"cannot import the benchmark or the engine: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), rows=args.rows,
                               require_chip=args.rows is None, t_start=T0)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    if args.rows is not None:
        print(f"rehearsal at {args.rows} rows: correct={out['correct']}; "
              "no result", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
