"""Benchmark harness — one module per paper table/figure.

  fig6          paper Fig. 6: map/groupby(n)/groupby(1)/transpose, eager-1p
                (pandas stand-in) vs block-partitioned parallel
  opportunistic paper §6.1.1/6.1.2: eager vs lazy vs opportunistic + prefix
  rewrite       paper §5: transpose-elimination rewrites
  reuse         paper §6.2: session materialization/reuse
  approx        paper §6.1.3: progressive aggregation to ±1%
  fusion        paper §5: fused row-local pipelines vs per-node evaluation
                (also writes BENCH_fusion.json)
  blocking_fusion  barrier fusion through GROUPBY/SORT/JOIN/WINDOW
                (also writes BENCH_blocking_fusion.json)
  scheduling    adaptive block scheduling: coalesced pool dispatch +
                plan-time grid sizing vs per-block dispatch
                (also writes BENCH_scheduling.json)
  dedup         block-parallel + barrier-fused DIFFERENCE/DROP-DUPLICATES
                vs the serial seed path (also writes BENCH_dedup.json)
  outofcore     memory-governed spill/fault residency (REPRO_MEM_BUDGET) +
                chunk-parallel streaming CSV ingest vs the seed parser
                (also writes BENCH_outofcore.json)
  faults        fault-tolerant execution: retry-machinery overhead at 0%
                faults + completion under a seeded 5% chaos plan
                (also writes BENCH_faults.json)
  shuffle       shuffle-native JOIN/SORT: grace-hash + sample-sort exchange
                (serial_seed vs shuffled vs fused) + 4x-budget join
                (also writes BENCH_shuffle.json)
  service       concurrent multi-session query service: 16 think-time
                tenants vs 1 on a 2-worker pool — admission control +
                cross-session MQO (also writes BENCH_service.json)
  trace         statement tracing: disabled-path overhead on the scheduling
                chain + traced chaos span/ExecStats exactness
                (also writes BENCH_trace.json)

Prints ``name,us_per_call,derived`` CSV.  Select with ``--only fig6,reuse``.
``--smoke`` runs every suite at tiny sizes with no JSON/artifact overwrite —
the CI gate (scripts/check.sh) uses it so each bench at least executes.
"""
from __future__ import annotations

import os

# Single-threaded XLA intra-op execution (MUST precede jax init): the paper's
# baseline is single-core pandas; with default settings XLA:CPU multithreads
# single-partition ops internally, which would hide exactly the parallelism
# Modin-style partitioning adds.  One partition ↔ one core, as in Modin's
# worker model.
os.environ.setdefault(
    "XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")

import argparse  # noqa: E402
import sys  # noqa: E402

from ._util import Reporter


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny row counts, no JSON overwrite (CI sanity mode)")
    args, _ = ap.parse_known_args()

    from repro import compile_cache
    compile_cache.enable()
    from . import (bench_approx, bench_blocking_fusion, bench_dedup,
                   bench_faults, bench_fig6, bench_fusion,
                   bench_opportunistic, bench_outofcore, bench_reuse,
                   bench_rewrite, bench_scheduling,
                   bench_service, bench_shuffle, bench_trace)
    suites = {
        "fig6": bench_fig6.run,
        "opportunistic": bench_opportunistic.run,
        "rewrite": bench_rewrite.run,
        "reuse": bench_reuse.run,
        "approx": bench_approx.run,
        "fusion": bench_fusion.run,
        "blocking_fusion": bench_blocking_fusion.run,
        "scheduling": bench_scheduling.run,
        "dedup": bench_dedup.run,
        "outofcore": bench_outofcore.run,
        "faults": bench_faults.run,
        "shuffle": bench_shuffle.run,
        "service": bench_service.run,
        "trace": bench_trace.run,
    }
    picked = suites if args.only == "all" else {
        k: suites[k] for k in args.only.split(",")}

    rep = Reporter()
    print("name,us_per_call,derived")
    failures = []
    for name, fn in picked.items():
        try:
            fn(rep, smoke=args.smoke)
        except Exception as e:  # keep the harness going; record the failure
            rep.add(f"{name}/ERROR", 0.0, repr(e)[:120])
            failures.append(name)
    sys.stdout.flush()
    if args.smoke and failures:   # the CI gate must notice a broken bench
        raise SystemExit(f"smoke failures: {', '.join(failures)}")


if __name__ == "__main__":
    main()
