"""Scalability demo (paper §4.3): the Fig.-6 operator mix on a taxi-like
frame, eager single-partition (the pandas stand-in) vs block-partitioned
parallel execution, plus the billions-of-columns transpose trick and
progressive approximate aggregation.

Run:  PYTHONPATH=src python examples/dataframe_at_scale.py [--rows 2000000]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, "src")

from repro import compile_cache
from repro.core import DataFrame, EvalMode, Session, set_session
from repro.core.approx import progressive_aggregate
from repro.core.partition import PartitionedFrame
from repro.data.synthetic import numeric_matrix_frame, taxi_like_frame


def timed(label, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    print(f"  {label:42s} {dt*1e3:9.1f} ms")
    return out, dt


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    args = ap.parse_args()
    cores = os.cpu_count() or 4
    print(f"rows={args.rows:,} cores={cores}")

    frame = taxi_like_frame(args.rows, seed=0)

    print("\n— eager single partition (pandas stand-in) —")
    s1 = set_session(Session(mode=EvalMode.EAGER, default_row_parts=1))
    d1 = DataFrame(frame)
    _, t_map1 = timed("map (fillna)", lambda: d1.fillna(0.0).collect())
    _, t_gb1 = timed("groupby(n) count", lambda: d1.groupby("passenger_count").count().collect())
    s1.close()

    print(f"\n— block-partitioned ({cores} row parts) —")
    s2 = set_session(Session(mode=EvalMode.EAGER, default_row_parts=cores))
    d2 = DataFrame(frame)
    _, t_mapN = timed("map (fillna)", lambda: d2.fillna(0.0).collect())
    _, t_gbN = timed("groupby(n) count", lambda: d2.groupby("passenger_count").count().collect())
    print(f"  speedups: map {t_map1/t_mapN:.2f}x, groupby {t_gb1/t_gbN:.2f}x")

    print("\n— transpose: wide output via grid metadata swap —")
    mat = numeric_matrix_frame(200_000, 32, seed=1)
    dm = DataFrame(mat)
    t, _ = timed("transpose 200k×32 → 32×200k", lambda: dm.T.collect())
    print(f"  result shape: {t.shape} (200k columns)")

    print("\n— progressive approximate aggregation (§6.1.3) —")
    pf = PartitionedFrame.from_frame(frame, row_parts=32)
    t0 = time.perf_counter()
    for est in progressive_aggregate(pf, "f0", "mean"):
        print(f"  {est.fraction*100:5.1f}% rows: mean≈{est.value:+.4f} "
              f"[{est.ci_low:+.4f}, {est.ci_high:+.4f}]"
              + ("  (exact)" if est.final else ""))
        if est.fraction > 0.25 and not est.final:
            break
    print(f"  early estimate in {1e3*(time.perf_counter()-t0):.0f} ms")
    s2.close()


if __name__ == "__main__":
    main()
