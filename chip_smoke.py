#!/usr/bin/env python3
"""Smoke test of the dataframe engine's main path on one TPU chip.

Drives the engine once through the entry points a user calls — ``Session``,
``DataFrame``, ``read_csv``, ``from_pydict``, ``collect()`` and
``QueryService`` — over a taxi-shaped table of 10,000,000 rows generated from
``--seed``, plus a 1,000,000-row CSV ingest, and checks every phase against
pandas on the same data.

    python chip_smoke.py [--seed 0] [--rows 10000000]

The CSV holds a tenth as many rows as the table.

Each phase prints its wall time (engine only, results on the host), how many
XLA programs it compiled or found in the persistent compile cache, and its
check.  The last line is ``{"ok": true, "device": {...}}`` only on a TPU with
every phase passing.  Without a TPU the phases still run as a rehearsal, at
most ``REHEARSAL_ROWS`` rows (``JAX_PLATFORMS=cpu python chip_smoke.py
--rows 20000``), and the script exits 1 without that line.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import threading
import time
import traceback
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

REHEARSAL_ROWS = 200_000
DIM_ROWS = 10_000            # zone dimension table (join key 0..9999)
MATRIX_SHAPE = (200_000, 32)  # TRANSPOSE frame of the paper's Fig. 6 mix
PAYMENT = ("card", "cash", "dispute")
FLOATS = [f"f{j}" for j in range(6)]

# Floats are stored in float32 and the engine accumulates them in float32;
# pandas sums the same float32 values in float64.  A sum or mean must land
# within RTOL·Σ|x| of pandas, with RTOL by how the engine sums:
#  * groupby sums and means go through the one-hot MXU matmul, which has to
#    run at HIGHEST precision.  A default-precision pass rounds every value
#    to bf16 (up to 2**-9 relative): on a v5e at 10M rows it read
#    2.8e-6·Σ|x| on the ~1.7M-row groups and 1.3e-7 on the whole-frame sums,
#    against 1e-9 and 2.3e-10 at HIGHEST.  The bound sits between the two,
#    so a bf16 pass fails the run.
GROUPBY_RTOL = 1e-7
#  * running sums carry float32 partials along the rows on the VPU, no MXU.
#    A sequential float32 sum of n terms drifts by about sqrt(n)·2**-24·Σ|x|
#    (1.9e-4 at n = 10M); the scan's tiled carry is shallower, and the v5e
#    chip read 6.3e-8·Σ|x|.
SCAN_RTOL = 1e-4
# One float32 map result: XLA may contract a*b + c into one fused
# multiply-add (one rounding where numpy rounds twice), so allow 2 ulp.
MAP_ULPS = 2
# Everything else — ints, keys, counts, min/max, orders, copied values — must
# match exactly.

# the jitted programs of repro.kernels.ops that the engine calls around its
# Pallas kernels: groupby partials, cumsum/cummax, get_dummies, transpose
KERNELS = ("_segment_reduce_multi_prog", "_pallas_winscan", "_pallas_onehot",
           "_pallas_transpose")


# ---------------------------------------------------------------------------
class KernelCalls:
    """Wraps the ``KERNELS`` programs of ``repro.kernels.ops`` for the run:
    counts the engine's calls to each and keeps the abstract arguments of its
    first call, so that ``mosaic_check`` can lower that very program again."""

    def __init__(self):
        from repro.kernels import ops
        self._lock = threading.Lock()
        self.calls: Counter = Counter()
        self.first: dict = {}
        self.programs = {name: getattr(ops, name) for name in KERNELS}
        for name, fn in self.programs.items():
            setattr(ops, name, self._wrap(name, fn))

    def _wrap(self, name, fn):
        import jax

        def abstract(x):
            return (jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if isinstance(x, jax.Array) else x)

        def call(*args, **kw):
            with self._lock:
                self.calls[name] += 1
                if name not in self.first:
                    self.first[name] = jax.tree.map(abstract, (args, kw))
            return fn(*args, **kw)
        return call


def host(col):
    """(values, valid) host arrays of an engine column."""
    v = np.asarray(col.data)
    m = np.ones(v.shape[0], bool) if col.mask is None else np.asarray(col.mask)
    return v, m


def labels(frame) -> np.ndarray:
    rl = frame.row_labels
    if hasattr(rl, "values"):
        return np.asarray(rl.values)
    if hasattr(rl, "start"):
        return np.arange(rl.start, rl.start + len(rl))
    return np.asarray(rl.to_list())


def expect(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def exact(name: str, got: np.ndarray, want: np.ndarray) -> None:
    got, want = np.asarray(got), np.asarray(want)
    expect(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    bad = ~((got == want) | (_isnan(got) & _isnan(want)))
    expect(not bad.any(), f"{name}: {int(bad.sum())} mismatches, first at "
           f"{int(np.argmax(bad))}: {got[bad][:3]} != {want[bad][:3]}")


def _isnan(a):
    return np.isnan(a) if a.dtype.kind == "f" else np.zeros(a.shape, bool)


def close(name: str, got, want, scale, rtol: float) -> float:
    """|got - want| ≤ rtol·scale elementwise; returns max error/scale."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.broadcast_to(np.asarray(scale, np.float64), got.shape)
    err = np.abs(got - want)
    ratio = float(np.max(err / np.maximum(scale, 1e-30))) if err.size else 0.0
    expect(bool(np.all(err <= rtol * scale)),
           f"{name}: max error {ratio:.3g}·Σ|x| > {rtol}")
    return ratio


def column_values(frame, name):
    """Engine column as float64 with nulls as NaN (pandas' representation)."""
    v, m = host(frame.col(name))
    return np.where(m, v.astype(np.float64), np.nan)


# ---------------------------------------------------------------------------
def make_data(rows: int, seed: int):
    """The taxi-shaped fact table (numpy, and its pandas twin) and the zone
    dimension table, all generated from ``seed`` in bulk."""
    import pandas as pd
    rng = np.random.default_rng(seed)
    cols = {
        "passenger_count": rng.integers(1, 7, rows, dtype=np.int32),
        "payment_type": rng.integers(0, len(PAYMENT), rows, dtype=np.int32),
        "zone": rng.integers(0, DIM_ROWS, rows, dtype=np.int32),
    }
    valid = {}
    for name in FLOATS:
        cols[name] = rng.standard_normal(rows, dtype=np.float32)
        valid[name] = rng.random(rows) >= 0.01
    pdf = pd.DataFrame({
        "passenger_count": cols["passenger_count"].astype(np.int64),
        "payment_type": np.asarray(PAYMENT, dtype=object)[cols["payment_type"]],
        "zone": cols["zone"].astype(np.int64),
        **{n: np.where(valid[n], cols[n].astype(np.float64), np.nan)
           for n in FLOATS},
    })
    dim = {
        "zone": np.arange(DIM_ROWS, dtype=np.int64),
        "zone_rate": np.round(rng.uniform(1.0, 5.0, DIM_ROWS), 2),
        "borough": np.asarray(["Bronx", "Brooklyn", "Manhattan", "Queens",
                               "Staten Island"], dtype=object)[
                                   rng.integers(0, 5, DIM_ROWS)],
    }
    return cols, valid, pdf, dim


def engine_frame(cols, valid):
    import jax.numpy as jnp
    from repro.core import Column, Domain, Frame
    from repro.core.labels import RangeLabels, labels_from_values
    out = [Column(jnp.asarray(cols["passenger_count"]), Domain.INT),
           Column(jnp.asarray(cols["payment_type"]), Domain.CATEGORY, None,
                  PAYMENT),
           Column(jnp.asarray(cols["zone"]), Domain.INT)]
    out += [Column(jnp.asarray(cols[n]), Domain.FLOAT, jnp.asarray(valid[n]))
            for n in FLOATS]
    names = ["passenger_count", "payment_type", "zone", *FLOATS]
    return Frame(out, RangeLabels(len(cols["zone"])), labels_from_values(names))


def write_csv(path: str, rows: int, seed: int):
    """A trip-record CSV with empty fields for nulls; returns pandas' parse."""
    import pandas as pd
    rng = np.random.default_rng(seed + 1)
    pdf = pd.DataFrame({
        "vendor_id": rng.integers(1, 3, rows),
        "passenger_count": rng.integers(1, 7, rows),
        "payment_type": np.asarray(PAYMENT, dtype=object)[
            rng.integers(0, len(PAYMENT), rows)],
        "trip_distance": np.round(rng.exponential(3.0, rows), 2),
        "fare_amount": np.round(rng.uniform(2.5, 80.0, rows), 2),
    })
    pdf.loc[rng.random(rows) < 0.01, "fare_amount"] = np.nan
    pdf.to_csv(path, index=False)
    return pd.read_csv(path, float_precision="round_trip")


# ---------------------------------------------------------------------------
def phases(s, df, pdf, dim_df, dim, args, tmpdir):
    """(name, run, check) triples: ``run()`` builds and collects one
    statement; ``check(result)`` compares it with pandas and returns a short
    description of what matched."""
    import pandas as pd
    from repro.core import DataFrame, QueryService, Session, EvalMode, get_dummies
    from repro.core import algebra as alg
    from repro.core import physical
    from repro.core.dtypes import Domain
    from repro.core.frame import Column

    out = []

    # --- fillna → filter → arithmetic map: the jit-traced UDF chain -------
    def fare(cols, frame):
        res = dict(cols)
        a, b = cols["f1"], cols["f2"]
        res["fare"] = Column(a.data * 2.0 + b.data, Domain.FLOAT, None, None)
        return res

    fare_udf = alg.Udf(name="smoke_fare", fn=fare, deps=frozenset(["f1", "f2"]),
                       elementwise=True)
    numeric = ["passenger_count", "zone", *FLOATS]
    jit_before = {}

    def run_udf():
        jit_before.update(physical.map_jit_counts())
        filled = df[numeric].fillna(0.0)
        return filled[filled["f0"] > 0.5].map_udf(fare_udf).collect()

    def check_udf(r):
        ref = pdf[numeric].fillna(0.0)
        ref = ref[ref["f0"] > 0.5]
        exact("labels", labels(r), ref.index.values)
        for n in numeric:
            exact(n, host(r.col(n))[0], ref[n].values.astype(host(r.col(n))[0].dtype))
        f1 = ref["f1"].values.astype(np.float32)
        f2 = ref["f2"].values.astype(np.float32)
        want = f1 * np.float32(2.0) + f2
        got = host(r.col("fare"))[0]
        ulp = np.spacing(np.abs(f1 * np.float32(2.0)) + np.abs(f2))
        expect(bool(np.all(np.abs(got - want) <= MAP_ULPS * ulp)),
               "fare: beyond 2 ulp of float32 numpy")
        now = physical.map_jit_counts()
        adopted = now["adopted"] - jit_before["adopted"]
        fell = now["fell_back"] - jit_before["fell_back"]
        print(f"  jit map chains: {adopted} adopted, {fell} fell back")
        if physical._jit_udfs_enabled():   # CPU keeps map runs eager
            expect(adopted >= 2 and fell == 0,
                   f"traceable map chains fell back ({adopted} adopted, "
                   f"{fell} fell back)")
        return f"{r.nrows} rows, fillna/filter exact, fare within {MAP_ULPS} ulp"

    out.append(("udf_chain", run_udf, check_udf))

    # --- groupby(n) with sum/mean/count/min/max ---------------------------
    spec = {"f0": ["sum", "mean", "count", "min", "max"],
            "f1": ["min", "max"], "f2": ["sum", "count"]}

    def check_groupby_n(r):
        ref = pdf.groupby("passenger_count").agg(spec)
        exact("keys", host(r.col("passenger_count"))[0], ref.index.values)
        worst = 0.0
        for c, fns in spec.items():
            absum = pdf[c].abs().groupby(pdf["passenger_count"]).sum().values
            cnt = pdf[c].groupby(pdf["passenger_count"]).count().values
            for fn in fns:
                got = column_values(r, f"{c}_{fn}")
                want = ref[(c, fn)].values
                if fn == "sum":
                    worst = max(worst, close(f"{c}_sum", got, want, absum,
                                             GROUPBY_RTOL))
                elif fn == "mean":
                    worst = max(worst, close(f"{c}_mean", got, want, absum / cnt,
                                             GROUPBY_RTOL))
                else:
                    exact(f"{c}_{fn}", got, want)
        return (f"{r.nrows} groups, counts/min/max exact, sums within "
                f"{worst:.2g}·Σ|x|")

    out.append(("groupby_n",
                lambda: df.groupby("passenger_count").agg(spec).collect(),
                check_groupby_n))

    # --- groupby(1): whole-frame aggregates -------------------------------
    aggs1 = ["sum", "count", "min", "max"]

    def check_groupby_1(r):
        ref = pdf[FLOATS].agg(aggs1)
        worst = 0.0
        for n in FLOATS:
            got = column_values(r, n)
            exact(f"{n} count/min/max", got[1:], ref[n].values[1:])
            worst = max(worst, close(f"{n} sum", got[:1], ref[n].values[:1],
                                     pdf[n].abs().sum(), GROUPBY_RTOL))
        return f"{len(FLOATS)} columns, sums within {worst:.2g}·Σ|x|"

    out.append(("groupby_1", lambda: df[FLOATS].agg(aggs1).collect(),
                check_groupby_1))

    # --- cumsum and cummax (WINDOW) ---------------------------------------
    def check_cumsum(r):
        worst = 0.0
        for n in ("f0", "f1"):
            want = pdf[n].cumsum().values
            got = column_values(r, n)
            exact(f"{n} nulls", np.isnan(got), np.isnan(want))
            ok = ~np.isnan(want)
            scale = pdf[n].abs().fillna(0.0).cumsum().values
            worst = max(worst, close(f"{n} cumsum", got[ok], want[ok], scale[ok],
                                     SCAN_RTOL))
        return f"{r.nrows} rows x 2, running sums within {worst:.2g}·Σ|x|"

    out.append(("cumsum", lambda: df[["f0", "f1"]].cumsum().collect(),
                check_cumsum))

    def check_cummax(r):
        for n in ("f2", "f3"):
            exact(f"{n} cummax", column_values(r, n), pdf[n].cummax().values)
        return f"{r.nrows} rows x 2 exact"

    out.append(("cummax", lambda: df[["f2", "f3"]].cummax().collect(),
                check_cummax))

    # --- sort_values -------------------------------------------------------
    def check_sort(r):
        ref = pdf.sort_values("f0", kind="stable")
        exact("order", labels(r), ref.index.values)
        exact("f0", column_values(r, "f0"), ref["f0"].values)
        exact("zone", host(r.col("zone"))[0], ref["zone"].values)
        return f"{r.nrows} rows, stable order exact"

    out.append(("sort_values", lambda: df.sort_values("f0").collect(),
                check_sort))

    # --- merge with the dimension table -----------------------------------
    def check_merge(r):
        ref = pdf.merge(pd.DataFrame(dim), on="zone")
        expect(r.nrows == len(ref), f"rows {r.nrows} != {len(ref)}")
        exact("zone", host(r.col("zone"))[0], ref["zone"].values)
        exact("f3", column_values(r, "f3"), ref["f3"].values)
        exact("zone_rate", column_values(r, "zone_rate"),
              ref["zone_rate"].values.astype(np.float32))
        codes, _ = host(r.col("borough"))
        table = np.asarray(r.col("borough").dictionary, dtype=object)
        exact("borough", table[codes], ref["borough"].values)
        return f"{r.nrows} rows, keys/values/order exact"

    out.append(("merge", lambda: df.merge(dim_df, on="zone").collect(),
                check_merge))

    # --- drop_duplicates on (key, category) -------------------------------
    def check_dedup(r):
        ref = pdf.drop_duplicates(["passenger_count", "payment_type"])
        exact("labels", labels(r), ref.index.values)
        exact("f4", column_values(r, "f4"), ref["f4"].values)
        return f"{r.nrows} first occurrences exact"

    out.append(("drop_duplicates",
                lambda: df.drop_duplicates(["passenger_count",
                                            "payment_type"]).collect(),
                check_dedup))

    # --- get_dummies (one-hot kernel) ---------------------------------------
    def check_dummies(r):
        ref = pd.get_dummies(pdf[["payment_type"]], columns=["payment_type"])
        for v in PAYMENT:
            exact(v, host(r.col(f"payment_type_{v}"))[0],
                  ref[f"payment_type_{v}"].values.astype(np.int32))
        return f"{r.nrows} rows x {len(PAYMENT)} indicators exact"

    out.append(("get_dummies",
                lambda: get_dummies(df[["payment_type", "f5"]],
                                    ["payment_type"]).collect(),
                check_dummies))

    # --- TRANSPOSE of a 200k x 32 matrix frame -------------------------------
    mrows = min(MATRIX_SHAPE[0], args.rows)
    mat = np.random.default_rng(args.seed + 2).standard_normal(
        (mrows, MATRIX_SHAPE[1]), dtype=np.float32)

    def run_transpose():
        import jax.numpy as jnp
        from repro.core import Frame
        from repro.core.labels import RangeLabels
        f = Frame([Column(jnp.asarray(mat[:, j]), Domain.FLOAT)
                   for j in range(mat.shape[1])],
                  RangeLabels(mrows), RangeLabels(mat.shape[1]))
        return DataFrame(f, session=s).T.collect()

    def check_transpose(r):
        expect(r.shape == (mat.shape[1], mrows), f"shape {r.shape}")
        got = np.stack([host(c)[0] for c in r.columns], axis=1)
        exact("values", got, mat.T)
        return f"{mrows}x{mat.shape[1]} -> {r.shape[0]}x{r.shape[1]} exact"

    out.append(("transpose", run_transpose, check_transpose))

    # --- CSV ingest through read_csv ---------------------------------------
    csv_path = os.path.join(tmpdir, "trips.csv")
    csv_pdf = write_csv(csv_path, max(1, args.rows // 10), args.seed)

    def run_csv():
        from repro.core import read_csv
        return read_csv(csv_path, session=s).collect()

    def check_csv(r):
        ref = csv_pdf
        expect(r.nrows == len(ref), f"rows {r.nrows} != {len(ref)}")
        for n in ("vendor_id", "passenger_count"):
            exact(n, host(r.col(n))[0], ref[n].values.astype(np.int32))
        for n in ("trip_distance", "fare_amount"):
            exact(n, column_values(r, n),
                  ref[n].values.astype(np.float32).astype(np.float64))
        codes, _ = host(r.col("payment_type"))
        table = np.asarray(r.col("payment_type").dictionary, dtype=object)
        exact("payment_type", table[codes], ref["payment_type"].values)
        return f"{r.nrows} rows x {r.ncols} cols parsed exact"

    out.append(("csv_ingest", run_csv, check_csv))

    # --- 4 QueryService tenants, concurrent vs serial -----------------------
    tenant_plans = [
        lambda d: d.groupby("passenger_count").agg({"f0": "mean", "f1": "max"}),
        lambda d: d[d["f2"] > 1.0].groupby("payment_type").agg({"f3": "sum"}),
        lambda d: d[d["f4"] < -2.0][["passenger_count", "f4"]].sort_values("f4"),
        lambda d: d.drop_duplicates(["zone"]),
    ]

    def as_arrays(f):
        return [labels(f)] + [a for c in f.columns for a in host(c)]

    def run_service():
        serial = []
        ref_s = Session(mode=EvalMode.LAZY)
        try:
            src = DataFrame(df.collect(), session=ref_s)
            for plan in tenant_plans:
                serial.append(as_arrays(plan(src).collect()))
        finally:
            ref_s.close()
        results, errors = {}, []
        with QueryService() as svc:
            node = svc.register_frame(df.collect())
            tenants = [svc.session(mode=EvalMode.LAZY) for _ in tenant_plans]

            def work(i):
                try:
                    d = DataFrame(session=tenants[i], node=node)
                    results[i] = as_arrays(tenant_plans[i](d).collect())
                except Exception as e:   # noqa: BLE001 - reported below
                    errors.append((i, e))

            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(len(tenants))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            expect(not any(t.is_alive() for t in threads), "tenant hung")
        if errors:
            raise errors[0][1]
        return serial, results

    def check_service(r):
        serial, results = r
        for i, want in enumerate(serial):
            got = results[i]
            expect(len(got) == len(want), f"tenant {i}: column count")
            for j, (g, w) in enumerate(zip(got, want)):
                exact(f"tenant {i} array {j}", g, w)
        return f"{len(serial)} tenants bit-identical to serial runs"

    out.append(("query_service", run_service, check_service))
    return out


def mosaic_check(kernels: KernelCalls) -> None:
    """The engine called every kernel program, and the program of its first
    call (same function, same argument shapes and static arguments) lowers to
    a Mosaic custom call on this backend, not to an interpret-mode emulation
    or the jnp reference."""
    for name in KERNELS:
        expect(kernels.calls[name] > 0, f"{name}: the engine never called it")
        args, kw = kernels.first[name]
        if "pallas" in kw:
            expect(kw["pallas"], f"{name}: traced for the jnp references")
        text = kernels.programs[name].lower(*args, **kw).compile().as_text()
        expect("tpu_custom_call" in text, f"{name}: no tpu_custom_call")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=10_000_000)
    args = ap.parse_args()

    try:
        import jax
        from repro import compile_cache
        from repro.core import DataFrame, EvalMode, Session, from_pydict
        from repro.core.schedule import pool_width
    except ImportError as e:
        print(f"cannot import the engine: {e}", file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}  jax {jax.__version__}")
    if dev.platform != "tpu" and args.rows > REHEARSAL_ROWS:
        print(f"no TPU (platform {dev.platform}); a rehearsal takes "
              f"--rows <= {REHEARSAL_ROWS}", file=sys.stderr)
        return 1

    cache_dir = compile_cache.enable()
    compile_cache.listen()
    kernels = KernelCalls()
    logging.basicConfig(level=logging.WARNING)
    logging.getLogger("repro.core.physical").setLevel(logging.INFO)
    print(f"rows: {args.rows}  csv rows: {max(1, args.rows // 10)}  seed: {args.seed}  "
          f"pool width: {pool_width()}  compile cache: {cache_dir}")

    t0 = time.perf_counter()
    cols, valid, pdf, dim = make_data(args.rows, args.seed)
    s = Session(mode=EvalMode.LAZY)
    df = DataFrame(engine_frame(cols, valid), session=s)
    dim_df = from_pydict({k: v.tolist() for k, v in dim.items()}, session=s)
    print(f"setup: data generated and placed in {time.perf_counter() - t0:.2f} s")

    failed = []
    with tempfile.TemporaryDirectory() as tmpdir:
        for name, run, check in phases(s, df, pdf, dim_df, dim, args, tmpdir):
            p0, h0 = compile_cache.counts()
            t = time.perf_counter()
            try:
                result = run()
                wall = time.perf_counter() - t
                p1, h1 = compile_cache.counts()
                programs, hits = p1 - p0, h1 - h0
                kind = "warm" if programs == hits else "cold"
                detail = check(result)
                print(f"phase {name}: {wall:.3f} s, {programs} programs "
                      f"({programs - hits} compiled, {hits} cache hits, {kind}); "
                      f"check ok: {detail}", flush=True)
            except Exception as e:   # noqa: BLE001 - every phase is reported
                failed.append(name)
                print(f"phase {name}: FAILED: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    s.close()

    print("kernel calls: " + ", ".join(
        f"{k} x{kernels.calls[k]}" for k in KERNELS))
    if dev.platform == "tpu":
        try:
            mosaic_check(kernels)
            print("mosaic kernels: ok (each kernel program the engine called "
                  "first, lowered again, holds a tpu_custom_call)")
        except Exception as e:   # noqa: BLE001
            failed.append("mosaic")
            print(f"mosaic kernels: FAILED: {e}", flush=True)
    mem = dev.memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        print(f"device memory: peak {mem['peak_bytes_in_use'] / 2**30:.2f} GiB "
              f"of {mem.get('bytes_limit', 0) / 2**30:.2f} GiB")
    programs, hits = compile_cache.counts()
    print(f"total: {programs} programs ({programs - hits} compiled, "
          f"{hits} persistent-cache hits) in {time.perf_counter() - t0:.1f} s")

    if failed:
        print(f"FAILED phases: {failed}", file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        print(f"rehearsal passed on {dev.platform}; no TPU, no result",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
