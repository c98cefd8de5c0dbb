"""One run of one benchmark cell: set-up, a measured window, the check.

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``bench/configs/<config>/``: ``config.json`` and one
generator per table) and its traffic (``bench/traffic/<traffic>.json``: the
tables it reads and each client's round of statements, each a template of
``bench/templates/`` with its parameters); the per-layer metrics that list
the cell are read by ``bench/metrics/<metric>.py``.  Adding a cell, a mix or
a metric adds files and entries, and edits none.

A run:

1. generates the traffic's tables from the seed (numpy, vectorised) and
   places them on the device as the engine's frames, in one ``Session`` or,
   for a service mix, as a shared table of one ``QueryService`` with one
   tenant session per client; the materialization cache is off;
2. warms up: every client runs its round once, the clients concurrently as
   in the window, so every program the window uses is compiled (first run in
   a checkout) or loaded from the persistent compile cache;
3. measures: the clients run concurrently, closed loop, each repeating its
   round in an order drawn from the seed, until ``seconds`` have passed; each
   then finishes the round it is in, so every run does whole rounds.  A
   statement's latency runs from the call until every array of its result is
   ready;
4. with ``trace``, the profiler records the window and the engine's spans
   are on; the per-layer readers take their numbers from both;
5. after the window: reads peak device memory, closes the engine, and
   compares the window's answers (all of them, or one round per client drawn
   from the seed where answers are large) with the plain reference.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import threading
import time
from collections import Counter
from pathlib import Path

import numpy as np

from bench import check, tables

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".traces"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def template(name: str):
    return importlib.import_module(f"bench.templates.{name}")


def metric(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
class CompileLog:
    """XLA programs built in this process (``programs``) and how many came
    from the persistent cache (``hits``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.programs = 0
        self.hits = 0

    def on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.programs += 1

    def on_event(self, event, **kw):
        if event == CACHE_HIT:
            with self._lock:
                self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.programs, self.hits


class KernelCalls:
    """Records the arguments' shapes of each call the engine makes to the
    programs of ``repro.kernels.ops`` that the roofline readers need, for
    the traced window only (``start`` / ``stop``)."""

    PROGRAMS = ("_segment_reduce_multi_prog",)

    def __init__(self):
        self._lock = threading.Lock()
        self.calls: dict[str, list] = {n: [] for n in self.PROGRAMS}
        self._orig: dict = {}

    def start(self):
        from repro.kernels import ops
        for name in self.PROGRAMS:
            fn = getattr(ops, name)
            self._orig[name] = fn
            setattr(ops, name, self._wrap(name, fn))

    def stop(self):
        from repro.kernels import ops
        for name, fn in self._orig.items():
            setattr(ops, name, fn)
        self._orig.clear()

    def _wrap(self, name, fn):
        import jax

        def shape(x):
            return (tuple(x.shape), str(x.dtype), id(x)) if hasattr(x, "shape") else x

        def call(*args, **kw):
            rec = jax.tree.map(shape, (args, kw),
                               is_leaf=lambda x: x is None or hasattr(x, "shape"))
            with self._lock:
                self.calls[name].append(rec)
            return fn(*args, **kw)
        return call


def ready(result) -> None:
    """Wait until every device array of a collected result is computed."""
    import jax
    if hasattr(result, "columns") and hasattr(result, "col_labels"):
        jax.block_until_ready([a for c in result.columns for a in (c.data, c.mask)
                               if isinstance(a, jax.Array)])


def exec_stats(sessions) -> dict:
    import dataclasses
    out: Counter = Counter()
    for s in sessions:
        out.update({k: v for k, v in dataclasses.asdict(s.stats).items()
                    if isinstance(v, int)})
    return dict(out)


# ---------------------------------------------------------------------------
class Engine:
    """The system under test for one run: its sessions and each client's
    view of the traffic's tables."""

    def __init__(self, generated: dict, service: bool, clients: int, trace: bool):
        from repro.core import DataFrame, EvalMode, QueryService, Session
        self.service = None
        from repro.core.trace import Tracer
        # a ring that holds every span of a traced window
        tracer = (lambda: Tracer(ring=1 << 22)) if trace else (lambda: None)
        if service:
            self.service = QueryService(cache_budget_bytes=0)
            self.sessions = [self.service.session(mode=EvalMode.LAZY, trace=tracer())
                             for _ in range(clients)]
            nodes = {n: self.service.register_frame(t.frame()) for n, t in generated.items()}
            self.tables = [{n: DataFrame(session=s, node=node) for n, node in nodes.items()}
                           for s in self.sessions]
        else:
            s = Session(mode=EvalMode.LAZY, cache_budget_bytes=0, trace=tracer())
            self.sessions = [s]
            views = {n: DataFrame(t.frame(), session=s) for n, t in generated.items()}
            self.tables = [views] * clients

    def stats(self) -> dict:
        return exec_stats(self.sessions)

    def spans(self) -> list:
        out = []
        for s in self.sessions:
            tr = s.tracer
            if tr is not None:
                out.extend(tr.snapshot())
        return out

    def close(self) -> None:
        if self.service is not None:
            self.service.close()
        else:
            for s in self.sessions:
                s.close()


class Statement:
    __slots__ = ("client", "round", "index", "template", "params", "t0", "t1",
                 "error", "result", "kept")

    def __init__(self, client, rnd, index, template, params):
        self.client, self.round, self.index = client, rnd, index
        self.template, self.params = template, params
        self.t0 = self.t1 = 0.0
        self.error = None
        self.result = None
        self.kept = False


class Window:
    """What a per-layer reader sees of the measured window."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---------------------------------------------------------------------------
def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             rows: int | None = None, require_chip: bool = True,
             t_start: float | None = None, log=None, keep=None) -> dict:
    """One run; returns the result line's object.  ``rows`` caps every
    table's rows (a rehearsal or a test); ``require_chip`` raises
    :class:`NoChip` unless JAX sees a TPU with the chips the cell asks for.
    ``t_start`` is when the process started (``setup_s`` counts from it).
    ``keep`` receives the window's statements and the reference tables
    before the check (the readings of ``bench/readings.py``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    bench = benchmark()
    w = workload(bench, cell)
    mix = traffic(w["traffic"])
    conf = tables.config(w["config"])

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_chip and (platform != "tpu" or len(devs) < w["chips"]):
        raise NoChip(f"cell {cell} needs {w['chips']} TPU chip(s); JAX found "
                     f"{len(devs)} {platform} device(s)")
    devs = devs[:w["chips"]]
    t_dev = time.perf_counter() - t_start
    if platform == "tpu":
        # The engine's own cache, <checkout>/.jax_cache, whatever
        # JAX_COMPILATION_CACHE_DIR the machine sets: two checkouts measured
        # side by side (a parent and its change) then share no program, and
        # no cap the machine sets evicts what the next run of this one needs.
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        from repro import compile_cache
        compile_cache.enable()
        jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)

    # ---- set-up: tables, engine, statement parameters ----------------------
    generated = {}
    for name in mix["tables"]:
        n = conf["tables"][name]["rows"]
        generated[name] = tables.load(w["config"], name, min(n, rows or n), seed)
    t_gen = time.perf_counter() - t_start
    host = tables.Host(generated)
    clients = mix["clients"]
    engine = Engine(generated, mix.get("service", False), len(clients), trace)
    rounds = [[(s["template"], template(s["template"]).prepare(host, s["params"]))
               for s in stmts] for stmts in clients]
    log(f"set-up: JAX and the device ready at {t_dev:.2f} s; "
        f"{sum(t.rows for t in generated.values())} rows, "
        f"{sum(t.nbytes() for t in generated.values()) / 2**30:.3f} GiB generated "
        f"at {t_gen:.2f} s, placed at {time.perf_counter() - t_start:.2f} s")

    # ---- warm-up: each client's round once, the clients concurrently --------
    def warm(k: int):
        for name, p in rounds[k]:
            ready(template(name).run(engine.tables[k], p))

    threads = [threading.Thread(target=warm, args=(k,), name=f"bench-warm-{k}")
               for k in range(len(rounds))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    c1 = compiles.snapshot()
    log(f"warm-up: {c1[0]} programs ({c1[1]} from the persistent cache) "
        f"at {time.perf_counter() - t_start:.2f} s")

    # ---- window ------------------------------------------------------------
    kernels = KernelCalls() if trace else None
    profile = None
    if trace:
        from bench import xtrace
        profile = xtrace.Capture(TRACE_DIR / f"{cell}-{seed}")
    keep_round = [int(tables.stream(seed, 7, k).integers(0, 2)) for k in range(len(rounds))]
    done: list[list[Statement]] = [[] for _ in rounds]
    stats0 = engine.stats()
    setup_s = time.perf_counter() - t_start
    if trace:
        kernels.start()
        profile.start()
    c_w0 = compiles.snapshot()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def client(k: int):
        rng = tables.stream(seed, 8, k)
        stmts = rounds[k]
        r = 0
        while True:
            for i in rng.permutation(len(stmts)):
                name, p = stmts[i]
                st = Statement(k, r, int(i), name, p)
                st.t0 = time.perf_counter()
                try:
                    res = template(name).run(engine.tables[k], p)
                    ready(res)
                    if template(name).SMALL or r == keep_round[k] or (r == 0 and keep_round[k] == 1):
                        st.result, st.kept = res, True
                except Exception as e:   # noqa: BLE001 - a failed statement is counted
                    st.error = f"{type(e).__name__}: {e}"
                st.t1 = time.perf_counter()
                done[k].append(st)
            if r == 1 and keep_round[k] == 1:
                for s in done[k]:
                    if s.round == 0 and not template(s.template).SMALL:
                        s.result, s.kept = None, False
            r += 1
            if time.perf_counter() >= deadline:
                return

    threads = [threading.Thread(target=client, args=(k,), name=f"bench-client-{k}")
               for k in range(len(rounds))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t1 = max(s.t1 for d in done for s in d)
    c_w1 = compiles.snapshot()
    if trace:
        profile.stop()
        kernels.stop()
    stats1 = engine.stats()
    spans = [s for s in engine.spans() if s.t0 >= t0 * 1e9]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    engine.close()

    stmts = [s for d in done for s in d]
    ok = [s for s in stmts if s.error is None]
    lat_ms = np.asarray([(s.t1 - s.t0) * 1e3 for s in ok])
    window_s = t1 - t0
    per_tmpl = Counter(s.template for s in stmts)
    log(f"window: {len(stmts)} statements ({len(stmts) - len(ok)} failed) in "
        f"{window_s:.3f} s; rounds per client "
        f"{[max(s.round for s in d) + 1 for d in done]}; per template "
        + ", ".join(f"{k} {v}" for k, v in sorted(per_tmpl.items())))
    if len(lat_ms):
        log(f"latency: p50 {np.percentile(lat_ms, 50):.1f} ms, p90 "
            f"{np.percentile(lat_ms, 90):.1f} ms, max {lat_ms.max():.1f} ms over "
            f"{len(lat_ms)} statements")
    for s in stmts:
        if s.error:
            log(f"failed: {s.template} {s.params}: {s.error}")
    delta = {k: stats1.get(k, 0) - stats0.get(k, 0) for k in stats1}
    log(f"compiles in window: {c_w1[0] - c_w0[0]} programs "
        f"({c_w1[1] - c_w0[1]} from the persistent cache); cache hits "
        f"{delta.get('cache_hits', 0)} (source-table reads; the cache is off)")

    # ---- end-to-end metrics --------------------------------------------------
    e2e = {"setup_s": setup_s,
           "stmt_per_s": len(ok) / window_s if window_s > 0 else 0.0,
           "stmt_p90_ms": float(np.percentile(lat_ms, 90)) if len(lat_ms) else None}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    device = {"platform": platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": int(mem)}
    metrics = {}
    breakdown = None
    if not trace:
        for m in cell_metrics(bench, cell, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        dtrace = profile.load()
        win = Window(statements=len(ok), stats=delta, spans=spans, t0=t0, t1=t1,
                     compiles=(c_w1[0] - c_w0[0], c_w1[1] - c_w0[1]), setup_compiles=c_w0,
                     device=dtrace, kernels=kernels.calls, device_kind=devs[0].device_kind)
        for m in cell_metrics(bench, cell, "per_layer"):
            v = metric(m["name"]).read(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        if dtrace is not None:
            device["busy_s"] = dtrace.busy_s()
            device["window_s"] = dtrace.window_s()
            breakdown = xtrace.breakdown(dtrace, spans)
        reuse = sum(1 for s in spans if s.cat == "cache"
                    and not s.name.endswith(":source"))
        log(f"materialization-cache and in-flight reuse in window: {reuse}")

    # ---- the check -----------------------------------------------------------
    if keep is not None:
        keep(stmts, host)
    t_check = time.perf_counter()
    checks = compare(stmts, host)
    log(f"reference and comparison: {time.perf_counter() - t_check:.1f} s")
    correct = (len(ok) == len(stmts) and len(stmts) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:g})")
    out = {"correct": bool(correct), "attempted": len(stmts),
           "failed": len(stmts) - len(ok), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def compare(stmts, host, lowp: bool = False) -> dict:
    """The window's kept answers against the reference, each template's
    numbers as the worst over its statements, with the template's limits.
    With ``lowp`` the answers compared are the control's (the reference in
    bfloat16) for the same statements.  Each answer is released once
    compared."""
    numbers: dict[str, float] = {}
    limits: dict[str, float] = {}
    want: dict = {}
    control: dict = {}
    for s in stmts:
        if not s.kept:
            continue
        mod = template(s.template)
        key = (s.template, json.dumps(s.params, sort_keys=True, default=str))
        if key not in want:
            want[key] = mod.reference(host, s.params)
        if lowp:
            if key not in control:
                control[key] = check.view(mod.reference(host, s.params, lowp=True))
            got = control[key]
        else:
            got = check.view(s.result)
            s.result = None
        for name, v in mod.compare(got, want[key]).items():
            numbers[name] = max(numbers.get(name, 0.0), float(v))
            limits[name] = mod.LIMITS[name]
    return {n: {"value": numbers[n], "limit": limits[n]} for n in sorted(numbers)}
