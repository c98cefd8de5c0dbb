"""The device's side of a traced window, from JAX's profiler.

:class:`Capture` records the window with ``jax.profiler`` and marks it with a
host annotation; :meth:`Capture.load` reduces the ``.xplane.pb`` to a
:class:`DeviceTrace`: the device's operations and its programs (XLA
modules) as ``(start_ns, end_ns, name)`` on the trace's clock, the window on
that clock, and the offset from the host's ``perf_counter_ns`` to it.  The
per-layer readers of ``bench/metrics/`` take their numbers from a
``DeviceTrace``; one cut from a chip trace is kept as a test fixture.
"""
from __future__ import annotations

import glob
import shutil
import time
from pathlib import Path

WINDOW_MARK = "bench_window"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


class DeviceTrace:
    def __init__(self, ops, programs, window, offset_ns=0):
        self.ops = sorted((int(a), int(b), n) for a, b, n in ops)
        self.programs = sorted((int(a), int(b), n) for a, b, n in programs)
        self.window = (int(window[0]), int(window[1]))
        self.offset_ns = int(offset_ns)

    # -- what the readers ask --------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self) -> list[tuple[int, int]]:
        """The union of the device operations' intervals, inside the window."""
        out: list[list[int]] = []
        w0, w1 = self.window
        for a, b, _ in self.ops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy()) / 1e9

    def program_s(self, name: str) -> float:
        """Device seconds of the programs whose name holds ``name``."""
        w0, w1 = self.window
        return sum(min(b, w1) - max(a, w0) for a, b, n in self.programs
                   if name in n and min(b, w1) > max(a, w0)) / 1e9

    def gaps(self) -> list[tuple[int, int]]:
        """The device's idle intervals inside the window."""
        out, t = [], self.window[0]
        for a, b in self.busy():
            if a > t:
                out.append((t, a))
            t = b
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    # -- the test fixture's form -----------------------------------------
    @classmethod
    def from_json(cls, doc: dict) -> "DeviceTrace":
        return cls(doc["ops"], doc["programs"], doc["window"], doc.get("offset_ns", 0))


class Capture:
    """Profiler capture of the measured window into ``directory`` (emptied
    first; the trace is deleted once read)."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self._mark = None
        self._t0_ns = 0

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._t0_ns = time.perf_counter_ns()
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()

    def stop(self) -> None:
        import jax
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def load(self) -> DeviceTrace | None:
        """The device's operations inside the marked window; None when the
        trace holds no device."""
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not files:
            return None
        try:
            return reduce_xplane(files[0], self._t0_ns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def reduce_xplane(path: str, mark_perf_ns: int = 0) -> DeviceTrace | None:
    """Read an ``.xplane.pb``: the first TPU's operations and programs, and
    the window from the host annotation ``WINDOW_MARK``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, programs, window = [], [], None
    device = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = plane.name
            for line in plane.lines:
                dest = (ops if line.name == OPS_LINE
                        else programs if line.name == PROGRAMS_LINE else None)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if device is None:
        return None
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_MARK!r} annotation on the host")
    return DeviceTrace(ops, programs, window, offset_ns=window[0] - mark_perf_ns)


# ---------------------------------------------------------------------------
def breakdown(trace: DeviceTrace, spans, top: int = 10) -> dict:
    """The device programs that took most time, and the device's idle time
    by what the host was doing: each idle gap goes to the innermost engine
    span open at its middle, on any thread (``client`` where none was)."""
    by_prog: dict[str, float] = {}
    w0, w1 = trace.window
    for a, b, n in trace.programs:
        d = min(b, w1) - max(a, w0)
        if d > 0:
            by_prog[n] = by_prog.get(n, 0.0) + d / 1e9
    # sweep over span starts (0), gap middles (1) and span ends (2)
    points = []
    for i, sp in enumerate(spans):
        if sp.dur > 0:
            a = sp.t0 + trace.offset_ns
            points += [(a, 0, i), (a + sp.dur, 2, i)]
    gaps = trace.gaps()
    points += [((a + b) // 2, 1, g) for g, (a, b) in enumerate(gaps)]
    points.sort()
    active: dict[int, int] = {}
    idle: dict[str, float] = {}
    for _, kind, i in points:
        if kind == 0:
            active[i] = spans[i].dur
        elif kind == 2:
            active.pop(i, None)
        else:
            a, b = gaps[i]
            inner = min(active, key=active.get) if active else None
            label = _family(spans[inner].name) if inner is not None else "client"
            idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_prog), "idle_gaps": rank(idle)}


def _family(name: str) -> str:
    """A span's name without the per-call detail after a ':' argument list
    (``chunk:12`` and ``chunk:13`` are one family)."""
    head, _, tail = name.partition(":")
    return name if not tail or not tail[:1].isdigit() else head

