"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read.

The trace is the ``.xplane.pb`` the profiler writes, read with
``jax.profiler.ProfileData``.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<i>`` plane; host spans are the
events named ``bench.*`` that the benchmark records with
``jax.profiler.TraceAnnotation`` around its own calls (``bench.window``
around the measured window, ``bench.panel`` around each panel the batcher
hands the engine).  Both are on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str
    start: int
    end: int
    device: str
    text: str = ""    # name and string stats, what kernel patterns search


@dataclass
class Span:
    name: str
    start: int
    end: int
    stats: dict = field(default_factory=dict)


@dataclass
class Trace:
    ops: list[Op] = field(default_factory=list)
    spans: list[Span] = field(default_factory=list)

    # -------------------------------------------------------------- window
    def window(self) -> tuple[int, int]:
        """(start, end) ns of the ``bench.window`` span."""
        ws = [s for s in self.spans if s.name == SPAN_PREFIX + "window"]
        if len(ws) != 1:
            raise ValueError(f"trace holds {len(ws)} bench.window spans")
        return ws[0].start, ws[0].end

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    def devices(self) -> list[str]:
        return sorted({o.device for o in self.ops})

    # ---------------------------------------------------------------- busy
    def busy_s(self, start: int, end: int, device: str | None = None
               ) -> float:
        """Seconds in [start, end) in which some op ran on ``device``
        (the union of op intervals, so nested or overlapping ops count
        once)."""
        ivs = sorted((max(o.start, start), min(o.end, end)) for o in self.ops
                     if (device is None or o.device == device)
                     and o.end > start and o.start < end)
        return union_length(ivs) * 1e-9

    def window_busy_s(self) -> float:
        """Device-busy seconds of the window, averaged over devices."""
        a, b = self.window()
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(self.busy_s(a, b, d) for d in devs) / len(devs)

    def idle_share(self) -> float | None:
        w = self.window_s()
        if w <= 0 or not self.ops:
            return None
        return 1.0 - self.window_busy_s() / w

    # ------------------------------------------------------------- kernels
    def spans_named(self, name: str) -> list[Span]:
        """``bench.<name>`` spans that start inside the window."""
        a, b = self.window()
        return [s for s in self.spans
                if s.name == SPAN_PREFIX + name and a <= s.start < b]

    def ops_within(self, start: int, end: int, pattern: str) -> list[Op]:
        """Ops starting in [start, end) whose name or string stats match
        ``pattern``."""
        rx = re.compile(pattern)
        return [o for o in self.ops if start <= o.start < end
                and rx.search(o.text)]

    # ----------------------------------------------------------- breakdown
    def breakdown(self, top: int = 10) -> dict:
        """The ``top`` device ops by total time in the window, and the idle
        time named by the innermost benchmark span the host was in at each
        gap's middle: per name the total over all gaps first, then the
        longest single gaps."""
        a, b = self.window()
        per_op: dict[str, int] = {}
        for o in self.ops:
            if o.end > a and o.start < b:
                name = o.name.split(" = ", 1)[0]   # HLO text: its name
                per_op[name] = per_op.get(name, 0) + (
                    min(o.end, b) - max(o.start, a))
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for dev in self.devices():
            ivs = sorted((max(o.start, a), min(o.end, b)) for o in self.ops
                         if o.device == dev and o.end > a and o.start < b)
            for g0, g1 in idle_gaps(merge(ivs), a, b):
                gaps.append((self._label((g0 + g1) // 2), g1 - g0))
        totals: dict[str, list[int]] = {}
        for name, t in gaps:
            tot = totals.setdefault(name, [0, 0])
            tot[0] += t
            tot[1] += 1
        idle = [[f"{n}: all {c} gaps", t * 1e-9] for n, (t, c) in
                sorted(totals.items(), key=lambda kv: -kv[1][0])]
        gaps.sort(key=lambda g: -g[1])
        idle += [[f"{n}: one gap", t * 1e-9] for n, t in gaps]
        return {"device_ops": [[n, t * 1e-9] for n, t in ops],
                "idle_gaps": idle[:top]}

    def _label(self, t: int) -> str:
        inner = None
        for sp in self.spans:
            if sp.start <= t < sp.end and sp.name != SPAN_PREFIX + "window":
                if inner is None or sp.start >= inner.start:
                    inner = sp
        return inner.name if inner else "outside bench.panel"


def merge(ivs: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in ivs:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(ivs: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in merge(sorted(ivs)))


def idle_gaps(busy: list[tuple[int, int]], a: int, b: int):
    """The intervals of [a, b) that no merged busy interval covers."""
    t = a
    for s, e in busy:
        if s > t:
            yield t, s
        t = max(t, e)
    if t < b:
        yield t, b


def _text(ev) -> str:
    parts = [ev.name]
    for key, val in ev.stats:
        if isinstance(val, str):
            parts.append(f"{key}={val}")
    return " ".join(parts)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    trace.ops.append(Op(ev.name, s, s + int(ev.duration_ns),
                                        plane.name, _text(ev)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        trace.spans.append(Span(
                            ev.name, s, s + int(ev.duration_ns),
                            dict(ev.stats)))
    return trace
