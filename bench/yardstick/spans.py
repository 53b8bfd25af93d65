"""The program's own ``serve.*`` spans in a traced window, for the metric
readers in ``bench/metrics``.

The serving stack records each step of the served path as a
``jax.profiler.TraceAnnotation`` named ``serve.<layer>.<step>`` (the table
is in ``repro.serving``): host events on the profiler's clock, the clock of
``run.trace``'s device ops.  They are read, with the line (thread) each is
on, from the newest ``.xplane.pb`` under ``<checkout>/.bench_trace``, the
directory ``bench/run.py`` traces into, once per trace file and process.
A program that records no such span gives an empty list, and every reader
built on it returns None.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from pathlib import Path

from yardstick.trace import DEVICE_PLANE, idle_gaps, merge

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
PREFIX = "serve."
PANEL = "serve.batcher.panel"      # marks the dispatcher's line
SUBMIT = "serve.batcher.submit"    # a caller's span, wherever it runs
NO_SPAN = "no span"

_loaded: dict[tuple[str, float], list] = {}


@dataclass
class HostSpan:
    name: str
    start: int
    end: int
    line: tuple            # (plane name, line index): one thread
    stats: dict = field(default_factory=dict)


def load(trace_dir: Path = TRACE_DIR) -> list[HostSpan]:
    """The ``serve.*`` events of the newest trace under ``trace_dir``,
    sorted by start; read once per file."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return []
    key = (files[-1], os.path.getmtime(files[-1]))
    if key not in _loaded:
        from jax.profiler import ProfileData

        spans = []
        for plane in ProfileData.from_file(files[-1]).planes:
            if DEVICE_PLANE.match(plane.name):
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        spans.append(HostSpan(ev.name, s,
                                              s + int(ev.duration_ns),
                                              (plane.name, i),
                                              dict(ev.stats)))
        _loaded[key] = sorted(spans, key=lambda sp: sp.start)
    return _loaded[key]


def events(run) -> list[HostSpan]:
    """Every ``serve.*`` span of the run's trace (none untraced)."""
    return load() if run.trace is not None else []


def named(run, name: str) -> list[HostSpan]:
    """The spans called ``name`` that start in the window."""
    a, b = run.trace.window()
    return [sp for sp in events(run) if sp.name == name and a <= sp.start < b]


def per_panel_ms(run, names: tuple[str, ...]) -> float | None:
    """Mean over the window's panels of the summed length, in ms, of the
    spans called ``names`` inside each panel on its own line.  None with no
    panel span, and where the trace holds no device op: there the engine
    ran on the host and its spans time the work, not the host's share of
    it."""
    panels = named(run, PANEL)
    if not panels or not run.trace.ops:
        return None
    inner = [sp for sp in events(run) if sp.name in names]
    starts = [sp.start for sp in inner]
    total = 0
    for p in panels:
        lo = bisect.bisect_left(starts, p.start)
        hi = bisect.bisect_right(starts, p.end)
        total += sum(sp.end - sp.start for sp in inner[lo:hi]
                     if sp.line == p.line and sp.end <= p.end)
    return total / len(panels) * 1e-6


def innermost(spans: list[HostSpan]):
    """``(times, names)``: from ``times[i]`` until the next time, the
    innermost of ``spans`` open is ``names[i]`` (None where none is): the
    one that started last, which for the spans of one thread is the one
    nested deepest."""
    marks = sorted([(sp.end, 0, -sp.start, i) for i, sp in enumerate(spans)]
                   + [(sp.start, 1, -sp.end, i) for i, sp in enumerate(spans)])
    open_: list[int] = []
    times: list[int] = []
    names: list = []
    for t, is_start, _, i in marks:
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        name = spans[open_[-1]].name if open_ else None
        if times and times[-1] == t:
            names[-1] = name
        else:
            times.append(t)
            names.append(name)
    return times, names


def idle_gaps_by_span(run) -> list[tuple[str, int, int]]:
    """Each device-idle gap of the window as ``(span, start, end)`` ns,
    named by the innermost ``serve.*`` span open on the dispatcher's line
    (the line of the panel spans) at the gap's midpoint; a submit span
    there is the callback of an answer and does not count.  ``NO_SPAN``
    where none is open.  Empty without panel spans or device ops."""
    tr = run.trace
    spans = events(run)
    lines = {sp.line for sp in spans if sp.name == PANEL}
    if not lines or not tr.ops:
        return []
    times, names = innermost([sp for sp in spans if sp.line in lines
                              and sp.name != SUBMIT])
    a, b = tr.window()
    out = []
    for dev in tr.devices():
        busy = merge(sorted((max(o.start, a), min(o.end, b)) for o in tr.ops
                            if o.device == dev and o.end > a and o.start < b))
        for g0, g1 in idle_gaps(busy, a, b):
            i = bisect.bisect_right(times, (g0 + g1) // 2) - 1
            out.append((names[i] if i >= 0 and names[i] else NO_SPAN, g0, g1))
    return out


def idle_by_span(run) -> dict[str, float]:
    """The window's device-idle seconds by the span that holds each gap
    (``idle_gaps_by_span``), per device on average, largest first."""
    gaps = idle_gaps_by_span(run)
    if not gaps:
        return {}
    n_dev = len(run.trace.devices())
    total: dict[str, float] = {}
    for name, g0, g1 in gaps:
        total[name] = total.get(name, 0.0) + (g1 - g0) * 1e-9 / n_dev
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))
