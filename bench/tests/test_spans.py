"""The program's ``serve.*`` spans on a synthesised trace: the device's idle
gaps named by the dispatcher's innermost span, the three per-panel host
readers, None where the program records no span, and the loader on a real
profiler trace."""
import importlib.util
import threading
from pathlib import Path

import pytest

from yardstick import spans
from yardstick.spans import HostSpan
from yardstick.trace import Op, Span, Trace

BENCH = Path(__file__).resolve().parents[1]
MS = 1_000_000      # ns
US = 1_000
D = ("/host:CPU", 1)    # the dispatcher's line
C = ("/host:CPU", 0)    # a caller's line
DEV = "/device:TPU:0"


def _host():
    def sp(name, a, b, line=D, **stats):
        return HostSpan(name, int(a), int(b), line, stats)

    return sorted([
        sp("serve.batcher.coalesce", 0, 10 * MS, rows=1),
        sp("serve.batcher.panel", 10 * MS, 45 * MS, panel=0, rows=1,
           bucket=8, requests=0),
        sp("serve.batcher.assemble", 10 * MS, 12 * MS),
        sp("serve.guard.admit", 12 * MS, 14 * MS, rows=8),
        sp("serve.engine.dispatch", 14 * MS, 16 * MS, rows=8, n=100),
        sp("serve.batcher.wait", 16 * MS, 40 * MS),
        sp("serve.batcher.resolve", 44 * MS, 45 * MS, requests=1),
        # an answer's callback sends the next request from the dispatcher
        sp("serve.batcher.submit", 44 * MS + 200 * US, 44 * MS + 800 * US,
           request=1),
        sp("serve.batcher.coalesce", 46 * MS, 50 * MS, rows=16),
        sp("serve.batcher.panel", 50 * MS, 90 * MS, panel=1, rows=16,
           bucket=16, requests="1 2"),
        sp("serve.batcher.assemble", 50 * MS, 51 * MS),
        sp("serve.guard.admit", 51 * MS, 53 * MS, rows=16),
        sp("serve.engine.dispatch", 53 * MS, 55 * MS, rows=16, n=100),
        sp("serve.engine.trace", 53 * MS + 500 * US, 54 * MS + 500 * US,
           rows=16, n=100),
        sp("serve.batcher.wait", 55 * MS, 85 * MS),
        sp("serve.batcher.resolve", 85 * MS, 89 * MS, requests=2),
        sp("serve.batcher.coalesce", 90 * MS, 205 * MS, rows=8),
        # a panel after the window closes is not one of the window's
        sp("serve.batcher.panel", 205 * MS, 230 * MS, panel=2, rows=8,
           bucket=8, requests="3"),
        sp("serve.batcher.assemble", 205 * MS, 215 * MS),
        sp("serve.guard.admit", 215 * MS, 220 * MS, rows=8),
        sp("serve.engine.dispatch", 220 * MS, 225 * MS, rows=8, n=100),
        sp("serve.batcher.resolve", 225 * MS, 230 * MS, requests=1),
        # the callers' own line: never the dispatcher's
        sp("serve.batcher.submit", 1 * MS, 2 * MS, C, request=0),
        sp("serve.batcher.submit", 45 * MS, 46 * MS, C, request=2),
    ], key=lambda s: s.start)


def _ops(busy):
    return [Op("scan", a, b, DEV, "scan") for a, b in busy]


# device busy around seven idle gaps, each with its midpoint in another span
BUSY = [(0, 11 * MS), (14 * MS, 17 * MS), (20 * MS, 44 * MS),
        (45 * MS, 45 * MS + 200 * US), (46 * MS, 53 * MS + 600 * US),
        (54 * MS + 400 * US, 89 * MS + 200 * US),
        (89 * MS + 800 * US, 150 * MS)]
WANT = {
    "serve.batcher.coalesce": 0.050,     # 150-200: waiting for arrivals
    "serve.guard.admit": 0.003,          # 11-14
    "serve.batcher.wait": 0.003,         # 17-20
    "serve.batcher.resolve": 0.001,      # 44-45, a submit inside it
    "no span": 0.0008,                   # 45.2-46, only a caller's submit
    "serve.engine.trace": 0.0008,        # 53.6-54.4, inside the dispatch
    "serve.batcher.panel": 0.0006,       # 89.2-89.8, after the resolve
}


class _Run:
    def __init__(self, ops):
        self.trace = Trace(ops=ops, spans=[Span("bench.window", 0, 200 * MS)])


@pytest.fixture
def host(monkeypatch):
    evs = _host()
    monkeypatch.setattr(spans, "events", lambda run: evs)
    return evs


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_innermost_prefers_the_deepest_open_span():
    evs = [HostSpan("a", 0, 10, D), HostSpan("b", 2, 5, D),
           HostSpan("c", 5, 7, D)]
    times, names = spans.innermost(evs)
    assert times == [0, 2, 5, 7, 10]
    # at 5, b's end and c's start coincide: c holds it
    assert names == ["a", "b", "c", "a", None]


def test_idle_seconds_are_named_by_the_dispatchers_innermost_span(host):
    got = spans.idle_by_span(_Run(_ops(BUSY)))
    assert got.keys() == WANT.keys()
    for name, s in WANT.items():
        assert got[name] == pytest.approx(s, abs=1e-12), name
    assert list(got)[0] == "serve.batcher.coalesce"    # largest first
    idle = _Run(_ops(BUSY)).trace.idle_share() * 0.2
    assert sum(got.values()) == pytest.approx(idle)


def test_gaps_carry_their_span_and_place(host):
    gaps = spans.idle_gaps_by_span(_Run(_ops(BUSY)))
    longest = max(gaps, key=lambda g: g[2] - g[1])
    assert longest == ("serve.batcher.coalesce", 150 * MS, 200 * MS)
    assert ("serve.batcher.resolve", 44 * MS, 45 * MS) in gaps


@pytest.mark.parametrize("name,want", [
    ("batcher_host_ms", (2 + 1 + 1 + 4) / 2),   # assemble + resolve
    ("admit_ms", (2 + 2) / 2),
    ("dispatch_ms", (2 + 2) / 2),
])
def test_per_panel_readers(host, name, want):
    assert _reader(name)(_Run(_ops(BUSY))) == pytest.approx(want)


@pytest.mark.parametrize("name", ["batcher_host_ms", "admit_ms",
                                  "dispatch_ms"])
def test_readers_find_nothing_without_serve_spans(monkeypatch, name):
    monkeypatch.setattr(spans, "events", lambda run: [])
    run = _Run(_ops(BUSY))
    assert _reader(name)(run) is None
    assert spans.idle_by_span(run) == {}


@pytest.mark.parametrize("name", ["batcher_host_ms", "admit_ms",
                                  "dispatch_ms"])
def test_readers_find_nothing_without_device_ops(host, name):
    """With the engine run on the host (no device plane), its spans time
    the work itself: nothing to read."""
    run = _Run([])
    assert _reader(name)(run) is None
    assert spans.idle_by_span(run) == {}


def test_load_reads_serve_spans_with_their_thread(tmp_path):
    import jax

    def dispatcher():
        with jax.profiler.TraceAnnotation("serve.batcher.panel", rows=2,
                                          requests="0 1"):
            with jax.profiler.TraceAnnotation("serve.guard.admit"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("serve.batcher.submit") as sp:
            sp.set_metadata(request=0)
        with jax.profiler.TraceAnnotation("bench.panel"):
            t = threading.Thread(target=dispatcher)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    got = spans.load(tmp_path)
    assert [s.name for s in got] == ["serve.batcher.submit",
                                     "serve.batcher.panel",
                                     "serve.guard.admit"]
    submit, panel, admit = got
    assert submit.stats == {"request": 0}
    assert panel.stats == {"rows": 2, "requests": "0 1"}
    assert panel.line == admit.line != submit.line
    assert panel.start <= admit.start <= admit.end <= panel.end
    assert spans.load(tmp_path) is got      # read once
    assert spans.load(tmp_path / "empty") == []
