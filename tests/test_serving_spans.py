"""The serving path's profiler spans: every ``serve.*`` span of the
batcher, guard and engine is recorded on the profiler's clock, nested as
the call path nests, numbered so that a panel names its requests, and the
serve program's trace span fires (with its counter) only while JAX traces.

One profiler trace covers, through ``MicrobatchServer -> GuardedEngine ->
RetrievalEngine``: the warm-up of both buckets, a burst of 32 one-row
requests (two full 16-row panels) and then one lone request (an 8-row
panel once its wait runs out).  The ``.xplane.pb`` is read back with
``jax.profiler.ProfileData``.
"""
import glob
import os
from dataclasses import dataclass

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import SAEConfig, build_index, encode, init_params
from repro.kernels.sparse_dot.kernel import BLOCK_Q
from repro.serving import (
    EngineConfig,
    GuardedEngine,
    MicrobatchServer,
    RetrievalEngine,
)

CFG = SAEConfig(d=32, h=128, k=8)
B = BLOCK_Q
TOP_N = 5
SPANS = (
    "serve.batcher.submit", "serve.batcher.coalesce", "serve.batcher.panel",
    "serve.batcher.assemble", "serve.guard.admit", "serve.engine.dispatch",
    "serve.engine.trace", "serve.batcher.wait", "serve.batcher.resolve",
)


@dataclass
class Ev:
    name: str
    start: int
    end: int
    line: tuple
    stats: dict

    def holds(self, other: "Ev") -> bool:
        return (self.line == other.line and self.start <= other.start
                and other.end <= self.end)


def _read(trace_dir) -> list[Ev]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    assert files, f"no trace under {trace_dir}"
    evs = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    s = int(e.start_ns)
                    evs.append(Ev(e.name, s, s + int(e.duration_ns),
                                  (plane.name, i), dict(e.stats)))
    return sorted(evs, key=lambda e: e.start)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    params = init_params(CFG, jax.random.PRNGKey(0))
    corpus = jax.random.normal(jax.random.PRNGKey(1), (310, CFG.d))
    queries = jax.random.normal(jax.random.PRNGKey(2), (40, CFG.d))
    engine = RetrievalEngine(build_index(encode(params, corpus, CFG.k),
                                         params), params,
                             config=EngineConfig(use_kernel=False))
    rows = [queries[i] for i in range(33)]
    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        # a full bucket fires at once; the lone request after its wait
        with MicrobatchServer(GuardedEngine(engine), buckets=(B, 2 * B),
                              max_wait_us=1_000_000) as server:
            server.warmup(TOP_N)
            warm_traces = engine.counters["serve_traces"]
            futures = [server.submit(x, TOP_N) for x in rows[:32]]
            for f in futures:
                f.result(timeout=60)
            server.serve(rows[32], TOP_N, timeout=60)
    finally:
        jax.profiler.stop_trace()
    return _read(trace_dir), warm_traces, engine.counters["serve_traces"]


def _named(evs, name):
    return [e for e in evs if e.name == name]


def _requests(panel: Ev) -> list[int]:
    # one number reads back as an int, several as a spaced string
    return [int(v) for v in str(panel.stats["requests"]).split()]


def test_every_span_is_recorded(traced):
    evs, _, _ = traced
    assert {e.name for e in evs} == set(SPANS)
    assert all(e.end >= e.start for e in evs)


def test_admit_and_dispatch_nest_inside_the_panel_on_one_line(traced):
    evs, _, _ = traced
    panels = _named(evs, "serve.batcher.panel")
    assert [p.stats["rows"] for p in panels] == [2 * B, 2 * B, 1]
    assert [p.stats["bucket"] for p in panels] == [2 * B, 2 * B, B]
    assert [p.stats["panel"] for p in panels] == [0, 1, 2]
    for p in panels:
        for name in ("serve.batcher.assemble", "serve.guard.admit",
                     "serve.engine.dispatch", "serve.batcher.wait",
                     "serve.batcher.resolve"):
            inside = [e for e in _named(evs, name) if p.holds(e)]
            assert len(inside) == 1, (name, p)
        admit, = [e for e in _named(evs, "serve.guard.admit") if p.holds(e)]
        disp, = [e for e in _named(evs, "serve.engine.dispatch")
                 if p.holds(e)]
        assert admit.end <= disp.start
        assert admit.stats["rows"] == p.stats["bucket"]
        assert disp.stats["rows"] == p.stats["bucket"]
        assert disp.stats["n"] == TOP_N
        resolve, = [e for e in _named(evs, "serve.batcher.resolve")
                    if p.holds(e)]
        assert resolve.stats["requests"] == p.stats["rows"]
    # every panel follows the dispatcher's coalescing on the same line
    coalesce = _named(evs, "serve.batcher.coalesce")
    for p in panels:
        before = [c for c in coalesce if c.line == p.line
                  and c.end <= p.start]
        assert before and before[-1].stats["rows"] == p.stats["rows"]


def test_panel_names_the_requests_its_submit_spans_numbered(traced):
    evs, _, _ = traced
    submits = _named(evs, "serve.batcher.submit")
    numbers = sorted(s.stats["request"] for s in submits)
    assert numbers == list(range(33))
    panels = _named(evs, "serve.batcher.panel")
    assert [_requests(p) for p in panels] == [
        list(range(16)), list(range(16, 32)), [32]]
    # the submits ran on the caller's thread, not the dispatcher's
    assert not {s.line for s in submits} & {p.line for p in panels}


def test_serve_program_traces_once_per_bucket_in_warmup_only(traced):
    evs, warm_traces, all_traces = traced
    traces = _named(evs, "serve.engine.trace")
    assert len(traces) == warm_traces == all_traces == 2
    assert sorted(t.stats["rows"] for t in traces) == [B, 2 * B]
    first_submit = min(e.start for e in _named(evs, "serve.batcher.submit"))
    assert all(t.end <= first_submit for t in traces)
    # each trace happens inside the engine dispatch that caused it
    dispatches = _named(evs, "serve.engine.dispatch")
    assert all(any(d.holds(t) for d in dispatches) for t in traces)
