"""Microbatching serving front (ISSUE 10 tentpole): coalescing, padding
bit-identity, the max-wait bound, per-bucket jit reuse, typed overload
shedding, and the deterministic loadtest smoke run.

The load-bearing contract is bit-identity: a request's (scores, ids) —
ties included — must be exactly what a per-request ``retrieve_dense``
call returns, at every bucket size, because the panel padding rows are
scored and discarded before any slice can see them.
"""
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import SAEConfig, build_index, encode, init_params
from repro.errors import (
    EngineConfigError,
    InvalidQueryError,
    QueueFullError,
)
from repro.kernels.sparse_dot.kernel import BLOCK_Q
from repro.serving import (
    EngineConfig,
    GuardedEngine,
    MicrobatchServer,
    RetrievalEngine,
    RetrievalResponse,
)

REPO = pathlib.Path(__file__).parents[1]
CFG = SAEConfig(d=32, h=128, k=8)
B = BLOCK_Q  # 8: the panel-size quantum


@pytest.fixture(scope="module")
def setup():
    params = init_params(CFG, jax.random.PRNGKey(0))
    corpus = jax.random.normal(jax.random.PRNGKey(1), (310, CFG.d))
    queries = jax.random.normal(jax.random.PRNGKey(2), (64, CFG.d))
    codes = encode(params, corpus, CFG.k)
    index = build_index(codes, params)
    return params, index, queries


def make_engine(setup):
    params, index, _ = setup
    return RetrievalEngine(index, params,
                           config=EngineConfig(use_kernel=False))


# ------------------------------------------------- coalescing + identity
def test_burst_coalesces_into_one_full_panel_bit_identical(setup):
    """A burst whose rows fill the largest bucket dispatches as ONE
    panel, and every request's slice is bit-identical to its own
    per-request retrieve_dense call — including the 1-D (squeezed)
    submission."""
    params, index, queries = setup
    engine = make_engine(setup)
    # 3 + 1 (1-D) + 4 + 8 = 16 rows = largest bucket -> fires on the
    # last submit, no deadline involved
    reqs = [queries[0:3], queries[3], queries[4:8], queries[8:16]]
    with MicrobatchServer(engine, buckets=(B, 2 * B),
                          max_wait_us=30_000_000) as server:
        futures = [server.submit(x, 5) for x in reqs]
        resps = [f.result(timeout=60) for f in futures]
    for x, resp in zip(reqs, resps):
        want_s, want_i, *_ = engine.retrieve_dense(x, 5)
        assert isinstance(resp, RetrievalResponse)
        assert resp.scores.shape == want_s.shape  # squeeze preserved
        np.testing.assert_array_equal(np.asarray(resp.ids),
                                      np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(resp.scores),
                                      np.asarray(want_s))
        assert resp.queue_us >= 0.0
    s = server.stats()
    assert s["panels"] == 1 and s["panels_by_bucket"][2 * B] == 1
    assert s["padded_rows"] == 0 and s["occupancy_mean"] == 1.0


@pytest.mark.parametrize("rows", [1, 3, B, B + 1, 2 * B - 1, 2 * B])
def test_padding_never_leaks_at_any_bucket_fill(setup, rows):
    """A lone request of every fill level pads to the smallest bucket
    that fits; the sliced response is bit-identical to the unpadded
    per-request call, so the zero padding rows are unobservable."""
    params, index, queries = setup
    engine = make_engine(setup)
    x = queries[:rows]
    with MicrobatchServer(engine, buckets=(B, 2 * B),
                          max_wait_us=1000) as server:
        resp = server.serve(x, 7, timeout=60)
    want_s, want_i, *_ = engine.retrieve_dense(x, 7)
    np.testing.assert_array_equal(np.asarray(resp.ids), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(resp.scores),
                                  np.asarray(want_s))
    s = server.stats()
    bucket = B if rows <= B else 2 * B
    assert s["panels"] == 1 and s["panels_by_bucket"][bucket] == 1
    assert s["padded_rows"] == bucket - rows


def test_trickle_fires_partial_panels_on_max_wait(setup):
    """Requests arriving slower than max_wait never coalesce: each fires
    as its own padded panel once the oldest-request deadline passes — a
    trickle is never starved waiting for a batch that isn't coming."""
    params, index, queries = setup
    engine = make_engine(setup)
    with MicrobatchServer(engine, buckets=(B,),
                          max_wait_us=1000, max_queue_rows=B) as server:
        for r in range(3):
            resp = server.serve(queries[r], 5, timeout=60)
            want_s, want_i, *_ = engine.retrieve_dense(queries[r], 5)
            np.testing.assert_array_equal(np.asarray(resp.ids),
                                          np.asarray(want_i))
            time.sleep(0.02)  # > max_wait: the next request is alone too
    s = server.stats()
    assert s["panels"] == 3 and s["padded_rows"] == 3 * (B - 1)
    assert s["occupancy_mean"] == pytest.approx(1 / B)


def test_mixed_topn_requests_never_share_a_panel(setup):
    """top-n is a compile-time constant of the serve jit, so requests
    with different n ride separate panels but all resolve correctly."""
    params, index, queries = setup
    engine = make_engine(setup)
    with MicrobatchServer(engine, buckets=(B,),
                          max_wait_us=1000) as server:
        f5 = [server.submit(queries[i], 5) for i in range(2)]
        f9 = [server.submit(queries[i + 2], 9) for i in range(2)]
        r5 = [f.result(timeout=60) for f in f5]
        r9 = [f.result(timeout=60) for f in f9]
    assert all(r.ids.shape == (5,) for r in r5)
    assert all(r.ids.shape == (9,) for r in r9)
    for i, resp in enumerate(r5):
        _, want_i, *_ = engine.retrieve_dense(queries[i], 5)
        np.testing.assert_array_equal(np.asarray(resp.ids),
                                      np.asarray(want_i))
    for i, resp in enumerate(r9):
        _, want_i, *_ = engine.retrieve_dense(queries[i + 2], 9)
        np.testing.assert_array_equal(np.asarray(resp.ids),
                                      np.asarray(want_i))
    assert server.stats()["panels"] >= 2  # n=5 and n=9 panels are disjoint


# ------------------------------------------------------- jit reuse
def test_one_trace_per_bucket_then_cache_hits(setup):
    """The engine only ever sees bucket-shaped panels, so the serve jit
    traces exactly once per (bucket, n) — warmup pre-pays all of them and
    steady-state traffic adds zero retraces.  ``encode_queries``'s Python
    body runs once per trace, making it the compile counter."""
    params, index, queries = setup
    engine = make_engine(setup)
    traces = []
    orig = engine.encode_queries
    engine.encode_queries = lambda xb: (traces.append(tuple(xb.shape)),
                                        orig(xb))[1]
    with MicrobatchServer(engine, buckets=(B, 2 * B),
                          max_wait_us=1000) as server:
        server.warmup(5)
        assert sorted(t[0] for t in traces) == [B, 2 * B]
        # traffic at both fill levels: partial (pads to B) and full 2B
        server.serve(queries[:3], 5, timeout=60)
        fs = [server.submit(queries[i * B:(i + 1) * B], 5)
              for i in range(2)]
        for f in fs:
            f.result(timeout=60)
    assert sorted(t[0] for t in traces) == [B, 2 * B]  # zero retraces


# ------------------------------------------------------ overload shedding
class _GatedEngine:
    """Blocks the dispatcher inside retrieve_dense until released, so the
    queue state during an in-flight panel is deterministic."""

    def __init__(self, inner):
        self.engine = inner  # warmup unwraps via .engine
        self.entered = threading.Event()
        self.release = threading.Event()

    def retrieve_dense(self, x, n):
        self.entered.set()
        assert self.release.wait(timeout=60)
        return self.engine.retrieve_dense(x, n)


def test_queue_full_sheds_typed_then_retry_succeeds(setup):
    params, index, queries = setup
    gated = _GatedEngine(make_engine(setup))
    server = MicrobatchServer(gated, buckets=(B,), max_queue_rows=B,
                              max_wait_us=1000)
    try:
        # panel A fills the only bucket -> dispatcher drains it and
        # blocks inside the gated engine; the queue is empty again
        fa = server.submit(queries[:B], 5)
        assert gated.entered.wait(timeout=60)
        # panel B refills the queue to max_queue_rows
        fb = server.submit(queries[B:2 * B], 5)
        # request C finds 8 + 1 > max_queue_rows -> typed shed, and the
        # error carries the admission numbers
        with pytest.raises(QueueFullError) as exc:
            server.submit(queries[0], 5)
        assert exc.value.queued_rows == B
        assert exc.value.max_queue_rows == B
        assert server.stats()["shed"] == 1
        gated.release.set()
        ra, rb = fa.result(timeout=60), fb.result(timeout=60)
        # the retried request flows through the normal path and carries
        # the same ServingStatus surface as every response
        rc = server.serve(queries[0], 5, timeout=60)
        assert rc.status.path == ra.status.path
        assert not rc.status.degraded
        _, want_i, *_ = gated.engine.retrieve_dense(queries[0], 5)
        np.testing.assert_array_equal(np.asarray(rc.ids),
                                      np.asarray(want_i))
    finally:
        gated.release.set()
        server.close()


# ----------------------------------------------------- guard + validation
def test_batcher_over_guard_passes_status_through(setup):
    """GuardedEngine under the batcher: responses carry the guard's
    ServingStatus and stay bit-identical to the guard's own answers."""
    params, index, queries = setup
    guard = GuardedEngine(make_engine(setup))
    with MicrobatchServer(guard, buckets=(B,),
                          max_wait_us=1000) as server:
        server.warmup(5)
        resp = server.serve(queries[:3], 5, timeout=60)
    want_s, want_i, status, *_ = guard.retrieve_dense(queries[:3], 5)
    assert resp.status.path == status.path
    np.testing.assert_array_equal(np.asarray(resp.ids), np.asarray(want_i))


def test_submit_validation_and_lifecycle(setup):
    params, index, queries = setup
    engine = make_engine(setup)
    server = MicrobatchServer(engine, buckets=(B,), max_wait_us=1000)
    with pytest.raises(InvalidQueryError, match="rank"):
        server.submit(jnp.zeros((2, 2, CFG.d)), 5)
    with pytest.raises(InvalidQueryError, match="empty"):
        server.submit(queries[:0], 5)
    with pytest.raises(InvalidQueryError, match="largest panel bucket"):
        server.submit(jnp.asarray(np.zeros((B + 1, CFG.d))), 5)
    assert server.stats()["requests"] == 0  # none of those were admitted
    server.close()
    server.close()  # idempotent
    with pytest.raises(EngineConfigError, match="closed"):
        server.submit(queries[0], 5)


def test_bucket_configuration_is_validated(setup):
    engine = make_engine(setup)
    with pytest.raises(EngineConfigError, match="ascending"):
        MicrobatchServer(engine, buckets=(2 * B, B))
    with pytest.raises(EngineConfigError, match="multiples"):
        MicrobatchServer(engine, buckets=(B, B + 1))
    with pytest.raises(EngineConfigError, match="max_queue_rows"):
        MicrobatchServer(engine, buckets=(B, 4 * B), max_queue_rows=B)


# ------------------------------------------------------- loadtest smoke
@pytest.mark.timeout(600)
def test_loadtest_smoke_writes_schema_valid_record(tmp_path):
    """The traffic-shaped loadtest driver end to end at smoke size: the
    run must emit a BENCH_serving.json that the serving-schema gate
    (tools/check_bench.py --schema serving) accepts against itself.
    Timing is machine noise, so a slow/failed run SKIPs (non-gating, like
    the benchmark smoke) — but a SUCCEEDED run's record schema gates."""
    out = tmp_path / "BENCH_serving.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.loadtest", "--smoke",
             "--catalog", "1200", "--train-steps", "8", "--requests", "48",
             "--users", "64", "--out", str(out)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=540,
        )
    except subprocess.TimeoutExpired:
        pytest.skip("loadtest smoke timed out (non-gating)")
    if proc.returncode != 0:
        pytest.skip(
            "loadtest smoke failed (non-gating):\n"
            f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}"
        )
    records = json.loads(out.read_text())
    by_name = {r["name"]: r for r in records}
    assert {"serving_closed_loop", "serving_open_loop"} <= set(by_name)
    for r in records:
        assert 0.0 <= r["shed_rate"] <= 1.0, r
        assert 0.0 <= r["occupancy_mean"] <= 1.0, r
        assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"], r
        assert r["requests"] == 48 and r["smoke"] is True, r
    # the serving-schema gate accepts the fresh record against itself
    gate = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_bench.py"),
         str(out), str(out), "--schema", "serving"],
        capture_output=True, text=True, timeout=60,
    )
    assert gate.returncode == 0, gate.stdout + gate.stderr
