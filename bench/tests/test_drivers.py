"""The window arithmetic of both traffic drivers, against a fake server
whose timing and answers the test controls."""
import importlib.util
import threading
import time
from concurrent.futures import Future
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from yardstick.window import Window, percentile

DRIVERS = Path(__file__).resolve().parents[1] / "drivers"


class Full(Exception):
    pass


def _driver(name):
    spec = importlib.util.spec_from_file_location(f"drv_{name}",
                                                  DRIVERS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeServer:
    """Answers everything queued once every ``service_s`` (one panel), on
    path "ok", except that every ``off_every``-th request is answered on
    another rung and every ``shed_every``-th submit is refused."""

    def __init__(self, service_s, *, off_every=0, shed_every=0,
                 stall_s=0.0):
        self.service_s, self.off_every = service_s, off_every
        self.shed_every, self.stall_s = shed_every, stall_s
        self.queue, self.lock, self.n = [], threading.Lock(), 0
        self.closed = False
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def submit(self, x, n):
        with self.lock:
            self.n += 1
            if self.shed_every and self.n % self.shed_every == 0:
                raise Full()
            f = Future()
            self.queue.append((self.n, f))
            return f

    def _loop(self):
        time.sleep(self.stall_s)
        while not self.closed:
            time.sleep(self.service_s)
            with self.lock:
                batch, self.queue = self.queue, []
            for i, f in batch:
                off = self.off_every and i % self.off_every == 0
                status = SimpleNamespace(path="other" if off else "ok",
                                         step=1 if off else 0,
                                         degraded=bool(off))
                f.set_result(SimpleNamespace(status=status, queue_us=1.0))

    def close(self):
        self.closed = True
        self.thread.join(timeout=5)


def _ctx(server, traffic, seed=3):
    return SimpleNamespace(server=server, pool=[np.zeros(4)] * 16,
                           traffic=traffic, seed=seed, queue_full=Full,
                           top_n=10, drain_s=10.0)


def test_closed_loop_counts_the_work_due_in_the_window():
    server = FakeServer(0.05)
    w = Window(1.0, "ok")
    try:
        _driver("closed").run(_ctx(server, {"clients": 4}), w)
    finally:
        server.close()
    done = w.served()
    assert all(w.t0 <= r.due < w.t1 for r in done)
    # 4 clients, one answer each per 50 ms panel: about 80 in 1 s, and the
    # rate over the time to the last answer is 4 per panel, not a count
    # that steps by a panel with where the window happens to close
    assert 60 <= len(done) <= 88
    assert w.served_rate() == pytest.approx(4 / 0.05, rel=0.15)
    assert w.served_rate() == len(done) / (max(r.done for r in done) - w.t0)
    lat = w.latencies_s()
    assert percentile(lat, 50) == pytest.approx(0.05, abs=0.03)
    # every request sent inside the window was answered: the clients kept
    # the load up past the close until it was, and what they sent after
    # the close is not counted
    sent = w.sent()
    assert all(r.done is not None for r in sent)
    assert all(r.due >= w.t1 for r in w.requests if r not in sent)
    c = w.counts()
    assert c["attempted"] == len(sent) and c["failed"] == 0


def _open_dues(seed, schedule_seed=0):
    server = FakeServer(0.005)
    w = Window(0.3, "ok")
    try:
        _driver("open").run(_ctx(server, {"rate_rps": 50, "schedule_seed":
                                          schedule_seed}, seed), w)
    finally:
        server.close()
    return [round(r.due - w.t0, 9) for r in w.requests]


def test_open_loop_schedule_is_the_cells_not_the_runs():
    assert _open_dues(1) == _open_dues(2 ** 33 + 5)
    assert _open_dues(1) != _open_dues(1, schedule_seed=1)


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 5])
def test_open_loop_sends_a_fixed_count_and_times_from_the_schedule(seed):
    server = FakeServer(0.01, stall_s=0.3)
    w = Window(1.0, "ok")
    try:
        _driver("open").run(_ctx(server, {"rate_rps": 50,
                                          "schedule_seed": 0}, seed), w)
    finally:
        server.close()
    assert w.counts()["attempted"] == 50
    dues = sorted(r.due - w.t0 for r in w.requests)
    assert 0 <= dues[0] and dues[-1] < 1.0
    # the server stalls 0.3 s first: a request due at t0 + u waits about
    # 0.3 - u, charged from its due time, not from a late send
    early = [r for r in w.requests if r.due - w.t0 < 0.1]
    for r in early:
        assert r.done - r.due >= 0.3 - (r.due - w.t0) - 0.02
    assert w.generator_late_s < 0.05


def test_shed_and_off_rung_requests_fail_and_are_not_served():
    server = FakeServer(0.02, off_every=5, shed_every=7)
    w = Window(0.5, "ok")
    try:
        _driver("open").run(_ctx(server, {"rate_rps": 140,
                                          "schedule_seed": 0}), w)
    finally:
        server.close()
    c = w.counts()
    sent = w.sent()
    assert c["attempted"] == 70
    assert c["shed"] == sum(r.shed for r in sent) == 10
    assert c["off_rung"] > 0
    assert c["failed"] == c["shed"] + c["off_rung"] + c["unanswered"]
    served = w.served()
    assert all(r.response.status.path == "ok" for r in served)
    assert len(served) == c["attempted"] - c["failed"]
    assert len(w.latencies_s()) == c["attempted"] - c["failed"]


def test_percentile_is_numpys_linear_one():
    v = np.arange(1, 101, dtype=float)
    assert percentile(v, 50) == 50.5 and percentile(v, 95) == pytest.approx(
        95.05)
    assert percentile(np.array([]), 50) is None


def test_closed_loop_client_sends_again_after_a_shed():
    server = FakeServer(0.02, off_every=5, shed_every=7)
    w = Window(0.5, "ok")
    try:
        _driver("closed").run(_ctx(server, {"clients": 4}), w)
    finally:
        server.close()
    c = w.counts()
    assert c["shed"] > 0 and c["off_rung"] > 0 and c["unanswered"] == 0
    assert c["failed"] == c["shed"] + c["off_rung"]
    # every client kept sending: far more requests than clients
    assert c["attempted"] > 40
