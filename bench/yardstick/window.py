"""One measured window: what each request did, and the arithmetic over it.

The drivers in ``bench/drivers`` fill a ``Window`` with one ``Request`` per
request they sent.  A request's latency runs from when it was due (the
send in a closed loop, the scheduled arrival in an open loop) to when its
future resolved.  A request that was shed, raised, or was answered on any
path but the configured one at ladder step 0 has failed.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np


class Request:
    __slots__ = ("pool_index", "due", "submitted", "done", "response",
                 "error", "shed")

    def __init__(self, pool_index: int, due: float):
        self.pool_index = pool_index
        self.due = due
        self.submitted = None
        self.done = None
        self.response = None
        self.error = None
        self.shed = False

    def resolve(self, future) -> None:
        """Done-callback of the request's future: stamp and keep what it
        resolved to."""
        self.done = time.monotonic()
        err = future.exception()
        if err is None:
            self.response = future.result()
        else:
            self.error = err


class Window:
    """The requests of one run's measured window, ``[t0, t1)``."""

    def __init__(self, seconds: float, expected_path: str):
        self.seconds = float(seconds)
        self.expected_path = expected_path
        self.t0 = None
        self.t1 = None
        self.requests: list[Request] = []
        self.generator_late_s = 0.0   # worst lateness of a send
        self._lock = threading.Lock()
        self._span = None

    def open(self) -> float:
        """Open the window (and its ``bench.window`` trace span) now."""
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.monotonic()
        self.t1 = self.t0 + self.seconds
        return self.t0

    def wait_close(self) -> None:
        """Sleep until the window's end and close its span; call from the
        thread that opened it."""
        wait = self.t1 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        self._span.__exit__(None, None, None)

    def add(self, req: Request) -> None:
        with self._lock:
            self.requests.append(req)

    # ------------------------------------------------------------ verdicts
    def on_rung(self, req: Request) -> bool:
        s = req.response.status
        return s.path == self.expected_path and s.step == 0 and not s.degraded

    def ok(self, req: Request) -> bool:
        return (req.response is not None and req.done is not None
                and self.on_rung(req))

    def sent(self) -> list[Request]:
        """Requests due inside the window."""
        return [r for r in self.requests if self.t0 <= r.due < self.t1]

    def counts(self) -> dict:
        sent = self.sent()
        return {
            "attempted": len(sent),
            "shed": sum(r.shed for r in sent),
            "unanswered": sum(1 for r in sent if not r.shed
                              and (r.error is not None or r.done is None)),
            "off_rung": sum(1 for r in sent if r.response is not None
                            and not self.on_rung(r)),
            "failed": sum(1 for r in sent if not self.ok(r)),
        }

    def served(self) -> list[Request]:
        """Requests due inside the window that were answered on the
        configured path at ladder step 0 (the drivers wait for each)."""
        return [r for r in self.sent() if self.ok(r)]

    def served_rate(self) -> float | None:
        """Requests served of those due in the window, over the time from
        the window's opening to the last of their answers: all the work
        the window was sent and all the time it took.  Counting only the
        answers inside ``[t0, t1)`` would step by a whole panel."""
        done = self.served()
        if not done:
            return None
        return len(done) / (max(r.done for r in done) - self.t0)

    def latencies_s(self) -> np.ndarray:
        """Latency of every request due in the window that was served."""
        return np.asarray([r.done - r.due for r in self.served()],
                          dtype=np.float64)


def percentile(values: np.ndarray, q: float) -> float | None:
    """Linear-interpolated percentile (``numpy.percentile``), None when
    there are no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(values, q))
