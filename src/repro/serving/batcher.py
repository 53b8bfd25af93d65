"""Dynamic microbatching serving front (ISSUE 10 tentpole).

Production retrieval traffic is thousands of concurrent single-user
requests; the kernels want BLOCK_Q-aligned query panels.
``MicrobatchServer`` is the admission layer between the two: a request
queue plus one dispatcher thread that coalesces concurrent arrivals into
bucketed panels and serves each panel with ONE call into the existing
``RetrievalEngine``/``GuardedEngine`` stack.

Semantics, in order of what matters:

* **Bit-identity.**  A request's rows ride a shared panel, padded with
  zero rows up to the smallest configured bucket that fits; responses
  are sliced back per request before the padding can leak.  Because
  every scoring path is row-independent, the sliced (scores, ids) are
  bit-identical — ties included — to a per-request ``retrieve_dense``
  call at ANY bucket size (gated by ``tests/test_batcher.py``).
* **Bounded tail latency.**  The dispatcher waits for more arrivals only
  until the OLDEST queued request is ``max_wait_us`` old, then fires a
  partial panel — a lone trickle request is never starved waiting for a
  batch that isn't coming.  A full bucket fires immediately.
* **One jit per bucket.**  Buckets are the only panel shapes the engine
  ever sees (requests wider than the largest bucket are rejected at
  submit as ``InvalidQueryError``), so the engine's per-``n`` jit
  retraces exactly ``len(buckets)`` times and steady state is a cache
  hit regardless of arrival pattern.  ``warmup(n)`` pre-compiles all of
  them before traffic.
* **Typed overload shedding.**  ``submit`` raises ``QueueFullError``
  (never blocks, never buffers unboundedly) once ``max_queue_rows`` rows
  are already queued.  A shed-then-retried request flows through the
  normal path and still carries its ``ServingStatus``.
* **The unified response.**  Every request resolves to the same
  ``RetrievalResponse`` the engine and guard return, with ``queue_us``
  (submit → dispatch) filled in and the underlying layer's
  ``ServingStatus`` passed through — batching is invisible to the
  response surface.
* **Spans.**  Each step is a ``serve.batcher.*`` profiler span (see
  ``repro.serving``): ``submit`` on the caller's thread, and on the
  dispatcher's ``coalesce``, then ``panel`` around ``assemble``, the
  engine call, ``wait`` and ``resolve``.  Admitted requests and panels
  are numbered, so a panel's span names the requests it carries.

Requests with different ``n`` never share a panel (the top-n width is a
compile-time constant of the serve jit); the queue stays FIFO per ``n``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.errors import EngineConfigError, InvalidQueryError, QueueFullError
from repro.kernels.sparse_dot.kernel import BLOCK_Q
from repro.serving.response import RetrievalResponse

DEFAULT_BUCKETS = (BLOCK_Q, 2 * BLOCK_Q, 4 * BLOCK_Q, 8 * BLOCK_Q)


class _Request:
    """One queued submission: rows + bookkeeping + the caller's future."""

    __slots__ = ("x", "n", "rows", "squeeze", "t_submit", "future", "seq")

    def __init__(self, x, n: int, rows: int, squeeze: bool):
        self.x = x
        self.n = n
        self.rows = rows
        self.squeeze = squeeze
        self.t_submit = time.monotonic()
        self.future: Future = Future()
        self.seq = -1   # numbered at admission


class MicrobatchServer:
    """Coalesce concurrent ``retrieve_dense`` submissions into
    BLOCK_Q-aligned panels served by one underlying engine.

    engine:        a ``RetrievalEngine`` or ``GuardedEngine`` — anything
                   whose ``retrieve_dense(x, n)`` returns a
                   ``RetrievalResponse``.
    buckets:       ascending panel sizes, each a BLOCK_Q multiple; a
                   panel pads to the smallest bucket that fits its rows.
    max_wait_us:   how long the oldest queued request may age before a
                   partial panel fires (the trickle-latency bound).
    max_queue_rows: admission bound — ``submit`` sheds with a typed
                   ``QueueFullError`` once this many rows are queued.
    """

    def __init__(
        self,
        engine,
        *,
        buckets: Optional[Sequence[int]] = None,
        max_wait_us: float = 2000.0,
        max_queue_rows: int = 256,
    ):
        buckets = tuple(int(b) for b in (buckets or DEFAULT_BUCKETS))
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise EngineConfigError(
                f"buckets must be ascending and distinct: {buckets}"
            )
        bad = [b for b in buckets if b < 1 or b % BLOCK_Q]
        if bad:
            raise EngineConfigError(
                f"buckets must be positive multiples of BLOCK_Q="
                f"{BLOCK_Q}: {bad}"
            )
        if max_wait_us < 0:
            raise EngineConfigError(
                f"max_wait_us must be >= 0, got {max_wait_us}"
            )
        if max_queue_rows < buckets[-1]:
            raise EngineConfigError(
                f"max_queue_rows ({max_queue_rows}) must fit at least one "
                f"largest-bucket panel ({buckets[-1]} rows)"
            )
        self.engine = engine
        self.buckets = buckets
        self.max_wait_us = float(max_wait_us)
        self.max_queue_rows = int(max_queue_rows)
        self._queue: deque[_Request] = deque()
        self._queued_rows = 0
        self._cond = threading.Condition()
        self._closed = False
        self._stats = {
            "requests": 0, "rows": 0, "shed": 0, "panels": 0,
            "padded_rows": 0, "occupancy_sum": 0.0,
            "panels_by_bucket": {b: 0 for b in buckets},
        }
        self._request_seq = itertools.count()
        self._panel_seq = itertools.count()
        self._dispatcher = threading.Thread(
            target=self._loop, name="microbatch-dispatcher", daemon=True
        )
        self._dispatcher.start()

    # ----------------------------------------------------------- lifecycle
    def __enter__(self) -> "MicrobatchServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Stop accepting work, drain what is queued, join the
        dispatcher.  Idempotent."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._dispatcher.join()

    # ------------------------------------------------------------- serving
    def submit(self, x, n: int) -> Future:
        """Enqueue one request; returns a ``Future`` resolving to its
        ``RetrievalResponse`` (or raising the engine's typed error).

        Raises ``QueueFullError`` immediately when ``max_queue_rows``
        rows are already queued (overload shedding — never blocks the
        caller), and ``InvalidQueryError`` for malformed queries, so bad
        or shed requests never occupy panel slots.
        """
        with TraceAnnotation("serve.batcher.submit") as span:
            req = self._enqueue(x, n)
            span.set_metadata(request=req.seq)
        return req.future

    def _enqueue(self, x, n: int) -> _Request:
        """Validate one submission, then queue and number it (or shed)."""
        x = jnp.asarray(x) if isinstance(x, (list, np.ndarray)) else x
        if not hasattr(x, "ndim") or x.ndim not in (1, 2):
            raise InvalidQueryError(
                "x: expected a (d,) query or a (q, d) batch, got "
                f"{type(x).__name__}"
                + (f" of rank {x.ndim}" if hasattr(x, "ndim") else "")
            )
        squeeze = x.ndim == 1
        rows = 1 if squeeze else int(x.shape[0])
        if rows == 0:
            raise InvalidQueryError("x: empty query batch (0 rows)")
        if rows > self.buckets[-1]:
            raise InvalidQueryError(
                f"x: {rows} query rows exceed the largest panel bucket "
                f"({self.buckets[-1]}) — split the batch or configure "
                "larger buckets"
            )
        req = _Request(x[None] if squeeze else x, int(n), rows, squeeze)
        with self._cond:
            if self._closed:
                raise EngineConfigError("MicrobatchServer is closed")
            if self._queued_rows + rows > self.max_queue_rows:
                self._stats["shed"] += 1
                raise QueueFullError(
                    f"queue full: {self._queued_rows} rows queued + "
                    f"{rows} submitted > max_queue_rows="
                    f"{self.max_queue_rows}; request shed",
                    queued_rows=self._queued_rows,
                    max_queue_rows=self.max_queue_rows,
                )
            req.seq = next(self._request_seq)
            self._queue.append(req)
            self._queued_rows += rows
            self._stats["requests"] += 1
            self._stats["rows"] += rows
            self._cond.notify_all()
        return req

    def serve(self, x, n: int, timeout: Optional[float] = None
              ) -> RetrievalResponse:
        """Synchronous convenience: ``submit`` + wait."""
        return self.submit(x, n).result(timeout=timeout)

    def warmup(self, n: int) -> None:
        """Pre-compile the serve jit at every bucket size for top-``n``
        (zero panels through the real path), so first-traffic latency is
        a cache hit, not a trace."""
        core = getattr(self.engine, "engine", self.engine)  # unwrap guard
        d = core.params["w_enc"].shape[0]
        for b in self.buckets:
            resp = self.engine.retrieve_dense(jnp.zeros((b, d)), n)
            jax.block_until_ready(resp.ids)

    def stats(self) -> dict:
        """A consistent snapshot of the serving counters, with the mean
        panel occupancy (real rows / bucket rows) derived."""
        with self._cond:
            s = dict(self._stats)
            s["panels_by_bucket"] = dict(self._stats["panels_by_bucket"])
        s["occupancy_mean"] = (
            s.pop("occupancy_sum") / s["panels"] if s["panels"] else 0.0
        )
        return s

    # ---------------------------------------------------------- dispatcher
    def _rows_ready(self, n: int) -> int:
        """Rows queued for panels of top-``n`` (lock held)."""
        return sum(r.rows for r in self._queue if r.n == n)

    def _drain(self, n: int) -> list[_Request]:
        """Pop the FIFO prefix of ``n``-compatible requests that fits the
        largest bucket (lock held).  Requests for other ``n`` keep their
        queue positions."""
        batch, taken, keep = [], 0, deque()
        cap = self.buckets[-1]
        while self._queue:
            req = self._queue.popleft()
            if req.n == n and taken + req.rows <= cap:
                batch.append(req)
                taken += req.rows
            else:
                keep.append(req)
        self._queue = keep
        self._queued_rows -= taken
        return batch

    def _loop(self) -> None:
        while True:
            with TraceAnnotation("serve.batcher.coalesce") as span, \
                    self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                head = self._queue[0]
                deadline = head.t_submit + self.max_wait_us * 1e-6
                # coalesce until the largest bucket fills or the oldest
                # request has waited its bound (close drains immediately)
                while (not self._closed
                       and self._rows_ready(head.n) < self.buckets[-1]):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0.0:
                        break
                    self._cond.wait(timeout=remaining)
                batch = self._drain(head.n)
                span.set_metadata(rows=sum(r.rows for r in batch))
            if batch:
                self._dispatch(batch)

    def _dispatch(self, batch: list[_Request]) -> None:
        """Serve one coalesced panel and slice the responses back out."""
        t_dispatch = time.monotonic()
        rows = sum(r.rows for r in batch)
        bucket = next(b for b in self.buckets if b >= rows)
        with TraceAnnotation("serve.batcher.panel", panel=next(
                self._panel_seq), rows=rows, bucket=bucket) as span:
            if span.is_enabled():   # the list is built only when traced
                span.set_metadata(
                    requests=" ".join(str(r.seq) for r in batch))
            try:
                with TraceAnnotation("serve.batcher.assemble"):
                    panel = jnp.concatenate([r.x for r in batch], axis=0)
                    if bucket > rows:
                        # zero padding rows: scored and discarded — they can
                        # never appear in any request's slice below
                        panel = jnp.concatenate(
                            [panel, jnp.zeros((bucket - rows, panel.shape[1]),
                                              dtype=panel.dtype)], axis=0
                        )
                resp = self.engine.retrieve_dense(panel, batch[0].n)
                with TraceAnnotation("serve.batcher.wait"):
                    jax.block_until_ready(resp.ids)
            except BaseException as err:  # noqa: BLE001 — the caller's error
                for r in batch:
                    r.future.set_exception(err)
                return
            with self._cond:
                self._stats["panels"] += 1
                self._stats["panels_by_bucket"][bucket] += 1
                self._stats["padded_rows"] += bucket - rows
                self._stats["occupancy_sum"] += rows / bucket
            with TraceAnnotation("serve.batcher.resolve", requests=len(batch)):
                off = 0
                for r in batch:
                    s = resp.scores[off:off + r.rows]
                    i = resp.ids[off:off + r.rows]
                    off += r.rows
                    if r.squeeze:
                        s, i = s[0], i[0]
                    r.future.set_result(RetrievalResponse(
                        scores=s, ids=i, status=resp.status,
                        queue_us=(t_dispatch - r.t_submit) * 1e6,
                    ))
