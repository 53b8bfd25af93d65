"""Closed loop: ``clients`` callers, each sending its next request as soon as
the previous one is answered, until the window closes.

Traffic keys: ``clients``.  One thread sends for every client: the first
request of each in one burst when the window opens, so all are queued
before the batcher forms its first panel, and then a client's next request
as soon as the callback of its answer hands the client back.  A single
sender keeps the load generator off the interpreter lock the batcher's
thread needs: with a thread per client, up to 128 woken threads stood
between the batcher and the lock at every panel.  A request is due when it
is sent; the worst delay from an answer to the client's next send is kept
on the window.  Request ``i`` of the run carries query ``i`` of the pool
(modulo its size).  After the window closes the clients go on sending
until every request sent inside it is answered, so those last requests are
served as the window's were, not in the part-filled panels of a thinning
load; what is sent after the close is not counted.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time

from yardstick.window import Request, Window

SHED_BACKOFF_S = 1e-3   # a shed client waits this long before sending again


def run(ctx, window: Window) -> None:
    server, pool, top_n = ctx.server, ctx.pool, ctx.top_n
    answered: queue.SimpleQueue = queue.SimpleQueue()  # clients handed back
    counter = itertools.count()
    open_in_window = 0   # requests sent inside the window, not yet answered

    def send() -> None:
        nonlocal open_in_window
        req = Request(next(counter) % len(pool), time.monotonic())
        window.add(req)
        try:
            fut = server.submit(pool[req.pool_index], top_n)
        except ctx.queue_full:
            req.shed, req.done = True, req.due
            threading.Timer(SHED_BACKOFF_S, answered.put, (req,)).start()
            return
        req.submitted = req.due
        open_in_window += req.due < window.t1

        def settle(f) -> None:
            req.resolve(f)
            answered.put(req)

        fut.add_done_callback(settle)

    window.open()
    for _ in range(int(ctx.traffic["clients"])):
        send()
    closed = False
    while True:
        now = time.monotonic()
        if not closed and now >= window.t1:
            window.wait_close()
            closed = True
        if closed and open_in_window == 0:
            return
        try:
            req = answered.get(timeout=ctx.drain_s if closed
                               else window.t1 - now)
        except queue.Empty:
            if closed:
                return   # what never came counts as unanswered
            continue
        if not req.shed:
            open_in_window -= req.due < window.t1
            window.generator_late_s = max(window.generator_late_s,
                                          time.monotonic() - req.done)
        send()
