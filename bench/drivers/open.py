"""Open loop: independent users arriving on a Poisson process at
``rate_rps``, sent on schedule whatever the system's state.

Traffic keys: ``rate_rps`` and ``schedule_seed``.  The arrivals are
``round(rate_rps * seconds)`` sorted uniform times over the window (a
Poisson process given its count), drawn from ``schedule_seed``: every run
of a cell offers the same schedule, and ``--seed`` changes which queries
arrive on it.  Drawn from ``--seed``, the schedule's clumps set a run's
tail: p95 read 251-368 ms over six seeds at 56 req/s, two runs of one seed
within 4% of each other.  A request is due at its scheduled arrival; the
worst lateness of the sender is kept on the window.  A shed request is not
retried.
"""
from __future__ import annotations

import time

import numpy as np

from yardstick.window import Request, Window


def run(ctx, window: Window) -> None:
    server, pool, top_n = ctx.server, ctx.pool, ctx.top_n
    count = int(round(float(ctx.traffic["rate_rps"]) * window.seconds))
    rng = np.random.default_rng([int(ctx.traffic["schedule_seed"]), 0xA7])
    offsets = np.sort(rng.uniform(0.0, window.seconds, size=count))
    futures = []
    t0 = window.open()
    for i, off in enumerate(offsets):
        due = t0 + float(off)
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        req = Request(i % len(pool), due)
        window.add(req)
        req.submitted = time.monotonic()
        window.generator_late_s = max(window.generator_late_s,
                                      req.submitted - due)
        try:
            fut = server.submit(pool[req.pool_index], top_n)
        except ctx.queue_full:
            req.shed, req.done = True, req.submitted
            continue
        fut.add_done_callback(req.resolve)
        futures.append(fut)
    window.wait_close()
    for fut in futures:
        fut.exception(timeout=ctx.drain_s)
