"""Shares of the chip's peaks read from a traced window, for the metric
readers in ``bench/metrics``.  Each returns None where the trace holds
nothing to read (no device ops, no panel spans, no call of the kernel),
never 0."""
from __future__ import annotations

from yardstick import counts


def kernel_roofline(run, pattern: str, cost) -> float | None:
    """A kernel's share of its roofline, in %: over the window's panels,
    the least time the peaks allow for each call of the kernel whose ops
    match ``pattern`` (``cost(rows) -> (flops, bytes)``, one chip's call),
    over the device time of those calls.  On several chips each chip's call
    is an op of its own, charged for what that chip does."""
    tr = run.trace
    least = took = 0.0
    for span in tr.spans_named("panel"):
        flops_bytes = cost(int(span.stats["rows"]))
        for op in tr.ops_within(span.start, span.end, pattern):
            least += counts.least_seconds(*flops_bytes, run.peak)
            took += (op.end - op.start) * 1e-9
    return 100.0 * least / took if took > 0 else None


def serve_mfu(run) -> float | None:
    """The whole serve step's share of the chip's peaks, in %: the sum over
    the window's panels of the step's least time on one chip (encoding the
    panel and one read of that chip's rows of the index to score and select
    it), over the window."""
    tr = run.trace
    spans = tr.spans_named("panel")
    if not spans or not tr.ops:
        return None
    least = sum(counts.serve_least_seconds(
        run.config, int(s.stats["rows"]), run.top_n, run.peak)
        for s in spans)
    return 100.0 * least / tr.window_s()


def idle_share(run) -> float | None:
    """Share of the window, in %, in which no op ran on the device."""
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
