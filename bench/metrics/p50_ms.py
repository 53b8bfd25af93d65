"""Median latency, in ms, of the requests due in the window that were
served: from the send (closed loop) or the scheduled arrival (open loop) to
the answer."""
from yardstick.window import percentile


def read(run):
    p = percentile(run.window.latencies_s(), 50)
    return None if p is None else p * 1e3
