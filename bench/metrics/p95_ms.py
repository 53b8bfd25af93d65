"""95th-percentile latency, in ms, timed as ``p50_ms`` is."""
from yardstick.window import percentile


def read(run):
    p = percentile(run.window.latencies_s(), 95)
    return None if p is None else p * 1e3
