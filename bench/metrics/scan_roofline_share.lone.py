"""The scan-and-select kernel's share of its roofline, in %: over the
window's calls, the least time the chip's peaks allow for scoring the
panel against the chip's rows of the index and selecting its top-n
(those N rows read once per call, 2*B*N*k flops) over the kernel's
device time; on a sharded index each chip's call is one op.  The
kernel's ops (every generation of ``fused_retrieve*``) are found by
their HLO name in the trace.  In the lone cell, where one pass is the
request, it moves ``p50_ms``."""
from yardstick.shares import kernel_roofline

KERNEL = r"^%fused_retrieve\w*_pallas"


def read(run):
    cfg = run.config
    n, h, k = run.counts.rows_per_chip(cfg), int(cfg["h"]), int(cfg["k"])
    return kernel_roofline(run, KERNEL, lambda rows: run.counts.scan_cost(
        cfg["index_format"], n, rows, k, h, run.top_n))
