"""The encode kernel's share of its roofline, in %: over the window's calls,
the least time the chip's peaks allow for encoding each panel (2*B*d*h
flops at the bf16 peak, or queries, W_enc and b_enc in and codes out at
the HBM bandwidth, whichever is longer) over the kernel's device time.
The kernel's ops are found by their HLO name in the trace."""
from yardstick.shares import kernel_roofline

KERNEL = r"^%fused_encode_pallas"


def read(run):
    cfg = run.config
    d, h, k = int(cfg["d"]), int(cfg["h"]), int(cfg["k"])
    return kernel_roofline(run, KERNEL, lambda rows: run.counts.encode_cost(
        rows, d, h, k))
