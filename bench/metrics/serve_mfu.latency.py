"""The whole serve step's share of the chip's peaks, in %: over the traced
window, the sum over served panels of the least time the peaks allow for
the step (encoding the panel plus one read of the index to score and
select it) over the window's length.  It bounds the step whichever kernels
carry it.  This one moves ``p50_ms``."""
from yardstick.shares import serve_mfu


def read(run):
    return serve_mfu(run)
