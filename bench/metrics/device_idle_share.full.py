"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of device-op intervals) / window.  Cells that keep a
full panel queued; it moves ``qps``."""
from yardstick.shares import idle_share


def read(run):
    return idle_share(run)
