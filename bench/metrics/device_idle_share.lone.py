"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of device-op intervals) / window.  Cells that send
one request at a time; it moves ``p50_ms``."""
from yardstick.shares import idle_share


def read(run):
    return idle_share(run)
