"""The guard's admission per panel, in ms: its ``serve.guard.admit`` span
(validation, the panel's copy to the host and its finiteness check),
averaged over the window's panels."""
from yardstick.spans import per_panel_ms


def read(run):
    return per_panel_ms(run, ("serve.guard.admit",))
