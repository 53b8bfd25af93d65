"""The engine's host path per panel, in ms: its ``serve.engine.dispatch``
span (validation, padding and the serve program's call, not its device
work), averaged over the window's panels."""
from yardstick.spans import per_panel_ms


def read(run):
    return per_panel_ms(run, ("serve.engine.dispatch",))
