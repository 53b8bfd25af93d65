"""The batcher's own host time per panel, in ms: its ``serve.batcher.
assemble`` (concatenation and padding) and ``serve.batcher.resolve``
(per-request slicing and answers) spans inside each panel span, averaged
over the window's panels."""
from yardstick.spans import per_panel_ms


def read(run):
    return per_panel_ms(run, ("serve.batcher.assemble",
                              "serve.batcher.resolve"))
