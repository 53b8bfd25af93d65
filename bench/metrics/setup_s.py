"""Seconds from process start to the first request of the window: catalog,
weights, index, self-check, warm-up, and compilation where the cache does
not hold it."""


def read(run):
    return run.setup_s
