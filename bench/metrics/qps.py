"""Requests served on the configured path, of those due in the window,
per second from the window's opening to the last of their answers."""


def read(run):
    return run.window.served_rate()
