"""Signals completed in the window over the window's length: every call,
from the first issue to the last result, on the host clock."""


def read(run):
    return run.window.signals / run.window.seconds
