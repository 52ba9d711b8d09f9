"""The window's wall time over the steps it completed, in ms."""


def read(run):
    return run.window_s / run.steps * 1e3
