"""Frames answered "ok" inside the window, over the window's seconds."""


def read(run):
    return float(run.served_in_window().sum()) / run.seconds
