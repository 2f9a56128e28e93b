"""Set-up seconds: process start to the window's start (import, build
from the seed, compile, warm every shape the traffic uses)."""


def read(run):
    return run.setup_s
