"""Median host time of staging one batch, in ms: the program's
``detect.stage`` span (per-frame conversion and upload, stack, pad) over
the dispatches of the traced window."""
import numpy as np

from bench.core import program


def read(run):
    dur = [d for _, _, d, _, _ in program.spans(run, "detect.stage")]
    return float(np.median(dur)) * 1e3 if dur else None
