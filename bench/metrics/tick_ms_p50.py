"""Median wall time of one Scheduler.tick in the window, from the
benchmark's span around it (scheduler and backend host path together)."""
import numpy as np


def read(run):
    return float(np.median(run.tick_s)) * 1e3 if len(run.tick_s) else None
