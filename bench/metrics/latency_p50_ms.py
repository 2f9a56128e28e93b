"""Median of (answer time - due time) over every frame due in the window,
followed to its answer after the window. A failed frame counts as missing
every limit; a run with a failed frame reports no latency."""
import numpy as np


def read(run):
    due = run.in_window()
    lat = np.where(run.ok, run.done - run.due, np.inf)[due] * 1e3
    if not len(lat) or not np.isfinite(lat).all():
        return None
    return float(np.percentile(lat, 50))
