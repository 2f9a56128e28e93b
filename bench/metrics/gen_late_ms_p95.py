"""95th percentile of how late the load generator submitted a frame
after it was due, over the frames due in the window (open loop only)."""
import numpy as np


def read(run):
    if run.traffic["kind"] != "streams":
        return None
    due = run.in_window()
    if not due.any():
        return None
    return float(np.percentile((run.submit - run.due)[due], 95)) * 1e3
