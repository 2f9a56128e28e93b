"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), averaged over chips."""
from bench.core import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    return 1.0 - trace.busy(run.trace, lo, hi) / (hi - lo)
