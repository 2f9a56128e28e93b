"""Share of the 8-bit peak while the served program runs, in percent:
operations of the frames answered in the window over the device time of
the bundle's executions in the traced window x peak."""
from bench.core import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    execs = trace.modules_named(run.trace, run.bundle, lo, hi)
    dev_s = sum(e - s for _, s, e in execs)
    if not execs or dev_s <= 0:
        return None
    frames = float(run.served_in_window().sum())
    return 100.0 * frames * run.frame_ops / (dev_s
                                             * run.peaks["peak_ops_int8"])
