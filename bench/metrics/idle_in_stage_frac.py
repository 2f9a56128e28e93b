"""Share of the traced window in which the device was idle while the host
staged a batch (inside the program's ``detect.stage`` spans)."""
from bench.core import program


def read(run):
    stage = program.spans(run, "detect.stage")
    if run.trace is None or not stage:
        return None
    lo, hi = run.trace_window
    idle = program.idle_inside(run.trace, [(s, s + d) for _, s, d, _, _
                                           in stage], lo, hi)
    return idle / (hi - lo)
