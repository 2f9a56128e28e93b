"""Median wall time a request waited in the scheduler's queue, from its
submit to its admission (the program's ``sched.queue`` span), over the
requests due in the window, in ms."""
import numpy as np

from bench.core import program


def read(run):
    got = getattr(run, "program_spans", None)
    if not got:
        return None
    due = run.in_window()
    wait = [d for name, _, d, rid, _ in got
            if name == program.QUEUE and rid < len(due) and due[rid]]
    return float(np.median(wait)) * 1e3 if wait else None
