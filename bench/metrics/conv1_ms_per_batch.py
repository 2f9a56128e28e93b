"""Device time of conv1 per execution of the served program, in ms: the
ops under the ``conv1`` layer scope (its convolution, ReLU, pool and
quantization, with their layout copies), by the executable's HLO
metadata."""
from bench.core import program, trace


def read(run):
    scopes = getattr(run, "op_scopes", None)
    if run.trace is None or not scopes:
        return None
    lo, hi = run.trace_window
    execs = trace.modules_named(run.trace, run.bundle, lo, hi)
    per = program.scope_seconds(run.trace, execs, scopes)
    if not execs or "conv1" not in per:
        return None
    return 1e3 * per["conv1"] / len(execs)
