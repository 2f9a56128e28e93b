"""Device time per execution of the served program spent outside the
Pallas kernels (conv1, the head, decode, NMS, the wire), in ms."""
from bench.core import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    per = trace.ops_in(run.trace,
                       trace.modules_named(run.trace, run.bundle, lo, hi))
    if not per:
        return None
    xla = sum(d for ops in per for _, opcode, _, d in ops
              if not trace.is_kernel(opcode))
    return 1e3 * xla / len(per)
