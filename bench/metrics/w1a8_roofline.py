"""W1A8 kernels' share of their roofline, in percent: the least time the
chip could take for the Pallas calls of the served program's executions
in the traced window (each call's operations at the 8-bit peak, or its
bytes at HBM bandwidth, whichever is larger, from its layer's shapes),
over the device time of every Pallas call inside those executions."""
from bench.core import costs, trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    least = sum(costs.least_seconds(ops, nbytes, run.peaks)
                for _, ops, nbytes in run.kernel_calls)
    need = spent = 0.0
    for ops in trace.ops_in(run.trace,
                            trace.modules_named(run.trace, run.bundle,
                                                lo, hi)):
        kernels = [d for _, opcode, _, d in ops if trace.is_kernel(opcode)]
        if kernels:
            need += least
            spent += sum(kernels)
    return 100.0 * need / spent if spent > 0 else None
