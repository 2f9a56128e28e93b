"""W1A8 kernels' share of their roofline in a graph configuration, in
percent: over the served program's executions in the traced window, the
least time the chip could take for every ``w1a8_<layer>`` Pallas call
(its operations at the 8-bit peak or its bytes at HBM bandwidth,
whichever is larger, from the configuration's graph), over their device
time."""
from bench.core import costs_graph


def read(run):
    return costs_graph.kernel_share(
        run, costs_graph.w1a8_calls(run.cfg, run.width))
