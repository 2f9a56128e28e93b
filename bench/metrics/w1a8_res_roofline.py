"""Roofline share, in percent, of the W1A8 calls that end a residual
block, their shortcut input read in the epilogue counted in their bytes
(``w1a8_graph_roofline`` over those calls only)."""
from bench.core import costs_graph


def read(run):
    return costs_graph.kernel_share(
        run, [c for c in costs_graph.w1a8_calls(run.cfg, run.width)
              if c[3]["res"]])
