"""Roofline share, in percent, of the stride-2 W1A8 calls that open each
backbone stage (``w1a8_graph_roofline`` over those calls only)."""
from bench.core import costs_graph


def read(run):
    return costs_graph.kernel_share(
        run, [c for c in costs_graph.w1a8_calls(run.cfg, run.width)
              if c[3]["s2"]])
