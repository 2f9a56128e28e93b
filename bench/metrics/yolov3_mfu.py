"""Whole-step share of the chip's 8-bit peak for a graph configuration, in
percent: frames answered in the window x operations per frame from the
configuration's graph (every MAC twice, binary ones too), over window
seconds x chips x peak."""
from bench.core import costs_graph


def read(run):
    ops = float(run.served_in_window().sum()) * costs_graph.frame_ops(
        run.cfg)
    return 100.0 * ops / (run.seconds * run.chips
                          * run.peaks["peak_ops_int8"])
