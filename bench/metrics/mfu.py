"""Whole-step share of the chip's 8-bit peak, in percent: frames answered
in the window x operations per frame (every MAC twice, binary ones too),
over window seconds x chips x peak."""


def read(run):
    ops = float(run.served_in_window().sum()) * run.frame_ops
    return 100.0 * ops / (run.seconds * run.chips
                          * run.peaks["peak_ops_int8"])
