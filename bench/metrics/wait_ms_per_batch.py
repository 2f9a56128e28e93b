"""Median time the host blocked fetching one batch's results, in ms: the
program's ``detect.wait`` span (``jax.device_get`` of the oldest batch in
flight) over the batches fetched in the traced window."""
import numpy as np

from bench.core import program


def read(run):
    dur = [d for _, _, d, _, _ in program.spans(run, "detect.wait")]
    return float(np.median(dur)) * 1e3 if dur else None
