"""Median latency of every read scheduled in the window, from its
scheduled send time to its answer; a refused, failed or unanswered read
counts as waiting until the run stopped waiting for answers (see
``Readings.window_latency_s``)."""
import numpy as np


def read(r):
    if not len(r.window_reads()):
        return None
    return float(np.percentile(r.window_latency_s(), 50)) * 1e3
