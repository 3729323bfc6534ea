"""Median time of the program's ``publish`` span (refresh from the KB's
dirty log, re-weight, upload, snapshot capture) over the publishes that
started in the window, in ms."""
import numpy as np


def read(r):
    spans = r.window_spans("publish")
    if not spans:
        return None
    return float(np.median([s.dur_ns for s in spans])) / 1e6
