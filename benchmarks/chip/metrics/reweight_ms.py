"""Median host re-weight of a publish: the window's ``reweight`` spans
(``finalize_matrix`` over the cached rows when idf moved), in ms."""
import numpy as np


def read(r):
    spans = r.window_spans("reweight")
    if not spans:
        return None
    return float(np.median([s.dur_ns for s in spans])) / 1e6
