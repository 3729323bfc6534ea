"""Host time of the IVF probe plane per dispatch: the window's
``ivf_probe`` spans (centroid scores, cluster bounds, probe order and
first widths, on the flusher thread) over their count, in
microseconds."""


def read(r):
    spans = r.window_spans("ivf_probe")
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / len(spans) / 1e3
