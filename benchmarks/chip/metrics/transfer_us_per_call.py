"""Host time to copy one dispatch's top-k arrays back from the chip: the
mean ``host_transfer`` span of the window, in microseconds.  Read beside
the device trace whose idle gaps these spans name; a run without one (no
chip) reports nothing."""


def read(r):
    if r.device is None:
        return None
    spans = r.window_spans("host_transfer")
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / len(spans) / 1e3
