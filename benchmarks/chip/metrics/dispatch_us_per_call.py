"""Host time to hand one scoring dispatch to the chip: the window's
``query_upload`` (query block to the device) plus ``launch`` (the jitted
call returning) span time over its ``device_dispatch`` spans, in
microseconds.  Read beside the device trace whose idle gaps these spans
name; a run without one (no chip) reports nothing."""


def read(r):
    if r.device is None:
        return None
    calls = r.window_spans("device_dispatch")
    if not calls:
        return None
    spent = sum(s.dur_ns for name in ("query_upload", "launch")
                for s in r.window_spans(name))
    if not spent:
        return None
    return spent / len(calls) / 1e3
