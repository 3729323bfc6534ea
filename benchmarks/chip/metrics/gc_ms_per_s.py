"""Garbage-collector pauses per second of the window: the ``gc`` spans
(one per collection, from the program's tracer) that started in the
window, their time summed over the window's seconds, in ms per s.  Read
beside the device trace, whose idle time the pauses explain; a run
without one (no chip) reports nothing."""


def read(r):
    if r.device is None:
        return None
    spans = r.window_spans("gc")
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / 1e6 / (r.t1 - r.t0)
