"""99th percentile latency of every read scheduled in the window, from its
scheduled send time to its answer, read in traced runs; a refused, failed
or unanswered read counts as waiting until the run stopped waiting for
answers (see ``Readings.window_latency_s``).  A host stall of a second or
more sets it, so it stands beside ``query_p50_ms`` rather than as an
end-to-end metric of its own."""
import numpy as np


def read(r):
    if not len(r.window_reads()):
        return None
    return float(np.percentile(r.window_latency_s(), 99)) * 1e3
