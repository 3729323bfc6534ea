"""95th percentile, over the updates scheduled in the window, of the time
from an update's scheduled time to the return of the first publish()
that holds it; an update never published counts as published when the
run stopped waiting (``give_up``)."""
import numpy as np


def read(r):
    if r.writes is None:
        return None
    due, pub = r.writes["due"], r.writes["published"]
    sel = (due >= r.t0) & (due < r.t1)
    if not sel.any():
        return None
    lag = pub[sel] - due[sel]
    lag[np.isnan(lag)] = r.give_up - due[sel][np.isnan(lag)]
    return float(np.percentile(lag, 95)) * 1e3
