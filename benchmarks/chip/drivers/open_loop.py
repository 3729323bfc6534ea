"""Open loop: independent users sending at a fixed Poisson rate.

Parameters (traffic file): ``rate_of_knee``, the offered rate as a share
of the configuration's measured ``knee_queries_per_s``.  Requests are
sent on schedule whether or not earlier ones have been answered.
"""
from __future__ import annotations

from corpus import seed_rng
from load import Requests, open_loop, sleep_until
from traffic import arrival_times


def prepare(cell, seconds: float, stream: int) -> dict:
    rate = float(cell.traffic["rate_of_knee"]) * cell.knee
    offsets = arrival_times(rate, seconds, seed_rng(cell.seed, stream))
    return {"seconds": seconds, "offsets": offsets,
            "queries": cell.queries(stream).make(len(offsets))}


def drive(cell, plan: dict, t0: float) -> dict:
    n = len(plan["queries"])
    reqs = Requests(n, cell.keep(n, n))
    open_loop(cell.runtime, cell.k, reqs, plan["offsets"], plan["queries"],
              t0)
    t1 = t0 + plan["seconds"]
    sleep_until(t1)
    return {"reads": reqs, "t0": t0, "t1": t1}
