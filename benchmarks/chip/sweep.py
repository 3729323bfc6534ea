"""Find a configuration's knee once: open-loop Poisson load at a series of
fixed rates, in one process, on the chip.

    python3 benchmarks/chip/sweep.py --config dpr-flat --seed 5 \\
        --seconds 8 --rates 1000 2000 3000 4000

For each rate it prints the answers per second, p50/p95/p99 latency from
the scheduled send, the generator's lateness and whether the backlog
grew (the p95 of the window's last third against its first third).  The
knee is the highest rate that is answered in full without a growing
backlog; it goes into the configuration as ``knee_queries_per_s`` by
hand.  Lines go to standard output, one JSON object per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run


def pct(x: np.ndarray, q: float):
    x = x[np.isfinite(x)]
    return float(np.percentile(x, q)) if len(x) else None


def summary(reqs, t0: float, t1: float) -> dict:
    idx = reqs.in_window(t0, t1)
    lat = (reqs.done[idx] - reqs.due[idx]) * 1e3
    late = (reqs.sent[idx] - reqs.due[idx]) * 1e3
    third = max(1, len(idx) // 3)
    return {"offered": len(idx),
            "answered_per_s": float(np.isfinite(lat).sum()) / (t1 - t0),
            "failed": reqs.failed(idx),
            "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
            "p99_ms": pct(lat, 99),
            "p95_first_third_ms": pct(lat[:third], 95),
            "p95_last_third_ms": pct(lat[-third:], 95),
            "generator_late_p95_ms": pct(late, 95)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["RAGDB_SANITIZERS"] = "1"
    compiles = run.jax_setup()
    run.check_devices(1, True)
    config = json.loads(
        (run.HERE / "configs" / f"{args.config}.json").read_text())
    traffic = {"driver": "open_loop", "rate_of_knee": 1.0, "warmup_seconds": 2}
    cell = run.Cell(f"{args.config}.sweep", config, traffic, args.seed)
    phases: dict = {}
    cell.knee = args.rates[0]
    run.build(cell, phases)
    run.warm(cell, phases, 1.0)
    print(json.dumps({"phases": phases}), flush=True)
    for i, rate in enumerate(args.rates):
        cell.knee = rate
        plan = cell.driver.prepare(cell, args.seconds, 100 + i)
        t0 = time.perf_counter() + 0.05
        out = cell.driver.drive(cell, plan, t0)
        out["reads"].wait(out["t1"] + 30)
        n = len(compiles.inside(t0, out["t1"]))
        print(json.dumps({"rate": rate, "compiles_in_window": n,
                          **summary(out["reads"], t0, out["t1"])}), flush=True)
        time.sleep(1.0)
    cell.runtime.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
