"""The control of ``correct``: the reference put in the program's place,
computed one precision lower, must come out not correct.

The configuration states float32 vectors, so the control takes the
reference's answers with bfloat16 query and document weights (products
summed in float32) and hands them to the same comparison a run uses, at
the cell's own corpus size and on the cell's own window queries.  It
prints, per seed, the numbers compared with their limits; every seed
must read ``correct: false``.

    python3 benchmarks/chip/control.py --workload dpr-flat.steady \\
        --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
from reference import control_rows, judge


def control_numbers(name: str, config: dict, traffic: dict, seed: int,
                    seconds: float, sample: int = run.SAMPLE) -> dict:
    """Numbers of the comparison with the control in the program's place,
    over ``sample`` queries of the cell's window stream."""
    cell = run.Cell(name, config, traffic, seed)
    cell.corpus = run.make_corpus(config["corpus"], seed)
    cell.published[0] = 0
    rate = float(traffic.get("rate_of_knee", 1.0)) * (cell.knee or 1.0)
    count = min(sample, max(1, int(rate * seconds)))
    queries = cell.queries(run.STREAM_WINDOW).make(count)
    picked = [(q, [], 0, 0) for q in queries]
    return run.compare(cell, picked, answer=lambda state, text:
                       control_rows(state.answer(text, cell.k, "bf16")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    failed_all = True
    for seed in args.seeds:
        t = time.perf_counter()
        numbers = control_numbers(args.workload, spec["config"],
                                  spec["traffic"], seed, args.seconds)
        correct, checks = judge(numbers, spec["config"]["limits"])
        failed_all &= not correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
