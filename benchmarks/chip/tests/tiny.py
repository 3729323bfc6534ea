"""Tiny cells for CPU tests of the harness: the cell's own configuration
and mix, cut to a few hundred passages, a 512-wide hash and batches of 8."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402


def cell(workload: str, knee: float = 60.0):
    """(metrics, config, traffic) of a cell of BENCHMARK.json, read from its
    files and cut to the tiny size; the metrics are its end-to-end ones."""
    name, mix = workload.split(".", 1)
    config = json.loads((HERE / "configs" / f"{name}.json").read_text())
    traffic = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    metrics = run.resolve(workload)["end_to_end"]
    config["corpus"].update(passages=600, entity_passages=120,
                            update_codes=min(config["corpus"]["update_codes"],
                                             1000))
    config["engine"].update(dim=512, max_batch=8)
    config["knee_queries_per_s"] = knee
    traffic["warmup_seconds"] = 0.5
    if "warmup_publishes" in traffic:
        traffic["warmup_publishes"] = [2, 4]
    return metrics, config, traffic


def run_tiny(workload: str, seed: int, seconds: float = 1.0) -> dict:
    metrics, config, traffic = cell(workload)
    return run.run_cell(workload, config, traffic, metrics, seed=seed,
                        seconds=seconds, trace=False, require_tpu=False)
