"""The harness end to end on the CPU, at a tiny size: each driver returns
a well-formed result line, a run without a TPU reports nothing, and a
run whose timed path is broken comes out not correct."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny import run_tiny  # noqa: E402

SEED = 2**31 + 77


def well_formed(out: dict, e2e: set) -> None:
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == e2e
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(out)


@pytest.mark.parametrize("workload,e2e", [
    ("dpr-flat.steady", {"query_p50_ms", "setup_s"}),
    ("dpr-flat.live", {"visible_lag_p95_ms", "setup_s"}),
])
def test_each_driver_returns_a_well_formed_line(workload, e2e):
    out = run_tiny(workload, SEED)["result"]
    assert out["correct"] is True
    well_formed(out, e2e)


def test_traced_run_reads_program_spans():
    from tiny import cell
    import run
    _, config, traffic = cell("dpr-flat.steady")
    per_layer = run.resolve("dpr-flat.steady")["per_layer"]
    out = run.run_cell("dpr-flat.steady", config, traffic, per_layer,
                       seed=SEED + 1, seconds=1.0, trace=True,
                       require_tpu=False)["result"]
    assert out["correct"] is True
    # the CPU has no device trace: only the span and clock readers read
    assert set(out["metrics"]) == {"sched_wait_ms", "embed_us_per_query",
                                   "read_p99_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_no_tpu_exits_nonzero_and_reports_nothing(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "dpr-flat.steady", "--seed", "3", "--seconds", "1"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
    # a directory holding only the benchmark's files cannot run either
    (tmp_path / "benchmarks").mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_compiles_inside_the_window_are_named():
    import logging
    import time

    import jax
    import jax.numpy as jnp
    import run

    compiles = run.Compiles()
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    level = pxla.level
    pxla.setLevel(logging.DEBUG)
    pxla.addHandler(compiles)
    try:
        t0 = time.perf_counter()
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        t1 = time.perf_counter()
    finally:
        pxla.removeHandler(compiles)
        pxla.setLevel(level)
    inside = compiles.inside(t0, t1)
    assert inside and inside[0].startswith("Compiling")
    assert compiles.inside(t1, t1 + 60.0) == []


def test_unanswered_reads_count_as_waiting_until_the_run_gave_up():
    import numpy as np
    import run
    from load import Requests

    reqs = Requests(4)
    for i, (due, done) in enumerate([(1.0, 1.5), (2.0, np.nan), (3.0, 3.2),
                                     (9.0, 9.1)]):
        reqs.queries.append(("q", "topical", 0))
        reqs.futures.append(None)
        reqs.due[i], reqs.done[i] = due, done
    reqs.errors.append((2, "RequestRejected"))
    r = run.Readings(reads=reqs, t0=0.0, t1=5.0, give_up=65.0)
    assert np.allclose(r.window_latency_s(), [0.5, 63.0, 62.0])
    mod = run.load_module(run.HERE / "metrics" / "query_p50_ms.py", "p50")
    assert mod.read(r) == 62.0 * 1e3
