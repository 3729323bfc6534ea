"""On-chip benchmark of RAGdb's served retrieval: one cell, one run.

    python3 benchmarks/chip/run.py --workload dpr-flat.steady --seed 7 \\
        --seconds 30 --trace 0

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; the configuration is
``configs/<config>.json`` (a deployment: corpus shape and size, engine
settings, query shape, the limits of ``correct``), the mix is
``traffic/<traffic>.json`` and names its driver in ``drivers/``, and each
metric is read by ``metrics/<metric>.py``.  A new cell needs only new
files and new entries.

A run generates the corpus from ``--seed``, ingests it through
``KnowledgeBase.add_text``, builds ``ServingRuntime``, warms every shape
the cell's traffic uses (the power-of-two batch buckets at the cell's k,
then a few seconds of the cell's own traffic on another stream), and
measures for ``--seconds``.  ``--trace 1`` also records the program's
spans and a profiler trace of the window and reports the per-layer
metrics instead of the end-to-end ones.  After the window a sample of
the answered requests is compared with ``reference.py``.  The last line
of standard output is the result; the numbers compared are printed with
their limits as the last lines of standard error and under ``checks``.
It fails, and prints no result, without a TPU.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """When this process started, on ``time.perf_counter``'s clock."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from corpus import UpdateMaker, make_corpus, seed_rng  # noqa: E402
from load import DROPPED  # noqa: E402
from traffic import QueryMaker  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"   # the program's own default
OUT_DIR = ROOT / "chiprun_out" / "chipbench"
PROFILE_DIR = ROOT / ".chipbench" / "profile"
STREAM_WARMUP, STREAM_WINDOW, STREAM_SAMPLE = 10, 11, 12
SAMPLE = 1024          # answered requests compared with the reference
LATE_S = 60.0          # wait for answers this long past the window


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic mix and metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


class Cell:
    """What drivers and metric readers see of a run."""

    def __init__(self, name: str, config: dict, traffic: dict, seed: int):
        self.name, self.config, self.traffic, self.seed = (
            name, config, traffic, seed)
        self.k = int(config["queries"]["k"])
        self.max_batch = int(config["engine"]["max_batch"])
        self.knee = float(config.get("knee_queries_per_s") or 0.0)
        self.driver = load_module(
            HERE / "drivers" / f"{traffic['driver']}.py",
            f"chipbench_driver_{traffic['driver']}")
        self.corpus = self.kb = self.runtime = None
        self._updates = None
        self.applied: list = []      # updates applied, in order
        self.published: dict = {}    # generation -> updates it holds
        self.visible = 0             # updates visible to new requests

    def queries(self, stream: int) -> QueryMaker:
        return QueryMaker(self.corpus, self.config["queries"], self.seed,
                          stream)

    def keep(self, capacity: int, expected: float) -> np.ndarray:
        """Which requests of a phase keep their answers for the
        comparison: each with the same seeded chance, so that about one
        and a half times the sample is kept of ``expected`` requests."""
        rng = seed_rng(self.seed, STREAM_SAMPLE)
        return rng.random(capacity) < 1.5 * SAMPLE / max(expected, 1.0)

    def updates(self, count: int) -> list:
        if self._updates is None:
            self._updates = UpdateMaker(
                self.corpus, float(self.traffic["write_zipf"]), self.seed)
        return self._updates.make(count)


# ---- set-up ---------------------------------------------------------------

def jax_setup():
    """Persistent compile cache at a fixed path inside the checkout, and
    a record of every compile and cache read: (time, event, seconds) from
    ``jax.monitoring`` and (time, what) from JAX's compile log.  Call it
    before anything imports JAX."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    for where in (enable_compile_cache(),
                  jax.config.jax_compilation_cache_dir):
        if not Path(where or "/").resolve().is_relative_to(ROOT):
            raise RuntimeError(f"compile cache {where} is not in {ROOT}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = Compiles()

    def listen(event: str, duration: float, **_):
        if "backend_compile" in event or "cache_retrieval" in event:
            compiles.events.append((time.perf_counter(), event, duration))
    jax.monitoring.register_event_duration_secs_listener(listen)
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    pxla.setLevel(logging.DEBUG)
    pxla.propagate = False
    pxla.addHandler(compiles)
    return compiles


class Compiles(logging.Handler):
    """What compiled, and when (on ``time.perf_counter``)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.events: list = []       # (time, event, seconds)
        self.names: list = []        # (time, first words of the log line)

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling"):
            self.names.append((time.perf_counter(), msg[:160]))
        elif record.levelno >= logging.WARNING:
            log(msg)

    def slowest(self, t1: float, count: int = 5) -> list:
        """The ``count`` longest backend compiles before ``t1``:
        (seconds, the name logged last before the compile began)."""
        out = []
        for t, event, secs in self.events:
            if "backend_compile" not in event or t >= t1:
                continue
            names = [m for tn, m in self.names if tn <= t - secs]
            out.append((round(secs, 3), names[-1][:100] if names else "?"))
        return sorted(out, reverse=True)[:count]

    def inside(self, t0: float, t1: float) -> list:
        """Compiles and cache reads in [t0, t1), named where JAX said."""
        names = [m for t, m in self.names if t0 <= t < t1]
        events = [e for t, e, _ in self.events if t0 <= t < t1]
        return names or events


def check_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} devices, the cell asks for {chips}")
    return devices


def build(cell: Cell, phases: dict) -> None:
    from repro.core.ingest import KnowledgeBase
    from repro.serving import ServingRuntime

    cfg, eng = cell.config, cell.config["engine"]
    t = time.perf_counter()
    cell.corpus = corpus = make_corpus(cfg["corpus"], cell.seed)
    phases["corpus_s"] = time.perf_counter() - t
    t = time.perf_counter()
    cell.kb = KnowledgeBase(dim=int(eng["dim"]),
                            sig_words=int(eng["signature_words"]))
    for i in range(corpus.n):
        cell.kb.add_text(corpus.doc_id(i), corpus.text(i))
    phases["ingest_s"] = time.perf_counter() - t
    t = time.perf_counter()
    options = {k: eng[k] for k in ("index", "nprobe", "guarantee",
                                   "n_clusters", "max_queue") if k in eng}
    cell.runtime = ServingRuntime(
        cell.kb, scoring_path=eng["scoring_path"],
        max_batch=cell.max_batch, flush_deadline=float(eng["flush_deadline"]),
        alpha=float(eng["alpha"]), beta=float(eng["beta"]), **options)
    cell.runtime.start()
    cell.published[cell.runtime.generation] = 0
    phases["engine_s"] = time.perf_counter() - t
    phases["scoring_path"] = cell.runtime.engine.scoring_path


def warm(cell: Cell, phases: dict, seconds: float) -> dict:
    """Warm the batch buckets at the cell's k (which arms the retrace
    guard), the driver's own ``warm`` (if it has one), then the cell's own
    traffic for ``traffic.warmup_seconds`` on another stream; returns the
    window's prepared plan."""
    t = time.perf_counter()
    cell.runtime.arm_sanitizers(cell.k)
    plan = cell.driver.prepare(cell, float(cell.traffic["warmup_seconds"]),
                               STREAM_WARMUP)
    window = cell.driver.prepare(cell, seconds, STREAM_WINDOW)
    phases["prepare_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if hasattr(cell.driver, "warm"):
        cell.driver.warm(cell, window)
    out = cell.driver.drive(cell, plan, time.perf_counter() + 0.01)
    for key in ("reads", "checks"):
        if key in out:
            out[key].wait(time.perf_counter() + LATE_S)
    cell.runtime.arm_sanitizers(cell.k)
    phases["warmup_s"] = time.perf_counter() - t
    return window


class GcPauses:
    """Garbage-collector pauses of the process inside a window."""

    def __init__(self):
        self.pauses: list = []
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info.get("generation")))

    def summary(self, t0: float, t1: float) -> dict:
        ours = [(d, g) for t, d, g in self.pauses if t0 <= t < t1]
        full = [d for d, g in ours if g == 2]
        return {"gc_pauses": len(ours),
                "gc_total_ms": 1e3 * sum(d for d, _ in ours),
                "gc_full": len(full),
                "gc_full_max_ms": 1e3 * max(full, default=0.0)}


# ---- tracing --------------------------------------------------------------

class Tracing:
    """The program's spans and a profiler trace around the window."""

    def __init__(self, on: bool):
        self.on = on
        self.mark_ns = 0

    def start(self, capacity: int) -> None:
        if not self.on:
            return
        import jax
        from repro.obs import trace

        trace.get().drain()
        trace.enable(sample=1.0, capacity=capacity)
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(PROFILE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench_mark"):
            self.mark_ns = time.perf_counter_ns()

    def stop(self):
        """-> (spans, path of the profile) or (None, None)."""
        if not self.on:
            return None, None
        import jax
        from repro.obs import trace

        jax.profiler.stop_trace()
        trace.disable()
        spans = trace.drain()
        files = sorted(PROFILE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        return spans, (files[-1] if files else None)


# ---- reading the window ---------------------------------------------------

def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def counters(runtime) -> dict:
    from repro.obs.metrics import global_registry

    out = dict(global_registry().snapshot())
    out.update(runtime.metrics.registry.snapshot())
    return out


class Readings:
    """Everything a metric reader may read; see ``metrics/``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_reads(self) -> np.ndarray:
        return self.reads.in_window(self.t0, self.t1)

    def window_latency_s(self) -> np.ndarray:
        """Latency of each read scheduled in the window, from its
        scheduled send to its answer.  A refused, failed or unanswered
        read counts as waiting until the run stopped waiting for answers,
        ``give_up``."""
        idx = self.window_reads()
        lat = self.reads.done[idx] - self.reads.due[idx]
        bad = np.isnan(lat) | np.isin(idx, [i for i, _ in self.reads.errors])
        lat[bad] = self.give_up - self.reads.due[idx][bad]
        return lat

    def delta(self, name: str) -> float:
        return float(self.after.get(name, 0.0)) - float(
            self.before.get(name, 0.0))

    def window_spans(self, name: str) -> list:
        lo, hi = self.t0 * 1e9, self.t1 * 1e9
        return [s for s in self.spans or []
                if s.name == name and lo <= s.t0_ns < hi]


def read_metrics(entries: list, readings: Readings) -> dict:
    out = {}
    for m in entries:
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          f"chipbench_metric_{m['name']}")
        value = mod.read(readings)
        if value is None or not np.isfinite(value):
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def lateness_ms(reqs, idx) -> dict:
    late = (reqs.sent[idx] - reqs.due[idx]) * 1e3
    if not len(late):
        return {}
    return {"generator_late_p50_ms": float(np.percentile(late, 50)),
            "generator_late_p95_ms": float(np.percentile(late, 95)),
            "generator_late_max_ms": float(np.max(late))}


# ---- correctness ----------------------------------------------------------

def sample_answers(cell: Cell, result: dict) -> list:
    """A seeded sample of answered window requests: (query, served rows,
    generation, visible floor).  Visibility lookups are all kept up to a
    quarter of the sample."""
    from reference import as_rows

    rng = seed_rng(cell.seed, STREAM_SAMPLE)
    row_of = {cell.corpus.doc_id(i): i for i in range(cell.corpus.n)}
    picked = []
    groups = [(result["reads"], result["reads"].in_window(
        result["t0"], result["t1"]), SAMPLE)]
    if "checks" in result:
        chk = result["checks"]
        groups.append((chk, np.arange(len(chk)), SAMPLE // 4))
    for reqs, idx, size in groups:
        ok = [i for i in idx if reqs.futures[i] is not None
              and reqs.futures[i] is not DROPPED
              and reqs.futures[i].done()
              and reqs.futures[i].exception() is None]
        for i in rng.permutation(ok)[:size]:
            served = reqs.futures[i].result()
            picked.append((reqs.queries[i], as_rows(served.results, row_of),
                           served.generation, reqs.floor[i]))
    return picked


def compare(cell: Cell, picked: list, answer=None) -> dict:
    """Numbers of ``reference.Tally`` over the sample.  ``answer``
    replaces the served rows (the control puts the reference here)."""
    from reference import State, Tally

    state = State(cell.corpus, cell.config["engine"])
    tally = Tally()
    last_write = {}
    order = sorted(picked, key=lambda p: cell.published.get(p[2], -1))
    for query, served, gen, floor in order:
        held = cell.published.get(gen)
        if held is None or held < floor:
            tally.stale_reads += 1
            continue
        if held > state.applied:
            for row, _, code in cell.applied[state.applied:held]:
                last_write[row] = code
            state.apply(cell.applied[state.applied:held])
        text, kind, target = query
        ref = state.answer(text, cell.k)
        if answer is not None:
            served = answer(state, text)
        entity = -1
        if kind == "entity" and target not in last_write:
            entity = target
        elif kind == "update":
            row, _, code = cell.applied[target]
            if target < held and last_write.get(row) == code:
                entity = row
        tally.add(served, ref, cell.k, entity)
    return tally.numbers()


# ---- one run --------------------------------------------------------------

def run_cell(name: str, config: dict, traffic: dict, metrics: list, *,
             seed: int, seconds: float, trace: bool, chips: int = 1,
             require_tpu: bool = True, compiles=None) -> dict:
    """One run of one cell -> its record (the result line is
    ``record["result"]``).  ``compiles`` (from ``jax_setup``) records
    every compile; those inside the window are listed in the record."""
    compiles = compiles if compiles is not None else Compiles()
    devices = check_devices(chips, require_tpu)
    cell = Cell(name, config, traffic, seed)
    phases: dict = {}
    build(cell, phases)
    plan = warm(cell, phases, seconds)
    runtime = cell.runtime
    tracing = Tracing(trace)
    n_plan = len(plan.get("queries", ()))
    tracing.start(capacity=max(1 << 16, 8 * n_plan + (1 << 16)))
    before = counters(runtime)
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    t0 = time.perf_counter() + 0.01
    setup_s = t0 - T_PROCESS
    phases["setup_s"] = setup_s
    for key, word in (("compile_s", "backend_compile"),
                      ("cache_read_s", "cache_retrieval")):
        phases[key] = sum(d for t, e, d in compiles.events
                          if word in e and t < t0)
    phases["slowest_compiles"] = compiles.slowest(t0)
    log(f"set-up {setup_s:.3f} s: " + json.dumps(phases))
    result = cell.driver.drive(cell, plan, t0)
    t1 = result["t1"]
    gc.callbacks.remove(pauses)
    spans, profile = tracing.stop()
    for key in ("reads", "checks"):
        if key in result:
            result[key].wait(t1 + LATE_S)
    after = counters(runtime)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:chips])
    reads = result["reads"]
    idx = reads.in_window(t0, t1)
    in_window = compiles.inside(t0, t1)
    device_trace = None
    if profile is not None:
        import trace_reduce
        device_trace = trace_reduce.reduce(profile, tracing.mark_ns, t0, t1,
                                           spans)
    peaks = peaks_for(devices[0].device_kind) if device_trace else None
    readings = Readings(
        cell=cell, t0=t0, t1=t1, give_up=t1 + LATE_S, seconds=seconds,
        setup_s=setup_s,
        reads=reads, checks=result.get("checks"),
        writes=result.get("writes"), before=before, after=after,
        spans=spans, device=device_trace, peaks=peaks)
    metrics_out = read_metrics(metrics, readings)
    extra = {"requests": int(len(idx)), "compiles_in_window":
             len(in_window), "compiled_in_window": in_window[:20],
             **lateness_ms(reads, idx),
             **pauses.summary(t0, t1)}
    if result.get("writes") is not None:
        pub = result["writes"]["published"]
        sizes = np.unique(pub[np.isfinite(pub)], return_counts=True)[1]
        if len(sizes):
            extra.update(publishes=int(len(sizes)),
                         writes_per_publish=[int(sizes.min()),
                                             float(np.median(sizes)),
                                             int(sizes.max())])
    if len(idx):
        lat = readings.window_latency_s() * 1e3     # as the metrics read it
        for q in (50, 75, 90, 95, 99):
            extra[f"latency_p{q}_ms"] = float(np.percentile(lat, q))
    log("window: " + json.dumps(extra))
    failed = reads.failed(idx)
    attempted = int(len(idx))
    if "checks" in result:
        chk = result["checks"]
        all_checks = np.arange(len(chk))
        failed += chk.failed(all_checks)
        attempted += len(chk)

    picked = sample_answers(cell, result)
    runtime.stop()
    cell.runtime = cell.kb = runtime = None
    gc.collect()
    t = time.perf_counter()
    numbers = compare(cell, picked)
    from reference import judge
    correct, checks = judge(numbers, config["limits"])
    log(f"compared {len(picked)} answered requests with the reference in "
        f"{time.perf_counter() - t:.1f} s")
    out = {"correct": bool(correct and len(picked) > 0),
           "attempted": attempted, "failed": int(failed),
           "metrics": metrics_out,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices), "memory_peak_bytes": peak}}
    if device_trace is not None:
        out["device"]["busy_s"] = device_trace["busy_s"]
        out["device"]["window_s"] = device_trace["window_s"]
        out["breakdown"] = device_trace["breakdown"]
    out["checks"] = checks
    record = {"cell": name, "seed": seed, "seconds": seconds,
              "trace": trace, "phases": phases, "window": extra,
              "result": out}
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a recompile inside the window fails the batch that caused it
    os.environ["RAGDB_SANITIZERS"] = "1"
    compiles = jax_setup()
    spec = resolve(args.workload)
    metrics = spec["per_layer"] if args.trace else spec["end_to_end"]
    try:
        record = run_cell(args.workload, spec["config"], spec["traffic"],
                          metrics, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          chips=int(spec["cell"]["chips"]),
                          compiles=compiles)
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{args.workload}.{args.seed}.t{args.trace}.json").write_text(
        json.dumps(record, indent=1))
    window = record["window"]
    if window["compiles_in_window"]:
        # the window has to time warm programs only
        print(f"chipbench: {window['compiles_in_window']} compiles inside "
              f"the window: {window['compiled_in_window']}", file=sys.stderr)
        return 3
    out = record["result"]
    for key, c in out["checks"].items():
        print(f"check {key}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
