"""The ``msmarco-ivf`` cells on the CPU at the tiny size: both run
``correct`` through the served exact IVF path; the ``light`` flushes take
the gather side of the index's choice first; the index-plane readers
read what they should from synthetic spans and trace calls; the bfloat16
control and a planted exactness fault both come out not correct."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import control  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from reference import judge  # noqa: E402
from tiny import cell, run_tiny  # noqa: E402

from repro.index import ivf as ivf_mod  # noqa: E402

SEED = 2**31 + 1515
T0, T1 = 10.0, 20.0
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
IVF_READERS = ("ivf_probe_us_per_call", "ivf_rows_share")


class Span:
    def __init__(self, name, t0_s, dur_us, **args):
        self.name, self.t0_ns = name, int(t0_s * 1e9)
        self.dur_ns, self.args = int(dur_us * 1e3), args


def reader(name):
    return run.load_module(HERE / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}").read


def readings(spans=None, calls=None, n=1000):
    return run.Readings(
        t0=T0, t1=T1, spans=spans, peaks=PEAKS,
        cell=SimpleNamespace(corpus=SimpleNamespace(n=n)),
        device=None if calls is None else {"calls": calls})


def traced_tiny(workload: str, seed: int) -> dict:
    _, config, traffic = cell(workload)
    per_layer = run.resolve(workload)["per_layer"]
    return run.run_cell(workload, config, traffic, per_layer, seed=seed,
                        seconds=1.0, trace=True, require_tpu=False)


@pytest.mark.parametrize("workload", ["msmarco-ivf.steady",
                                      "msmarco-ivf.light"])
def test_tiny_cells_are_correct(workload):
    record = traced_tiny(workload, SEED)
    out = record["result"]
    assert out["correct"] is True and out["failed"] == 0
    assert record["window"]["compiles_in_window"] == 0
    # the CPU has no device trace: the index plane's span readers and
    # the request plane's span and clock readers read
    assert set(out["metrics"]) == set(IVF_READERS) | {
        "sched_wait_ms", "embed_us_per_query", "read_p99_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_tiny_light_gathers_under_half_the_rows_first(monkeypatch):
    from repro.obs import trace as obs_trace

    firsts = []
    record = obs_trace.record

    def keep(name, t0, dur, **args):
        if name == "ivf_widen_round" and args.get("round") == 1:
            firsts.append(args)
        return record(name, t0, dur, **args)
    monkeypatch.setattr(obs_trace, "record", keep)
    out = traced_tiny("msmarco-ivf.light", SEED + 1)
    n = cell("msmarco-ivf.light")[1]["corpus"]["passages"]
    assert out["result"]["correct"] is True
    # tracing is on in the window alone: one to four queries a flush,
    # so each first round's probe union gathers (well under half the
    # rows) instead of collapsing to the flat scan
    assert firsts and all(not a["collapsed"] for a in firsts)
    assert sum(a["rows"] for a in firsts) / len(firsts) < n / 2


def test_ivf_probe_us_per_call():
    spans = [Span("ivf_probe", 11.0, 300.0), Span("ivf_probe", 12.0, 500.0),
             Span("ivf_probe", 25.0, 9000.0), Span("flush", 11.0, 900.0)]
    assert reader("ivf_probe_us_per_call")(readings(spans)) == (
        pytest.approx(400.0))


def test_ivf_rows_share_sums_each_dispatchs_rounds():
    spans = [Span("ivf_widen_round", 11.0, 50.0, round=1, rows=100),
             Span("ivf_widen_round", 11.1, 50.0, round=2, rows=1000,
                  collapsed=True),
             Span("ivf_widen_round", 12.0, 50.0, round=1, rows=200),
             Span("ivf_widen_round", 25.0, 50.0, round=1, rows=900)]
    # (100 + 1000 + 200) rows over two dispatches, of N = 1000
    assert reader("ivf_rows_share")(readings(spans)) == pytest.approx(65.0)


def gather_call(rows, source_rows=69120, width=4096, dtype="f32",
                dur_ns=10_000.0):
    t = (f"%fusion.1 = {dtype}[{rows},{width}]{{1,0:T(8,128)}} fusion("
         f"{dtype}[{source_rows},{width}]{{1,0:T(8,128)}} %doc_vecs.1, "
         f"s32[{rows}]{{0:T(1024)S(1)}} %fusion.2), kind=kCustom, "
         f"calls=%fused_computation.1")
    return ("fusion", t, dur_ns)


def test_gather_roofline_counts_the_gathered_rows_not_the_source():
    mod = run.load_module(HERE / "metrics" / "ivf_gather_roofline.py", "g")
    outs, args = trace_reduce.operands(gather_call(4096)[1])
    moved = mod.gather_bytes(outs, args)
    # 2 x the [4096, 4096] f32 block, plus the index vector
    assert moved == 2 * 4096 * 4096 * 4 + 4096 * 4
    # ... and not the [69120, 4096] source matrix named in the text
    assert moved < trace_reduce.nbytes("f32", (69120, 4096))
    calls = [gather_call(4096, dur_ns=200_000.0),
             gather_call(4096, width=128, dtype="s32", dur_ns=10_000.0)]
    least = (moved + 2 * 4096 * 128 * 4 + 4096 * 4) / PEAKS["hbm_bytes_per_s"]
    got = reader("ivf_gather_roofline")(readings(calls=calls))
    assert got == pytest.approx(100.0 * least / 210e-6)
    assert 0 < got < 100


def test_gather_roofline_reads_nothing_without_a_candidate_gather():
    read = reader("ivf_gather_roofline")
    assert read(readings()) is None              # no device trace
    # the kernel's gather of a batch's selected rows, and the kernel,
    # from a trace recorded on a v5e: neither is the candidate gather
    data = trace_reduce.load(HERE / "tests" / "data" / "serve3.xplane.pb")
    ops = data["chips"]["/device:TPU:0"]["ops"]
    calls = [(trace_reduce.op_name(n), n, d) for n, _, d in ops]
    assert read(readings(calls=calls)) is None
    assert read(readings(calls=[gather_call(640)])) is None


@pytest.mark.parametrize("name", IVF_READERS)
def test_index_readers_read_nothing_without_ivf_spans(name):
    unrelated = [Span("flush", 12.0, 100.0), Span("query_embed", 12.0, 50.0)]
    outside = [Span("ivf_probe", 25.0, 10.0),
               Span("ivf_widen_round", 25.0, 10.0, round=1, rows=5)]
    for spans in (None, unrelated, outside):
        assert reader(name)(readings(spans)) is None


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_bf16_control_is_not_correct(seed):
    _, config, traffic = cell("msmarco-ivf.steady")
    config["corpus"].update(passages=5000, entity_passages=500)
    config["engine"]["dim"] = 4096
    numbers = control.control_numbers("msmarco-ivf.steady", config, traffic,
                                      seed, seconds=30.0, sample=128)
    correct, checks = judge(numbers, config["limits"])
    assert correct is False
    assert checks["score_gap"]["value"] > 3 * checks["score_gap"]["limit"]


def test_planted_exactness_fault_is_not_correct(monkeypatch):
    # a cluster bound scaled below the true one: the stop test passes
    # after the first round, so clusters holding top-k rows go unread
    bound = ivf_mod.exact_cos_upper_bound
    monkeypatch.setattr(ivf_mod, "exact_cos_upper_bound",
                        lambda a, radius: 0.01 * bound(a, radius) - 2.0)
    out = run_tiny("msmarco-ivf.light", SEED + 2)["result"]
    assert out["correct"] is False
    assert (out["checks"]["score_gap"]["value"]
            > out["checks"]["score_gap"]["limit"]
            or out["checks"]["entity_misses"]["value"] > 0)
