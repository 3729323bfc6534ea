"""The reduction from a profiler trace to device metrics, checked against a
small trace recorded on a TPU v5e (three served batches of 64 queries
over 2,048 docs), and the kernel work function against hand counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

TRACE = HERE / "tests" / "data" / "serve3.xplane.pb"
MARK_NS = 37727580.0          # host annotation in the recorded trace
KERNEL_NS = [112793.0, 112992.0, 112776.0]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_union_and_gaps_arithmetic():
    iv = np.array([[0, 10], [5, 15], [20, 30], [29, 31], [40, 41]])
    assert tr.union_ns(iv, 0, 50) == 15 + 11 + 1
    assert tr.union_ns(iv, 8, 25) == 7 + 5
    assert tr.gaps(iv, 0, 50) == [(15, 20), (31, 40), (41, 50)]
    assert tr.gaps(iv, 2, 12) == []
    assert tr.union_ns(np.zeros((0, 2)), 0, 10) == 0


def test_recorded_trace_kernel_time_by_name():
    t = tr.load(TRACE)
    assert t["mark_ns"] == MARK_NS
    ops = t["chips"]["/device:TPU:0"]["ops"]
    kernel = [o for o in ops if tr.op_name(o[0]) == "hsf_topk_batched"]
    assert [o[2] for o in kernel] == KERNEL_NS
    outs, args = tr.operands(kernel[0][0])
    assert outs == [("f32", (64, 128)), ("s32", (64, 128))]
    assert args == [("s32", (1,)), ("f32", (64, 4096)), ("s32", (64, 128)),
                    ("f32", (2048, 4096)), ("s32", (2048, 128))]


def test_recorded_trace_reduce():
    # a window of 1 s starting at the mark, with the mark at 1 s
    r = tr.reduce(TRACE, int(MARK_NS) + 10**9, 1.0, 2.0)
    assert r["window_s"] == pytest.approx(1.0)
    ops = tr.load(TRACE)["chips"]["/device:TPU:0"]["ops"]
    iv = np.array([(s, s + d) for _, s, d in ops])
    assert r["busy_s"] == pytest.approx(tr.union_ns(iv, -1e18, 1e18) / 1e9)
    assert r["busy_s"] == pytest.approx(533510e-9)
    top = dict(r["breakdown"]["device_ops"])
    assert top["jit__score_topk_pallas/hsf_topk_batched"] == pytest.approx(
        sum(KERNEL_NS) / 1e9)
    assert len(r["breakdown"]["device_ops"]) <= 10
    idle = r["breakdown"]["idle_gaps"]
    assert idle[0][0] == "no host span"
    assert idle[0][1] == pytest.approx(1.0 - r["busy_s"])


def test_idle_gaps_named_by_host_span():
    class Span:
        def __init__(self, name, t0_ns, dur_ns):
            self.name, self.t0_ns, self.dur_ns = name, t0_ns, dur_ns
    shift = 10**9 + 0.0
    start = MARK_NS + shift
    # 'flush' covers the whole window, 'query_embed' the first 0.6 s;
    # per-request stages never name a gap
    spans = [Span("flush", start, 10**9), Span("query_embed", start, 6e8),
             Span("queue_wait", start, 10**9)]
    r = tr.reduce(TRACE, int(MARK_NS + shift), 1.0 + MARK_NS / 1e9,
                  2.0 + MARK_NS / 1e9, spans)
    names = dict(r["breakdown"]["idle_gaps"])
    assert set(names) <= {"query_embed", "flush"}
    assert sum(names.values()) == pytest.approx(1.0 - r["busy_s"])


def test_work_counts_by_hand():
    outs = [("f32", (64, 128)), ("s32", (64, 128))]
    args = [("s32", (1,)), ("f32", (64, 4096)), ("s32", (64, 128)),
            ("f32", (2048, 4096)), ("s32", (2048, 128))]
    by_hand = (4 + 64 * 4096 * 4 + 64 * 128 * 4 + 2048 * 4096 * 4
               + 2048 * 128 * 4 + 2 * 64 * 128 * 4)
    assert work.call_bytes(outs, args) == by_hand
    assert work.hsf_topk_flops(args) == 2 * 64 * 2048 * 4096
    t, bound = work.roofline_seconds(2 * 64 * 2048 * 4096, by_hand, PEAKS)
    assert bound == "bytes" and t == pytest.approx(by_hand / 819e9)
    bf16 = [("bf16", (64, 4096)), ("bf16", (82432, 4096))]
    assert work.call_bytes([], bf16) == 2 * (64 + 82432) * 4096
    assert work.hsf_topk_flops(bf16) == 2 * 64 * 82432 * 4096
    t, bound = work.roofline_seconds(1e15, 1.0, PEAKS)
    assert bound == "flops" and t == pytest.approx(1e15 / 197e12)


def test_roofline_reader_on_recorded_trace():
    """The kernel's roofline share from the recorded calls: bytes bound,
    well under 100%."""
    sys.path.insert(0, str(HERE / "metrics"))
    import hsf_topk_roofline

    class R:
        device = tr.reduce(TRACE, int(MARK_NS) + 10**9, 1.0, 2.0)
        peaks = PEAKS
    share = hsf_topk_roofline.read(R)
    ops = tr.load(TRACE)["chips"]["/device:TPU:0"]["ops"]
    first = [o for o in ops if tr.op_name(o[0]) == "hsf_topk_batched"][0]
    outs, args = tr.operands(first[0])
    least = work.call_bytes(outs, args) / 819e9
    assert share == pytest.approx(100 * 3 * least / (sum(KERNEL_NS) / 1e9))
    assert 0 < share < 100
