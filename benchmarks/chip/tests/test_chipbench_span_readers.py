"""The readers of the dispatch, garbage-collector and publish spans, each
on a synthetic span list: what they read from the window's spans, and
nothing (None) where the window holds no such span."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402

T0, T1 = 10.0, 20.0          # the window, on time.perf_counter seconds


class Span:
    def __init__(self, name, t0_s, dur_us, **args):
        self.name, self.t0_ns = name, int(t0_s * 1e9)
        self.dur_ns, self.args = int(dur_us * 1e3), args


def reader(name):
    return run.load_module(HERE / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}").read


def readings(spans, device=True):
    return run.Readings(t0=T0, t1=T1, spans=spans,
                        device={} if device else None)


def dispatches():
    """Two dispatches in the window and one before it."""
    out = []
    for t in (5.0, 11.0, 12.0):
        out += [Span("device_dispatch", t, 900.0),
                Span("query_upload", t, 40.0, bytes=1 << 20),
                Span("launch", t + 1e-4, 160.0 if t > T0 else 5000.0),
                Span("device_wait", t + 3e-4, 600.0),
                Span("host_transfer", t + 1e-3, 300.0 if t > 11.5 else 100.0)]
    return out


def test_dispatch_us_per_call():
    # (40 + 160) us per dispatch; the dispatch before the window is not read
    assert reader("dispatch_us_per_call")(readings(dispatches())) == (
        pytest.approx(200.0))


def test_transfer_us_per_call():
    assert reader("transfer_us_per_call")(readings(dispatches())) == (
        pytest.approx(200.0))


def test_gc_ms_per_s():
    spans = [Span("gc", 9.0, 50_000.0, generation=2, collected=9),
             Span("gc", 12.0, 150_000.0, generation=2, collected=100),
             Span("gc", 15.0, 50.0, generation=0, collected=3),
             Span("flush", 13.0, 1000.0)]
    # (150 ms + 0.05 ms) over the 10 s window
    assert reader("gc_ms_per_s")(readings(spans)) == pytest.approx(15.005)


def test_publish_upload_mb():
    spans = [Span("publish", 11.0, 3e6), Span("publish", 15.0, 3e6),
             Span("upload", 11.5, 1e6, bytes=1_344_000_000, what="vecs"),
             Span("upload", 11.2, 10.0, bytes=80_000, what="row_patch"),
             Span("upload", 15.5, 1e6, bytes=1_344_000_000, what="vecs"),
             Span("upload", 5.0, 1e6, bytes=10**12, what="vecs")]
    assert reader("publish_upload_mb")(readings(spans)) == pytest.approx(
        (2 * 1_344_000_000 + 80_000) / 2 / 1e6)


def test_reweight_ms():
    spans = [Span("reweight", t, us) for t, us in
             ((11.0, 1.2e6), (13.0, 1.4e6), (15.0, 9e6), (25.0, 1e3))]
    assert reader("reweight_ms")(readings(spans)) == pytest.approx(1400.0)


@pytest.mark.parametrize("name", ["dispatch_us_per_call",
                                  "transfer_us_per_call", "gc_ms_per_s",
                                  "publish_upload_mb", "reweight_ms"])
def test_nothing_to_read(name):
    """A window without the spans (a program that does not emit them, or
    spans outside the window) reads None; so does a run without spans."""
    outside = [Span(n, 25.0, 100.0, bytes=1) for n in (
        "device_dispatch", "query_upload", "launch", "host_transfer", "gc",
        "publish", "upload", "reweight")]
    unrelated = [Span("flush", 12.0, 100.0), Span("query_embed", 12.0, 50.0),
                 Span("publish", 12.0, 100.0)]
    read = reader(name)
    assert read(readings(outside)) is None
    assert read(readings(unrelated)) is None
    assert read(readings(None)) is None


@pytest.mark.parametrize("name", ["dispatch_us_per_call",
                                  "transfer_us_per_call", "gc_ms_per_s"])
def test_steady_readers_need_the_device_trace(name):
    spans = dispatches() + [Span("gc", 12.0, 100.0)]
    assert reader(name)(readings(spans)) is not None
    assert reader(name)(readings(spans, device=False)) is None
