"""Span coverage of the served and publish paths (docs/ARCHITECTURE.md
§12): the flusher loop, the query embed, the device dispatch, garbage
collections and the publish each record named spans when tracing is on,
nest as documented, cover the flusher thread's time, and change neither
results nor state when tracing is off."""
import gc
import threading

import numpy as np
import pytest

from repro.core.engine import QueryEngine
from repro.core.ingest import KnowledgeBase
from repro.obs import trace as obs_trace
from repro.obs.metrics import global_registry
from repro.serving import ServingRuntime
from repro.serving.snapshot import SnapshotManager, results_equal

DIM = 256
QUERIES = [f"alpha report INV-{i:04d} gamma" for i in range(0, 40, 3)] + [
    "beta gamma status", "never seen words", "INV-0007"]


def _kb(n_docs: int = 40) -> KnowledgeBase:
    kb = KnowledgeBase(dim=DIM)
    for i in range(n_docs):
        kb.add_text(f"doc_{i:03d}.txt",
                    f"alpha beta entity INV-{i:04d} report gamma {i}")
    return kb


@pytest.fixture
def tracer():
    """The default tracer, enabled and empty; disabled and drained after."""
    tr = obs_trace.get()
    tr.disable()
    tr.drain()
    tr.enable(sample=1.0)
    yield tr
    tr.disable()
    tr.drain()


def _serve(rt, texts, k=3):
    """Submit in two waves, so the flusher forms several batches."""
    out = []
    for wave in (texts[: len(texts) // 2], texts[len(texts) // 2:]):
        futs = [rt.submit(t, k=k) for t in wave]
        out += [f.result(timeout=120).results for f in futs]
    return out


def _runtime(**kw):
    return ServingRuntime(_kb(), max_batch=8, flush_deadline=0.002,
                          scoring_path="map", **kw)


def test_new_spans_nest_under_embed_and_dispatch(tracer):
    with _runtime() as rt:
        _serve(rt, QUERIES)
    spans = tracer.drain()
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert {"batch_wait", "flush", "fanout", "trace_emit", "query_embed",
            "query_vector", "query_signature", "query_pack",
            "device_dispatch", "query_upload", "launch", "device_wait",
            "host_transfer"} <= names
    parent_of = {"query_vector": "query_embed",
                 "query_signature": "query_embed",
                 "query_pack": "query_embed",
                 "query_upload": "device_dispatch",
                 "launch": "device_dispatch",
                 "device_wait": "device_dispatch",
                 "fanout": "flush"}
    for s in spans:
        if s.name in parent_of:
            assert by_id[s.parent_id].name == parent_of[s.name], s
            assert by_id[s.parent_id].trace_id == s.trace_id
    # batch_wait rides the trace of the request that opened the batch:
    # it takes no trace (sampling slot) of its own
    flush_traces = {s.trace_id for s in spans if s.name == "flush"}
    waits = [s for s in spans if s.name == "batch_wait"]
    assert waits and {s.trace_id for s in waits} <= flush_traces
    assert all(s.parent_id == 0 for s in waits)
    # the upload carries the padded query block: a power-of-two bucket
    # of (vector, signature) rows
    row = DIM * 4 + rt.snapshots.engine.kb.sig_words * 4
    for s in spans:
        if s.name == "query_upload":
            rows = s.args["bytes"] // row
            assert s.args["bytes"] == rows * row and rows in (1, 2, 4, 8)


def _union_ns(iv):
    total, end = 0, None
    for a, b in sorted(iv):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def test_flusher_thread_is_covered_by_spans(tracer):
    with _runtime() as rt:
        _serve(rt, QUERIES)
        _serve(rt, [q + " again" for q in QUERIES])
    spans = tracer.drain()
    flusher = {s.tid for s in spans if s.name == "flush"}
    assert len(flusher) == 1
    mine = [(s.t0_ns, s.t0_ns + s.dur_ns) for s in spans
            if s.tid in flusher]
    lo = min(a for a, _ in mine)
    hi = max(b for _, b in mine)
    assert _union_ns(mine) >= 0.95 * (hi - lo)


def test_results_bit_identical_with_tracing_on_and_off():
    tr = obs_trace.get()
    tr.disable()
    tr.drain()
    with _runtime() as rt:
        off = _serve(rt, QUERIES, k=5)
    eng_off = QueryEngine(_kb(), scoring_path="map", max_batch=8)
    eng_off_res = eng_off.query_batch(QUERIES + QUERIES[:3], k=5)
    assert tr.drain() == []
    tr.enable(sample=1.0)
    try:
        with _runtime() as rt:
            on = _serve(rt, QUERIES, k=5)
        eng_on = QueryEngine(_kb(), scoring_path="map", max_batch=8)
        eng_on_res = eng_on.query_batch(QUERIES + QUERIES[:3], k=5)
    finally:
        tr.disable()
        tr.drain()
    assert all(results_equal(a, b) for a, b in zip(off, on))
    assert all(results_equal(a, b) for a, b in zip(eng_off_res, eng_on_res))
    assert eng_off.cache_stats() == eng_on.cache_stats()
    assert list(eng_off._qcache) == list(eng_on._qcache)


def test_query_vector_cache_replays_the_per_query_lru():
    """The batched embed leaves the LRU as per-query lookups would:
    duplicates inside a chunk hit, eviction order is request order."""
    batched = QueryEngine(_kb(), scoring_path="map", cache_size=4)
    single = QueryEngine(_kb(), scoring_path="map", cache_size=4)
    texts = ["alpha INV-0001", "beta", "ALPHA inv-0001", "gamma", "delta",
             "epsilon", "beta", "zeta"]
    pairs = batched._query_pairs(texts)
    ref = [single._query_arrays(t) for t in texts]
    for (v, s), (rv, rs) in zip(pairs, ref):
        np.testing.assert_array_equal(v, rv)
        np.testing.assert_array_equal(s, rs)
    assert batched.cache_stats() == single.cache_stats()
    assert list(batched._qcache) == list(single._qcache)


def test_tracing_off_buffers_nothing_and_gc_hook_comes_and_goes():
    tr = obs_trace.get()
    tr.disable()
    tr.drain()
    before = list(gc.callbacks)
    with _runtime() as rt:
        _serve(rt, QUERIES[:6])
        rt.publish()
    gc.collect()
    assert tr.drain() == []
    assert gc.callbacks == before
    tr.enable()
    try:
        assert len(gc.callbacks) == len(before) + 1
        tr.enable()                      # enabling twice hooks once
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
        spans = [s for s in tr.drain() if s.name == "gc"]
        assert spans
        full = spans[-1]
        assert full.args["generation"] == 2
        assert full.args["collected"] >= 0
        assert full.parent_id == 0 and full.trace_id not in (0, full.span_id)
        assert full.tid == threading.get_ident()
    finally:
        tr.disable()
    assert gc.callbacks == before
    gc.collect()
    assert tr.drain() == []


def test_private_tracers_leave_gc_alone():
    before = list(gc.callbacks)
    tr = obs_trace.Tracer().enable()
    assert gc.callbacks == before
    gc.collect()
    assert tr.drain() == []
    tr.disable()


def _publish_spans(tracer, mgr):
    tracer.drain()
    mgr.publish()
    return tracer.drain()


def test_publish_that_moves_idf_reweights_and_uploads_the_matrix(tracer):
    kb = _kb()
    mgr = SnapshotManager(kb, scoring_path="map")
    # a new term in one doc moves df, so idf moves: full re-weight
    kb.add_text("doc_003.txt", "alpha beta entity INV-0003 novelterm")
    spans = _publish_spans(tracer, mgr)
    by_id = {s.span_id: s for s in spans}
    (rw,) = [s for s in spans if s.name == "reweight"]
    assert by_id[rw.parent_id].name == "refresh"
    ups = [s for s in spans if s.name == "upload"]
    vecs = [s for s in ups if s.args["what"] == "vecs"]
    assert len(vecs) == 1
    assert vecs[0].args["bytes"] == mgr.engine.doc_vecs.nbytes
    assert vecs[0].args["bytes"] == 40 * DIM * 4
    assert {s.args["what"] for s in ups} == {"vecs", "row_patch"}
    assert all(by_id[s.parent_id].name == "refresh" for s in ups)
    assert by_id[by_id[rw.parent_id].parent_id].name == "publish"


def test_kernel_path_publish_reweights_on_the_device(tracer):
    kb = _kb(n_docs=640)
    mgr = SnapshotManager(kb, scoring_path="kernel")
    total = global_registry().counter("ragdb_reweight_total", on="device")
    before = total.value
    kb.add_text("doc_003.txt", "alpha beta entity INV-0003 novelterm")
    spans = _publish_spans(tracer, mgr)
    by_id = {s.span_id: s for s in spans}
    (rw,) = [s for s in spans if s.name == "reweight"]
    assert rw.args["on"] == "device"
    assert by_id[rw.parent_id].name == "refresh"
    ups = [s for s in spans if s.name == "upload"]
    assert {s.args["what"] for s in ups} == {"u_patch", "row_patch", "idf"}
    sent = sum(s.args["bytes"] for s in ups)
    # one padded u chunk, one signature row and idf: no [N, D] matrix
    assert sent == (64 + 1) * DIM * 4 + kb.sig_words * 4
    assert sent * 8 < mgr.engine.doc_vecs.nbytes
    assert all(by_id[s.parent_id].name == "refresh" for s in ups)
    assert total.value == before + 1


def test_publish_with_stable_idf_patches_rows(tracer):
    kb = _kb()
    mgr = SnapshotManager(kb, scoring_path="map")
    # the same terms, counted differently: df and idf stay put
    kb.add_text("doc_005.txt",
                "alpha alpha beta entity INV-0005 report gamma 5")
    spans = _publish_spans(tracer, mgr)
    assert not [s for s in spans if s.name == "reweight"]
    ups = [s for s in spans if s.name == "upload"]
    assert ups and {s.args["what"] for s in ups} == {"row_patch"}
    # one changed row, bucketed to a power of two (one row): vecs + sigs
    assert sorted(s.args["bytes"] for s in ups) == sorted(
        [DIM * 4, kb.sig_words * 4])


def test_ivf_served_batch_records_ivf_counters():
    reg = global_registry()
    searches = reg.counter("ragdb_ivf_searches_total", "ivf dispatches")
    before = searches.value
    mgr = SnapshotManager(_kb(60), scoring_path="map", index="ivf",
                          nprobe=2)
    out = mgr.current.query_batch(["alpha INV-0007", "beta gamma"], k=3)
    assert len(out) == 2 and all(len(r) == 3 for r in out)
    assert searches.value == before + 1
    assert reg.series("ragdb_ivf_widen_rounds")
