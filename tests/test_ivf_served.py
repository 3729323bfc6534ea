"""The exact IVF index on the served path: ``ServingRuntime(index="ivf",
guarantee="exact")`` answers bit-identically to the flat scan at every
flush size, on both sides of its choice between gathering the probed
clusters and collapsing to the flat scan; every shape it dispatches is
compiled before it is armed; the kernel path scores from the snapshot's
one aligned operand pair; and the gather is traced with its size."""
import numpy as np
import pytest

from repro.analysis import sanitizers
from repro.core import hsf
from repro.core.engine import QueryEngine
from repro.core.ingest import KnowledgeBase
from repro.index.ivf import row_bucket
from repro.obs import trace as obs_trace
from repro.serving import ServingRuntime

from conftest import assert_bit_identical

TOPICS, PER_TOPIC, DIM, K = 24, 10, 512, 5


def _topic_words(t: int, rng) -> list:
    return [f"t{t:02d}w{j:02d}{''.join(rng.choice(list('qxzv'), 3))}"
            for j in range(12)]


def _kb(seed: int = 0, per_topic: int = PER_TOPIC):
    """Tight topics: each of TOPICS groups of near-identical passages, so
    a query on one topic provably excludes every other cluster and a
    small flush gathers; a flush spanning most topics collapses."""
    rng = np.random.default_rng(seed)
    kb = KnowledgeBase(dim=DIM)
    words = [_topic_words(t, rng) for t in range(TOPICS)]
    for t in range(TOPICS):
        for i in range(per_topic):
            kb.add_text(f"t{t:02d}_{i:02d}",
                        " ".join(words[t]) + f" var{t}x{i} CODE-{t:02d}{i:02d}")
    queries = [" ".join(words[t][:6]) for t in range(TOPICS)]
    queries += [f"CODE-{t:02d}{i:02d}" for t in range(TOPICS)
                for i in (0, 7)]
    order = np.random.default_rng(seed + 1).permutation(len(queries))
    return kb, [queries[i] for i in order]


def _runtime(kb, **kw):
    return ServingRuntime(kb, index="ivf", guarantee="exact",
                          n_clusters=TOPICS, nprobe=1, max_batch=64,
                          flush_deadline=0.001, result_cache_size=0, **kw)


@pytest.fixture
def tracer():
    tr = obs_trace.get()
    tr.disable()
    tr.drain()
    tr.enable(sample=1.0)
    yield tr
    tr.disable()
    tr.drain()


@pytest.fixture
def armed_sanitizers():
    sanitizers.enable(True)
    yield
    sanitizers._enabled = None


def test_served_exact_ivf_matches_flat_at_every_batch_size(tracer):
    kb, queries = _kb()
    flat = QueryEngine(kb, scoring_path="map")
    with _runtime(kb, scoring_path="map") as rt:
        snap = rt.snapshots.current
        for b in range(1, 65):
            batch = (queries * 2)[:b]
            # inside a span, as the flusher's are: the IVF rounds are
            # recorded under the enclosing trace
            with obs_trace.span("flush", queries=b):
                got = snap.query_batch(batch, k=K)
            assert_bit_identical(flat.query_batch(batch, k=K), got,
                                 label=f"b={b}")
        served = rt.query_batch(queries, k=K)
    assert_bit_identical(flat.query_batch(queries, k=K), served)
    rounds = [s for s in tracer.drain() if s.name == "ivf_widen_round"]
    last = {}
    for s in rounds:     # each dispatch's rounds run in order on one thread
        if s.args["round"] == 1 and last.get("open"):
            last.setdefault("ends", []).append(last["open"])
        last["open"] = s
    ends = last.get("ends", []) + [last["open"]]
    # both sides of the choice were served, and each said which it took
    assert {s.args["collapsed"] for s in ends} == {True, False}
    n = kb.n_docs
    for s in rounds:
        want = n if s.args["collapsed"] else row_bucket(
            s.args["rows"], n, "map")
        assert s.args["bucket"] == want


def test_armed_ivf_runtime_compiles_nothing_for_any_mix(armed_sanitizers):
    # 110 passages a topic: a flush of one topic gathers a 128-row
    # block, one of ten to twelve topics a 2,048-row block, which the
    # warm-up's own queries never reach
    kb, queries = _kb(seed=3, per_topic=110)
    topical = [q for q in queries if not q.startswith("CODE-")]
    rng = np.random.default_rng(5)
    with _runtime(kb, scoring_path="map") as rt:
        rt.arm_sanitizers(k=K)
        for size in [1, 2, 3, 5, 8, 13, 21, 34, 55, 64, 1, 4, 17, 64]:
            picks = rng.integers(0, len(queries), size)
            # one wave per size: the flusher forms batches of any mix
            futs = [rt.submit(queries[int(j)], k=K) for j in picks]
            for f in futs:
                f.result(timeout=120)  # a retrace after arming raises here
        snap = rt.snapshots.current
        for topics in range(1, len(topical) + 1):
            snap.query_batch(topical[:topics], k=K)
        assert rt.retrace_guard.report() == {}


def test_kernel_path_scores_from_the_pinned_aligned_operands(monkeypatch):
    kb, queries = _kb(seed=1)
    n = kb.n_docs
    padded = []
    real_pad = hsf.hsf_kernel_pad_docs

    def counting_pad(doc_vecs, doc_sigs):
        padded.append(int(doc_vecs.shape[0]))
        return real_pad(doc_vecs, doc_sigs)

    monkeypatch.setattr(hsf, "hsf_kernel_pad_docs", counting_pad)
    flat = QueryEngine(kb, scoring_path="kernel")
    rt = ServingRuntime(kb, index="ivf", guarantee="exact",
                        n_clusters=TOPICS, nprobe=1, max_batch=8,
                        flush_deadline=0.001, result_cache_size=0,
                        scoring_path="kernel")
    snap = rt.snapshots.current
    # one [N, D] buffer per generation: the aligned copy, no second
    assert snap.doc_vecs is None and snap.kernel_operands is not None
    batches = [[queries[j % len(queries)] for j in range(0, 8 * b, 8)]
               for b in (1, 3, 8)]           # gather side and collapse side
    want = [flat.query_batch(batch, k=K) for batch in batches]
    rt.arm_sanitizers(k=K)
    padded.clear()
    with rt:
        for batch, rows in zip(batches, want):
            got = snap.query_batch(batch, k=K)
            assert [[r.doc_id for r in row] for row in got] == [
                [r.doc_id for r in row] for row in rows]
        rt.query_batch(queries[:6], k=K)
    # no dispatch padded the corpus matrix (the parent padded it on every
    # flat collapse); gathered blocks are already block-aligned buckets
    assert n not in padded


def test_gather_span_carries_bucket_and_bytes(tracer):
    kb, queries = _kb(seed=2)
    n = kb.n_docs
    with _runtime(kb, scoring_path="map") as rt:
        with obs_trace.span("flush", queries=1):
            rt.snapshots.current.query_batch([queries[0]], k=K)
    spans = tracer.drain()
    gathers = [s for s in spans if s.name == "ivf_gather"]
    rounds = [s for s in spans if s.name == "ivf_widen_round"]
    assert gathers and rounds and not rounds[0].args["collapsed"]
    g = gathers[0]
    assert g.args["rows"] == row_bucket(rounds[0].args["rows"], n, "map")
    assert g.args["bytes"] == g.args["rows"] * (4 * DIM + 4 * kb.sig_words)
