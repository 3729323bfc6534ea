"""Serving runtime contracts (serving/): the micro-batching scheduler
returns exactly what a direct engine call would, generation-pinned
snapshots give torn-read-free serving under live ingest, the result
cache never crosses generations, backpressure is explicit, and the
KnowledgeBase single-writer contract is asserted."""
import os
import threading
import time

import pytest

from repro.core.engine import QueryEngine
from repro.core.ingest import KnowledgeBase
from repro.data.corpus import make_corpus, write_corpus_dir
from repro.serving import (
    MicroBatchScheduler,
    RequestRejected,
    ResultCache,
    ServingMetrics,
    ServingRuntime,
    SnapshotManager,
)
from repro.serving.metrics import LatencyHistogram

from conftest import assert_bit_identical


def _kb(n_docs=40, dim=256, n_entities=6, seed=0):
    docs, entities = make_corpus(n_docs=n_docs, n_entities=n_entities,
                                 seed=seed)
    kb = KnowledgeBase(dim=dim)
    for i, d in enumerate(docs):
        kb.add_text(f"doc_{i:05d}.txt", d)
    return kb, entities


# --------------------------------------------------------------------------
# scheduler: results identical to a direct engine call
# --------------------------------------------------------------------------

def test_scheduled_results_match_direct_engine():
    kb, entities = _kb()
    runtime = ServingRuntime(kb, max_batch=8, flush_deadline=0.002,
                             result_cache_size=0)
    engine = QueryEngine(kb)
    queries = [*entities, "quarterly forecast", "unrelated text", ""]
    with runtime:
        futs = [(q, k, runtime.submit(q, k=k))
                for k in (3, 5) for q in queries]
        for q, k, fut in futs:
            served = fut.result(timeout=60)
            want = engine.query_batch([q], k=k)[0]
            assert_bit_identical([served.results], [want],
                                 label=f"{q!r} k={k}")
            assert served.generation == runtime.generation


def test_runtime_query_batch_blocking_facade():
    kb, entities = _kb(n_docs=20)
    engine = QueryEngine(kb)
    queries = list(entities)[:4]
    with ServingRuntime(kb, max_batch=4) as runtime:
        got = runtime.query_batch(queries, k=2)
    assert_bit_identical(got, engine.query_batch(queries, k=2))


def test_scheduler_coalesces_duplicate_queries():
    kb, entities = _kb(n_docs=20)
    code = next(iter(entities))
    sched = MicroBatchScheduler(SnapshotManager(kb), max_batch=16,
                                flush_deadline=0.01)
    # fill the queue before starting the flusher: one flush, one batch
    futs = [sched.submit(code, k=3) for _ in range(5)]
    futs.append(sched.submit(code.lower(), k=3))  # same canonical text
    futs.append(sched.submit("something else", k=3))
    with sched:
        done = [f.result(timeout=60) for f in futs]
    m = sched.metrics.snapshot()
    assert m["batches"] == 1
    assert m["batch_occupancy_mean"] == 7.0
    assert m["scored_queries"] == 2  # 7 requests, 2 distinct queries
    for d in done[:6]:
        assert_bit_identical([d.results], [done[0].results])


def test_scheduler_backpressure_rejects_when_full():
    kb, _ = _kb(n_docs=10)
    sched = MicroBatchScheduler(SnapshotManager(kb), max_batch=4,
                                max_queue=2)
    ok = [sched.submit("q1"), sched.submit("q2")]  # queue now full
    with pytest.raises(RequestRejected):
        sched.submit("q3")
    assert sched.metrics.snapshot()["rejected"] == 1
    with sched:  # admitted requests still complete
        for f in ok:
            assert f.result(timeout=60).results


def test_scheduler_stop_rejects_queued_and_new_requests():
    kb, _ = _kb(n_docs=10)
    sched = MicroBatchScheduler(SnapshotManager(kb))
    fut = sched.submit("never served")
    sched.stop()  # never started: queued request must not hang forever
    with pytest.raises(RequestRejected):
        fut.result(timeout=5)
    with pytest.raises(RequestRejected):
        sched.submit("after stop")


# --------------------------------------------------------------------------
# generation-pinned snapshots
# --------------------------------------------------------------------------

def test_snapshot_pins_generation_across_mutations():
    kb, entities = _kb(n_docs=25)
    code = next(iter(entities))
    manager = SnapshotManager(kb)
    snap0 = manager.current
    before = snap0.query_batch([code, "TORN-1111"], k=3)

    kb.add_text("torn_doc", "fresh document about TORN-1111 exactly")
    snap1 = manager.publish()
    assert snap1.generation > snap0.generation
    assert manager.current is snap1

    # the pinned snapshot still serves generation g bit-identically …
    again = snap0.query_batch([code, "TORN-1111"], k=3)
    assert_bit_identical(before, again)
    assert all(r.doc_id != "torn_doc" for r in again[1])
    # … while the published one sees the new generation
    top = snap1.query_batch(["TORN-1111"], k=1)[0][0]
    assert top.doc_id == "torn_doc" and top.boosted


def test_kernel_path_snapshot_pins_only_the_aligned_operands():
    """On the flat kernel path a snapshot scores from the block-aligned
    operands alone, so it does not pin the unaligned matrix too, and it
    still serves its own generation after the next publish."""
    kb, entities = _kb(n_docs=25)
    queries = [next(iter(entities)), "TORN-1111"]
    manager = SnapshotManager(kb, scoring_path="kernel")
    snap0 = manager.current
    assert snap0.doc_vecs is None and snap0.kernel_operands is not None
    before = snap0.query_batch(queries, k=3)
    assert_bit_identical(before, manager.engine.query_batch(queries, k=3))

    kb.add_text("torn_doc", "fresh document about TORN-1111 exactly")
    snap1 = manager.publish()
    assert snap1.doc_vecs is None
    assert_bit_identical(before, snap0.query_batch(queries, k=3))
    top = snap1.query_batch(["TORN-1111"], k=1)[0][0]
    assert top.doc_id == "torn_doc" and top.boosted


def test_snapshot_matches_engine_frozen_at_same_generation():
    """A snapshot's query vectors come from its own idf copy: results
    equal a direct engine on a KB frozen at that generation, even after
    the live KB's df statistics move on."""
    kb, entities = _kb(n_docs=30)
    queries = [*list(entities)[:3], "generic filler query"]
    frozen = QueryEngine(kb)
    want = frozen.query_batch(queries, k=4)  # engine at generation g

    manager = SnapshotManager(kb)
    snap = manager.current
    for i in range(5):  # shift idf hard after the pin
        kb.add_text(f"noise_{i}", f"noise document {i} about filler query")
    got = snap.query_batch(queries, k=4)
    assert_bit_identical(got, want)


def test_publish_is_noop_without_mutations():
    kb, _ = _kb(n_docs=10)
    manager = SnapshotManager(kb)
    snap = manager.current
    assert manager.publish() is snap  # same object: no spurious swap


def test_snapshot_pins_frozen_ivf_index_per_generation():
    """The clustered index is pinned exactly like the doc arrays: a
    snapshot captured at generation g keeps serving g's IVFIndex object
    (maintenance only rebinds engine.ivf), so readers never observe a
    half-retrained index and pinned results stay bit-stable."""
    kb, entities = _kb(n_docs=60)
    code = next(iter(entities))
    manager = SnapshotManager(kb, scoring_path="map", index="ivf",
                              nprobe=2, guarantee="exact")
    snap0 = manager.current
    assert snap0.index_kind == "ivf" and snap0.ivf is not None
    before = snap0.query_batch([code, "PINNED-9090"], k=3)

    kb.add_text("pinned_doc", "fresh document about PINNED-9090 exactly")
    snap1 = manager.publish()
    assert snap1.ivf is not snap0.ivf  # maintenance rebound the index
    assert snap1.ivf is manager.engine.ivf  # the live reference moved on

    again = snap0.query_batch([code, "PINNED-9090"], k=3)
    assert_bit_identical(before, again)  # g's index still serves g's results
    assert all(r.doc_id != "pinned_doc" for r in again[1])
    top = snap1.query_batch(["PINNED-9090"], k=1)[0][0]
    assert top.doc_id == "pinned_doc" and top.boosted
    # the pinned snapshots match a flat engine frozen at each generation
    flat_now = QueryEngine(kb, scoring_path="map")
    assert_bit_identical(snap1.query_batch([code], k=3),
                         flat_now.query_batch([code], k=3))


# --------------------------------------------------------------------------
# result cache: (query, k, generation) keying
# --------------------------------------------------------------------------

def test_result_cache_generation_keying_and_lru():
    cache = ResultCache(capacity=2)
    cache.put("Q", 5, 1, ["r1"])
    assert cache.get("q", 5, 1) == ["r1"]  # canonicalized text
    assert cache.get("Q", 5, 2) is None    # new generation → miss
    assert cache.get("Q", 3, 1) is None    # different k → miss
    cache.put("other", 5, 1, ["r2"])
    cache.put("third", 5, 2, ["r3"])       # evicts LRU ("Q")
    assert cache.get("Q", 5, 1) is None
    assert cache.evict_generations_before(2) == 1  # drops "other"@gen1
    assert len(cache) == 1


def test_result_cache_evict_generations_before():
    """The hygiene hook drops exactly the entries pinned below the
    cutoff, keeps the rest queryable, and is idempotent."""
    cache = ResultCache(capacity=16)
    for gen in (1, 1, 2, 3):
        cache.put(f"q{gen}", 5, gen, [f"r{gen}"])
    cache.put("q1b", 5, 1, ["r1b"])
    assert len(cache) == 4  # ("q1",1) was overwritten by the dup put
    assert cache.evict_generations_before(3) == 3  # both gen-1 + gen-2
    assert len(cache) == 1
    assert cache.get("q3", 5, 3) == ["r3"]
    assert cache.get("q1", 5, 1) is None
    assert cache.evict_generations_before(3) == 0  # idempotent
    # eviction never touches the hit/miss counters' consistency
    s = cache.stats()
    assert s["hits"] == 1 and s["misses"] == 1


def test_result_cache_capacity_eviction_is_lru_ordered():
    """Capacity pressure evicts least-recently-*used*, not
    least-recently-inserted: a get() refreshes recency, and a put() to
    an existing key does too."""
    cache = ResultCache(capacity=3)
    cache.put("a", 5, 1, ["a"])
    cache.put("b", 5, 1, ["b"])
    cache.put("c", 5, 1, ["c"])
    assert cache.get("a", 5, 1) == ["a"]   # a → most recent
    cache.put("d", 5, 1, ["d"])            # evicts b (LRU), not a
    assert cache.get("b", 5, 1) is None
    assert cache.get("a", 5, 1) == ["a"]
    cache.put("c", 5, 1, ["c2"])           # refresh c by re-put
    cache.put("e", 5, 1, ["e"])            # evicts d (now LRU)
    assert cache.get("d", 5, 1) is None
    assert cache.get("c", 5, 1) == ["c2"]
    assert len(cache) == 3


def test_result_cache_keyspaces_scope_generation_eviction():
    """Regression: ``evict_generations_before`` used to be global —
    one tenant's publish would sweep another tenant's entries pinned
    to *its own* (unrelated) generation counter.  Scoped semantics:
    only the named keyspace is swept, even with interleaved puts."""
    cache = ResultCache(capacity=16)
    # interleave two keyspaces across the same generation numbers
    for gen in (1, 2, 3):
        cache.put(f"qa{gen}", 5, gen, [f"a{gen}"], keyspace="alice")
        cache.put(f"qb{gen}", 5, gen, [f"b{gen}"], keyspace="bob")
    assert cache.evict_generations_before(3, keyspace="alice") == 2
    # alice keeps only gen-3; bob is untouched at every generation
    assert cache.get("qa3", 5, 3, keyspace="alice") == ["a3"]
    assert cache.get("qa1", 5, 1, keyspace="alice") is None
    for gen in (1, 2, 3):
        assert cache.get(f"qb{gen}", 5, gen, keyspace="bob") == [f"b{gen}"]
    # same (text, k, generation) key in two keyspaces: distinct entries
    cache.put("shared", 5, 3, ["alice's"], keyspace="alice")
    cache.put("shared", 5, 3, ["bob's"], keyspace="bob")
    assert cache.get("shared", 5, 3, keyspace="alice") == ["alice's"]
    assert cache.get("shared", 5, 3, keyspace="bob") == ["bob's"]
    assert cache.stats()["keyspaces"] == 2


def test_result_cache_capacity_is_per_keyspace():
    """A hot keyspace filling its own LRU never evicts a cold
    keyspace's entries (capacity accounting is scoped too)."""
    cache = ResultCache(capacity=2)
    cache.put("cold", 5, 1, ["kept"], keyspace="bob")
    for i in range(10):  # alice churns way past capacity
        cache.put(f"hot{i}", 5, 1, [i], keyspace="alice")
    assert cache.get("cold", 5, 1, keyspace="bob") == ["kept"]
    assert len(cache) == 3  # 2 alice + 1 bob
    # drop_keyspace removes wholesale and reports the count
    assert cache.drop_keyspace("alice") == 2
    assert cache.drop_keyspace("alice") == 0
    assert cache.get("cold", 5, 1, keyspace="bob") == ["kept"]


def test_result_cache_counters_consistent_under_concurrent_access():
    """hits + misses must equal total get() calls even under concurrent
    get/put from many threads (the counters sit inside the lock)."""
    cache = ResultCache(capacity=32)
    n_threads, n_ops = 8, 400
    errors = []

    def worker(tid):
        try:
            for i in range(n_ops):
                key = f"q{(tid * n_ops + i) % 16}"  # overlap across threads
                if cache.get(key, 5, 1) is None:
                    cache.put(key, 5, 1, [key])
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s = cache.stats()
    assert s["hits"] + s["misses"] == n_threads * n_ops
    assert s["hits"] > 0 and s["misses"] > 0
    assert len(cache) <= 32


def test_runtime_cache_hit_serves_same_generation_results():
    kb, entities = _kb(n_docs=20)
    code = next(iter(entities))
    with ServingRuntime(kb, flush_deadline=0.001) as runtime:
        first = runtime.submit(code, k=3).result(timeout=60)
        second = runtime.submit(code, k=3).result(timeout=60)
        assert second.cached and not first.cached
        assert_bit_identical([first.results], [second.results])
        assert second.generation == first.generation

        # a publish invalidates naturally: new generation → fresh miss
        kb.add_text("shift", f"new doc mentioning {code} loudly")
        runtime.publish()
        third = runtime.submit(code, k=3).result(timeout=60)
        assert not third.cached
        assert third.generation > first.generation
    m = runtime.metrics.snapshot()
    assert m["cache_hits"] == 1 and m["cache_misses"] == 2


# --------------------------------------------------------------------------
# metrics plane
# --------------------------------------------------------------------------

def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in range(1, 101):  # 1..100 ms uniform
        h.record(ms / 1e3)
    assert h.n == 100
    # log-bucket quantization error is bounded by one growth step
    assert 0.050 * 0.8 <= h.percentile(50) <= 0.050 * 1.25
    assert 0.099 * 0.8 <= h.percentile(99) <= 0.100 * 1.25
    assert h.percentile(100) == pytest.approx(h.max)
    assert h.mean == pytest.approx(0.0505)


def test_metrics_snapshot_counters():
    m = ServingMetrics()
    m.on_submit()
    m.on_submit()
    m.on_batch(2, 1)
    m.on_complete(0.010)
    m.on_complete(0.020)
    m.on_reject()
    s = m.snapshot()
    assert s["requests"] == 2 and s["completed"] == 2
    assert s["rejected"] == 1
    assert s["batches"] == 1 and s["batch_occupancy_mean"] == 2.0
    assert s["scored_queries"] == 1
    assert 0 < s["latency_p50_ms"] < 30
    m.reset()
    assert m.snapshot()["requests"] == 0


# --------------------------------------------------------------------------
# KnowledgeBase single-writer contract
# --------------------------------------------------------------------------

def test_kb_mutations_assert_single_writer(tmp_path):
    kb = KnowledgeBase(dim=256)
    kb.add_text("a", "first document")
    # simulate a second in-flight writer holding the mutation lock
    assert kb._write_lock.acquire(blocking=False)
    try:
        with pytest.raises(RuntimeError, match="single-writer"):
            kb.add_text("b", "competing writer")
        with pytest.raises(RuntimeError, match="single-writer"):
            kb.sync(str(tmp_path))
    finally:
        kb._write_lock.release()
    kb.add_text("b", "writer released: fine again")
    assert kb.n_docs == 2


def test_kb_concurrent_second_writer_raises(tmp_path, monkeypatch):
    """A real second thread mutating mid-sync trips the guard."""
    src = str(tmp_path / "corpus")
    docs, _ = make_corpus(n_docs=5, n_entities=2, seed=2)
    write_corpus_dir(src, docs)
    kb = KnowledgeBase(dim=256)

    in_sync = threading.Event()
    release = threading.Event()
    orig_walk = os.walk

    def stalled_walk(d):
        in_sync.set()
        assert release.wait(timeout=30)
        return orig_walk(d)

    monkeypatch.setattr(os, "walk", stalled_walk)
    t = threading.Thread(target=kb.sync, args=(src,))
    t.start()
    try:
        assert in_sync.wait(timeout=30)
        with pytest.raises(RuntimeError, match="single-writer"):
            kb.add_text("intruder", "second writer while sync runs")
    finally:
        release.set()
        t.join()
    assert kb.n_docs == 5  # the legitimate sync completed


# --------------------------------------------------------------------------
# THE stress test: concurrent queries + live sync, zero torn reads
# --------------------------------------------------------------------------

N_READERS = 4
N_ROUNDS = 6


def test_concurrent_serving_with_live_sync_is_torn_read_free(tmp_path):
    """≥4 reader threads query through the scheduler while a single
    writer thread continuously mutates the corpus, syncs, and publishes.
    Every served result must be (a) bit-identical to a direct
    ``QueryEngine.query_batch`` on the KB state at the pinned
    generation, and (b) attributable to a *published* generation — a
    partially refreshed snapshot would fail both."""
    src = str(tmp_path / "corpus")
    docs, entities = make_corpus(n_docs=40, n_entities=6, seed=1)
    write_corpus_dir(src, docs)
    kb = KnowledgeBase(dim=256)
    kb.sync(src)

    runtime = ServingRuntime(kb, max_batch=8, flush_deadline=0.002,
                             result_cache_size=0)  # force real scoring
    containers: dict[int, str] = {}  # generation → frozen KB container

    def save_generation(gen: int) -> None:
        path = str(tmp_path / f"gen_{gen}.ragdb")
        kb.save(path, generation=gen)
        containers[gen] = path

    save_generation(runtime.generation)
    queries = [*entities, "escalation runbook", "quarterly forecast",
               "LIVE-7777"]
    # warm the jit caches so readers overlap every generation below
    with runtime:
        runtime.query_batch(queries[:2], k=3)

        served = []  # (query, k, ServedResult)
        served_lock = threading.Lock()
        stop = threading.Event()

        def reader(rid: int):
            i = rid
            while not stop.is_set():
                q = queries[i % len(queries)]
                k = 3 if (i % 2) else 5
                i += 1
                res = runtime.submit(q, k=k).result(timeout=120)
                with served_lock:
                    served.append((q, k, res))

        threads = [threading.Thread(target=reader, args=(r,))
                   for r in range(N_READERS)]
        for t in threads:
            t.start()

        # the single writer: mutate files → sync → freeze → publish
        for rnd in range(N_ROUNDS):
            with open(os.path.join(src, f"doc_{rnd:05d}.txt"), "a") as f:
                f.write(f" LIVE-7777 edit round {rnd}")
            if rnd % 2:
                with open(os.path.join(src, f"extra_{rnd}.txt"), "w") as f:
                    f.write(f"brand new doc in round {rnd}")
            if rnd == 4:
                os.unlink(os.path.join(src, "doc_00030.txt"))
            kb.sync(src)
            save_generation(kb.version)
            gen = runtime.publish()
            assert gen == kb.version
            time.sleep(0.05)  # let readers overlap this generation

        stop.set()
        for t in threads:
            t.join()

    assert len(served) >= 4 * N_ROUNDS  # readers really overlapped
    observed = {res.generation for _, _, res in served}
    # (b) every request came from a published generation
    assert observed <= set(containers), (
        f"torn read: generations {observed - set(containers)} were never "
        "published"
    )
    assert len(observed) >= 2  # the run actually spanned generations

    # (a) bit-identical to a direct engine call at the pinned generation
    references = {
        gen: QueryEngine(KnowledgeBase.load(containers[gen]))
        for gen in observed
    }
    for q, k, res in served:
        want = references[res.generation].query_batch([q], k=k)[0]
        assert_bit_identical([res.results], [want], label=(
            f"torn read: {q!r}@k={k} vs the engine at pinned "
            f"generation {res.generation}"
        ))
