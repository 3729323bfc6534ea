"""QueryEngine contracts: batched scoring is bit-identical to the
single-query Retriever, incremental materialization equals a cold
rebuild bit-exactly, and the query cache never changes results."""
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.engine import QueryEngine, _bucket, resolve_scoring_path
from repro.core.ingest import KnowledgeBase
from repro.core.retrieval import Retriever
from repro.data.corpus import make_corpus


def _kb(n_docs=80, dim=1024, n_entities=6, seed=0):
    docs, entities = make_corpus(n_docs=n_docs, n_entities=n_entities,
                                 seed=seed)
    kb = KnowledgeBase(dim=dim)
    for i, d in enumerate(docs):
        kb.add_text(f"doc_{i:05d}.txt", d)
    return kb, entities


def _queries(entities):
    return (
        [code for code in entities]
        + [f"lookup {code} record" for code in entities]
        + ["quarterly forecast", "unrelated text", ""]
    )


# --------------------------------------------------------------------------
# batched == looped (scores, ids, tie order — bit-identical)
# --------------------------------------------------------------------------

def test_query_batch_bit_identical_to_looped_retriever():
    kb, entities = _kb()
    engine = QueryEngine(kb)
    retriever = Retriever(kb)
    queries = _queries(entities)

    batch = engine.query_batch(queries, k=5)
    assert len(batch) == len(queries)
    for q, got in zip(queries, batch):
        want = retriever.query(q, k=5)
        assert [r.doc_id for r in got] == [r.doc_id for r in want], q
        # bit-identical, not approx: same floats out of both paths
        assert [r.score for r in got] == [r.score for r in want], q
        assert [r.cosine for r in got] == [r.cosine for r in want], q
        assert [r.boosted for r in got] == [r.boosted for r in want], q


def test_query_batch_independent_of_batch_composition():
    """A query's results don't depend on what else is in the batch (the
    padding-bucket contract)."""
    kb, entities = _kb()
    engine = QueryEngine(kb)
    queries = _queries(entities)
    alone = [engine.query_batch([q], k=3)[0] for q in queries]
    together = engine.query_batch(queries, k=3)
    for q, a, t in zip(queries, alone, together):
        assert [(r.doc_id, r.score) for r in a] == \
            [(r.doc_id, r.score) for r in t], q


def test_query_batch_kernel_path_bit_identical():
    kb, entities = _kb(n_docs=64)
    engine = QueryEngine(kb, use_kernel=True)
    retriever = Retriever(kb, use_kernel=True)
    for q in list(entities)[:3]:
        got = engine.query_batch([q, "decoy query"], k=4)[0]
        want = retriever.query(q, k=4)
        assert [(r.doc_id, r.score) for r in got] == \
            [(r.doc_id, r.score) for r in want]


def test_kernel_path_matches_default_ranking_batched():
    """The fused batched kernel (in-kernel top-k) returns the same
    ranking, boosted flags, and near-identical scores as the bit-stable
    lax.map path, across batch sizes and for tie-heavy corpora."""
    kb, entities = _kb(n_docs=60)
    for i in range(10):
        kb.add_text(f"tie_{i:02d}", "identical tie content ZZ-4242")
    default = QueryEngine(kb)
    kernel = QueryEngine(kb, use_kernel=True)
    queries = _queries(entities) + ["ZZ-4242"]
    a = default.query_batch(queries, k=6)
    b = kernel.query_batch(queries, k=6)
    for q, ra, rb in zip(queries, a, b):
        assert [r.doc_id for r in ra] == [r.doc_id for r in rb], q
        assert [r.boosted for r in ra] == [r.boosted for r in rb], q
        np.testing.assert_allclose([r.score for r in ra],
                                   [r.score for r in rb], rtol=1e-5)
        np.testing.assert_allclose([r.cosine for r in ra],
                                   [r.cosine for r in rb],
                                   rtol=1e-5, atol=1e-6)


def test_kernel_operand_cache_reused_until_refresh():
    """The block-aligned kernel operands are padded once per refresh,
    not per dispatch (the hot loop never pays the O(N·D) pad copy),
    and are rebuilt when a KB mutation rebinds the device arrays."""
    kb, entities = _kb(n_docs=30)  # 30 docs → ragged vs the 32-block
    engine = QueryEngine(kb, use_kernel=True)
    code = next(iter(entities))
    engine.query_batch([code], k=3)
    dv1, ds1 = engine._kernel_operands()
    assert dv1.shape[0] % 8 == 0 and dv1.shape[0] >= 30
    engine.query_batch([code, "other"], k=3)
    dv2, ds2 = engine._kernel_operands()
    assert dv2 is dv1 and ds2 is ds1  # cache hit across dispatches

    kb.add_text("doc_00003.txt", "rewritten content AB-1212")
    res = engine.query_batch(["AB-1212"], k=1)[0]
    assert res[0].doc_id == "doc_00003.txt" and res[0].boosted
    dv3, _ = engine._kernel_operands()
    assert dv3 is not dv1  # refresh rebound the arrays → re-padded


@pytest.mark.parametrize("make_engine", [
    lambda kb: QueryEngine(kb, beta=0.0),
    lambda kb: QueryEngine(kb, beta=0.0, gemm_batch=True),
    lambda kb: QueryEngine(kb, beta=0.0, use_kernel=True),
])
def test_boosted_flag_exact_at_beta_zero(make_engine):
    """β=0 regression: ``boosted`` used to be inferred as
    score − α·cos > 0.5·β, which any positive rounding noise satisfies
    when β=0.  It must now reflect the exact containment indicator:
    True for the doc containing the query substring, False elsewhere."""
    kb = KnowledgeBase(dim=512)
    kb.add_text("with_code", "the target document mentions QX-9090 here")
    for i in range(15):
        kb.add_text(f"filler_{i:02d}", f"unrelated filler text number {i}")
    engine = make_engine(kb)
    res = engine.query_batch(["QX-9090"], k=16)[0]
    flags = {r.doc_id: r.boosted for r in res}
    assert flags["with_code"] is True  # indicator fires even at β=0
    assert not any(v for d, v in flags.items() if d != "with_code")


def test_boosted_flag_exact_at_beta_zero_prefiltered():
    """Same β=0 regression for the Retriever postings-prefilter path."""
    kb = KnowledgeBase(dim=512)
    kb.add_text("with_code", "the target document mentions QX-9090 here")
    for i in range(15):
        kb.add_text(f"filler_{i:02d}", f"unrelated filler text number {i}")
    r = Retriever(kb, beta=0.0, prefilter=True)
    res = r.query("QX-9090", k=5)
    flags = {x.doc_id: x.boosted for x in res}
    assert flags["with_code"] is True
    assert not any(v for d, v in flags.items() if d != "with_code")


def test_tie_order_matches_between_batch_and_single():
    """Duplicate docs produce exact score ties; both paths must break
    them identically (lax.top_k order)."""
    kb = KnowledgeBase(dim=512)
    for i in range(12):
        kb.add_text(f"dup_{i:02d}", "identical tie content INV-7777")
    engine = QueryEngine(kb)
    retriever = Retriever(kb)
    got = engine.query_batch(["INV-7777"], k=6)[0]
    want = retriever.query("INV-7777", k=6)
    assert [r.doc_id for r in got] == [r.doc_id for r in want]
    assert len({r.score for r in got}) == 1  # genuinely tied


# --------------------------------------------------------------------------
# incremental materialization == cold rebuild (bit-exact device arrays)
# --------------------------------------------------------------------------

def _assert_matches_cold(engine, kb):
    matrix, sigs, ids = kb.materialize()
    assert engine.doc_ids == ids
    np.testing.assert_array_equal(np.asarray(engine.doc_vecs), matrix)
    np.testing.assert_array_equal(np.asarray(engine.doc_sigs), sigs)


def test_incremental_refresh_add_update_remove_equals_cold():
    kb, _ = _kb(n_docs=50)
    engine = QueryEngine(kb)
    v0 = kb.version

    kb.add_text("zz_new_doc", "a brand new document QQ-1111")   # add
    stats = engine.refresh()
    assert stats.changed == 1 and stats.restacked
    _assert_matches_cold(engine, kb)

    kb.add_text("doc_00007.txt", "doc seven rewritten RR-2222")  # update
    stats = engine.refresh()
    assert stats.changed == 1 and stats.removed == 0
    assert not stats.restacked  # same layout: rows patched, not restacked
    _assert_matches_cold(engine, kb)

    kb._remove_doc("doc_00003.txt")                              # remove
    stats = engine.refresh()
    assert stats.removed == 1 and stats.restacked
    _assert_matches_cold(engine, kb)

    assert kb.version > v0
    assert engine.refresh().no_op  # converged: next refresh does nothing


def test_refresh_does_not_revectorize_unchanged_docs(monkeypatch):
    kb, _ = _kb(n_docs=40)
    engine = QueryEngine(kb)
    kb.add_text("doc_00001.txt", "updated content for doc one SS-3333")

    calls = []
    orig = kb.vectorizer.unweighted_row
    monkeypatch.setattr(
        kb.vectorizer, "unweighted_row",
        lambda tc: (calls.append(1), orig(tc))[1],
    )
    stats = engine.refresh()
    assert stats.changed == 1
    assert len(calls) == 1  # exactly the dirty doc, nothing else
    _assert_matches_cold(engine, kb)


def test_sync_driven_refresh_equals_cold(tmp_path):
    from repro.data.corpus import write_corpus_dir

    docs, _ = make_corpus(n_docs=30, seed=4)
    src = str(tmp_path / "corpus")
    write_corpus_dir(src, docs)
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    engine = QueryEngine(kb)

    # touch 3 files, delete 1, add 1 — the paper's incremental loop
    for i in range(3):
        with open(f"{src}/doc_{i:05d}.txt", "a") as f:
            f.write(" appended TT-4444")
    import os
    os.unlink(f"{src}/doc_00010.txt")
    with open(f"{src}/doc_99999.txt", "w") as f:
        f.write("entirely new corpus member UU-5555")
    stats_sync = kb.sync(src)
    assert stats_sync.updated == 3 and stats_sync.removed == 1 \
        and stats_sync.added == 1

    stats = engine.refresh()
    assert stats.changed == 4 and stats.removed == 1
    _assert_matches_cold(engine, kb)


def test_queries_see_kb_mutations_automatically():
    kb, _ = _kb(n_docs=20)
    engine = QueryEngine(kb)
    assert not any(
        r.doc_id == "late_doc" for r in engine.query_batch(["VV-6666"], k=3)[0]
    )
    kb.add_text("late_doc", "late arrival about VV-6666 exactly")
    top = engine.query_batch(["VV-6666"], k=1)[0][0]
    assert top.doc_id == "late_doc" and top.boosted


# --------------------------------------------------------------------------
# kernel path: the reweight runs on the device from resident u rows
# --------------------------------------------------------------------------

def _assert_kernel_matches_cold(engine, kb):
    """Bit-identical to a cold build on the kernel path, and within
    float32 rounding of the host ``kb.materialize()``."""
    cold = QueryEngine(kb, scoring_path="kernel")
    assert engine.doc_ids == cold.doc_ids
    got = np.asarray(engine.doc_vecs)
    np.testing.assert_array_equal(got, np.asarray(cold.doc_vecs))
    np.testing.assert_array_equal(np.asarray(engine.doc_sigs),
                                  np.asarray(cold.doc_sigs))
    np.testing.assert_array_equal(np.asarray(engine._u_dev), engine._u)
    matrix, sigs, ids = kb.materialize()
    assert engine.doc_ids == ids
    assert np.abs(got - matrix).max() <= 1e-6
    np.testing.assert_array_equal(np.asarray(engine.doc_sigs), sigs)


def test_device_reweight_matches_host_finalize():
    from repro.core.vectorizer import HashedTfIdf

    rng = np.random.default_rng(7)
    u = rng.normal(size=(37, 512)).astype(np.float32)
    u[[0, 5, 36]] = 0.0
    idf = rng.uniform(0.5, 6.0, size=512).astype(np.float32)
    got = np.asarray(engine_mod._reweight_rows(u, idf))
    want = HashedTfIdf(dim=512).finalize_matrix(u.copy(), idf)
    assert got.dtype == np.float32
    assert np.abs(got - want).max() <= 1e-6
    assert not got[[0, 5, 36]].any()  # zero rows stay zero
    np.testing.assert_allclose(
        np.linalg.norm(np.delete(got, [0, 5, 36], axis=0), axis=1), 1.0,
        rtol=1e-6)


def test_kernel_path_refresh_add_update_remove_equals_cold():
    kb, _ = _kb(n_docs=50)
    engine = QueryEngine(kb, scoring_path="kernel")
    _assert_kernel_matches_cold(engine, kb)

    kb.add_text("zz_new_doc", "a brand new document QQ-1111")   # add
    assert engine.refresh().restacked
    _assert_kernel_matches_cold(engine, kb)

    kb.add_text("doc_00007.txt", "doc seven rewritten RR-2222")  # update
    stats = engine.refresh()
    assert not stats.restacked and stats.reweighted
    _assert_kernel_matches_cold(engine, kb)

    text = kb.texts["doc_00011.txt"]                # idf-stable update:
    kb.add_text("doc_00011.txt", text + " " + text.split()[0])  # same terms
    stats = engine.refresh()
    assert not stats.reweighted and stats.rows_patched == 1
    _assert_kernel_matches_cold(engine, kb)

    kb._remove_doc("doc_00003.txt")                              # remove
    assert engine.refresh().restacked
    _assert_kernel_matches_cold(engine, kb)
    assert engine.refresh().no_op


def test_kernel_path_delta_larger_than_a_patch_chunk():
    n = engine_mod._U_PATCH_ROWS + 9
    kb, _ = _kb(n_docs=n + 5, dim=512)
    engine = QueryEngine(kb, scoring_path="kernel")
    for i in range(n):
        kb.add_text(f"doc_{i:05d}.txt", f"rewritten passage {i} XY-{i:04d}")
    stats = engine.refresh()
    assert stats.changed == n and not stats.restacked
    _assert_kernel_matches_cold(engine, kb)


def test_kernel_path_after_adopting_persisted_matrix(tmp_path):
    kb, _ = _kb(n_docs=30)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path, include_matrix=True)
    kb2 = KnowledgeBase.load(path)
    engine = QueryEngine(kb2, scoring_path="kernel")
    assert engine._u_dev is None  # persisted matrix adopted, u deferred
    kb2.add_text("doc_00002.txt", "rewritten after load WW-7777")
    engine.refresh()  # u uploads whole on the first delta
    _assert_kernel_matches_cold(engine, kb2)


@pytest.mark.parametrize("n_changed", [1, 3, 64, 65])
def test_kernel_path_publish_compiles_nothing(n_changed):
    """After the engine build, an in-place delta of any size reuses the
    one compiled u patch and the one compiled [N, D] reweight."""
    from repro.analysis.sanitizers import RetraceGuard

    kb, _ = _kb(n_docs=70, dim=512)
    engine = QueryEngine(kb, scoring_path="kernel")
    guard = RetraceGuard()
    guard.arm()
    for i in range(n_changed):
        kb.add_text(f"doc_{i:05d}.txt", f"fresh text {i} ZQ-{i:04d}")
    assert engine.refresh().changed == n_changed
    assert guard.report() == {}
    _assert_kernel_matches_cold(engine, kb)


# --------------------------------------------------------------------------
# query-vector LRU cache
# --------------------------------------------------------------------------

def test_cache_hits_return_identical_results():
    kb, entities = _kb()
    engine = QueryEngine(kb)
    code = next(iter(entities))
    first = engine.query_batch([code], k=5)[0]
    assert engine.cache_stats()["hits"] == 0
    second = engine.query_batch([code], k=5)[0]
    assert engine.cache_stats()["hits"] == 1
    assert [(r.doc_id, r.score, r.cosine) for r in first] == \
        [(r.doc_id, r.score, r.cosine) for r in second]
    # case-insensitive: normalization is the cache key
    third = engine.query_batch([code.lower()], k=5)[0]
    assert engine.cache_stats()["hits"] == 2
    assert [(r.doc_id, r.score) for r in third] == \
        [(r.doc_id, r.score) for r in first]


def test_query_vector_cache_not_stale_after_explicit_refresh():
    """Regression (PR 3): the query-vector LRU must not serve vectors
    weighted with pre-refresh idf statistics.  An *explicit*
    ``refresh()`` (the serving runtime's publish path — no query in
    between) has to invalidate it just like the query-driven refresh."""
    kb, entities = _kb(n_docs=30)
    engine = QueryEngine(kb)
    code = next(iter(entities))
    engine.query_batch([code, "generic filler"], k=3)
    assert engine.cache_stats()["entries"] == 2

    kb.add_text("fresh_doc", "completely fresh document shifting idf")
    stats = engine.refresh()  # idf moved → cached vectors are stale
    assert stats.reweighted
    assert engine.cache_stats()["entries"] == 0  # invalidated, not kept

    got = engine.query_batch([code], k=3)[0]
    want = QueryEngine(kb).query_batch([code], k=3)[0]  # cold: no cache
    assert [(r.doc_id, r.score, r.cosine) for r in got] == \
        [(r.doc_id, r.score, r.cosine) for r in want]


def test_cache_invalidated_when_idf_changes():
    kb, entities = _kb(n_docs=30)
    engine = QueryEngine(kb)
    code = next(iter(entities))
    engine.query_batch([code], k=3)
    kb.add_text("fresh", "completely fresh doc shifting idf")
    engine.query_batch([code], k=3)  # auto-refresh must drop stale vecs
    retriever = Retriever(kb)
    got = engine.query_batch([code], k=3)[0]
    want = retriever.query(code, k=3)
    assert [(r.doc_id, r.score) for r in got] == \
        [(r.doc_id, r.score) for r in want]


def test_cache_eviction_is_lru():
    kb, _ = _kb(n_docs=10)
    engine = QueryEngine(kb, cache_size=2)
    engine.query_batch(["alpha", "beta"], k=1)
    engine.query_batch(["alpha"], k=1)        # alpha now most-recent
    engine.query_batch(["gamma"], k=1)        # evicts beta
    stats0 = engine.cache_stats()
    engine.query_batch(["alpha"], k=1)        # still cached
    assert engine.cache_stats()["hits"] == stats0["hits"] + 1
    engine.query_batch(["beta"], k=1)         # was evicted → miss
    assert engine.cache_stats()["misses"] == stats0["misses"] + 1


# --------------------------------------------------------------------------
# edges
# --------------------------------------------------------------------------

def test_empty_kb_and_empty_batch():
    kb = KnowledgeBase(dim=512)
    engine = QueryEngine(kb)
    assert engine.query_batch(["anything"], k=3) == [[]]
    assert engine.query_batch([], k=3) == []


@pytest.mark.parametrize("make_engine", [
    lambda kb: QueryEngine(kb, scoring_path="map"),
    lambda kb: QueryEngine(kb, scoring_path="gemm"),
    lambda kb: QueryEngine(kb, use_kernel=True),
    lambda kb: QueryEngine(kb, scoring_path="auto"),
    lambda kb: QueryEngine(kb, scoring_path="map", index="ivf"),
    lambda kb: QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                           n_shards=2),
    lambda kb: QueryEngine(kb, scoring_path="auto", index="ivf-sharded",
                           n_shards=2),
])
def test_empty_container_on_every_path_and_index(make_engine):
    """Regression: an n=0 container (fresh tenant mount, or every doc
    removed) must return empty result lists on every scoring path and
    index kind — the padded-bucket dispatch used to ask top_k for k of
    0 candidate columns and trip inside the jitted function."""
    kb = KnowledgeBase(dim=512)
    engine = make_engine(kb)
    assert engine.query_batch(["anything", "else"], k=3) == [[], []]
    assert engine.query_batch([], k=3) == []


def test_all_docs_removed_returns_to_empty_path(tmp_path):
    """A corpus whose every document was removed (sync against an
    emptied source dir) must serve [] too, not trip padded top-k."""
    src = tmp_path / "docs"
    src.mkdir()
    (src / "only.txt").write_text("transient invoice forecast")
    kb = KnowledgeBase(dim=512)
    kb.sync(str(src))
    engine = QueryEngine(kb)
    assert len(engine.query_batch(["invoice"], k=3)[0]) == 1
    (src / "only.txt").unlink()
    kb.sync(str(src))
    assert kb.n_docs == 0
    assert engine.query_batch(["invoice"], k=3) == [[]]


def test_score_batch_arrays_zero_docs_short_circuits():
    """Direct contract at the dispatch layer: n_docs=0 yields [B, 0]
    arrays on every scoring path, not a top-k shape error."""
    import jax.numpy as jnp

    from repro.core.engine import score_batch_arrays

    qv = np.zeros((2, 512), dtype=np.float32)
    qs = np.zeros((2, 4), dtype=np.uint32)
    docs = jnp.zeros((0, 512), dtype=jnp.float32)
    sigs = jnp.zeros((0, 4), dtype=jnp.uint32)
    for path in ("map", "gemm"):
        vals, idx, cos, ind = score_batch_arrays(
            docs, sigs, qv, qs, scoring_path=path, k=3,
            alpha=0.2, beta=0.3, n_docs=0)
        assert vals.shape == (2, 0) and idx.shape == (2, 0)
        assert cos.shape == (2, 0) and ind.shape == (2, 0)


def test_empty_container_save_load_roundtrip(tmp_path):
    """An empty KB persists and reloads to a queryable empty engine."""
    path = str(tmp_path / "empty.ragdb")
    KnowledgeBase(dim=512).save(path)
    kb = KnowledgeBase.load(path)
    assert kb.n_docs == 0
    assert QueryEngine(kb).query_batch(["anything"], k=5) == [[]]


def test_k_larger_than_corpus():
    kb, _ = _kb(n_docs=4, n_entities=2)
    engine = QueryEngine(kb)
    res = engine.query_batch(["whatever text"], k=50)[0]
    assert len(res) == 4


@pytest.mark.parametrize("bad_k", [0, -1, -50])
def test_query_batch_rejects_non_positive_k(bad_k):
    """k ≤ 0 raises a clear ValueError instead of falling through to
    the padded top-k machinery (regression for the silent k=0 case)."""
    kb, _ = _kb(n_docs=6, n_entities=2)
    engine = QueryEngine(kb)
    with pytest.raises(ValueError, match="k must be"):
        engine.query_batch(["anything"], k=bad_k)
    with pytest.raises(ValueError, match="k must be"):
        engine.query(  # single-query wrapper shares the contract
            "anything", k=bad_k)
    # the snapshot read plane enforces the same contract
    from repro.serving import SnapshotManager

    snap = SnapshotManager(kb, scoring_path="map").current
    with pytest.raises(ValueError, match="k must be"):
        snap.query_batch(["anything"], k=bad_k)
    # and the prefiltered Retriever path
    from repro.core.retrieval import Retriever

    with pytest.raises(ValueError, match="k must be"):
        Retriever(kb, prefilter=True).query("anything", k=bad_k)


@pytest.mark.parametrize("make_engine", [
    lambda kb: QueryEngine(kb),
    lambda kb: QueryEngine(kb, gemm_batch=True),
    lambda kb: QueryEngine(kb, use_kernel=True),
    lambda kb: QueryEngine(kb, scoring_path="map", index="ivf",
                           guarantee="exact"),
])
def test_k_larger_than_corpus_clamps_on_every_path(make_engine):
    """k > n_docs clamps to the corpus size on every scoring path and
    on the clustered index plane — results stay full-length-n and
    identical to an exact-k query."""
    kb, _ = _kb(n_docs=5, n_entities=2)
    engine = make_engine(kb)
    res = engine.query_batch(["invoice forecast"], k=50)[0]
    assert len(res) == 5
    exact = engine.query_batch(["invoice forecast"], k=5)[0]
    assert [(r.doc_id, r.score) for r in res] == \
        [(r.doc_id, r.score) for r in exact]


def test_bucket_boundaries():
    assert [_bucket(b) for b in (1, 2, 3, 4, 5, 8, 9, 16, 17)] == \
        [1, 2, 4, 4, 8, 8, 16, 16, 32]


def test_oversized_batch_chunks():
    kb, entities = _kb(n_docs=30)
    engine = QueryEngine(kb, max_batch=4)
    queries = [f"q {i} {code}" for i, code in
               enumerate(list(entities) * 3)]  # 18 queries, chunked by 4
    batch = engine.query_batch(queries, k=2)
    assert len(batch) == len(queries)
    retriever = Retriever(kb)
    for q, got in zip(queries, batch):
        want = retriever.query(q, k=2)
        assert [(r.doc_id, r.score) for r in got] == \
            [(r.doc_id, r.score) for r in want]


def test_engine_adopts_persisted_matrix_without_revectorizing(
        tmp_path, monkeypatch):
    """A container saved with include_matrix=True exists to skip the
    O(N·D) rebuild at load time (RQ3 trade) — the engine must honor it,
    and its lazy u-cache must still make later deltas bit-exact."""
    kb, _ = _kb(n_docs=40)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path, include_matrix=True)
    kb2 = KnowledgeBase.load(path)

    calls = []
    orig = kb2.vectorizer.build_unweighted_matrix
    monkeypatch.setattr(
        kb2.vectorizer, "build_unweighted_matrix",
        lambda tcs: (calls.append(len(tcs)), orig(tcs))[1],
    )
    engine = QueryEngine(kb2)
    assert calls == []  # persisted ⟨V⟩ adopted, nothing re-vectorized
    _assert_matches_cold(engine, kb2)

    kb2.add_text("doc_00002.txt", "rewritten after load WW-7777")
    kb2._remove_doc("doc_00009.txt")
    engine.refresh()  # u-cache builds lazily here
    _assert_matches_cold(engine, kb2)


# --------------------------------------------------------------------------
# scoring-path auto-selection
# --------------------------------------------------------------------------

def test_scoring_path_auto_picks_kernel_only_on_tpu(monkeypatch):
    """PR 2's shoot-out: the kernel path is ~4x slower than gemm in CPU
    interpret mode — "auto" must route it only on real TPU backends,
    with explicit overrides as the escape hatch."""
    kb, _ = _kb(n_docs=8, n_entities=2)

    monkeypatch.setattr(engine_mod, "_default_backend", lambda: "cpu")
    assert QueryEngine(kb).scoring_path == "map"
    assert resolve_scoring_path("auto") == "map"
    # explicit overrides win regardless of backend
    assert QueryEngine(kb, scoring_path="kernel").scoring_path == "kernel"
    assert QueryEngine(kb, use_kernel=True).scoring_path == "kernel"
    assert QueryEngine(kb, gemm_batch=True).scoring_path == "gemm"

    monkeypatch.setattr(engine_mod, "_default_backend", lambda: "tpu")
    eng = QueryEngine(kb)
    assert eng.scoring_path == "kernel" and eng.use_kernel
    assert resolve_scoring_path("auto") == "kernel"
    # the escape hatch: force the bit-stable path on TPU
    assert QueryEngine(kb, scoring_path="map").scoring_path == "map"

    with pytest.raises(ValueError):
        resolve_scoring_path("bogus")
    with pytest.raises(ValueError):
        resolve_scoring_path(use_kernel=True, gemm_batch=True)


def test_scoring_path_auto_agrees_between_engine_and_retriever(monkeypatch):
    """A default Retriever over a default engine must not trip the
    shared-engine validation on any backend (both resolve "auto" the
    same way)."""
    kb, entities = _kb(n_docs=16, n_entities=2)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(engine_mod, "_default_backend", lambda b=backend: b)
        engine = QueryEngine(kb)
        retriever = Retriever(kb, engine=engine)  # must not raise
        assert retriever.engine is engine
        code = next(iter(entities))
        # the resolved path actually serves queries (kernel runs in
        # interpret mode on the CPU host)
        assert retriever.query(code, k=1)[0].doc_id == \
            engine.query_batch([code], k=1)[0][0].doc_id


def test_retriever_rejects_mismatched_shared_engine():
    kb, _ = _kb(n_docs=10)
    with pytest.raises(ValueError):
        Retriever(kb, beta=0.0, engine=QueryEngine(kb))
    with pytest.raises(ValueError):
        Retriever(kb, engine=QueryEngine(kb, gemm_batch=True))


def test_retriever_is_thin_wrapper_over_engine():
    kb, entities = _kb(n_docs=20)
    engine = QueryEngine(kb)
    retriever = Retriever(kb, engine=engine)
    assert retriever.engine is engine
    code = next(iter(entities))
    assert retriever.query(code, k=1)[0].doc_id == \
        engine.query_batch([code], k=1)[0][0].doc_id
    assert retriever.doc_ids == engine.doc_ids


def test_rag_answer_batch_matches_serial_answers():
    import jax

    from repro.configs import ARCHS
    from repro.core.rag import RAGPipeline
    from repro.models import transformer as T

    kb, entities = _kb(n_docs=20, dim=512)
    cfg = ARCHS["llama3.2-3b"].smoke_config
    params = T.init(jax.random.PRNGKey(0), cfg)
    rag = RAGPipeline(kb, params, cfg, max_context_tokens=64)
    questions = [f"what is {code}?" for code in list(entities)[:3]]
    batched = rag.answer_batch(questions, max_new_tokens=3, top_k_docs=2)
    for q, out in zip(questions, batched):
        serial = rag.answer(q, max_new_tokens=3, top_k_docs=2)
        assert out.token_ids == serial.token_ids
        assert [r.doc_id for r in out.retrieved] == \
            [r.doc_id for r in serial.retrieved]
