"""Sharded IVF plane (src/repro/index/sharded.py): the exactness/parity
harness.  The claim under test is the §10 merge theorem — per-shard
local top-k over disjoint cluster subsets, widened per shard against
the same spherical-cap bound, stable-merged on ``(score desc, id asc)``
— returns *the same bits* as the flat single-device scan: ids, scores,
tie order, boost flags.  Sweeps shard counts (including shard counts
that don't divide N, and more shards than clusters), batch shapes,
β=0, duplicate-tie corpora, and the degenerate one-shard-owns-all
partition; then the operational planes on top: incremental maintenance
(restack + idf reweight), the serving runtime under live sync, and
delta-journal persistence with cross-shard-count adoption.

Multi-device (real ``shard_map`` mesh) legs run in subprocesses via
``run_with_devices`` so the main pytest process keeps its
single-device view; everything else exercises the logical per-shard
fallback, which shares every numeric with the mesh path.
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.core.engine import QueryEngine, pack_query_arrays
from repro.core import signature as sigmod
from repro.core.ingest import KnowledgeBase
from repro.data.corpus import make_corpus, write_corpus_dir
from repro.index import ShardedIVFIndex, partition_clusters

from conftest import assert_bit_identical
from test_sharded import run_with_devices

SHARD_COUNTS = (1, 2, 4, 8)


def _kb(n_docs=80, dim=512, n_entities=6, seed=0):
    docs, entities = make_corpus(n_docs=n_docs, n_entities=n_entities,
                                 seed=seed)
    kb = KnowledgeBase(dim=dim)
    for i, d in enumerate(docs):
        kb.add_text(f"doc_{i:05d}.txt", d)
    return kb, list(entities)


def _pack(kb, texts):
    pairs = [
        (kb.vectorizer.query_vector(t),
         sigmod.query_signature(t, width_words=kb.sig_words))
        for t in texts
    ]
    return pack_query_arrays(pairs, kb.vectorizer.dim, kb.sig_words)


# --------------------------------------------------------------------------
# the parity sweep: sharded-exact ≡ flat, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_docs", [7, 83])  # 83 ∤ 2,4,8; 7 < sqrt-clusters
@pytest.mark.parametrize("beta", [1.0, 0.0])  # β=0: pure cosine ranking
def test_sharded_exact_bit_identical_to_flat_sweep(n_docs, beta):
    kb, entities = _kb(n_docs=n_docs, dim=512,
                       n_entities=min(4, max(1, n_docs // 4)))
    flat = QueryEngine(kb, beta=beta, scoring_path="map")
    queries = (entities + [f"lookup {c} record" for c in entities[:2]]
               + ["quarterly forecast", "unrelated text", ""])
    want = {b: flat.query_batch((queries * 3)[:b], k=5) for b in (1, 3, 8)}
    for shards in SHARD_COUNTS:
        sharded = QueryEngine(kb, beta=beta, scoring_path="map",
                              index="ivf-sharded", guarantee="exact",
                              nprobe=1, n_shards=shards)
        for b in (1, 3, 8):  # batch sizes (padding buckets 1/4/8)
            assert_bit_identical(
                want[b], sharded.query_batch((queries * 3)[:b], k=5),
                label=f"n_docs={n_docs} beta={beta} S={shards} b={b}")


def test_sharded_exact_k_exceeds_n_clamps():
    kb, entities = _kb(n_docs=23, dim=512, n_entities=3)
    flat = QueryEngine(kb, scoring_path="map")
    sharded = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                          guarantee="exact", n_shards=4)
    queries = entities[:2] + ["filler text"]
    got = sharded.query_batch(queries, k=500)
    assert all(len(r) == kb.n_docs for r in got)  # clamped, full ranking
    assert_bit_identical(flat.query_batch(queries, k=500), got)


def test_sharded_exact_with_duplicate_ties():
    """12 identical docs tie exactly at the k-th score; the sharded
    merge must reproduce the flat scan's global-id tie order even when
    the tied rows land on *different shards* — this is precisely where
    an unstable merge key (or per-shard truncation below k) shows up."""
    kb = KnowledgeBase(dim=512)
    for i in range(12):
        kb.add_text(f"dup_{i:02d}", "identical tie content INV-7777")
    for i in range(20):
        kb.add_text(f"filler_{i:02d}", f"unrelated filler number {i}")
    flat = QueryEngine(kb, scoring_path="map")
    want = flat.query_batch(["INV-7777"], k=6)
    assert len({r.score for r in want[0]}) == 1  # genuinely tied
    for shards in (2, 4, 8):
        sharded = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                              guarantee="exact", nprobe=1, n_shards=shards)
        assert_bit_identical(want, sharded.query_batch(["INV-7777"], k=6),
                             label=f"S={shards}")


def test_degenerate_partition_all_clusters_on_one_shard():
    """A pathological hand-built partition (every cluster owned by
    shard 0, three empty shards) must still merge to the flat answer —
    empty shards contribute only sentinel rows, which the stable merge
    drops."""
    kb, entities = _kb(n_docs=60, dim=512)
    eng = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                      guarantee="exact", n_shards=4)
    base = eng.ivf.base
    deg = ShardedIVFIndex.from_base(
        base, eng.doc_vecs, eng.doc_sigs, n_shards=4,
        shard_of_cluster=np.zeros(base.n_clusters, np.int32))
    queries = entities[:3] + ["plain filler prose"]
    qv, qs = _pack(kb, queries)
    kw = dict(b=len(queries), k=5, nprobe=2, guarantee="exact",
              scoring_path="map", alpha=eng.alpha, beta=eng.beta)
    v1, i1, *_ = deg.search(eng.doc_vecs, eng.doc_sigs, qv, qs, **kw)
    v2, i2, *_ = eng.ivf.search(eng.doc_vecs, eng.doc_sigs, qv, qs, **kw)
    assert_bit_identical((v1, i1), (v2, i2))


def test_partition_clusters_covers_disjointly_and_balances():
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 200, size=37).astype(np.int64)
    for n_shards in (1, 2, 4, 8):
        soc = partition_clusters(sizes, n_shards)
        assert soc.shape == (37,)
        assert soc.min() >= 0 and soc.max() < n_shards
        loads = np.bincount(soc, weights=sizes, minlength=n_shards)
        # greedy LPT bound: no shard exceeds mean + max item
        assert loads.max() <= sizes.sum() / n_shards + sizes.max()
        np.testing.assert_array_equal(soc, partition_clusters(sizes,
                                                              n_shards))
    # fewer clusters than shards: valid owners, high shards just empty
    soc = partition_clusters(np.array([5, 3]), 8)
    assert soc.min() >= 0 and soc.max() < 8


def test_sharded_engine_validation_errors():
    kb, _ = _kb(n_docs=10, dim=256, n_entities=2)
    with pytest.raises(ValueError, match="n_shards"):
        QueryEngine(kb, index="flat", n_shards=2)
    with pytest.raises(ValueError, match="n_shards"):
        QueryEngine(kb, index="ivf-sharded", n_shards=0)
    with pytest.raises(ValueError, match="map"):
        QueryEngine(kb, index="ivf-sharded", scoring_path="gemm")


def test_sharded_index_stats_plumbing():
    kb, entities = _kb(n_docs=40, dim=512)
    eng = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                      guarantee="exact", n_shards=4)
    eng.query_batch(entities[:2], k=3)
    st = eng.index_stats()
    assert st["n_shards"] == 4
    assert st["merge_seconds"] >= 0.0
    assert 0.0 < st["probed_fraction"] <= 1.0
    assert st["rounds"] >= 1


# --------------------------------------------------------------------------
# incremental maintenance: dirty rows route to their owning shard
# --------------------------------------------------------------------------

def test_sharded_restack_maintenance_parity(tmp_path):
    """touch 2 / delete 1 / add 2 through kb.sync — the dirty-row log
    drives per-shard block maintenance, and the restacked plane stays
    bit-identical to a flat engine over the same KB."""
    docs, ents = make_corpus(n_docs=90, n_entities=6, seed=3)
    entities = list(ents)
    src = str(tmp_path / "corpus")
    write_corpus_dir(src, docs)
    kb_f = KnowledgeBase(dim=512)
    kb_f.sync(src)
    kb_s = KnowledgeBase(dim=512)
    kb_s.sync(src)
    flat = QueryEngine(kb_f, scoring_path="map")
    sharded = QueryEngine(kb_s, scoring_path="map", index="ivf-sharded",
                          guarantee="exact", n_shards=4)
    queries = entities[:3] + ["quarterly forecast"]
    assert_bit_identical(flat.query_batch(queries, k=6),
                         sharded.query_batch(queries, k=6), label="cold")

    for i in (4, 9):
        with open(f"{src}/doc_{i:05d}.txt", "a") as f:
            f.write(f" appended about {entities[1]}")
    os.unlink(f"{src}/doc_00010.txt")
    with open(f"{src}/doc_90000.txt", "w") as f:
        f.write(f"entirely new corpus member about {entities[2]} QQ-7777")
    with open(f"{src}/doc_90001.txt", "w") as f:
        f.write("another fresh arrival ZZ-8888 plain prose")
    for kb in (kb_f, kb_s):
        st = kb.sync(src)
        assert (st.updated, st.removed, st.added) == (2, 1, 2)

    q2 = queries + ["QQ-7777 fresh", f"{entities[1]} appended"]
    assert_bit_identical(flat.query_batch(q2, k=6),
                         sharded.query_batch(q2, k=6), label="restacked")
    assert len(sharded.ivf.base.assign) == kb_s.n_docs


def test_sharded_inplace_rewrite_reweighted_parity():
    """An in-place rewrite moves idf → the engine rebuilds *every* doc
    vector, so the per-shard resident blocks must regather in full (the
    O(U) scatter patch is only valid when idf held still).  Parity
    after the rewrite proves the reweighted path regathers."""
    kb_f, entities = _kb(n_docs=50, dim=512, seed=5)
    kb_s, _ = _kb(n_docs=50, dim=512, seed=5)
    flat = QueryEngine(kb_f, scoring_path="map")
    sharded = QueryEngine(kb_s, scoring_path="map", index="ivf-sharded",
                          guarantee="exact", n_shards=4)
    queries = entities[:3]
    assert_bit_identical(flat.query_batch(queries, k=5),
                         sharded.query_batch(queries, k=5), label="cold")
    for kb in (kb_f, kb_s):  # same id, brand-new terms → idf moves
        kb.add_text("doc_00007.txt", "rewritten with a new code RW-4242")
    q2 = queries + ["RW-4242"]
    got = sharded.query_batch(q2, k=5)
    assert_bit_identical(flat.query_batch(q2, k=5), got, label="rewritten")
    assert got[-1][0].doc_id == "doc_00007.txt"


# --------------------------------------------------------------------------
# real mesh: shard_map over forced host devices (subprocess legs)
# --------------------------------------------------------------------------

def test_sharded_mesh_parity_across_shard_counts():
    """On an 8-device host the plane places one cluster subset per
    device (``eng.ivf.mesh is not None``) and per-device top-k merges
    to the flat scan's bits — across shard counts 2/4/8 on one
    indivisible corpus."""
    run_with_devices("""
        import jax, numpy as np
        assert jax.device_count() == 8, jax.device_count()
        from conftest import assert_bit_identical
        from repro.core.engine import QueryEngine
        from repro.core.ingest import KnowledgeBase
        from repro.data.corpus import make_corpus

        docs, ents = make_corpus(n_docs=83, n_entities=6, seed=1)
        kb = KnowledgeBase(dim=512)
        for i, d in enumerate(docs):
            kb.add_text(f"doc_{i:05d}.txt", d)
        flat = QueryEngine(kb, scoring_path="map")
        queries = [f"report about {e}" for e in list(ents)[:4]] + [
            "plain prose words", ""]
        for b in (1, 3, 8):
            want = flat.query_batch((queries * 2)[:b], k=6)
            for S in (2, 4, 8):
                sh = QueryEngine(kb, scoring_path="map",
                                 index="ivf-sharded", guarantee="exact",
                                 n_shards=S)
                assert sh.ivf.mesh is not None, f"S={S}: no mesh"
                assert sh.ivf.mesh.devices.shape == (S,)
                assert_bit_identical(
                    want, sh.query_batch((queries * 2)[:b], k=6),
                    label=f"S={S} b={b}")
        print("OK")
    """)


def test_sharded_mesh_matches_logical_fallback():
    """The logical per-shard loop (1 device) and the shard_map mesh
    (4 devices) are the same numerics — run both placements in
    subprocesses over an identical corpus and diff the serialized
    results bit-for-bit in the parent."""
    code = """
        import jax
        from repro.core.engine import QueryEngine
        from repro.core.ingest import KnowledgeBase
        from repro.data.corpus import make_corpus
        docs, ents = make_corpus(n_docs=61, n_entities=4, seed=7)
        kb = KnowledgeBase(dim=512)
        for i, d in enumerate(docs):
            kb.add_text(f"doc_{i:05d}.txt", d)
        eng = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                          guarantee="exact", n_shards=4)
        assert (eng.ivf.mesh is not None) == (jax.device_count() >= 4)
        for res in eng.query_batch(list(ents) + ["misc words"], k=5):
            for r in res:
                print(r.doc_id, repr(r.score), repr(r.cosine), r.boosted)
    """
    out1 = run_with_devices(code, n_devices=1)   # logical fallback
    out4 = run_with_devices(code, n_devices=4)   # real mesh
    assert out1 == out4 and out1.strip()


def test_sharded_plane_places_each_block_on_its_own_device():
    """Building the mesh plane sends each shard's resident block from
    the host straight to its device: the whole [S, L, D] stack is never
    staged on one device (at 4 x 65,536 docs, D=4096 that stack is
    8 GiB and does not fit beside the doc matrix in one chip's HBM)."""
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core.engine import QueryEngine
        from repro.core.ingest import KnowledgeBase
        from repro.data.corpus import make_corpus

        staged = []
        asarray = jnp.asarray
        def spy(x, *a, **kw):
            out = asarray(x, *a, **kw)
            if getattr(out, "ndim", 0) == 3:
                staged.append(out.shape)
            return out
        jnp.asarray = spy

        docs, _ = make_corpus(n_docs=61, n_entities=4, seed=7)
        kb = KnowledgeBase(dim=512)
        for i, d in enumerate(docs):
            kb.add_text(f"doc_{i:05d}.txt", d)
        eng = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                          guarantee="exact", n_shards=4)
        assert staged == [], staged
        shards = eng.ivf.dv_stack.addressable_shards
        assert len({s.device for s in shards}) == 4
        assert all(s.data.shape == (1,) + eng.ivf.dv_stack.shape[1:]
                   for s in shards)
        print("OK")
    """, n_devices=4)


# --------------------------------------------------------------------------
# serving runtime: sharded index under live sync, pinned generations
# --------------------------------------------------------------------------

def test_serving_runtime_sharded_live_sync_bit_identical(tmp_path):
    """4 reader threads against a ServingRuntime on the sharded plane
    while the writer syncs/publishes: every served result must be
    bit-identical to a *flat* QueryEngine over the KB frozen at the
    same generation — the cross-plane version of test_serving.py's
    torn-read stress."""
    from repro.serving import ServingRuntime

    docs, ents = make_corpus(n_docs=60, n_entities=5, seed=2)
    entities = list(ents)
    src = str(tmp_path / "corpus")
    write_corpus_dir(src, docs)
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    runtime = ServingRuntime(kb, max_batch=4, flush_deadline=0.002,
                             scoring_path="map", index="ivf-sharded",
                             guarantee="exact", n_shards=4,
                             result_cache_size=0)  # force real scoring
    containers = {}

    def save_generation(gen):
        path = str(tmp_path / f"gen_{gen}.ragdb")
        kb.save(path, generation=gen)
        containers[gen] = path

    save_generation(runtime.generation)
    queries = entities + ["escalation runbook", "LIVE-7777"]
    with runtime:
        runtime.query_batch(queries[:2], k=3)  # warm the jit caches

        served, served_lock = [], threading.Lock()
        stop = threading.Event()

        def reader(rid):
            i = rid
            while not stop.is_set():
                q = queries[i % len(queries)]
                k = 3 if (i % 2) else 5
                i += 1
                res = runtime.submit(q, k=k).result(timeout=120)
                with served_lock:
                    served.append((q, k, res))

        threads = [threading.Thread(target=reader, args=(r,))
                   for r in range(4)]
        for t in threads:
            t.start()
        for rnd in range(6):
            with open(os.path.join(src, f"doc_{rnd:05d}.txt"), "a") as f:
                f.write(f" LIVE-7777 edit round {rnd}")
            if rnd == 3:
                os.unlink(os.path.join(src, "doc_00030.txt"))
            kb.sync(src)
            save_generation(kb.version)
            gen = runtime.publish()
            assert gen == kb.version
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join()

    assert len(served) >= 4 * 6
    observed = {res.generation for _, _, res in served}
    assert observed <= set(containers)
    assert len(observed) >= 2  # the run really spanned generations
    references = {
        gen: QueryEngine(KnowledgeBase.load(containers[gen]),
                         scoring_path="map")
        for gen in observed
    }
    for q, k, res in served:
        want = references[res.generation].query_batch([q], k=k)[0]
        assert_bit_identical([res.results], [want], label=(
            f"{q!r}@k={k} vs the flat engine at pinned generation "
            f"{res.generation}"))


# --------------------------------------------------------------------------
# persistence: delta journal → load → sharded adopt (and rejection)
# --------------------------------------------------------------------------

def test_sharded_state_survives_delta_load_and_adopts(tmp_path,
                                                      monkeypatch):
    import repro.index.ivf as ivf_mod

    kb, entities = _kb(n_docs=70, dim=512, seed=4)
    eng = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                      guarantee="exact", n_shards=4)
    p = str(tmp_path / "kb.ragdb")
    kb.save(p)
    kb.add_text("late.txt", f"late doc about {entities[0]} LATE-1212")
    eng.refresh()  # reassigns + writes sharded index state back
    kb.save_delta(p, compact_ratio=None)

    calls = []
    orig = ivf_mod.spherical_kmeans
    monkeypatch.setattr(ivf_mod, "spherical_kmeans",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    kb2 = KnowledgeBase.load(p)
    assert kb2.index_state is not None
    assert int(kb2.index_state["n_shards"]) == 4
    eng2 = QueryEngine(kb2, scoring_path="map", index="ivf-sharded",
                       guarantee="exact", n_shards=4)
    queries = entities[:3] + ["LATE-1212"]
    got = eng2.query_batch(queries, k=5)
    assert calls == []  # adopted — no cold retrain after the journal
    assert_bit_identical(eng.query_batch(queries, k=5), got)
    np.testing.assert_array_equal(eng2.ivf.shard_of_cluster,
                                  eng.ivf.shard_of_cluster)

    # same persisted state adopts across planes and shard counts: a
    # plain ivf engine and a 2-shard engine both reuse the clustering
    # (the 2-shard plane re-partitions but must not re-run k-means)
    for kwargs in (dict(index="ivf"),
                   dict(index="ivf-sharded", n_shards=2)):
        eng3 = QueryEngine(KnowledgeBase.load(p), scoring_path="map",
                           guarantee="exact", **kwargs)
        assert_bit_identical(eng.query_batch(queries, k=5),
                             eng3.query_batch(queries, k=5),
                             label=str(kwargs))
    assert calls == []


def test_sharded_stale_ids_sha_rejected(monkeypatch):
    """Persisted sharded state whose content digest no longer matches
    the live docs must be rejected → retrain, never silent adoption of
    stale per-shard bounds."""
    import repro.index.ivf as ivf_mod

    kb, _ = _kb(n_docs=40, dim=512)
    QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                n_shards=4)  # writes kb.index_state (kind "ivf" + shards)
    kb.add_text("doc_00012.txt", "rewritten with a brand new code PJ-3131")

    calls = []
    orig = ivf_mod.spherical_kmeans
    monkeypatch.setattr(ivf_mod, "spherical_kmeans",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    fresh = QueryEngine(kb, scoring_path="map", index="ivf-sharded",
                        guarantee="exact", n_shards=4)
    assert calls == [1]  # stale state rejected → retrained
    flat = QueryEngine(kb, scoring_path="map")
    assert_bit_identical(fresh.query_batch(["PJ-3131"], k=4),
                         flat.query_batch(["PJ-3131"], k=4))
