"""Smoke run of RAGdb retrieval serving on a TPU.

One chip (the default) runs the serving path at the full retrieval
config (``repro.configs.ragdb.FULL``: 65,536 docs on the chip, 4096
hashed dims, 128 signature words, k=16, batches of up to 64):

1. generate a corpus from ``--seed`` with injected entity codes;
2. ingest it with ``KnowledgeBase.add_text``, ``save`` the container and
   ``KnowledgeBase.load`` it back;
3. serve it through ``ServingRuntime(kb, scoring_path="auto")``, whose
   scheduler coalesces the submitted requests into batches of up to 64.

It fails unless the scoring path resolved to the fused Pallas kernel and
that kernel is compiled for the chip (``tpu_custom_call`` in the lowered
step), every entity query returns its doc at rank 1 marked boosted, the
topical queries agree with the float64 numpy reference to f32 accuracy,
and ``index="ivf", guarantee="exact"`` returns the flat scan's top-k.

``--chips 4`` runs only the sharded index: 4 x 65,536 docs on a
four-device mesh, ``index="ivf-sharded"`` with ``guarantee="exact"``,
which must be bit-identical to the flat scan on ``scoring_path="map"``.

Timings printed here are of one smoke run, not a benchmark.  The last
line of standard output is one JSON object naming the device; it is
printed only when every check passed:

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

N_ENTITIES = 32
N_TOPICAL = 32
QUERY_WORDS = 6


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def doc_id(i: int) -> str:
    return f"doc_{i:07d}"


def build_kb(n_docs: int, seed: int, dim: int):
    """Generate and ingest the corpus → (kb, {entity: row}, topical queries)."""
    import numpy as np

    from repro.core.ingest import KnowledgeBase
    from repro.data.corpus import make_corpus

    docs, entities = make_corpus(n_docs=n_docs, n_entities=N_ENTITIES,
                                 seed=seed)
    rng = np.random.default_rng(seed + 1)
    topical = []
    for row in rng.choice(n_docs, size=N_TOPICAL, replace=False):
        words = docs[int(row)].split()
        topical.append(" ".join(rng.choice(words, size=QUERY_WORDS)))
    t0 = time.perf_counter()
    kb = KnowledgeBase(dim=dim)
    for i, text in enumerate(docs):
        kb.add_text(doc_id(i), text)
    log(f"ingest: {n_docs} docs in {time.perf_counter() - t0:.3f} s "
        "(add_text, host)")
    return kb, entities, topical


def check_entities(results, entities) -> None:
    """Entity query → its doc at rank 1, boosted (the paper's Recall@1)."""
    for (code, row), res in zip(entities.items(), results):
        check(bool(res) and res[0].doc_id == doc_id(row),
              f"entity {code}: rank 1 is {res[0].doc_id if res else None}, "
              f"want {doc_id(row)}")
        check(res[0].boosted, f"entity {code}: rank 1 not boosted")


def f32_tolerance(query_vec, alpha: float, beta: float) -> float:
    """Largest deviation from the float64 score that f32 arithmetic
    allows.  Only products at the query's nonzero dims are nonzero, and
    zeros add exactly, so a dot of unit vectors rounds at most
    m = nnz(query) + 1 times, each by ≤ u·Σ|x_i·y_i| ≤ u; the α·cos + β·ind
    epilogue adds two more roundings.  The factor 4 covers the MXU's
    f32 emulation, which rounds each product's split terms separately."""
    m = int((query_vec != 0).sum()) + 1
    return 4 * (m + 2) * 2.0**-24 * (abs(alpha) + abs(beta))


def check_reference(engine, texts, results) -> int:
    """Served top-k against ``hsf.numpy_reference`` (float64 numpy) over
    the engine's own doc arrays.  Returns the number of rank slots whose
    id differed within a tie band."""
    import numpy as np

    from repro.core import hsf

    doc_vecs = np.asarray(engine.doc_vecs, np.float64)  # widened once
    doc_sigs = np.asarray(engine.doc_sigs)
    row_of = {d: r for r, d in enumerate(engine.doc_ids)}
    n = doc_vecs.shape[0]
    a, b = engine.alpha, engine.beta
    swaps = 0
    worst = 0.0
    for text, res in zip(texts, results):
        qv, qs = engine._query_arrays(text)
        tol = f32_tolerance(qv, a, b)
        ref = hsf.numpy_reference(doc_vecs, doc_sigs, qv, qs, a, b)
        order = np.lexsort((np.arange(n), -ref))[: len(res)]
        check(len(res) == min(engine.n_docs, len(order)),
              f"{text!r}: {len(res)} results")
        for rank, r in enumerate(res):
            row = row_of[r.doc_id]
            ind = bool(np.all((doc_sigs[row] & qs) == qs))
            ref_cos = (ref[row] - b * ind) / a
            err = max(abs(r.score - ref[row]), abs(r.score - ref[order[rank]]),
                      abs(r.cosine - ref_cos))
            worst = max(worst, err)
            check(err <= tol,
                  f"{text!r} rank {rank}: score {r.score!r} cos {r.cosine!r}"
                  f" vs reference {ref[row]!r} / {ref_cos!r}, rank score "
                  f"{ref[order[rank]]!r}, tolerance {tol:.3g}")
            check(r.boosted == ind, f"{text!r} rank {rank}: boosted flag")
            if row != order[rank]:
                check(abs(ref[row] - ref[order[rank]]) <= tol,
                      f"{text!r} rank {rank}: id {row} vs reference "
                      f"{order[rank]} outside the tie band")
                swaps += 1
    log(f"reference: {len(texts)} topical queries within f32 tolerance "
        f"(largest deviation {worst:.3g}; {swaps} tie-band id swaps)")
    return swaps


def device_memory(device) -> str:
    stats = device.memory_stats() or {}
    return " ".join(f"{key}={stats.get(key)}" for key in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"))


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.ragdb import FULL
    from repro.core.engine import (
        QueryEngine,
        _score_topk_pallas,
        pack_query_arrays,
    )
    from repro.core.ingest import KnowledgeBase
    from repro.kernels.hsf_score.hsf_score import KPAD
    from repro.serving import ServingRuntime

    k, batch = FULL.top_k, FULL.query_batch
    check(k <= KPAD, f"k={k} exceeds the kernel carry ({KPAD}) and would "
          "take the unfused fallback")
    kb, entities, topical = build_kb(FULL.docs_per_device, seed, FULL.dim)
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".smoke-") as tmp:
        path = str(Path(tmp) / "kb.ragdb")
        t0 = time.perf_counter()
        kb.save(path)
        t1 = time.perf_counter()
        kb = KnowledgeBase.load(path)
        log(f"container: save {t1 - t0:.3f} s, load "
            f"{time.perf_counter() - t1:.3f} s, "
            f"{Path(path).stat().st_size} bytes")
    check(kb.n_docs == FULL.docs_per_device, f"loaded {kb.n_docs} docs")
    check(kb.dim == FULL.dim and kb.sig_words == FULL.sig_words,
          f"loaded dim={kb.dim} sig_words={kb.sig_words}")

    queries = list(entities) + topical
    runtime = ServingRuntime(kb, scoring_path="auto", max_batch=batch,
                             flush_deadline=0.05)
    engine = runtime.engine
    check(engine.scoring_path == "kernel",
          f"scoring path resolved to {engine.scoring_path!r}, not 'kernel'")

    # the served step, lowered as the scheduler dispatches it
    dv, ds = engine._kernel_operands()
    qv, qs = pack_query_arrays([engine._query_arrays(q) for q in queries],
                               kb.dim, kb.sig_words)
    args = (dv, ds, jnp.asarray(qv), jnp.asarray(qs), jnp.int32(kb.n_docs))
    kw = dict(k=k, alpha=engine.alpha, beta=engine.beta)
    check("tpu_custom_call" in _score_topk_pallas.lower(*args, **kw).as_text(),
          "the fused kernel is not compiled for the chip (interpret mode?)")

    with runtime:
        t0 = time.perf_counter()
        futures = [runtime.submit(q, k=k) for q in queries]
        served = [f.result().results for f in futures]
        log(f"first batch (compile included): {len(queries)} requests in "
            f"{time.perf_counter() - t0:.3f} s; {runtime.metrics.format()}")
    check_entities(served[:N_ENTITIES], entities)
    log(f"entities: {N_ENTITIES}/{N_ENTITIES} at rank 1, boosted")
    check_reference(engine, topical, served[N_ENTITIES:])

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(_score_topk_pallas(*args, **kw))
        times.append(time.perf_counter() - t0)
    log(f"warm fused step, batch {len(queries)} over {kb.n_docs} docs: "
        f"{[round(t * 1e3, 3) for t in times]} ms (block_until_ready)")

    flat = engine.query_batch(queries, k)
    t0 = time.perf_counter()
    ivf = QueryEngine(kb, index="ivf", guarantee="exact")
    check(ivf.scoring_path == "kernel", f"ivf path {ivf.scoring_path!r}")
    got = ivf.query_batch(queries, k)
    stats = ivf.index_stats()
    for text, a, b in zip(queries, flat, got):
        check([r.doc_id for r in a] == [r.doc_id for r in b],
              f"{text!r}: ivf exact top-k differs from the flat scan")
        tol = f32_tolerance(engine._query_arrays(text)[0],
                            engine.alpha, engine.beta)
        check(all(abs(x.score - y.score) <= tol for x, y in zip(a, b)),
              f"{text!r}: ivf exact scores differ from the flat scan")
    same_bits = all(x.score == y.score for a, b in zip(flat, got)
                    for x, y in zip(a, b))
    log(f"ivf exact == flat for {len(queries)} queries (scores bitwise "
        f"equal: {same_bits}; {stats['n_clusters']} clusters, "
        f"{stats['rounds']} rounds, probed fraction "
        f"{stats['probed_fraction']}) in {time.perf_counter() - t0:.3f} s")
    log(f"device memory: {device_memory(jax.devices()[0])}")


def four_chips(seed: int) -> None:
    import jax

    from repro.configs.ragdb import FULL
    from repro.core.engine import QueryEngine

    n_shards = 4
    devices = jax.devices()
    check(len(devices) >= n_shards, f"{len(devices)} devices, need 4")
    kb, entities, topical = build_kb(n_shards * FULL.docs_per_device, seed,
                                     FULL.dim)
    queries = list(entities) + topical
    k = FULL.top_k

    t0 = time.perf_counter()
    flat = QueryEngine(kb, scoring_path="map")
    want = flat.query_batch(queries, k)
    del flat
    gc.collect()
    log(f"flat map scan: {len(queries)} queries over {kb.n_docs} docs in "
        f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    eng = QueryEngine(kb, index="ivf-sharded", n_shards=n_shards,
                      guarantee="exact")
    mesh = eng.ivf.mesh
    check(mesh is not None, "ivf-sharded fell back to the logical loop")
    mesh_devs = list(mesh.devices.flat)
    check(len(set(mesh_devs)) == n_shards
          and all(d.platform == "tpu" for d in mesh_devs),
          f"mesh spans {mesh_devs}")
    got = eng.query_batch(queries, k)
    stats = eng.index_stats()
    for text, a, b in zip(queries, want, got):
        check(a == b, f"{text!r}: ivf-sharded differs from the flat map "
              "scan")
    check_entities(got[:N_ENTITIES], entities)
    log(f"ivf-sharded exact == flat map scan, bit for bit, for "
        f"{len(queries)} queries on a {n_shards}-device mesh "
        f"(shard sizes {eng.ivf.shard_sizes()}, {stats['rounds']} rounds) "
        f"in {time.perf_counter() - t0:.3f} s")
    for d in mesh_devs:
        log(f"device {d.id} memory: {device_memory(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    n_cache = sum(1 for _ in Path(cache).glob("*")) if Path(cache).is_dir() \
        else 0
    log(f"{len(devices)} x {devices[0].device_kind}; compile cache {cache} "
        f"({n_cache} entries at start)")
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
