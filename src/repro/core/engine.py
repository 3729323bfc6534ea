"""Batched query engine with incremental materialization (serving plane).

This is the single entry point for retrieval at serving time.  It owns
the device-resident copies of the ⟨V⟩/⟨I⟩ regions and adds three things
the single-query `Retriever` could not give a multi-user deployment:

1. **Batched queries** — ``query_batch(texts, k)`` vectorizes query
   embedding + signature construction on the host, pads the batch to a
   power-of-two bucket (so jit recompiles are bounded by
   log2(max_batch) shapes, not one per batch size), and scores all
   queries in one dispatch.

   Determinism contract: the default scoring path maps the *single-query*
   HSF formulation over the batch (``lax.map`` of a [N,D]·[D] matvec),
   so each query's scores are **bit-identical** to `Retriever.query` on
   the same corpus regardless of batch size.  A [B,D]×[D,N] GEMM is
   mathematically equal but not bit-stable across batch sizes (BLAS
   reduction order depends on the M dimension); deployments that prefer
   MXU-saturating throughput over bit-stability opt in via
   ``gemm_batch=True`` — or via ``use_kernel=True``, which dispatches
   the fused batched Pallas kernel (one pass over HBM, in-kernel top-k,
   no [B, N] score intermediate; see kernels/hsf_score).  Both opt-in
   paths return the same ranking with doc-index tie-breaking.  The
   default ``scoring_path="auto"`` resolves per backend: the kernel on
   real TPUs, the bit-stable map path everywhere else (see
   ``resolve_scoring_path``).

2. **Incremental materialization** — the `KnowledgeBase` logs dirty rows
   on ``add_text``/``sync``/remove (``changes_since``); ``refresh()``
   re-vectorizes only those documents and patches the device arrays in
   place.  The factored form ``v_d = normalize(u_d ⊙ idf)``
   (vectorizer.py) is what makes this exact: per-doc ``u_d`` rows are
   cached, and the global idf reweight is a cheap elementwise pass —
   the same O(U) split the paper uses for ingest (§3.3), applied to the
   query plane.  Where the reweight runs follows the scoring path: the
   map/gemm paths run the host ``finalize_matrix`` and the refreshed
   arrays are bit-identical to a cold ``materialize()`` rebuild; the
   kernel path keeps the ``u`` rows resident on the device, patches the
   changed ones and reweights there (one jitted pass, no [N, D] upload),
   bit-identical to a cold build on the kernel path and within float32
   rounding of ``materialize()``.

3. **Query-vector LRU cache** — keyed on the canonicalized query text
   (tokenizer.normalize), invalidated only when the idf statistics
   actually change.  Repeated queries skip tokenize/hash/scatter.

4. **Clustered index plane** — ``index="ivf"`` (default ``"flat"``)
   routes queries through the IVF probe/rerank subsystem
   (src/repro/index/): score √N centroids, gather the top-``nprobe``
   clusters' rows, rerank with the exact HSF through the same
   ``score_batch_arrays`` dispatch — sublinear scan cost, exact scores
   within the probed set, and ``guarantee="exact"`` widens probes until
   the top-k is provably identical to the flat scan.  The index rides
   the same dirty-row log as the arrays (reassign-on-refresh, drift-
   triggered retrain) and persists via ``kb.index_state``.

See docs/ARCHITECTURE.md §5/§9 for how this composes with the
mesh-sharded path (retrieval.py) and the index plane.
"""
from __future__ import annotations

import contextlib
import time
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitizers
from repro.core import hsf, signature as sigmod
from repro.core.ingest import KnowledgeBase
from repro.core.tokenizer import normalize
from repro.obs import trace as obs_trace
from repro.obs.metrics import global_registry

# shared reentrant no-op scope for the explain=False query path
_NULL_CTX = contextlib.nullcontext()


@dataclass
class RetrievalResult:
    """One retrieved document (re-exported by retrieval.py for compat)."""

    doc_id: str
    score: float
    cosine: float
    boosted: bool


@dataclass
class RefreshStats:
    """What one ``refresh()`` actually did."""

    changed: int = 0        # docs re-vectorized (the O(U) part)
    removed: int = 0        # docs dropped
    rows_patched: int = 0   # device rows updated in place (.at[].set)
    restacked: bool = False  # row layout changed (add/remove) → host restack
    reweighted: bool = False  # idf changed → global reweight pass
    index_reassigned: int = 0  # dirty rows re-clustered (index plane)
    index_retrained: bool = False  # drift threshold hit → k-means retrain
    n_docs: int = 0
    seconds: float = 0.0

    @property
    def no_op(self) -> bool:
        return self.changed == 0 and self.removed == 0


# --------------------------------------------------------------------------
# jitted scoring core (module-level so all engines share the jit cache)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "alpha", "beta", "gemm"))
def _score_topk(doc_vecs, doc_sigs, q_vecs, q_sigs, n_valid,
                *, k, alpha, beta, gemm):
    """HSF scores + top-k for a padded query batch.

    Returns (vals [B,k], idx [B,k], cos [B,k], ind [B,k]) — ``ind`` is
    the exact containment indicator of each selected doc (0.0/1.0), the
    ground truth for the ``boosted`` flag (never inferred from float
    score arithmetic, which misfires at β=0).  The non-gemm path scores
    with ``hsf.stable_rowdot`` — the pinned-reduction-order matvec — so
    every row's cosine is the same bits whether it is scored here, in a
    gathered IVF candidate block, or on a shard's resident block.

    ``n_valid`` (traced) masks doc rows ≥ n_valid to −inf before the
    top-k — the index plane's candidate-gather path pads the doc
    operands to a power-of-two row bucket (index/ivf.py); full-matrix
    callers pass n_valid == N, where the mask is the identity (the
    ``where`` keeps every score bit-exactly).
    """
    dv = doc_vecs.astype(jnp.float32)
    if gemm:
        # analysis: allow[unpinned-reduction] -- opt-in gemm branch
        #   (scoring_path="gemm"), documented non-bit-stable
        cos = q_vecs.astype(jnp.float32) @ dv.T
    else:
        cos = jax.lax.map(lambda q: hsf.stable_rowdot(dv, q), q_vecs)
    ind = jax.vmap(lambda s: hsf.containment(doc_sigs, s))(q_sigs)
    scores = alpha * cos + beta * ind
    scores = jnp.where(
        jnp.arange(scores.shape[1])[None, :] < n_valid, scores, -jnp.inf
    )
    vals, idx = jax.lax.top_k(scores, k)
    return (vals, idx, jnp.take_along_axis(cos, idx, axis=1),
            jnp.take_along_axis(ind, idx, axis=1))


def _selected_cos_ind(doc_vecs, doc_sigs, q_vecs, q_sigs, idx):
    """Per-result cosine + exact containment for selected docs only —
    O(B·k·D) instead of the O(B·N·D) full recompute."""
    sel_vecs = jnp.take(doc_vecs, idx, axis=0).astype(jnp.float32)  # [B,k,D]
    # analysis: allow[unpinned-reduction] -- pallas-path per-result
    #   diagnostics only; ranking comes from the kernel scores, and the
    #   kernel path is already documented non-bit-stable vs map
    cos = jnp.einsum("bkd,bd->bk", sel_vecs, q_vecs.astype(jnp.float32))
    sel_sigs = jnp.take(doc_sigs, idx, axis=0)                      # [B,k,W]
    qs = q_sigs[:, None, :]
    ind = jnp.all((sel_sigs & qs) == qs, axis=-1).astype(jnp.float32)
    return cos, ind


@partial(jax.jit, static_argnames=("k", "alpha", "beta"))
def _score_topk_pallas(doc_vecs, doc_sigs, q_vecs, q_sigs, n_valid,
                       *, k, alpha, beta):
    """Fused batched Pallas path (kernels/hsf_score.hsf_score_batched).

    One kernel dispatch scores the whole query batch and reduces to
    top-k in VMEM — the [B, N] score matrix never reaches HBM, and no
    per-query ``lax.map`` dispatch remains.  ``doc_vecs``/``doc_sigs``
    arrive block-aligned from the engine's operand cache (appended zero
    rows masked via the traced ``n_valid``), so the wrapper's ragged-N
    pad is a no-op in the hot loop.  Ties break by doc index
    (``retrieval._stable_top_k`` order, same as ``lax.top_k`` on the
    full score matrix).  Like ``gemm_batch``, this path is opt-in
    w.r.t. the bit-stability contract: the kernel's [B, D]×[D, block]
    MXU reduction is mathematically equal to the single-query matvec
    but not guaranteed bit-identical across backends.
    """
    vals, idx = hsf.hsf_topk_batched_kernel(
        doc_vecs, doc_sigs, q_vecs, q_sigs, k=k, alpha=alpha, beta=beta,
        n_valid=n_valid,
    )
    cos, ind = _selected_cos_ind(doc_vecs, doc_sigs, q_vecs, q_sigs, idx)
    return vals, idx, cos, ind


# rows per device u-row patch: one compiled scatter shape serves any delta
_U_PATCH_ROWS = 64


@partial(jax.jit, donate_argnums=(0,))
def _patch_u_rows(u, rows, block):
    """Write ``block`` [_U_PATCH_ROWS, D] into rows ``rows`` of the
    device u cache.  ``u`` is donated: only the engine holds it."""
    return u.at[rows].set(block)


@jax.jit
def _reweight_rows(u, idf):
    """Device twin of ``HashedTfIdf.finalize_matrix``: ``u ⊙ idf`` with
    each row ℓ2-normalized, zero rows left zero.  Float32 throughout;
    the result is a new buffer, since published snapshots pin the old
    doc matrix."""
    v = u * idf[None, :]
    norm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))
    return jnp.where(norm > 0, v / jnp.where(norm > 0, norm, 1.0), 0.0)


# steady-state retrace accounting (no-op unless RAGDB_SANITIZERS is on)
sanitizers.register_jit("engine._score_topk", _score_topk)
sanitizers.register_jit("engine._score_topk_pallas", _score_topk_pallas)
sanitizers.register_jit("engine._patch_u_rows", _patch_u_rows)
sanitizers.register_jit("engine._reweight_rows", _reweight_rows)


def _bucket(b: int) -> int:
    """Next power of two ≥ b (query-batch shape bucket)."""
    return 1 << max(b - 1, 0).bit_length() if b > 1 else 1


# --------------------------------------------------------------------------
# scoring-path selection
# --------------------------------------------------------------------------

SCORING_PATHS = ("map", "gemm", "kernel")


def _default_backend() -> str:
    """The live jax backend name (monkeypatch point for tests).  A
    backend that fails to initialize raises here: serving a broken chip
    through the host path would hide it."""
    return jax.default_backend()


def resolve_scoring_path(
    scoring_path: str = "auto",
    use_kernel: bool = False,
    gemm_batch: bool = False,
) -> str:
    """Resolve the effective scoring path: "map" | "gemm" | "kernel".

    The legacy boolean flags are explicit overrides and win over
    ``scoring_path``.  ``"auto"`` picks the fused Pallas kernel only on
    a real TPU backend — PR 2's shoot-out showed the kernel ~4x slower
    than gemm in CPU interpret mode, so auto never routes a CPU host
    through it; the bit-stable ``lax.map`` default is used instead.
    Pass ``scoring_path="kernel"`` (or ``use_kernel=True``) to force the
    kernel anywhere (e.g. interpret-mode plumbing tests), or
    ``scoring_path="map"`` to force the bit-stable path on TPU.
    """
    if use_kernel and gemm_batch:
        raise ValueError("use_kernel and gemm_batch are mutually exclusive")
    if use_kernel:
        return "kernel"
    if gemm_batch:
        return "gemm"
    if scoring_path == "auto":
        return "kernel" if _default_backend() == "tpu" else "map"
    if scoring_path not in SCORING_PATHS:
        raise ValueError(
            f"scoring_path must be 'auto' or one of {SCORING_PATHS}, "
            f"got {scoring_path!r}"
        )
    return scoring_path


def score_batch_arrays(
    doc_vecs, doc_sigs, qv: np.ndarray, qs: np.ndarray, *,
    scoring_path: str, k: int, alpha: float, beta: float, n_docs: int,
    kernel_operands=None,
):
    """One padded-batch scoring dispatch → numpy (vals, idx, cos, ind).

    Pure function of its operands (no engine state): the serving-plane
    snapshot (serving/snapshot.py) calls this against frozen arrays, the
    engine against its live ones, and the index plane against gathered
    candidate subsets (``n_docs`` < doc rows masks the pad; full-matrix
    callers pass n_docs == rows, a bit-exact no-op).  ``kernel_operands``
    is the optional pre-padded (block-aligned) doc operand pair for the
    kernel path.

    ``n_docs == 0`` (a freshly-mounted empty tenant container, or a
    corpus whose every doc was removed) short-circuits to empty [B, 0]
    result arrays on every path: the padded-bucket dispatch would
    otherwise ask top_k for k of 0 candidate columns and trip inside
    the jitted function.
    """
    if n_docs <= 0:
        b = int(np.asarray(qv).shape[0])
        empty_f = np.zeros((b, 0), dtype=np.float32)
        empty_i = np.zeros((b, 0), dtype=np.int32)
        return empty_f, empty_i, empty_f.copy(), empty_f.copy()
    with obs_trace.span("device_dispatch", path=scoring_path,
                        rows=int(n_docs), k=k):
        if scoring_path == "kernel" and kernel_operands is None:
            kernel_operands = hsf.hsf_kernel_pad_docs(doc_vecs, doc_sigs)
        with obs_trace.span("query_upload", bytes=qv.nbytes + qs.nbytes):
            qv_dev, qs_dev = jax.device_put((qv, qs))
        with obs_trace.span("launch"):
            if scoring_path == "kernel":
                dv, ds = kernel_operands
                vals, idx, cos, ind = _score_topk_pallas(
                    dv, ds, qv_dev, qs_dev, jnp.int32(n_docs),
                    k=k, alpha=alpha, beta=beta,
                )
            else:
                vals, idx, cos, ind = _score_topk(
                    doc_vecs, doc_sigs, qv_dev, qs_dev, jnp.int32(n_docs),
                    k=k, alpha=alpha, beta=beta, gemm=scoring_path == "gemm",
                )
        if obs_trace.active():
            # tracing/explain-only audited sync: without it the async
            # dispatch returns immediately and all device time would be
            # charged to the host_transfer span below.  Never runs when
            # neither a trace nor an EXPLAIN collector is active.
            with obs_trace.span("device_wait"):
                jax.block_until_ready(vals)  # analysis: allow[host-sync] -- tracing/explain-only audited boundary attributing device time to the dispatch span; no-op when both are off
    with obs_trace.span("host_transfer", k=k):
        return (np.asarray(vals), np.asarray(idx),
                np.asarray(cos), np.asarray(ind))


def results_from_topk(
    doc_ids, b: int, vals, idx, cos, ind
) -> list[list[RetrievalResult]]:
    """Materialize RetrievalResult rows for the first ``b`` queries of a
    padded batch (the ``boosted`` flag is the exact containment
    indicator returned by the scoring path, never inferred from
    score − α·cos).

    This is the one audited device→host boundary every scoring path
    funnels through (flat scan, IVF rerank, sharded merge, scheduler),
    so the opt-in NaN/Inf sanitizer hooks here: only the first ``b``
    rows are checked — rows beyond are bucket padding and legitimately
    hold -inf sentinels."""
    sanitizers.check_finite_scores(vals, b, "engine.results_from_topk")
    with obs_trace.span("materialize", rows=b):
        out = _materialize_rows(doc_ids, b, vals, idx, cos, ind)
    return out


def _materialize_rows(doc_ids, b, vals, idx, cos, ind):
    out = []
    for i in range(b):
        row = []
        for v, j, c, bi in zip(vals[i], idx[i], cos[i], ind[i]):
            row.append(
                RetrievalResult(
                    doc_id=doc_ids[int(j)],
                    score=float(v),
                    cosine=float(c),
                    boosted=bool(bi > 0.5),
                )
            )
        out.append(row)
    return out


def pack_query_arrays(
    pairs: list[tuple[np.ndarray, np.ndarray]], dim: int, sig_words: int
) -> tuple[np.ndarray, np.ndarray]:
    """Stack per-query (vector, signature) pairs into a padded
    power-of-two bucket (zero rows beyond len(pairs))."""
    bucket = _bucket(len(pairs))
    qv = np.zeros((bucket, dim), np.float32)
    qs = np.zeros((bucket, sig_words), np.int32)
    for i, (v, s) in enumerate(pairs):
        qv[i] = v
        qs[i] = s
    return qv, qs


def _record_ivf_stats(s) -> None:
    """Surface the per-dispatch ``IVFSearchStats`` — previously computed
    and dropped — as first-class metrics in the obs global registry."""
    if s is None:
        return
    reg = global_registry()
    reg.histogram("ragdb_ivf_probed_fraction",
                  "fraction of clusters probed per dispatch").record(
        float(s.probed_fraction))
    reg.histogram("ragdb_ivf_widen_rounds",
                  "probe/widen rounds per dispatch").record(float(s.rounds))
    reg.counter("ragdb_ivf_candidate_rows_total",
                "candidate rows gathered for rerank").inc(
        int(s.candidate_rows))
    reg.counter("ragdb_ivf_searches_total", "ivf dispatches").inc()
    merge_s = getattr(s, "merge_seconds", None)
    if merge_s is not None:
        reg.histogram("ragdb_ivf_merge_seconds",
                      "sharded local-top-k merge per dispatch").record(
            float(merge_s))


def _upload(host: np.ndarray, what: str):
    """Send a host block of the doc planes to the device under an
    ``upload`` span (``what``: ``vecs``, ``sigs``, ``row_patch``, or on
    the kernel path ``u``, ``u_patch`` and ``idf``).
    With tracing or EXPLAIN on, the span waits for the copy to land, so
    its time is the transfer's and not the enqueue's."""
    with obs_trace.span("upload", bytes=host.nbytes, what=what):
        dev = jnp.asarray(host)
        if obs_trace.active():
            jax.block_until_ready(dev)  # analysis: allow[host-sync] -- tracing/explain-only audited boundary attributing the host-to-device copy to the upload span; no-op when both are off
    return dev


def _pad_row_update(rows: np.ndarray, block: np.ndarray):
    """Pad a row-scatter update to a power-of-two row count.

    Device row patches jit-compile per rows-shape; bucketing bounds the
    compile count just like query batching.  Padding duplicates row 0 —
    a scatter-set writing identical content twice is deterministic.
    """
    pad = _bucket(len(rows)) - len(rows)
    if pad:
        rows = np.concatenate([rows, np.repeat(rows[:1], pad)])
        block = np.concatenate([block, np.repeat(block[:1], pad, axis=0)])
    return rows, block


class QueryEngine:
    """Batched retrieval over a live KnowledgeBase.

    ``query_batch`` auto-refreshes from the KB's dirty log first, so an
    engine constructed once keeps serving correct results across
    ``add_text``/``sync``/removal — that is the point: refresh cost is
    O(changed docs), not O(corpus).
    """

    INDEX_KINDS = ("flat", "ivf", "ivf-sharded")
    GUARANTEES = ("probe", "exact")

    def __init__(
        self,
        kb: KnowledgeBase,
        alpha: float = hsf.DEFAULT_ALPHA,
        beta: float = hsf.DEFAULT_BETA,
        use_kernel: bool = False,
        gemm_batch: bool = False,
        scoring_path: str = "auto",
        cache_size: int = 256,
        max_batch: int = 256,
        index: str = "flat",
        nprobe: int = 8,
        guarantee: str = "probe",
        n_clusters: int | None = None,
        retrain_drift: float = 0.3,
        ivf_seed: int = 0,
        n_shards: int | None = None,
    ):
        self.kb = kb
        self.alpha = float(alpha)
        self.beta = float(beta)
        # ---- index plane (docs/ARCHITECTURE.md §9/§10) ------------------
        # "flat" (default) scans all N docs — the bit-stability baseline.
        # "ivf" probes the top-`nprobe` clusters and reranks candidates
        # with the exact HSF; `guarantee="exact"` widens probes until the
        # top-k provably equals the flat scan (bit-identical).
        # "ivf-sharded" partitions the clusters across a device mesh
        # (`n_shards`, default = the device count): each device reranks
        # its own cluster subset and only [B, k] candidates merge — the
        # same guarantees, applied per shard.
        if index not in self.INDEX_KINDS:
            raise ValueError(
                f"index must be one of {self.INDEX_KINDS}, got {index!r}"
            )
        if guarantee not in self.GUARANTEES:
            raise ValueError(
                f"guarantee must be one of {self.GUARANTEES}, "
                f"got {guarantee!r}"
            )
        if index != "flat" and (self.alpha < 0 or self.beta < 0):
            # the cluster pruning bound assumes non-negative HSF weights
            raise ValueError(
                f"index={index!r} requires alpha >= 0 and beta >= 0"
            )
        if nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {nprobe}")
        self.index = index
        self.nprobe = int(nprobe)
        self.guarantee = guarantee
        self.n_clusters = n_clusters
        self.retrain_drift = float(retrain_drift)
        self.ivf_seed = int(ivf_seed)
        self.ivf = None  # IVFIndex | ShardedIVFIndex | None (see refresh)
        self._last_index_stats = None
        self.retrains = 0  # cumulative k-means (re)trains this engine ran
        # "auto" resolves at construction: kernel on real TPU backends,
        # the bit-stable map path elsewhere.  The booleans are kept as
        # resolved views for back-compat (retrieval.py checks them).
        self.scoring_path = resolve_scoring_path(
            scoring_path, use_kernel=use_kernel, gemm_batch=gemm_batch
        )
        if index == "ivf-sharded":
            if n_shards is not None and n_shards < 1:
                raise ValueError(f"n_shards must be >= 1, got {n_shards}")
            # the per-shard local rerank always scores with the
            # bit-stable map formulation ("auto" coerces; an explicit
            # gemm/kernel request would silently change numerics, so it
            # is rejected rather than ignored)
            if self.scoring_path != "map":
                if scoring_path == "auto" and not use_kernel \
                        and not gemm_batch:
                    self.scoring_path = "map"
                else:
                    raise ValueError(
                        "index='ivf-sharded' reranks with the bit-stable "
                        "map formulation; scoring_path must be 'map' or "
                        f"'auto', got {self.scoring_path!r}"
                    )
            self.n_shards = int(n_shards) if n_shards is not None \
                else max(1, jax.device_count())
        else:
            if n_shards is not None:
                raise ValueError(
                    "n_shards is only meaningful with index='ivf-sharded'"
                )
            self.n_shards = None
        self.use_kernel = self.scoring_path == "kernel"
        self.gemm_batch = self.scoring_path == "gemm"
        self.cache_size = cache_size
        self.max_batch = max_batch

        self.doc_ids: list[str] = []
        self.doc_vecs = jnp.zeros((0, kb.dim), jnp.float32)
        self.doc_sigs = jnp.zeros((0, kb.sig_words), jnp.int32)
        self._row_of: dict[str, int] = {}
        self._u = np.zeros((0, kb.dim), np.float32)  # cached tf·sign rows
        # kernel path: device copy of ``_u``, the reweight's operand
        self._u_dev = None
        self._idf = np.zeros((0,), np.float32)
        self._synced = -1  # KB version the device arrays reflect

        # kernel-path operand cache: (src_vecs, src_sigs, padded_vecs,
        # padded_sigs) — holding the source refs both keys the cache and
        # pins them against id reuse
        self._kernel_cache: tuple | None = None

        self._qcache: OrderedDict[str, tuple[np.ndarray, np.ndarray]] = (
            OrderedDict()
        )
        self.cache_hits = 0
        self.cache_misses = 0

        self.refresh()

    # ---- incremental materialization -----------------------------------

    def refresh(self) -> RefreshStats:
        """Bring device arrays up to date with the KB (O(changed docs)).

        When ``index="ivf"`` the cluster index rides the same dirty-row
        delta: changed docs reassign to their nearest centroid (O(U)),
        layout restacks remap assignments by doc id, and the drift
        counter triggers a full k-means retrain past ``retrain_drift``
        (see ``_sync_ivf``).
        """
        t0 = time.perf_counter()
        kb = self.kb
        stats = RefreshStats()
        target = kb.version
        changed_ids: list[str] | None = None
        old_row_of: dict[str, int] = {}
        if self._synced < 0:
            stats.changed = kb.n_docs
            stats.restacked = True
            self._cold_build()
            stats.reweighted = True
        elif target != self._synced:
            changed, removed = kb.changes_since(self._synced)
            stats.changed, stats.removed = len(changed), len(removed)
            changed_ids = changed
            old_row_of = self._row_of  # pre-delta layout (for ivf remap)
            self._apply_delta(changed, stats)
        if self.index != "flat" and (self.ivf is None
                                     or changed_ids is not None):
            self._sync_ivf(changed_ids, old_row_of, stats)
        self._synced = target
        stats.n_docs = len(self.doc_ids)
        stats.seconds = time.perf_counter() - t0
        return stats

    def _cold_build(self) -> None:
        kb = self.kb
        if not kb._dirty and kb._matrix is not None:
            # a clean materialized matrix exists (e.g. a container loaded
            # with include_matrix=True): adopt it instead of re-vectorizing
            # — that skip is the whole point of persisting ⟨V⟩ (RQ3).
            # The u-row cache is built lazily on the first delta.
            matrix, sigs, ids = kb.materialize()
            self._u = self._u_dev = None
            self._idf = kb.vectorizer.idf()
            self.doc_vecs = _upload(matrix, "vecs")
            self.doc_sigs = _upload(sigs, "sigs")
        else:
            ids = sorted(kb.records)
            tcs = [kb.term_counts[i] for i in ids]
            self._u = kb.vectorizer.build_unweighted_matrix(tcs)
            self._idf = kb.vectorizer.idf()
            self._reweight()
            self.doc_sigs = _upload(
                np.stack([kb.signatures[i] for i in ids])
                if ids
                else np.zeros((0, kb.sig_words), np.int32), "sigs")
        self.doc_ids = ids
        self._row_of = {i: r for r, i in enumerate(ids)}

    def _patch_u(self, rows: np.ndarray) -> None:
        """Copy host u rows ``rows`` into the device u, in chunks of
        ``_U_PATCH_ROWS`` (the last padded by repeating its first row:
        writing identical content twice is deterministic)."""
        for s in range(0, len(rows), _U_PATCH_ROWS):
            chunk = rows[s: s + _U_PATCH_ROWS]
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[:1], _U_PATCH_ROWS - len(chunk))])
            self._u_dev = _patch_u_rows(
                self._u_dev, chunk, _upload(self._u[chunk], "u_patch"))

    def _reweight(self) -> None:
        """The global stage: idf reweight + row ℓ2-normalize of every
        cached u row into a new doc matrix.  The kernel path runs it on
        the device from the resident u; map/gemm run the host
        ``finalize_matrix``, whose bits ``kb.materialize()`` shares."""
        on = "device" if self.use_kernel else "host"
        global_registry().counter(
            "ragdb_reweight_total", "doc-matrix reweights by where they ran",
            on=on).inc()
        if on == "host":
            with obs_trace.span("reweight", rows=len(self._u), on=on):
                matrix = self.kb.vectorizer.finalize_matrix(self._u)
            self.doc_vecs = _upload(matrix, "vecs")
            return
        if self._u_dev is None:  # cold build, restack, adopted matrix
            self._u_dev = _upload(self._u, "u")
            if len(self._u):
                # compile the row patch now (row 0 rewritten with
                # itself), so that no later in-place delta compiles
                self._patch_u(np.zeros((1,), np.int32))
        idf = _upload(self._idf, "idf")
        with obs_trace.span("reweight", rows=len(self._u), on=on):
            self.doc_vecs = _reweight_rows(self._u_dev, idf)
            # the operand cache holds the previous matrix: let it go now,
            # before the next capture pads this one
            self._kernel_cache = None
            # a publish returns a finished matrix: one reweight in flight
            # at most, however fast the writer publishes
            jax.block_until_ready(self.doc_vecs)  # analysis: allow[host-sync] -- publish-path backpressure on the kernel path's device reweight (writer thread, never the query path); bounds in-flight reweights and their [N, D] buffers to one

    def _ensure_u(self) -> None:
        """Materialize the u-row cache for the engine's current layout.

        Deferred when the cold build adopted a persisted matrix; rows for
        docs since removed from the KB are left zero (they are never read
        — the restack path only copies rows for surviving ids), and rows
        for since-changed docs are recomputed from the new term counts,
        identical to the values the delta is about to write anyway.
        """
        if self._u is not None:
            return
        kb = self.kb
        rows = np.zeros((len(self.doc_ids), kb.dim), np.float32)
        for r, i in enumerate(self.doc_ids):
            tc = kb.term_counts.get(i)
            if tc is not None:
                rows[r] = kb.vectorizer.unweighted_row(tc)
        self._u = rows

    def _apply_delta(self, changed: list[str], stats: RefreshStats) -> None:
        kb = self.kb
        # the doc-id set is unchanged iff nothing was added (every added
        # id is in ``changed``) and the count still matches (nothing
        # removed) — O(U), no pass over the N ids
        same_layout = (len(kb.records) == len(self.doc_ids)
                       and all(i in self._row_of for i in changed))
        if not changed and same_layout:
            # metadata-only mutation (e.g. the KB re-armed stat fast-path
            # keys on a touched-but-unchanged file): no rows to patch and
            # df cannot have moved — skip the u-cache materialization
            return
        self._ensure_u()
        # the O(U) part: re-vectorize only the dirty docs
        new_u = {
            i: kb.vectorizer.unweighted_row(kb.term_counts[i])
            for i in changed
        }
        if same_layout:
            if changed:
                rows = np.array(
                    [self._row_of[i] for i in changed], np.int32
                )
                for r, i in zip(rows, changed):
                    self._u[r] = new_u[i]
                sig_block = np.stack([kb.signatures[i] for i in changed])
                rows_p, sig_p = _pad_row_update(rows, sig_block)
                self.doc_sigs = self.doc_sigs.at[rows_p].set(
                    _upload(sig_p, "row_patch")
                )
                if self._u_dev is not None:
                    self._patch_u(rows)
        else:
            # layout changed: restack cached rows on the host (pure
            # memcpy for unchanged docs — no re-vectorization)
            new_ids = sorted(kb.records)
            u = np.zeros((len(new_ids), kb.dim), np.float32)
            sig = np.zeros((len(new_ids), kb.sig_words), np.int32)
            old_sig = np.asarray(self.doc_sigs)
            for r, i in enumerate(new_ids):
                if i in new_u:
                    u[r] = new_u[i]
                    sig[r] = kb.signatures[i]
                else:
                    old_r = self._row_of[i]
                    u[r] = self._u[old_r]
                    sig[r] = old_sig[old_r]
            self._u = u
            self._u_dev = None  # the reweight below uploads it whole
            self.doc_sigs = _upload(sig, "sigs")
            self.doc_ids = new_ids
            self._row_of = {i: r for r, i in enumerate(new_ids)}
            stats.restacked = True

        idf = kb.vectorizer.idf()
        if stats.restacked or not np.array_equal(idf, self._idf):
            # idf moved: the cheap global stage — elementwise reweight +
            # renormalize of the cached U, nothing re-vectorized
            self._idf = idf
            self._reweight()
            stats.reweighted = True
            self._qcache.clear()  # query vectors depend on idf
        elif changed:
            # idf stable: only the dirty rows change
            rows = np.array([self._row_of[i] for i in changed], np.int32)
            if self.use_kernel:
                # the device pass over the patched u: a row block would
                # round its norms apart from the cold build's full pass
                self._reweight()
            else:
                block = kb.vectorizer.finalize_matrix(self._u[rows])
                rows_p, block_p = _pad_row_update(rows, block)
                self.doc_vecs = self.doc_vecs.at[rows_p].set(
                    _upload(block_p, "row_patch"))
            stats.rows_patched = len(rows)

    # ---- index plane maintenance (index="ivf") --------------------------

    def _sync_ivf(self, changed_ids: list[str] | None,
                  old_row_of: dict[str, int], stats: RefreshStats) -> None:
        """Keep the cluster index aligned with the device arrays.

        Cold: adopt the KB's persisted index state when it matches the
        current doc layout (no cold retrain on load — the acceptance
        contract of the persistence plane), else train.  Delta: changed
        rows reassign (O(U)); restacks remap assignments by doc id; the
        drift counter triggers a retrain past ``retrain_drift``.  Every
        state change is written back to ``kb.index_state`` so
        ``save``/``save_delta`` persist it (the writer thread calls
        refresh before a durable publish — serving/snapshot.py).
        """
        from repro.index.ivf import IVFIndex, ids_digest
        from repro.index.sharded import ShardedIVFIndex

        sharded = self.index == "ivf-sharded"

        def _train():
            if sharded:
                return ShardedIVFIndex.train(
                    self.doc_vecs, np.asarray(self.doc_sigs),
                    n_clusters=self.n_clusters, seed=self.ivf_seed,
                    n_shards=self.n_shards,
                )
            return IVFIndex.train(
                self.doc_vecs, np.asarray(self.doc_sigs),
                n_clusters=self.n_clusters, seed=self.ivf_seed,
            )

        n = len(self.doc_ids)
        if n == 0:
            self.ivf = None
            return
        if self.ivf is None:
            st = self.kb.index_state
            if (st is not None and st.get("kind") == "ivf"
                    and len(st["assign"]) == n
                    and st.get("ids_sha") == ids_digest(self._ivf_state_key())):
                # the key covers doc ids AND content hashes: a stale
                # state (doc rewritten in place with no live index
                # maintenance) must never adopt — its sig_union/radius
                # could underestimate a cluster and break exactness.
                # Both kinds persist kind="ivf": a sharded engine adopts
                # flat-written state (deriving its deterministic
                # partition) and vice versa — bit-identical, no retrain
                if sharded:
                    self.ivf = ShardedIVFIndex.from_state(
                        st, self.doc_vecs, self.doc_sigs,
                        n_shards=self.n_shards,
                    )
                else:
                    self.ivf = IVFIndex.from_state(st)
                return
            self.ivf = _train()
            stats.index_retrained = True
            self._note_retrain()
            self._write_index_state()
            return
        if stats.restacked:
            # layout changed: carry surviving rows' clusters by doc id;
            # new/changed rows (−1) assign to their nearest centroid
            # (the restack itself is already O(N), so full-array
            # recomputation is in budget here)
            old_assign = self.ivf.assign
            changed_set = set(changed_ids or ())
            carried = np.full((n,), -1, np.int32)
            for r, i in enumerate(self.doc_ids):
                old_r = old_row_of.get(i)
                if old_r is not None and i not in changed_set:
                    carried[r] = old_assign[old_r]
            self.ivf = self.ivf.remap(carried, self.doc_vecs,
                                      np.asarray(self.doc_sigs))
            stats.index_reassigned = int(np.sum(carried < 0))
        elif changed_ids:
            # O(U) path: gather only the dirty rows on device before the
            # host transfer — never a full [N, ·] device→host copy.
            # The sharded plane additionally routes each dirty row to
            # its owning shard's resident block (index/sharded.py), so
            # it takes the live doc arrays for cross-shard regathers
            rows = np.array([self._row_of[i] for i in changed_ids], np.int32)
            rows_j = jnp.asarray(rows)
            row_vecs = np.asarray(jnp.take(self.doc_vecs, rows_j, axis=0))
            row_sigs = np.asarray(jnp.take(self.doc_sigs, rows_j, axis=0))
            if sharded:
                # reweighted => the refresh rebuilt every doc vector
                # (idf moved), so the resident blocks regather in full;
                # otherwise only the dirty rows patch (O(U))
                self.ivf = self.ivf.reassign(
                    rows, row_vecs, row_sigs,
                    self.doc_vecs, self.doc_sigs,
                    reweighted=stats.reweighted,
                )
            else:
                self.ivf = self.ivf.reassign(rows, row_vecs, row_sigs)
            stats.index_reassigned = len(rows)
        else:
            return  # metadata-only mutation: index untouched
        if self.ivf.needs_retrain(self.retrain_drift):
            self.ivf = _train()
            stats.index_retrained = True
            self._note_retrain()
        self._write_index_state()

    def _note_retrain(self) -> None:
        self.retrains += 1
        global_registry().counter(
            "ragdb_ivf_retrains_total",
            "k-means (re)trains across all engines").inc()

    def _ivf_state_key(self) -> list[str]:
        """Layout **and content** key the persisted index is pinned to:
        one ``"id\\x01sha256"`` token per doc in engine row order."""
        recs = self.kb.records
        return [f"{i}\x01{recs[i].sha256}" for i in self.doc_ids]

    def _write_index_state(self) -> None:
        """Publish the index state into the KB so the persistence plane
        journals it alongside the doc segments (core/ingest.py).

        The layout-key digest is O(N) string hashing per refresh —
        noise next to the O(N·D) idf reweight the same refresh performs
        whenever df moved (i.e. on any content change)."""
        self.kb.set_index_state(self.ivf.state_dict(self._ivf_state_key()))

    def index_stats(self) -> dict:
        """Probe accounting of the most recent ivf dispatch (None fields
        when the engine is flat or hasn't served an ivf query yet)."""
        s = self._last_index_stats
        return {
            "index": self.index,
            "n_clusters": self.ivf.n_clusters if self.ivf else 0,
            "drift": self.ivf.drift if self.ivf else 0,
            "retrains": self.retrains,
            "probed_fraction": s.probed_fraction if s else None,
            "clusters_probed": s.clusters_probed if s else None,
            "candidate_rows": s.candidate_rows if s else None,
            "rounds": s.rounds if s else None,
            # distribution terms (None unless the sharded plane served)
            "n_shards": getattr(s, "n_shards", None) if s else None,
            "merge_seconds": getattr(s, "merge_seconds", None) if s else None,
        }

    # ---- query-vector cache --------------------------------------------

    def _query_arrays(self, text: str) -> tuple[np.ndarray, np.ndarray]:
        return self._query_pairs([text])[0]

    def _query_pairs(self, texts: list[str]) -> list[tuple]:
        """(vector, signature) per query, through the query-vector LRU.

        Two passes, each under its own span: every missing vector
        (``query_vector``), then every missing signature
        (``query_signature``).  The LRU bookkeeping then replays the
        per-query lookup in request order (hits, misses, recency,
        eviction), so the counts and the cache end as a query-by-query
        pass leaves them."""
        cache = self._qcache
        keys = [normalize(t) for t in texts]
        known: dict[str, tuple | None] = {}
        todo: list[tuple[str, str]] = []
        for key, t in zip(keys, texts):
            if key not in known:
                known[key] = cache.get(key)
                if known[key] is None:
                    todo.append((key, t))
        with obs_trace.span("query_vector", queries=len(todo)):
            vecs = [self.kb.vectorizer.query_vector(t) for _, t in todo]
        with obs_trace.span("query_signature", queries=len(todo)):
            sigs = [sigmod.query_signature(t, width_words=self.kb.sig_words)
                    for _, t in todo]
        for (key, _), v, sg in zip(todo, vecs, sigs):
            known[key] = (v, sg)
        pairs = []
        for key in keys:
            hit = cache.get(key)
            if hit is not None:
                cache.move_to_end(key)
                self.cache_hits += 1
            else:
                self.cache_misses += 1
                hit = cache[key] = known[key]
                if len(cache) > self.cache_size:
                    cache.popitem(last=False)
            pairs.append(hit)
        return pairs

    # ---- batched queries ------------------------------------------------

    def query_batch(
        self, texts: list[str], k: int = 5, *, explain: bool = False
    ):
        """Retrieve top-k for every query; one device dispatch per chunk.

        ``k`` must be ≥ 1 (a clear ValueError, not a silent fall-through
        to the padded top-k); ``k`` > corpus size clamps to the corpus
        size.  Results per query are identical to ``Retriever.query`` on
        the same KB — bit-identical when the resolved scoring path is
        ``"map"`` (what ``"auto"`` picks everywhere except real TPU
        backends, where it resolves to the non-bit-stable kernel; force
        ``scoring_path="map"`` to keep the bit-stability contract there).

        ``explain=True`` returns ``(results, plans)`` where ``plans``
        is one :class:`repro.obs.explain.QueryPlan` per query — the
        index/probe decomposition, cache status, and per-stage timings
        of the dispatch that served it (docs/ARCHITECTURE.md §14).
        """
        if k <= 0:
            raise ValueError(f"k must be a positive integer, got {k}")
        self.refresh()
        if not self.doc_ids or not texts:
            empty = [[] for _ in texts]
            if explain:
                from repro.obs import explain as explain_mod
                plans = explain_mod.plans_from_dispatch(
                    texts, k, index=self.index,
                    scoring_path=self.scoring_path, guarantee=self.guarantee,
                    n_docs=0)
                return empty, plans
            return empty
        out: list[list[RetrievalResult]] = []
        batches = []
        for start in range(0, len(texts), self.max_batch):
            chunk = texts[start: start + self.max_batch]
            if explain:
                res, ps = self._query_chunk(chunk, k, explain=True)
                out.extend(res)
                batches.append(ps)
            else:
                out.extend(self._query_chunk(chunk, k))
        if explain:
            from repro.obs.explain import PlanBatch
            return out, PlanBatch.concat(batches)
        return out

    def query(self, text: str, k: int = 5) -> list[RetrievalResult]:
        """Single-query convenience wrapper (batch of one)."""
        return self.query_batch([text], k)[0]

    def _query_chunk(self, texts: list[str], k: int, *,
                     explain: bool = False):
        b = len(texts)
        if explain:
            from repro.obs import explain as explain_mod
            col = obs_trace.StageCollector()
            scope = obs_trace.get().collect(col)
            vec_hits = tuple(normalize(t) in self._qcache for t in texts)
            t0 = time.perf_counter()
        else:
            scope = _NULL_CTX
        with scope:
            with obs_trace.span("query_embed", queries=b):
                pairs = self._query_pairs(texts)
                with obs_trace.span("query_pack", queries=b):
                    qv, qs = pack_query_arrays(
                        pairs, self.kb.dim, self.kb.sig_words)
            n = len(self.doc_ids)
            stats = None
            if self.index != "flat" and self.ivf is not None:
                # the kernel path's index reads the block-aligned pair
                docs = (self._kernel_operands() if self.use_kernel
                        else (self.doc_vecs, self.doc_sigs))
                vals, idx, cos, ind, stats = self.ivf.search(
                    *docs, qv, qs,
                    b=b, k=min(k, n), nprobe=self.nprobe,
                    guarantee=self.guarantee,
                    scoring_path=self.scoring_path,
                    alpha=self.alpha, beta=self.beta, explain=explain,
                )
                self._last_index_stats = stats
                _record_ivf_stats(stats)
            else:
                vals, idx, cos, ind = score_batch_arrays(
                    self.doc_vecs, self.doc_sigs, qv, qs,
                    scoring_path=self.scoring_path, k=min(k, n),
                    alpha=self.alpha, beta=self.beta, n_docs=n,
                    kernel_operands=(
                        self._kernel_operands() if self.use_kernel else None
                    ),
                )
            results = results_from_topk(self.doc_ids, b, vals, idx, cos, ind)
        if not explain:
            return results
        # capture only: the QueryPlan dataclasses are built on first
        # access (PlanBatch) — the hot path pays one closure + one
        # tuple() of the collected stages, not 20-field inits per query
        stages = tuple(col.stages)
        total_s = time.perf_counter() - t0
        index, path, guar = self.index, self.scoring_path, self.guarantee
        return results, explain_mod.PlanBatch(
            lambda: explain_mod.plans_from_dispatch(
                texts, k, index=index, scoring_path=path, guarantee=guar,
                n_docs=n, stats=stats, stages=stages,
                vector_cache_hits=vec_hits, total_s=total_s))

    def _kernel_operands(self):
        """Block-aligned doc operands for the fused kernel, re-padded
        only when refresh() rebound the device arrays — the per-dispatch
        O(N·D) pad copy never runs in the serving hot loop."""
        cache = self._kernel_cache
        if (cache is None or cache[0] is not self.doc_vecs
                or cache[1] is not self.doc_sigs):
            dv, ds = hsf.hsf_kernel_pad_docs(self.doc_vecs, self.doc_sigs)
            cache = (self.doc_vecs, self.doc_sigs, dv, ds)
            self._kernel_cache = cache
        return cache[2], cache[3]

    # ---- introspection ---------------------------------------------------

    @property
    def synced_version(self) -> int:
        """The KB mutation version the device arrays reflect — the
        generation a snapshot captured from this engine is pinned at,
        and the state a durable publish persists
        (serving/snapshot.py ``SnapshotManager.publish(durable=True)``).
        -1 until the first ``refresh()``."""
        return self._synced

    @property
    def n_docs(self) -> int:
        return len(self.doc_ids)

    def cache_stats(self) -> dict:
        return {
            "hits": self.cache_hits,
            "misses": self.cache_misses,
            "entries": len(self._qcache),
            "capacity": self.cache_size,
        }
