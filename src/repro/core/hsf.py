"""Hybrid Scoring Function (paper §4):

    Score(Q, D) = α · cos(v_Q, v_D) + β · 1_substr(Q, D)

with the TPU-native containment form of the indicator (signature.py).
This module is the *reference* (pure jnp) implementation plus the
dispatcher that routes the hot loop to the fused Pallas kernel
(kernels/hsf_score) when requested.

Default weights follow the paper's reported top score for the injected
entity (1.5753 with cosine ≈ 0.575 and a unit boost): α = 1.0, β = 1.0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitizers

DEFAULT_ALPHA = 1.0
DEFAULT_BETA = 1.0


def stable_rowdot(mat: jnp.ndarray, vec: jnp.ndarray) -> jnp.ndarray:
    """Deterministic [n, D] · [D] matvec — float32 [n].

    XLA's ``dot`` leaves the reduction order unspecified: the compiled
    schedule varies with operand height, gather fusion, and thread
    partitioning, so the *same row* can round to different last ulps
    between a flat [N, D] scan and a gathered candidate block — which
    silently breaks every bit-identity contract in this repo (flat vs
    IVF rerank, flat vs the sharded mesh plane, snapshot pins).  This
    formulation pins the order instead of hoping: elementwise products,
    then an explicit pairwise-halving tree over the feature axis
    (zero-padded to a power of two; padding with +0.0 is exact).
    Separate HLO adds are not reassociated by XLA, so each row's dot is
    a pure function of that row's values — independent of how many rows
    ride along, which device scores them, or where they were gathered
    from.  Every "map"-path cosine (engine, IVF rerank, sharded shard
    blocks) routes through here; that shared formulation *is* the
    exactness guarantee.
    """
    p = mat.astype(jnp.float32) * vec.astype(jnp.float32)[None, :]
    d = p.shape[-1]
    width = 1 << max(0, d - 1).bit_length() if d > 1 else 1
    if width != d:
        p = jnp.pad(p, ((0, 0), (0, width - d)))
    while width > 1:
        width //= 2
        p = p[:, :width] + p[:, width:]
    return p[:, 0]


def containment(doc_sigs: jnp.ndarray, query_sig: jnp.ndarray) -> jnp.ndarray:
    """Bloom containment indicator, float32 [n_docs].

    doc_sigs int32 [n, W], query_sig int32 [W].  Bitwise ops on int32 are
    well-defined (two's complement); equality is what matters.
    """
    hits = (doc_sigs & query_sig) == query_sig
    return jnp.all(hits, axis=-1).astype(jnp.float32)


@partial(jax.jit, static_argnames=("alpha", "beta"))
def hsf_scores(
    doc_vecs: jnp.ndarray,  # float32/bf16 [n, D], rows ℓ2-normalized
    doc_sigs: jnp.ndarray,  # int32 [n, W]
    query_vec: jnp.ndarray,  # [D]
    query_sig: jnp.ndarray,  # int32 [W]
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
) -> jnp.ndarray:
    """Reference HSF: α·(docs @ q) + β·containment.  float32 [n].

    The cosine rides the pinned-order ``stable_rowdot`` so this
    reference is bit-identical to the engine's map path row for row.
    """
    cos = stable_rowdot(doc_vecs, query_vec)
    return alpha * cos + beta * containment(doc_sigs, query_sig)


@partial(jax.jit, static_argnames=("alpha", "beta"))
def hsf_scores_batched(
    doc_vecs: jnp.ndarray,  # [n, D]
    doc_sigs: jnp.ndarray,  # int32 [n, W]
    query_vecs: jnp.ndarray,  # [q, D]
    query_sigs: jnp.ndarray,  # int32 [q, W]
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
) -> jnp.ndarray:
    """Multi-query HSF (serving batch): float32 [q, n]."""
    # analysis: allow[unpinned-reduction] -- opt-in batched gemm path,
    #   documented non-bit-stable vs the map path (ARCHITECTURE §5)
    cos = query_vecs.astype(jnp.float32) @ doc_vecs.astype(jnp.float32).T
    hits = (doc_sigs[None, :, :] & query_sigs[:, None, :]) == query_sigs[:, None, :]
    ind = jnp.all(hits, axis=-1).astype(jnp.float32)
    return alpha * cos + beta * ind


# steady-state retrace accounting (no-op unless RAGDB_SANITIZERS is on)
sanitizers.register_jit("hsf.hsf_scores", hsf_scores)
sanitizers.register_jit("hsf.hsf_scores_batched", hsf_scores_batched)


def hsf_scores_kernel(
    doc_vecs, doc_sigs, query_vec, query_sig,
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
    interpret: bool | None = None,
):
    """Fused Pallas path (see kernels/hsf_score).  Lazy import — keeps
    core/ importable without the kernels package in minimal builds."""
    from repro.kernels.hsf_score import ops as _ops

    return _ops.hsf_score(
        doc_vecs, doc_sigs, query_vec, query_sig,
        alpha=alpha, beta=beta, interpret=interpret,
    )


def hsf_topk_batched_kernel(
    doc_vecs, doc_sigs, query_vecs, query_sigs,
    *,
    k: int,
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
    n_valid=None,
    interpret: bool | None = None,
):
    """Batched-kernel dispatcher: fused multi-query HSF with in-kernel
    top-k (kernels/hsf_score).  Returns (vals [B, k'], ids [B, k']),
    k' = min(k, N), tie-broken by doc index exactly like
    `retrieval._stable_top_k`.  The [B, N] score matrix never
    materializes in HBM — this is the serving-plane hot loop.

    Lazy import for the same minimal-build reason as above."""
    from repro.kernels.hsf_score import ops as _ops

    return _ops.hsf_score_batched(
        doc_vecs, doc_sigs, query_vecs, query_sigs,
        k=k, alpha=alpha, beta=beta, n_valid=n_valid, interpret=interpret,
    )


def hsf_kernel_pad_docs(doc_vecs, doc_sigs):
    """Block-align doc operands for the batched kernel once (e.g. at
    engine refresh) instead of per dispatch; see
    `kernels/hsf_score/ops.pad_docs_for_kernel`."""
    from repro.kernels.hsf_score import ops as _ops

    return _ops.pad_docs_for_kernel(doc_vecs, doc_sigs)


def top_k(scores: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(values, indices) of the k best scores."""
    return jax.lax.top_k(scores, k)


def numpy_reference(doc_vecs, doc_sigs, query_vec, query_sig, alpha, beta):
    """Pure-numpy oracle for tests (no jax involvement at all)."""
    # analysis: allow[unpinned-reduction] -- float64 test oracle; extra
    #   mantissa absorbs reduction-order error, tests allow an eps band
    cos = np.asarray(doc_vecs, np.float64) @ query_vec.astype(np.float64)
    d = doc_sigs.view(np.uint32)
    q = query_sig.view(np.uint32)
    ind = np.all((d & q) == q, axis=-1).astype(np.float64)
    return alpha * cos + beta * ind
