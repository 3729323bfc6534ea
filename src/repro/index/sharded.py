"""Sharded IVF retrieval plane: the cluster index partitioned across a
JAX device mesh (docs/ARCHITECTURE.md §10).

Each shard (device) owns a disjoint subset of the IVF *clusters* —
centroids stay global (the probe plane is k_clusters ≈ √N, host-cheap),
but every cluster's member rows live on exactly one shard: the shard
holds a padded block of those rows' vectors and signatures, gathered in
ascending global-row order.  A query then runs:

1. **Global probe (host).**  Score the [k_clusters, D] centroid matrix
   once — the same interleaved probe order and (in exact mode) the same
   spherical-cap bound as the flat IVF path (`ivf.exact_cos_upper_bound`
   / `ivf.interleave_probe_order`), restricted per shard through the
   cluster→shard ownership map.

2. **Local rerank (per device).**  Each shard gathers its probed
   clusters' member rows from its resident block and scores them with
   the *bit-stable map formulation* (the same per-query matvec
   `_score_topk` dispatches), reducing to a local top-k.  Under
   `shard_map` this is one dispatch over the whole mesh; only the
   per-device ``[B, k]`` (vals, global ids, cos, contain) tuples cross
   the interconnect.

3. **Stable merge (host).**  The S·k candidates merge by
   (score desc, global id asc) — exactly `lax.top_k`'s tie rule on the
   flat score matrix, because each shard's local candidate order is the
   global row order restricted to that shard.

Exactness (``guarantee="exact"``): per-shard probe widths double until
the *merged* k-th exact score strictly beats every unprobed cluster's
cap bound in every shard (ties widen).  This is the unsharded exactness
theorem applied shard-wise: the bound says no unprobed cluster anywhere
can hold a doc scoring ≥ the current k-th, and per-shard local top-k +
stable merge reconstructs the global top-k of the probed union
bit-for-bit (asserted against ``index="flat"`` by
tests/test_index_sharded.py across shard counts, batch shapes, ragged
corpus sizes, tie-heavy corpora and degenerate partitions).

Cross-shard-count parity: the partition only decides *where* a cluster
is scored, never *what* is scored — the k-means fit, the probe bound,
the per-row dot products (bit-identical under row gather, the same
assumption the candidate-gather rerank already relies on) and the
merge rule are all partition-independent, so exact-mode results are
bit-identical across shards ∈ {1, 2, 4, 8, …} as well.

Incremental maintenance routes dirty rows to their owning shard off the
engine's existing dirty-row log: content-only changes scatter-patch the
owning shard's resident block in O(U) when the idf statistics held
still (the engine's own idf-stable fast path — an idf move rebuilds
every doc vector, and the blocks regather with it at the same O(N·D)
the reweight already paid); rows whose nearest centroid moved to a
cluster on another shard trigger a block regather for just the
affected shards; layout restacks rebuild the plane (the restack is
already O(N)).  All updates return a **new** ``ShardedIVFIndex`` — the
serving snapshots pin a frozen plane per generation with one reference
capture, same as the flat IVF index.

Persistence: ``state_dict`` extends the flat IVF state with the
cluster→shard map (segment ``ivf_shard_of_cluster``) and ``n_shards``,
under the same ``kind="ivf"`` — a sharded engine adopts a flat-written
state (deriving a deterministic partition) and vice versa (the flat
engine ignores the extra keys), and the same ``ids_sha`` content digest
rejects stale state per the exactness contract.

When fewer than ``n_shards`` devices exist (or n_shards == 1) the plane
falls back to a per-shard jitted loop on the default device — identical
block shapes, identical per-shard math, so logical-shard tests on one
CPU device exercise the exact same numerics the mesh dispatches.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hsf
from repro.core.engine import _bucket
from repro.obs import trace as obs_trace
from repro.index.ivf import (
    IVFIndex,
    IVFSearchStats,
    exact_cos_upper_bound,
    interleave_probe_order,
)
from repro.launch.mesh import make_shard_mesh

# pad sentinel for invalid rows in a shard's local top-k — loses every
# (score desc, id asc) merge (same sentinel the mesh retrieval path and
# the fused kernel use for unfillable rows)
_SENTINEL = np.int32(2**31 - 1)

P = jax.sharding.PartitionSpec


@dataclass(frozen=True)
class ShardedIVFSearchStats(IVFSearchStats):
    """Flat-IVF probe accounting plus the distribution terms."""

    n_shards: int = 1
    merge_seconds: float = 0.0   # host-side stable-merge time (all rounds)


def partition_clusters(sizes, n_shards: int) -> np.ndarray:
    """Deterministic balanced partition: cluster → shard.

    Greedy longest-processing-time: clusters sorted by (size desc,
    id asc) each go to the least-loaded shard (ties → lowest shard id).
    Pure function of (sizes, n_shards), so every engine that derives a
    partition for the same index state derives the *same* one — which
    is what lets a flat-written container adopt into a sharded engine
    reproducibly.
    """
    sizes = np.asarray(sizes, np.int64)
    out = np.zeros((sizes.size,), np.int32)
    load = np.zeros((n_shards,), np.int64)
    for c in np.lexsort((np.arange(sizes.size), -sizes)):
        s = int(np.argmin(load))        # argmin takes the lowest index on ties
        out[c] = s
        load[s] += sizes[c]
    return out


# --------------------------------------------------------------------------
# per-shard local scorer (the map formulation, over a resident block)
# --------------------------------------------------------------------------

def _shard_topk_core(dv, ds, gids, cand, n_cand, qv, qs, *, kk, alpha, beta):
    """Local top-k over one shard's candidate gather.

    ``dv``/``ds``/``gids`` are the shard's resident [L, D]/[L, W]/[L]
    block; ``cand`` [C] are local candidate rows (ascending → the
    gathered order is the global row order restricted to this shard, so
    ``lax.top_k``'s index-ascending tie rule matches the flat scan);
    ``n_cand`` (traced) masks the candidate pad.  The cosine is
    ``hsf.stable_rowdot`` — the pinned-reduction-order matvec shared
    with the flat engine's map path — which is what makes each
    candidate's score bit-identical to its row in the full scan
    regardless of block height, gather fusion, or which device runs it.
    """
    sub_v = jnp.take(dv.astype(jnp.float32), cand, axis=0)
    sub_s = jnp.take(ds, cand, axis=0)
    sub_g = jnp.take(gids, cand)
    cos = jax.lax.map(lambda q: hsf.stable_rowdot(sub_v, q), qv)
    ind = jax.vmap(lambda s: hsf.containment(sub_s, s))(qs)
    scores = alpha * cos + beta * ind
    scores = jnp.where(
        jnp.arange(scores.shape[1])[None, :] < n_cand, scores, -jnp.inf
    )
    vals, li = jax.lax.top_k(scores, kk)
    gi = jnp.where(vals > -jnp.inf, jnp.take(sub_g, li),
                   jnp.int32(_SENTINEL))
    return (vals, gi, jnp.take_along_axis(cos, li, axis=1),
            jnp.take_along_axis(ind, li, axis=1))


_shard_topk_jit = jax.jit(
    _shard_topk_core, static_argnames=("kk", "alpha", "beta")
)


@lru_cache(maxsize=64)
def _mesh_topk_fn(mesh, kk: int, alpha: float, beta: float):
    """jit(shard_map(local top-k)) for one (mesh, k, α, β): each device
    scores its own block; only the [B, kk] result tuples leave it."""
    def local_fn(dv, ds, gids, cand, n_cand, qv, qs):
        out = _shard_topk_core(dv[0], ds[0], gids[0], cand[0], n_cand[0],
                               qv, qs, kk=kk, alpha=alpha, beta=beta)
        return tuple(o[None] for o in out)

    return jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("shards"), P("shards"), P("shards"),
                  P("shards"), P("shards"), P(), P()),
        out_specs=(P("shards"),) * 4,
        check_vma=False,
    ))


@jax.jit
def _scatter_block_rows(s_idx, l_idx, vec_block, sig_block,
                        dv_stack, ds_stack):
    """Content patch: write U changed rows into their owning shards'
    resident blocks — one fused dispatch for both scatters."""
    return (dv_stack.at[s_idx, l_idx].set(vec_block),
            ds_stack.at[s_idx, l_idx].set(sig_block))


@partial(jax.jit, static_argnames=("block_len",))
def _gather_shard_block(doc_vecs, doc_sigs, rows, n_rows, *, block_len):
    """One shard's padded resident block, gathered on device —
    ``rows`` [L] (pad rows duplicate row 0; masked by ``n_rows``)."""
    dv = jnp.take(doc_vecs, rows, axis=0).astype(jnp.float32)
    ds = jnp.take(doc_sigs, rows, axis=0).astype(jnp.int32)
    valid = jnp.arange(block_len) < n_rows
    dv = jnp.where(valid[:, None], dv, 0.0)
    ds = jnp.where(valid[:, None], ds, 0)
    return dv, ds


@dataclass(frozen=True)
class ShardedIVFIndex:
    """Immutable cluster-sharded index plane (see module docstring).

    ``base`` carries the global IVF state (centroids, bounds, assign,
    members) — probing, maintenance bookkeeping and persistence all
    delegate to it, so the sharded plane provably prunes with the same
    bound the flat IVF search uses.  The fields below it are the
    distribution plane: ownership, per-shard row sets, and the padded
    device-resident blocks the local reranks score.
    """

    base: IVFIndex
    n_shards: int
    shard_of_cluster: np.ndarray  # [kc] int32 — cluster → owning shard
    shard_rows: tuple             # S × int32 [n_s] ascending global rows
    block_len: int                # L — power-of-two row pad per shard
    dv_stack: object              # jnp [S, L, D] (mesh-sharded on dim 0)
    ds_stack: object              # jnp [S, L, W]
    gid_stack: object             # jnp [S, L] int32 (pad = sentinel)
    mesh: object | None           # 1-D ("shards",) Mesh, or None = loop

    # ---- construction ---------------------------------------------------

    @staticmethod
    def train(doc_vecs, doc_sigs, *, n_clusters: int | None = None,
              seed: int = 0, n_iter: int = 8,
              n_shards: int = 1) -> "ShardedIVFIndex":
        """Fit the (partition-independent) k-means, then shard it."""
        base = IVFIndex.train(doc_vecs, doc_sigs, n_clusters=n_clusters,
                              seed=seed, n_iter=n_iter)
        return ShardedIVFIndex.from_base(base, doc_vecs, doc_sigs,
                                         n_shards=n_shards)

    @staticmethod
    def from_base(base: IVFIndex, doc_vecs, doc_sigs, *, n_shards: int,
                  shard_of_cluster=None) -> "ShardedIVFIndex":
        """Build the distribution plane over an existing IVF state.

        ``shard_of_cluster`` overrides the deterministic balanced
        partition (tests use it for degenerate all-in-one-shard
        ownership); it must map every cluster to [0, n_shards).
        """
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if shard_of_cluster is None:
            sizes = [m.size for m in base.members]
            shard_of_cluster = partition_clusters(sizes, n_shards)
        else:
            shard_of_cluster = np.asarray(shard_of_cluster, np.int32)
            if shard_of_cluster.shape != (base.n_clusters,):
                raise ValueError(
                    f"shard_of_cluster must have shape ({base.n_clusters},), "
                    f"got {shard_of_cluster.shape}"
                )
            if shard_of_cluster.size and (
                    shard_of_cluster.min() < 0
                    or shard_of_cluster.max() >= n_shards):
                raise ValueError("shard_of_cluster entries must lie in "
                                 f"[0, {n_shards})")
        shard_rows = _shard_rows_from(base, shard_of_cluster, n_shards)
        return _build_plane(base, n_shards, shard_of_cluster, shard_rows,
                            doc_vecs, doc_sigs)

    @staticmethod
    def from_state(state: dict, doc_vecs, doc_sigs, *,
                   n_shards: int) -> "ShardedIVFIndex":
        """Adopt persisted IVF state (flat- or sharded-written) —
        bit-identical bounds/assignments, no retrain; the persisted
        partition is reused when it was written for the same shard
        count, else a deterministic one is derived."""
        base = IVFIndex.from_state(state)
        soc = state.get("shard_of_cluster")
        if soc is not None and int(state.get("n_shards", -1)) == n_shards:
            soc = np.asarray(soc, np.int32)
        else:
            soc = None
        return ShardedIVFIndex.from_base(base, doc_vecs, doc_sigs,
                                         n_shards=n_shards,
                                         shard_of_cluster=soc)

    def state_dict(self, layout_keys) -> dict:
        """The flat IVF state plus the ownership map — still
        ``kind="ivf"`` so flat and sharded engines adopt each other's
        containers (core/ingest.py journals ``ivf_shard_of_cluster`` as
        one more index segment)."""
        st = self.base.state_dict(layout_keys)
        st["n_shards"] = int(self.n_shards)
        st["shard_of_cluster"] = self.shard_of_cluster
        return st

    # ---- delegation (engine/serving introspection + tests) --------------

    @property
    def n_clusters(self) -> int:
        return self.base.n_clusters

    @property
    def n_docs(self) -> int:
        return self.base.n_docs

    @property
    def centroids(self) -> np.ndarray:
        return self.base.centroids

    @property
    def assign(self) -> np.ndarray:
        return self.base.assign

    @property
    def members(self) -> tuple:
        return self.base.members

    @property
    def sig_union(self) -> np.ndarray:
        return self.base.sig_union

    @property
    def radius(self) -> np.ndarray:
        return self.base.radius

    @property
    def drift(self) -> int:
        return self.base.drift

    @property
    def trained_n(self) -> int:
        return self.base.trained_n

    @property
    def seed(self) -> int:
        return self.base.seed

    def needs_retrain(self, retrain_drift: float) -> bool:
        return self.base.needs_retrain(retrain_drift)

    def shard_sizes(self) -> list[int]:
        return [int(r.size) for r in self.shard_rows]

    # ---- incremental maintenance (engine dirty-row log) -----------------

    def reassign(self, rows, row_vecs, row_sigs, doc_vecs, doc_sigs, *,
                 reweighted: bool = False) -> "ShardedIVFIndex":
        """Route dirty rows to their owning shard.

        Delegates the cluster moves and bound widening to
        ``base.reassign`` (same drift accounting as the flat index),
        then repairs the device plane: rows whose old and new clusters
        live on the same shard only need their block content
        scatter-patched (O(U) — the shard's row set didn't change);
        rows that crossed shards invalidate both shards' row sets, so
        those shards' blocks regather from the live doc arrays
        (O(rows-on-affected-shards), never O(N) unless a shard outgrew
        its pad bucket, which rebuilds the plane like a restack).

        ``reweighted=True`` signals that the engine's refresh moved the
        idf statistics, i.e. *every* doc vector was rebuilt, not just
        the dirty rows — the resident blocks then regather in full
        (the refresh already paid O(N·D) for the reweight, so this
        keeps the same asymptotics; the O(U) patch path is exactly the
        engine's own idf-stable fast path, mirrored).
        """
        rows = np.asarray(rows, np.int32)
        if rows.size == 0:
            return self
        new_base = self.base.reassign(rows, row_vecs, row_sigs)
        if reweighted:
            return ShardedIVFIndex.from_base(
                new_base, doc_vecs, doc_sigs, n_shards=self.n_shards,
                shard_of_cluster=self.shard_of_cluster,
            )
        old_shard = self.shard_of_cluster[self.base.assign[rows]]
        new_shard = self.shard_of_cluster[new_base.assign[rows]]
        crossed = np.unique(np.concatenate(
            [old_shard[old_shard != new_shard],
             new_shard[old_shard != new_shard]]
        ))
        if crossed.size:
            new_rows = _shard_rows_from(new_base, self.shard_of_cluster,
                                        self.n_shards)
            if max(r.size for r in new_rows) > self.block_len:
                # a shard outgrew the row bucket: rebuild (rare — the
                # bucket doubles, so this amortizes like the restack)
                return _build_plane(new_base, self.n_shards,
                                    self.shard_of_cluster, new_rows,
                                    doc_vecs, doc_sigs)
        else:
            new_rows = self.shard_rows

        dv_stack, ds_stack, gid_stack = (
            self.dv_stack, self.ds_stack, self.gid_stack
        )
        # regather the shards whose row sets changed
        gid_host = None
        for s in crossed:
            srows = new_rows[s]
            padded = np.zeros((self.block_len,), np.int32)
            padded[: srows.size] = srows
            dv_s, ds_s = _gather_shard_block(
                doc_vecs, doc_sigs, jnp.asarray(padded),
                jnp.int32(srows.size), block_len=self.block_len,
            )
            dv_stack = dv_stack.at[int(s)].set(dv_s)
            ds_stack = ds_stack.at[int(s)].set(ds_s)
            if gid_host is None:
                gid_host = np.asarray(gid_stack).copy()
            gid_host[int(s)] = _SENTINEL
            gid_host[int(s), : srows.size] = srows
        if gid_host is not None:
            gid_stack = jnp.asarray(gid_host)

        # scatter-patch content for rows that stayed on their shard
        crossed_set = set(int(s) for s in crossed)
        keep = np.array([new_shard[j] not in crossed_set
                         and old_shard[j] not in crossed_set
                         for j in range(rows.size)], bool)
        if keep.any():
            s_idx = new_shard[keep].astype(np.int32)
            l_idx = np.array(
                [int(np.searchsorted(new_rows[s], r))
                 for s, r in zip(s_idx, rows[keep])], np.int32,
            )
            vec_block = np.asarray(row_vecs, np.float32)[keep]
            sig_block = np.asarray(row_sigs, np.int32)[keep]
            # pad the scatter to a power-of-two row count (bounded jit
            # recompiles; duplicate writes of identical content are
            # deterministic — same trick as engine._pad_row_update)
            pad = _bucket(int(keep.sum())) - int(keep.sum())
            if pad:
                s_idx = np.concatenate([s_idx, np.repeat(s_idx[:1], pad)])
                l_idx = np.concatenate([l_idx, np.repeat(l_idx[:1], pad)])
                vec_block = np.concatenate(
                    [vec_block, np.repeat(vec_block[:1], pad, axis=0)])
                sig_block = np.concatenate(
                    [sig_block, np.repeat(sig_block[:1], pad, axis=0)])
            dv_stack, ds_stack = _scatter_block_rows(
                jnp.asarray(s_idx), jnp.asarray(l_idx),
                jnp.asarray(vec_block), jnp.asarray(sig_block),
                dv_stack, ds_stack,
            )
        dv_stack, ds_stack, gid_stack = _pin_stacks(
            self.mesh, dv_stack, ds_stack, gid_stack
        )
        return replace(self, base=new_base, shard_rows=new_rows,
                       dv_stack=dv_stack, ds_stack=ds_stack,
                       gid_stack=gid_stack)

    def remap(self, carried_assign, doc_vecs, doc_sigs) -> "ShardedIVFIndex":
        """Rebuild after an engine layout restack — the restack is
        already O(N), so the plane regathers in full.  Centroids (and
        therefore the partition) are unchanged."""
        new_base = self.base.remap(carried_assign, doc_vecs, doc_sigs)
        return ShardedIVFIndex.from_base(
            new_base, doc_vecs, doc_sigs, n_shards=self.n_shards,
            shard_of_cluster=self.shard_of_cluster,
        )

    # ---- the sharded two-stage search -----------------------------------

    def search(self, doc_vecs, doc_sigs, qv: np.ndarray, qs: np.ndarray, *,
               b: int, k: int, nprobe: int, guarantee: str,
               scoring_path: str, alpha: float, beta: float,
               explain: bool = False):
        """Probe globally, rerank per shard, merge stably → the same
        (vals, idx, cos, ind, stats) contract as ``IVFIndex.search``
        (idx are global doc rows).

        ``scoring_path`` is accepted for signature compatibility; the
        local rerank always scores with the bit-stable map formulation
        (the engine rejects explicit gemm/kernel for this index kind).
        In exact mode, per-(query, shard) probe widths double until the
        merged k-th exact score strictly beats every unprobed cluster's
        spherical-cap bound in that shard; in probe mode each shard
        scores the batch union of its queries' top-``nprobe`` local
        clusters in a single round (a per-query superset of the flat
        IVF probe — recall can only improve).
        """
        del scoring_path
        base = self.base
        n, kc, S = base.n_docs, base.n_clusters, self.n_shards
        kk = min(k, n)
        sizes = np.array([m.size for m in base.members], np.int64)
        _t = time.perf_counter() if obs_trace.active() else 0.0

        # -- global probe plane (host, float64 bound) ---------------------
        # analysis: allow[unpinned-reduction] -- f64 probe bound, clipped
        #   to [-1,1]; prunes candidates only, exact rerank follows
        a = np.clip(
            qv[:b].astype(np.float64) @ base.centroids.T.astype(np.float64),
            -1.0, 1.0,
        )
        qsig = qs[:b].astype(np.int32)
        contain = np.all(
            (base.sig_union[None, :, :] & qsig[:, None, :])
            == qsig[:, None, :], axis=2,
        )
        if guarantee == "exact":
            ub = alpha * exact_cos_upper_bound(a, base.radius) \
                + beta * contain
            rank = ub
        else:
            ub = None
            rank = alpha * a + beta * contain
        order = interleave_probe_order(rank, a)             # [b, kc]

        # restrict the global order to each shard's clusters (the
        # restriction of a permutation is a permutation of the subset,
        # so per-shard probing follows the same priority as the flat
        # IVF search would within that shard)
        soc = self.shard_of_cluster
        shard_orders = []
        for s in range(S):
            own = soc[order] == s                           # [b, kc] bool
            kc_s = int((soc == s).sum())
            shard_orders.append(
                order[own].reshape(b, kc_s) if kc_s else
                np.empty((b, 0), np.int64)
            )

        # initial probe width per (shard, query): nprobe clamped to the
        # shard's cluster count, widened until the shard's own probed
        # clusters cover ≥ min(kk, n_s) docs — summed over shards that
        # guarantees ≥ kk real candidates, so the merged top-k is full
        p = np.zeros((S, b), np.int64)
        for s in range(S):
            kc_s = shard_orders[s].shape[1]
            if kc_s == 0:
                continue
            n_s = int(self.shard_rows[s].size)
            need_docs = min(kk, n_s)
            for i in range(b):
                csum = np.cumsum(sizes[shard_orders[s][i]])
                need = int(np.searchsorted(csum, need_docs)) + 1
                p[s, i] = min(max(min(max(nprobe, 1), kc_s), need), kc_s)

        if _t:
            obs_trace.record("shard_probe", _t, time.perf_counter() - _t,
                             clusters=kc, shards=S, queries=b,
                             guarantee=guarantee)
        shard_cluster_ids = [np.nonzero(soc == s)[0] for s in range(S)]
        qv_j, qs_j = jnp.asarray(qv), jnp.asarray(qs)
        rounds = 0
        merge_seconds = 0.0
        while True:
            rounds += 1
            _tr = time.perf_counter() if obs_trace.active() else 0.0
            cand_local: list[np.ndarray] = []
            probed_global: list[np.ndarray] = []
            for s in range(S):
                kc_s = shard_orders[s].shape[1]
                n_s = int(self.shard_rows[s].size)
                if kc_s == 0 or n_s == 0:
                    cand_local.append(np.zeros((0,), np.int32))
                    probed_global.append(shard_cluster_ids[s])
                    continue
                probed = np.unique(np.concatenate(
                    [shard_orders[s][i, : p[s, i]] for i in range(b)]
                ))
                if probed.size >= kc_s or sizes[probed].sum() * 2 > n_s:
                    # ≥50% of the shard probed: score the whole resident
                    # block — the shard-local analogue of the flat-scan
                    # collapse, trivially exact for this shard
                    cand_local.append(
                        np.arange(n_s, dtype=np.int32))
                    probed_global.append(shard_cluster_ids[s])
                else:
                    gmem = np.sort(np.concatenate(
                        [base.members[c] for c in probed]
                    ))
                    cand_local.append(np.searchsorted(
                        self.shard_rows[s], gmem).astype(np.int32))
                    probed_global.append(probed)

            C = _bucket(max(1, max(c.size for c in cand_local)))
            kk_loc = min(kk, C)
            cand_pad = np.zeros((S, C), np.int32)
            n_cand = np.zeros((S,), np.int32)
            for s, cl in enumerate(cand_local):
                cand_pad[s, : cl.size] = cl
                n_cand[s] = cl.size
            svals, sgids, scos, sind = self._dispatch(
                cand_pad, n_cand, qv_j, qs_j, kk_loc, alpha, beta
            )
            t0 = time.perf_counter()
            vals, idx, cos, ind = _merge_shard_topk(
                svals, sgids, scos, sind, kk
            )
            t1 = time.perf_counter()
            merge_seconds += t1 - t0
            if _tr:
                obs_trace.record("shard_merge", t0, t1 - t0,
                                 shards=S, round=rounds)
                obs_trace.record("shard_round", _tr, t1 - _tr,
                                 round=rounds,
                                 candidates=int(n_cand.sum()))

            if ub is None:
                break
            # stop test, per (query, shard): the merged k-th exact score
            # must strictly beat every unprobed cluster's bound in every
            # shard (ties could displace by doc-index order → widen)
            done = True
            for s in range(S):
                kc_s = shard_orders[s].shape[1]
                if kc_s == 0 or probed_global[s].size >= kc_s:
                    continue
                mask = np.zeros((kc,), bool)
                mask[probed_global[s]] = True
                un = shard_cluster_ids[s][~mask[shard_cluster_ids[s]]]
                for i in range(b):
                    if float(vals[i, kk - 1]) <= ub[i, un].max():
                        p[s, i] = min(p[s, i] * 2, kc_s)
                        done = False
            if done:
                break

        probe_orders, kth, bounds = [], [], []
        if explain:
            mask = np.zeros((kc,), bool)
            for pg in probed_global:
                mask[pg] = True
            for i in range(b):
                own = np.concatenate([
                    shard_orders[s][i, : min(int(p[s, i]),
                                             shard_orders[s].shape[1])]
                    for s in range(S)
                ]) if S else np.zeros((0,), np.int64)
                probe_orders.append(tuple(int(c) for c in own))
                kth.append(float(vals[i, kk - 1]))
                if ub is None:
                    bounds.append(None)
                else:
                    un = ub[i][~mask]
                    bounds.append(float(un.max()) if un.size else None)
        stats = ShardedIVFSearchStats(
            n_docs=n,
            candidate_rows=int(n_cand.sum()),
            clusters_probed=int(sum(pg.size for pg in probed_global)),
            n_clusters=kc,
            rounds=rounds,
            probe_order=tuple(probe_orders),
            kth_scores=tuple(kth),
            unprobed_bounds=tuple(bounds),
            n_shards=S,
            merge_seconds=merge_seconds,
        )
        return vals, idx, cos, ind, stats

    def _dispatch(self, cand_pad, n_cand, qv_j, qs_j, kk_loc, alpha, beta):
        """One rerank round → numpy (vals, gids, cos, ind), each
        [S, Bp, kk_loc].  Mesh path: one ``shard_map`` dispatch, each
        device scoring its resident block; only its [B, kk] tuple
        leaves the device.  Fallback: the identical jitted local scorer
        looped over logical shards on the default device."""
        if self.mesh is not None:
            # one collective dispatch: per-shard attribution is not
            # observable from the host, so a single span covers it
            with obs_trace.span("shard_local_topk",
                                shards=self.n_shards, mode="mesh"):
                fn = _mesh_topk_fn(self.mesh, kk_loc,
                                   float(alpha), float(beta))
                v, g, c, d = fn(self.dv_stack, self.ds_stack,
                                self.gid_stack,
                                jnp.asarray(cand_pad),
                                jnp.asarray(n_cand),
                                qv_j, qs_j)
                if obs_trace.active():
                    jax.block_until_ready(v)  # analysis: allow[host-sync] -- tracing/explain-only audited boundary attributing mesh dispatch time to its span; no-op when both are off
        else:
            outs = []
            for s in range(self.n_shards):
                with obs_trace.span("shard_local_topk", shard=s,
                                    rows=int(n_cand[s])):
                    o = _shard_topk_jit(
                        self.dv_stack[s], self.ds_stack[s],
                        self.gid_stack[s],
                        jnp.asarray(cand_pad[s]),
                        jnp.int32(int(n_cand[s])),
                        qv_j, qs_j,
                        kk=kk_loc, alpha=float(alpha), beta=float(beta),
                    )
                    if obs_trace.active():
                        jax.block_until_ready(o)  # analysis: allow[host-sync] -- tracing/explain-only audited boundary: per-shard local-top-k attribution in the logical-shard loop; no-op when both are off
                outs.append(o)
            v = jnp.stack([o[0] for o in outs])
            g = jnp.stack([o[1] for o in outs])
            c = jnp.stack([o[2] for o in outs])
            d = jnp.stack([o[3] for o in outs])
        return (np.asarray(v), np.asarray(g), np.asarray(c), np.asarray(d))


# --------------------------------------------------------------------------
# plane construction + merge
# --------------------------------------------------------------------------

def _shard_rows_from(base: IVFIndex, shard_of_cluster: np.ndarray,
                     n_shards: int) -> tuple:
    """Ascending global member rows per shard (union of owned clusters)."""
    out = []
    for s in range(n_shards):
        own = np.nonzero(shard_of_cluster == s)[0]
        if own.size:
            rows = np.sort(np.concatenate(
                [base.members[c] for c in own]
            )).astype(np.int32)
        else:
            rows = np.zeros((0,), np.int32)
        out.append(rows)
    return tuple(out)


def _pin_stacks(mesh, dv_stack, ds_stack, gid_stack):
    """Commit the stacked blocks to the mesh (dim 0 = shard axis) — one
    device_put each; a no-op when already resident with that sharding.
    Host stacks go shard by shard straight to their devices: staging a
    whole [S, L, D] stack on one device first does not fit HBM at
    deployment size (4 x 65,536 docs at D=4096 is an 8 GiB f32 stack)."""
    if mesh is None:
        return (jnp.asarray(dv_stack), jnp.asarray(ds_stack),
                jnp.asarray(gid_stack))
    sh = jax.sharding.NamedSharding(mesh, P("shards"))
    return (jax.device_put(dv_stack, sh), jax.device_put(ds_stack, sh),
            jax.device_put(gid_stack, sh))


def _build_plane(base: IVFIndex, n_shards: int, shard_of_cluster: np.ndarray,
                 shard_rows: tuple, doc_vecs, doc_sigs) -> ShardedIVFIndex:
    """Materialize the per-shard resident blocks (O(N) gather — only at
    train/adopt/restack time, never on the query path)."""
    L = _bucket(max(1, max((r.size for r in shard_rows), default=1)))
    dim = np.shape(doc_vecs)[1] if np.ndim(doc_vecs) == 2 else 0
    w = np.shape(doc_sigs)[1] if np.ndim(doc_sigs) == 2 else 0
    dvn = np.asarray(doc_vecs, np.float32)
    dsn = np.asarray(doc_sigs, np.int32)
    dv = np.zeros((n_shards, L, dim), np.float32)
    ds = np.zeros((n_shards, L, w), np.int32)
    gid = np.full((n_shards, L), _SENTINEL, np.int32)
    for s, rows in enumerate(shard_rows):
        if rows.size:
            dv[s, : rows.size] = dvn[rows]
            ds[s, : rows.size] = dsn[rows]
            gid[s, : rows.size] = rows
    mesh = make_shard_mesh(n_shards)
    dv_j, ds_j, gid_j = _pin_stacks(mesh, dv, ds, gid)
    return ShardedIVFIndex(
        base=base, n_shards=int(n_shards),
        shard_of_cluster=np.asarray(shard_of_cluster, np.int32),
        shard_rows=shard_rows, block_len=int(L),
        dv_stack=dv_j, ds_stack=ds_j, gid_stack=gid_j, mesh=mesh,
    )


def _merge_shard_topk(vals, gids, cos, ind, kk: int):
    """Stable global merge of per-shard top-k lists.

    Sort key (score desc, global id asc) — exactly ``lax.top_k``'s tie
    rule on the flat score matrix.  Sentinel-id rows carry -inf scores
    and lose every comparison; the per-shard coverage widening
    guarantees ≥ kk real candidates, so they never surface.
    """
    s, bp, kl = vals.shape
    v = np.swapaxes(vals, 0, 1).reshape(bp, s * kl)
    g = np.swapaxes(gids, 0, 1).reshape(bp, s * kl)
    c = np.swapaxes(cos, 0, 1).reshape(bp, s * kl)
    d = np.swapaxes(ind, 0, 1).reshape(bp, s * kl)
    pick = np.lexsort((g, -v), axis=-1)[:, :kk]
    return (np.take_along_axis(v, pick, axis=1),
            np.take_along_axis(g, pick, axis=1).astype(np.int32),
            np.take_along_axis(c, pick, axis=1),
            np.take_along_axis(d, pick, axis=1))
