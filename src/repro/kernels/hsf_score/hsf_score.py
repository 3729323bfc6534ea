"""Fused HSF scoring kernels (Pallas TPU): single-query scoring and the
batched multi-query variant with in-kernel top-k.

Single-query (`hsf_score_pallas`) — one grid step scores a
(block_docs × D) tile of the document matrix against a resident query:

    VMEM working set per step:
        docs tile   block_docs × D      (bf16/f32)   — MXU operand
        sigs tile   block_docs × W      (int32)      — VPU operand
        query       1 × D               (f32)
        query sig   1 × W               (int32)
        out tile    block_docs          (f32)

    compute: cos  = docs @ qᵀ                     (MXU, D-contraction)
             ind  = all((sigs & qsig) == qsig)    (VPU, bitwise+reduce)
             out  = α·cos + β·ind                 (fused epilogue)

Tiling constraints: D and W are multiples of 128 (lane alignment);
block_docs a multiple of 8 (sublane).  Default block_docs=512, D=4096,
W=128 → docs tile 4 MB (bf16) / 8 MB (f32), well inside a 16 MB VMEM
with double buffering headroom at bf16.

The fusion is the point: the unfused path reads the doc matrix for the
matmul and the signature matrix for the boost in two HBM passes and
materializes an [N] cosine intermediate; fused, every byte of ⟨V⟩ and ⟨I⟩
regions is read exactly once and the boost costs zero extra bandwidth.

Batched multi-query (`hsf_score_topk_pallas`) — the serving-plane hot
loop.  The whole query batch is VMEM-resident; one grid step consumes a
(block_docs × D) doc tile and a (block_docs × W) signature tile:

    cos    = q_batch @ docsᵀ                      (MXU, [B,D]×[block,D],
                                                   f32 accuracy)
    ind    = containment as 32 bit-plane counts   (MXU, no [B,block,W]
             |q ∧ d| == |q|                        intermediate)
    scores = α·cos + β·ind, padding masked to -inf
    top-k  = k passes of (max score, min id holding it) over the
             [B, KPAD] carry and the block, written into a new carry

The carry makes the kernel single-pass over HBM *and* keeps the full
[B, N] score matrix from ever existing: only [B, k] survives each step.
Tie-breaking is (score desc, doc-id asc), bit-identical to
`retrieval._stable_top_k`: each pass takes the maximum score and, among
its holders, the smallest id — no gather, no sortedness assumption.
Rows that never fill (k > n_valid) surface ID_SENTINEL with a -inf
score.  A scalar ``n_valid`` rides in SMEM so mesh-sharded callers can
mask their local padding range without a second kernel variant.

Compiled for v5e at the full retrieval config (N=65,536, D=4096, W=128,
B=64, k=16, block 512, f32) the kernel's scoped VMEM is 17.7 MiB under a
tight limit, so ``vmem_limit_bytes`` is set from the tile sizes
(``_vmem_limit_bytes``); tests/test_tpu_compile.py keeps that compile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hsf_kernel(q_ref, qsig_ref, docs_ref, sigs_ref, out_ref, *, alpha, beta):
    docs = docs_ref[...]
    q = q_ref[...]  # [1, D]
    # MXU: [B, D] x [D, 1] -> [B, 1]; accumulate in f32 regardless of
    # operand dtype.
    cos = jax.lax.dot_general(
        docs,
        q,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, 1]
    qs = qsig_ref[...]  # [1, W] int32
    hits = (sigs_ref[...] & qs) == qs  # [B, W] bool
    ind = jnp.all(hits, axis=-1, keepdims=True).astype(jnp.float32)  # [B, 1]
    out_ref[...] = alpha * cos + beta * ind


@functools.partial(
    jax.jit, static_argnames=("alpha", "beta", "block_docs", "interpret")
)
def hsf_score_pallas(
    doc_vecs: jnp.ndarray,  # [N, D], N % block_docs == 0
    doc_sigs: jnp.ndarray,  # [N, W] int32
    query_vec: jnp.ndarray,  # [D]
    query_sig: jnp.ndarray,  # [W] int32
    *,
    alpha: float,
    beta: float,
    block_docs: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    n, d = doc_vecs.shape
    w = doc_sigs.shape[1]
    assert n % block_docs == 0, (n, block_docs)
    grid = (n // block_docs,)

    kernel = functools.partial(_hsf_kernel, alpha=alpha, beta=beta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # query resident
            pl.BlockSpec((1, w), lambda i: (0, 0)),  # query sig resident
            pl.BlockSpec((block_docs, d), lambda i: (i, 0)),
            pl.BlockSpec((block_docs, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_docs, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="hsf_score",
    )(
        query_vec.reshape(1, d),
        query_sig.reshape(1, w),
        doc_vecs,
        doc_sigs,
    )[:, 0]


# ---------------------------------------------------------------------------
# batched multi-query HSF + in-kernel top-k
# ---------------------------------------------------------------------------

NEG_INF = -jnp.inf
KPAD = 128  # scratch lane width (same carry layout as kernels/topk)
ID_SENTINEL = 2**31 - 1  # id of never-filled carry slots


SIG_BITS = 32  # bits per int32 signature word
_NT = (((1,), (1,)), ((), ()))  # dot_general: contract both minor axes


def _containment(qs, sg):
    """[B, block] bool: every set bit of query row b is set in doc row j.

    Counted on the MXU one bit plane at a time: the 0/1 plane products
    sum to |q_b ∧ d_j|, which equals |q_b| exactly when q_b ⊆ d_j.  The
    planes are 0/1 in bf16 and the counts stay ≤ 32·W, so the f32
    accumulation is exact.  No [B, block, W] intermediate and no lane
    slicing; the loop keeps one plane of each operand live.
    """
    b, block = qs.shape[0], sg.shape[0]

    def plane(t, carry):
        hits, need = carry
        qb = jax.lax.shift_right_logical(qs, t) & 1
        db = jax.lax.shift_right_logical(sg, t) & 1
        hits = hits + jax.lax.dot_general(
            qb.astype(jnp.float32).astype(jnp.bfloat16),
            db.astype(jnp.float32).astype(jnp.bfloat16),
            _NT, preferred_element_type=jnp.float32,
        )
        need = need + jnp.sum(qb.astype(jnp.float32), axis=1, keepdims=True)
        return hits, need

    hits, need = jax.lax.fori_loop(
        0, SIG_BITS, plane,
        (jnp.zeros((b, block), jnp.float32), jnp.zeros((b, 1), jnp.float32)),
    )
    return hits == need


def _hsf_topk_kernel(nvalid_ref, q_ref, qsig_ref, docs_ref, sigs_ref,
                     vals_ref, ids_ref, vscr, iscr, bscr,
                     *, k, alpha, beta, block, nblocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        vscr[...] = jnp.full_like(vscr, NEG_INF)
        iscr[...] = jnp.full_like(iscr, jnp.int32(ID_SENTINEL))

    q = q_ref[...]        # [B, D] f32
    docs = docs_ref[...]  # [block, D]
    # MXU: [B, D] × [block, D] with D-contraction → [B, block], f32 acc.
    # HIGHEST keeps f32 operands at f32 accuracy (the default may take
    # a single bf16 pass); a bf16 doc tile is widened first.
    cos = jax.lax.dot_general(
        q, docs.astype(jnp.float32), _NT,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    ok = _containment(qsig_ref[...], sigs_ref[...])
    b = q.shape[0]

    # mask docs past n_valid (ragged-N padding, sharded-suffix padding)
    lids = i * block + jax.lax.broadcasted_iota(jnp.int32, (b, block), 1)
    bscr[...] = jnp.where(lids < nvalid_ref[0],
                          alpha * cos + beta * ok.astype(jnp.float32),
                          NEG_INF)

    # merge carry ∪ block with k passes of (max score, then min id among
    # that score's holders) — the (score desc, id asc) rule without a
    # gather.  Each pick is knocked out of the scratch it came from (ids
    # are unique across carry and block), so the loop's live set is the
    # two [B, KPAD] outputs.  A -inf pick means the row cannot fill: it
    # surfaces ID_SENTINEL, never a masked or already-emitted doc id.
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, KPAD), 1)

    def pick(j, out):
        out_v, out_i = out
        cv, ci, bv = vscr[...], iscr[...], bscr[...]
        m = jnp.maximum(jnp.max(cv, axis=1, keepdims=True),
                        jnp.max(bv, axis=1, keepdims=True))
        sel = jnp.minimum(
            jnp.min(jnp.where(cv == m, ci, ID_SENTINEL), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(bv == m, lids, ID_SENTINEL), axis=1,
                    keepdims=True),
        )
        vscr[...] = jnp.where(ci == sel, NEG_INF, cv)
        bscr[...] = jnp.where(lids == sel, NEG_INF, bv)
        sel = jnp.where(m == NEG_INF, ID_SENTINEL, sel)
        return (jnp.where(lane == j, m, out_v),
                jnp.where(lane == j, sel, out_i))

    out_v, out_i = jax.lax.fori_loop(
        0, k, pick,
        (jnp.full((b, KPAD), NEG_INF, jnp.float32),
         jnp.full((b, KPAD), ID_SENTINEL, jnp.int32)),
    )
    vscr[...] = out_v
    iscr[...] = out_i

    @pl.when(i == nblocks - 1)
    def _final():
        vals_ref[...] = out_v
        ids_ref[...] = out_i


def _vmem_limit_bytes(b, d, w, block, doc_itemsize):
    """Scoped-VMEM limit for the kernel.

    ``need`` counts the double-buffered doc, signature and query tiles,
    the resident outputs and scratch, and the [B, block] temporaries of
    the scoring epilogue.  The limit is twice that: given room, XLA also
    places the call's small operands in VMEM, which the compiler charges
    to the same scoped allocation (at B=256, block 512: 21.4 MiB under a
    tight limit, 43.9 MiB under a loose one).  v5e has 128 MiB of VMEM;
    the cap leaves a quarter of it to the surrounding XLA program.
    """
    tiles = (block * d * doc_itemsize + block * w * 4   # doc + sig tiles
             + b * d * 4 + b * w * 4)                   # query tiles
    carry = b * KPAD * (4 + 4)                          # values + ids
    need = (2 * tiles + 2 * carry                       # double-buffered
            + carry + b * block * 4                     # scratch
            + b * block * 4 * 8 + block * w * 4 * 4)    # temporaries
    return int(min(max(2 * need, 32 << 20), 96 << 20))


@functools.partial(
    jax.jit,
    static_argnames=("k", "alpha", "beta", "block_docs", "interpret"),
)
def hsf_score_topk_pallas(
    doc_vecs: jnp.ndarray,   # [N, D], N % block_docs == 0
    doc_sigs: jnp.ndarray,   # [N, W] int32
    query_vecs: jnp.ndarray,  # [B, D], B % 8 == 0
    query_sigs: jnp.ndarray,  # [B, W] int32
    n_valid: jnp.ndarray,    # [1] int32 — docs beyond score -inf
    *,
    k: int,
    alpha: float,
    beta: float,
    block_docs: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused batched HSF + top-k: (vals [B, k] f32, ids [B, k] i32)."""
    n, d = doc_vecs.shape
    b, w = query_sigs.shape
    assert n % block_docs == 0, (n, block_docs)
    assert 0 < k <= KPAD, k
    nblocks = n // block_docs

    kernel = functools.partial(
        _hsf_topk_kernel, k=k, alpha=alpha, beta=beta,
        block=block_docs, nblocks=nblocks,
    )
    vals, ids = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # n_valid scalar
            pl.BlockSpec((b, d), lambda i: (0, 0)),      # queries resident
            pl.BlockSpec((b, w), lambda i: (0, 0)),      # query sigs
            pl.BlockSpec((block_docs, d), lambda i: (i, 0)),
            pl.BlockSpec((block_docs, w), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, KPAD), lambda i: (0, 0)),
            pl.BlockSpec((b, KPAD), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, KPAD), jnp.float32),
            jax.ShapeDtypeStruct((b, KPAD), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, KPAD), jnp.float32),
            pltpu.VMEM((b, KPAD), jnp.int32),
            pltpu.VMEM((b, block_docs), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit_bytes(
                b, d, w, block_docs, doc_vecs.dtype.itemsize),
        ),
        interpret=interpret,
        name="hsf_topk_batched",
    )(n_valid, query_vecs, query_sigs, doc_vecs, doc_sigs)
    return vals[:, :k], ids[:, :k]
