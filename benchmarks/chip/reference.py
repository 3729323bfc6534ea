"""Plain reference of the served retrieval, and the comparison that
decides ``correct``.

The semantics are the paper's hybrid score (RAGdb §4), as the program
states them, re-implemented here in float64 numpy from the corpus text
alone.  Nothing is imported from the program and nothing it made is used:

- tokens: case-folded runs of ``[a-z0-9_]``; each vocabulary word and
  code of ``corpus.py`` is one such token;
- signed feature hashing: FNV-1a 64 of the token's UTF-8 bytes, bucket =
  hash mod D, sign = -1 where the top bit of splitmix64(hash) is set;
- sublinear tf = 1 + ln(count) per distinct token, summed with its sign
  into its bucket; df per bucket counts documents with a token there;
  idf = ln(N / (1 + df)) + 1; a vector is the l2-normalized u * idf;
- substring indicator: a Bloom signature of W 32-bit words over the
  case-folded text's 4-byte grams (polynomial rolling hash, base = the
  FNV prime, then splitmix64; two probes, the second from splitmix64 of
  the first), and a document contains a query when every query bit is
  set in it;
- score = alpha * cosine + beta * indicator; top k by (score desc,
  row asc), rows in the order of the ascending document ids.

``State`` replays in-place updates forward, so a result is compared with
the corpus as of the generation that served it.  The control computes
the same answers with bfloat16 vectors (``precision="bf16"``).
"""
from __future__ import annotations

import re

import numpy as np

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3
MIX = 0xFF51AFD7ED558CCD
U64 = np.uint64
TOKEN = re.compile(r"[a-z0-9_]+")


def mix64(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> U64(33))
    h = h * U64(MIX)
    return h ^ (h >> U64(33))


def fnv1a(words: list) -> np.ndarray:
    """FNV-1a 64 of each word's UTF-8 bytes, vectorized by length."""
    data = [w.encode("utf-8") for w in words]
    out = np.zeros(len(data), np.uint64)
    lengths = np.array([len(d) for d in data])
    with np.errstate(over="ignore"):
        for n in np.unique(lengths):
            sel = np.flatnonzero(lengths == n)
            b = np.frombuffer(b"".join(data[i] for i in sel),
                              np.uint8).reshape(len(sel), n)
            h = np.full(len(sel), FNV_OFFSET, np.uint64)
            for j in range(n):
                h = (h ^ b[:, j].astype(np.uint64)) * U64(FNV_PRIME)
            out[sel] = h
    return out


def bucket_sign(h: np.ndarray, dim: int):
    with np.errstate(over="ignore"):
        neg = (mix64(h) >> U64(63)) == 1
    return (h % U64(dim)).astype(np.int64), np.where(neg, -1.0, 1.0)


def gram_bits(data: np.ndarray, valid: np.ndarray, nbits: int,
              ngram: int = 4, probes: int = 2) -> np.ndarray:
    """Bloom bit positions of the grams starting at ``valid`` offsets of
    the byte array ``data``; shape [probes, len(valid)]."""
    b = data.astype(np.uint64)
    acc = np.zeros(len(valid), np.uint64)
    with np.errstate(over="ignore"):
        for j in range(ngram):
            power = U64(pow(FNV_PRIME, ngram - 1 - j, 1 << 64))
            acc = acc + b[valid + j] * power
        h = mix64(acc)
        out = []
        for _ in range(probes):
            out.append((h % U64(nbits)).astype(np.int64))
            h = mix64(h)
    return np.stack(out)


def signatures(texts: list, words: int, ngram: int = 4,
               probes: int = 2, block: int = 4096) -> np.ndarray:
    """Packed Bloom signatures, uint32 [len(texts), words]."""
    nbits = words * 32
    out = np.zeros((len(texts), words), np.uint32)
    for at in range(0, len(texts), block):
        raw = [t.lower().encode("utf-8") for t in texts[at: at + block]]
        sizes = np.array([len(r) for r in raw], np.int64)
        data = np.frombuffer(b"".join(raw), np.uint8)
        starts = np.cumsum(sizes) - sizes
        doc = np.repeat(np.arange(len(raw)), sizes)
        ok = np.flatnonzero(np.arange(len(data)) - starts[doc] + ngram
                            <= sizes[doc])
        bits = np.zeros((len(raw), nbits), bool)
        for p in gram_bits(data, ok, nbits, ngram, probes):
            bits[doc[ok], p] = True
        out[at: at + len(raw)] = np.packbits(
            bits, axis=1, bitorder="little").view("<u4")
    return out


def contains(sigs: np.ndarray, q: np.ndarray) -> np.ndarray:
    """bool [N]: every bit of ``q`` is set in the row."""
    rows = np.arange(len(sigs))
    for w in np.flatnonzero(q):
        rows = rows[(sigs[rows, w] & q[w]) == q[w]]
        if not len(rows):
            break
    out = np.zeros(len(sigs), bool)
    out[rows] = True
    return out


class State:
    """The reference corpus at one point of the update sequence."""

    def __init__(self, corpus, cfg: dict):
        self.dim = int(cfg["dim"])
        self.words = int(cfg["signature_words"])
        self.alpha, self.beta = float(cfg["alpha"]), float(cfg["beta"])
        self.corpus = corpus
        self.vocab = corpus.vocab
        h = fnv1a([w.lower() for w in self.vocab])
        self.v_bucket, self.v_sign = bucket_sign(h, self.dim)
        n = corpus.n
        self.n = n
        # per-document (bucket, u) entries, CSR by document
        doc = np.repeat(np.arange(n), [len(w) for w in corpus.words])
        flat = np.concatenate(corpus.words)
        self.e_doc, self.e_bucket, self.e_u = self._entries(doc, flat)
        self.df = np.bincount(self.e_bucket, minlength=self.dim)
        order = np.argsort(self.e_bucket, kind="stable")
        self.c_doc = self.e_doc[order]
        self.c_u = self.e_u[order]
        self.c_bucket = self.e_bucket[order]
        self.c_ptr = np.searchsorted(self.c_bucket, np.arange(self.dim + 1))
        self.override: dict = {}       # row -> (buckets, u)
        self.sigs = signatures([corpus.text(i) for i in range(n)], self.words)
        self.row_words = list(corpus.words)
        self.applied = 0
        self._weights()

    def _entries(self, doc: np.ndarray, vid: np.ndarray):
        """Distinct (doc, bucket) with u = sum of tf * sign there."""
        key, cnt = np.unique(doc * len(self.vocab) + vid, return_counts=True)
        d, v = key // len(self.vocab), key % len(self.vocab)
        tf = (1.0 + np.log(cnt)) * self.v_sign[v]
        key2, inv = np.unique(d * self.dim + self.v_bucket[v],
                              return_inverse=True)
        return key2 // self.dim, key2 % self.dim, np.bincount(inv, tf)

    def _weights(self) -> None:
        self.idf = np.log(self.n / (1.0 + self.df)) + 1.0
        sq = (self.e_u * self.idf[self.e_bucket]) ** 2
        self.norm = np.sqrt(np.bincount(self.e_doc, sq, minlength=self.n))
        for row, (b, u) in self.override.items():
            self.norm[row] = np.sqrt(np.sum((u * self.idf[b]) ** 2))
        self.norm[self.norm == 0] = np.inf   # an empty row scores 0
        self.masked = np.zeros(self.n, bool)
        self.masked[list(self.override)] = True

    def apply(self, updates: list) -> None:
        """Replay in-place updates (row, word ids, code id) in order."""
        for row, vids, _ in updates:
            old = self.override.get(row)
            if old is None:
                lo, hi = np.searchsorted(self.e_doc, [row, row + 1])
                old = (self.e_bucket[lo:hi], self.e_u[lo:hi])
            self.df[old[0]] -= 1
            _, b, u = self._entries(np.zeros(len(vids), np.int64), vids)
            self.df[b] += 1
            self.override[row] = (b, u)
            self.row_words[row] = vids
            self.sigs[row] = signatures(
                [" ".join(self.vocab[w] for w in vids)], self.words)[0]
        self.applied += len(updates)
        self._weights()

    def query_vector(self, text: str):
        toks = TOKEN.findall(text.lower())
        if not toks:
            return np.zeros(0, np.int64), np.zeros(0)
        uniq, cnt = np.unique(toks, return_counts=True)
        b, s = bucket_sign(fnv1a(list(uniq)), self.dim)
        qb, inv = np.unique(b, return_inverse=True)
        u = np.bincount(inv, (1.0 + np.log(cnt)) * s) * self.idf[qb]
        norm = np.sqrt(np.sum(u * u))
        return qb, (u / norm if norm > 0 else u)

    def cosines(self, text: str, precision: str = "f64") -> np.ndarray:
        qb, qv = self.query_vector(text)
        low = precision == "bf16"
        if low:
            import ml_dtypes
            bf = ml_dtypes.bfloat16

            def rnd(x):
                return np.asarray(x).astype(bf).astype(np.float32)
            qv = rnd(qv)
        acc = np.zeros(self.n, np.float32 if low else np.float64)
        for b, q in zip(qb, qv):
            lo, hi = self.c_ptr[b], self.c_ptr[b + 1]
            d = self.c_doc[lo:hi]
            w = self.c_u[lo:hi] * self.idf[b] / self.norm[d]
            if low:
                w = rnd(w)
            acc[d] += q * w
        acc[self.masked] = 0.0
        for row, (b, u) in self.override.items():
            w = u * self.idf[b] / self.norm[row]
            hit = np.searchsorted(qb, b)
            ok = (hit < len(qb)) & (qb[np.minimum(hit, len(qb) - 1)] == b)
            if ok.any():
                wq = qv[hit[ok]]
                ww = rnd(w[ok]) if low else w[ok]
                acc[row] = np.sum(wq * ww, dtype=acc.dtype)
        return acc

    def indicator(self, text: str) -> np.ndarray:
        q = signatures([text], self.words)[0]
        return contains(self.sigs, q).astype(np.float64)

    def answer(self, text: str, k: int, precision: str = "f64") -> dict:
        """Scores of every row and the top k rows in order."""
        cos = self.cosines(text, precision)
        ind = self.indicator(text)
        score = self.alpha * cos + self.beta * ind
        kk = min(k, self.n)
        t = np.partition(score, self.n - kk)[self.n - kk]
        cand = np.flatnonzero(score >= t)
        top = cand[np.lexsort((cand, -score[cand]))][:kk]
        return {"score": score, "cos": cos, "ind": ind, "top": top}


def as_rows(results, row_of: dict) -> list:
    """Served ``RetrievalResult`` list -> [(row, score, cosine, boosted)]."""
    return [(row_of.get(r.doc_id, -1), float(r.score), float(r.cosine),
             bool(r.boosted)) for r in results]


def control_rows(ans: dict) -> list:
    """A reference answer laid out as served results (for the control)."""
    return [(int(r), float(ans["score"][r]), float(ans["cos"][r]),
             bool(ans["ind"][r] > 0.5)) for r in ans["top"]]


class Tally:
    """The numbers ``correct`` is decided on, accumulated per request."""

    def __init__(self):
        self.score_gap = 0.0        # widest |served - reference| score gap
        self.short_results = 0      # results missing or unknown ids
        self.flag_errors = 0        # boosted flag != reference indicator
        self.entity_misses = 0      # entity not at rank 1 (Recall@1)
        self.stale_reads = 0        # served from a generation too old
        self.compared = 0

    def add(self, served: list, ref: dict, k: int, entity_row: int = -1):
        self.compared += 1
        top = ref["top"]
        if len(served) != min(k, len(ref["score"])):
            self.short_results += 1
        for rank, (row, score, cos, boosted) in enumerate(served):
            if row < 0 or rank >= len(top):
                self.short_results += 1
                continue
            gap = max(abs(score - ref["score"][row]),
                      abs(score - ref["score"][top[rank]]),
                      abs(cos - ref["cos"][row]))
            self.score_gap = max(self.score_gap, gap)
            if boosted != (ref["ind"][row] > 0.5):
                self.flag_errors += 1
        if entity_row >= 0 and (not served or served[0][0] != entity_row):
            self.entity_misses += 1

    def numbers(self) -> dict:
        return {"score_gap": self.score_gap,
                "short_results": self.short_results,
                "flag_errors": self.flag_errors,
                "entity_misses": self.entity_misses,
                "stale_reads": self.stale_reads}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): every number at most its limit."""
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
