"""Traffic generation: query texts and arrival schedules, from the seed.

One general generator for every mix.  A traffic file names a driver in
``drivers/`` and its parameters; a configuration's ``queries`` block gives
the query shape of its deployment (length, share of entity lookups, k).
The amount of work is fixed by the parameters: a seed changes which
passages, words and codes are drawn and in what order, never how many
requests there are, how long they are or how many are entity lookups.
"""
from __future__ import annotations

import numpy as np

from corpus import Corpus, draw, seed_rng


def arrival_times(rate: float, seconds: float, rng) -> np.ndarray:
    """Poisson arrivals conditioned on their count: ``round(rate *
    seconds)`` points, uniform on [0, seconds), sorted."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


class QueryMaker:
    """Fresh query texts of a configuration's shape.

    Topical queries take ``from_passage`` of their words from one target
    passage (positions drawn with replacement) and the rest from the
    global Zipf law, shuffled; their lengths cycle evenly over [min, max]
    words.  Entity lookups are the bare code of an entity passage, each
    code used once before any is used again.  Within a run no topical
    text repeats, so the result cache and the coalescer find nothing to
    share until the codes run out.
    """

    def __init__(self, corpus: Corpus, spec: dict, seed: int, stream: int):
        self.corpus = corpus
        self.spec = spec
        self.rng = seed_rng(seed, stream)
        self.codes = self.rng.permutation(corpus.entity_rows())
        self.next_code = 0
        self.seen: set = set()
        lo, hi = spec["words"]["min"], spec["words"]["max"]
        self.lengths = np.arange(lo, hi + 1)
        self.n_topical = 0
        self.flat = np.concatenate(corpus.words)
        self.starts = np.cumsum([0] + [len(w) for w in corpus.words[:-1]])
        self.sizes = np.array([len(w) for w in corpus.words])

    def _topical(self, count: int) -> list:
        rng, c = self.rng, self.corpus
        m = self.lengths[(self.n_topical + np.arange(count))
                         % len(self.lengths)]
        self.n_topical += count
        rows = rng.integers(0, c.n, count)
        n_own = np.minimum(np.rint(m * float(self.spec["from_passage"])),
                           self.sizes[rows]).astype(np.int64)
        qid = np.repeat(np.arange(count), m)
        slot = np.arange(len(qid)) - np.repeat(np.cumsum(m) - m, m)
        own = slot < np.repeat(n_own, m)
        pos = (rng.random(len(qid)) * self.sizes[rows][qid]).astype(np.int64)
        words = np.where(own, self.flat[self.starts[rows][qid] + pos],
                         draw(rng, c.sampler.cdf, len(qid)))
        order = np.lexsort((rng.random(len(qid)), qid))
        words = words[order]
        vocab = c.vocab
        out, at = [], 0
        for i in range(count):
            out.append((" ".join([vocab[w] for w in words[at: at + m[i]]]),
                        "topical", int(rows[i])))
            at += m[i]
        return out

    def _entity(self):
        row = int(self.codes[self.next_code % len(self.codes)])
        self.next_code += 1
        return self.corpus.vocab[int(self.corpus.code_of[row])], "entity", row

    def make(self, count: int) -> list:
        """``count`` (text, kind, target row) in a seeded order with
        exactly ``round(entity_share * count)`` entity lookups."""
        n_ent = int(round(float(self.spec["entity_share"]) * count))
        is_ent = np.zeros(count, bool)
        is_ent[self.rng.choice(count, n_ent, replace=False)] = True
        topical = self._topical(count - n_ent)
        out, t = [], 0
        for e in is_ent:
            if e:
                out.append(self._entity())
                continue
            q = topical[t]
            t += 1
            while q[0] in self.seen:
                q = self._topical(1)[0]
            self.seen.add(q[0])
            out.append(q)
        return out
