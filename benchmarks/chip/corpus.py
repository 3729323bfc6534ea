"""Synthetic passage corpus in the shape of a public collection, from a seed.

The idea is that of the program's ``data/corpus.make_topical_corpus``,
kept here so that the yardstick does not move when the program does:

- terms follow a Zipf law over a synthetic vocabulary;
- each passage belongs to one topic and draws a share of its words from
  that topic's own Zipf-weighted word list, the rest from the global law,
  so cosine neighbourhoods concentrate and a cluster index can prune;
- a unique entity code is injected into known passages, for the paper's
  entity Recall@1.

Everything is drawn from ``numpy.random.default_rng`` seeded from the run
seed, so the same seed gives the same corpus, queries and updates.  Words
are kept as vocabulary ids; ``text`` joins them.  Every vocabulary word and
code is one token of the program's tokenizer (lowercase ``[a-z0-9_]+``
after case folding), which ``reference.py`` relies on.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_LETTERS = "abcdefghijklmnopqrstuvwxyz"
_CODE_CHARS = np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"))
CODE_PREFIX = "UNIQUE_CODE_"
CODE_CHARS = 12


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed works."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def vocab_word(rank: int) -> str:
    """Bijective base-26 spelling of ``rank`` offset to four letters."""
    n = rank + 18279          # first four-letter bijective numeral
    out = []
    while n:
        n, r = divmod(n - 1, 26)
        out.append(_LETTERS[r])
    return "".join(reversed(out))


def zipf_cdf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    c = np.cumsum(w)
    return c / c[-1]


def draw(rng: np.random.Generator, cdf: np.ndarray, size) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(size)), len(cdf) - 1)


def passage_lengths(spec: dict, n: int) -> np.ndarray:
    """Words per passage (``fixed``, the one law a configuration uses)."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["words"]), np.int64)
    raise ValueError(f"unknown passage length law {spec['dist']!r}")


@dataclass
class Corpus:
    """Passages as vocabulary ids.  Ids >= ``n_vocab`` are entity codes."""

    vocab: list              # words, then codes
    n_vocab: int
    words: list              # per passage: int64 array of vocab ids
    code_of: np.ndarray      # [N] vocab id of its code, or -1
    spec: dict
    sampler: "PassageSampler"

    @property
    def n(self) -> int:
        return len(self.words)

    def text(self, i: int) -> str:
        v = self.vocab
        return " ".join([v[w] for w in self.words[i]])

    def doc_id(self, i: int) -> str:
        return f"p{i:08d}"

    def entity_rows(self) -> np.ndarray:
        return np.flatnonzero(self.code_of >= 0)


class PassageSampler:
    """Draws passages of the corpus's law: topic words and global words."""

    def __init__(self, spec: dict, rng: np.random.Generator):
        self.spec = spec
        v, t = int(spec["vocabulary"]), int(spec["topics"])
        self.cdf = zipf_cdf(v, float(spec["zipf_exponent"]))
        size = int(spec["topic_words"])
        # topic words come from below the head of the law: the head is
        # shared by all topics, as function words are
        head = int(spec["shared_head"])
        self.topic_words = np.stack(
            [head + rng.choice(v - head, size, replace=False)
             for _ in range(t)])
        self.topic_cdf = zipf_cdf(size, float(spec["zipf_exponent"]))

    def words(self, rng: np.random.Generator, topic: int,
              n: int) -> np.ndarray:
        from_topic = rng.random(n) < float(self.spec["topic_share"])
        local = self.topic_words[topic][draw(rng, self.topic_cdf, n)]
        return np.where(from_topic, local, draw(rng, self.cdf, n))


def make_codes(rng: np.random.Generator, n: int) -> list:
    chars = _CODE_CHARS[rng.integers(0, len(_CODE_CHARS), (n, CODE_CHARS))]
    codes = [CODE_PREFIX + "".join(row) for row in chars]
    if len(set(codes)) != n:
        raise RuntimeError("entity code collision; draw again")
    return codes


def make_corpus(spec: dict, seed: int) -> Corpus:
    """The corpus of ``spec`` (a configuration's ``corpus`` block)."""
    rng = seed_rng(seed, 1)
    n, v = int(spec["passages"]), int(spec["vocabulary"])
    sampler = PassageSampler(spec, rng)
    lengths = passage_lengths(spec["passage_words"], n)
    topic_of = rng.integers(0, int(spec["topics"]), n)
    words = []
    for i in range(n):
        words.append(sampler.words(rng, int(topic_of[i]), int(lengths[i])))
    n_codes = int(spec["entity_passages"]) + int(spec.get("update_codes", 0))
    codes = make_codes(rng, n_codes)
    vocab = [vocab_word(r) for r in range(v)] + codes
    code_of = np.full(n, -1, np.int64)
    for j, row in enumerate(rng.choice(n, int(spec["entity_passages"]),
                                       replace=False)):
        w = words[row]
        pos = int(rng.integers(0, len(w) + 1))
        words[row] = np.insert(w, pos, v + j)
        code_of[row] = v + j
    return Corpus(vocab, v, words, code_of, spec, sampler)


class UpdateMaker:
    """In-place updates (row, new word ids, code id), drawn in sequence.

    Rows are Zipf(``zipf``)-popular over a seeded permutation of the
    passages; each new text is a fresh passage of the corpus's law with a
    fresh entity code, taken from the ``update_codes`` after the initial
    ones."""

    def __init__(self, corpus: Corpus, zipf: float, seed: int):
        self.corpus = corpus
        self.rng = seed_rng(seed, 2)
        self.perm = self.rng.permutation(corpus.n)
        self.cdf = zipf_cdf(corpus.n, zipf)
        self.first = corpus.n_vocab + int(corpus.spec["entity_passages"])
        self.made = 0

    def make(self, count: int) -> list:
        spec, rng = self.corpus.spec, self.rng
        if self.made + count > int(spec.get("update_codes", 0)):
            raise ValueError(f"{self.made + count} updates but only "
                             f"{spec.get('update_codes', 0)} update codes")
        rows = self.perm[draw(rng, self.cdf, count)]
        lengths = passage_lengths(spec["passage_words"], count)
        out = []
        for j in range(count):
            topic = int(rng.integers(0, int(spec["topics"])))
            w = self.corpus.sampler.words(rng, topic, int(lengths[j]))
            code = self.first + self.made + j
            pos = int(rng.integers(0, len(w) + 1))
            out.append((int(rows[j]), np.insert(w, pos, code), code))
        self.made += count
        return out
