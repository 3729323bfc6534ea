"""The benchmark's corpus and traffic generators: deterministic per seed,
shaped as their configuration states, at a fixed amount of work."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from corpus import (CODE_PREFIX, UpdateMaker, make_corpus,  # noqa: E402
                    seed_rng, vocab_word)
from traffic import QueryMaker, arrival_times  # noqa: E402

BIG_SEED = 2**31 + 12345


def spec(config, passages=3000, entities=300, updates=200):
    s = json.loads((HERE / "configs" / f"{config}.json").read_text())
    corpus = dict(s["corpus"], passages=passages, entity_passages=entities,
                  update_codes=updates)
    return corpus, s["queries"]


def test_corpus_is_deterministic_per_seed():
    c, _ = spec("dpr-flat")
    a, b = make_corpus(c, BIG_SEED), make_corpus(c, BIG_SEED)
    other = make_corpus(c, 7)
    assert [a.text(i) for i in range(50)] == [b.text(i) for i in range(50)]
    assert [a.text(i) for i in range(50)] != [other.text(i) for i in range(50)]
    assert np.array_equal(a.code_of, b.code_of)


def test_passage_lengths_as_stated():
    c, _ = spec("dpr-flat")
    corpus = make_corpus(c, 3)
    sizes = np.array([len(w) for w in corpus.words])
    has_code = corpus.code_of >= 0
    assert set(sizes[~has_code]) == {100}
    assert set(sizes[has_code]) == {101}


def test_zipf_shape():
    """Global words follow the stated law: the log-log slope of rank
    against frequency over the shared head is close to -exponent."""
    c, _ = spec("dpr-flat", passages=4000)
    corpus = make_corpus(c, 5)
    counts = np.bincount(np.concatenate(corpus.words),
                         minlength=len(corpus.vocab))[: c["shared_head"]]
    ranks = np.arange(1, len(counts) + 1)
    slope = np.polyfit(np.log(ranks[2:]), np.log(counts[2:]), 1)[0]
    assert abs(slope + c["zipf_exponent"]) < 0.15


def test_entity_codes_injected_once_each():
    c, _ = spec("dpr-flat")
    corpus = make_corpus(c, 9)
    rows = corpus.entity_rows()
    assert len(rows) == c["entity_passages"]
    codes = corpus.code_of[rows]
    assert len(set(codes.tolist())) == len(rows)
    everywhere = np.concatenate(corpus.words)
    for row in rows[:50]:
        code = corpus.code_of[row]
        assert np.sum(corpus.words[row] == code) == 1
        assert np.sum(everywhere == code) == 1
        assert corpus.vocab[code].startswith(CODE_PREFIX)
        assert corpus.vocab[code] in corpus.text(row)


def test_vocabulary_words_are_single_tokens():
    import re
    token = re.compile(r"[a-z0-9_]+")
    words = [vocab_word(r) for r in (0, 1, 25, 26, 99999)]
    assert len(set(words)) == len(words)
    for w in words:
        assert token.fullmatch(w) and len(w) == 4


def test_queries_deterministic_fixed_work_and_fresh():
    c, q = spec("dpr-flat")
    corpus = make_corpus(c, 11)
    a = QueryMaker(corpus, q, BIG_SEED, 4).make(500)
    b = QueryMaker(corpus, q, BIG_SEED, 4).make(500)
    other = QueryMaker(corpus, q, 8, 4).make(500)
    assert a == b and a != other
    for qs in (a, other):
        kinds = [k for _, k, _ in qs]
        assert kinds.count("entity") == round(q["entity_share"] * 500)
        topical = [t for t, k, _ in qs if k == "topical"]
        lengths = [len(t.split()) for t in topical]
        assert min(lengths) >= q["words"]["min"]
        assert max(lengths) <= q["words"]["max"]
        assert sorted(lengths) == sorted(
            len(t.split()) for t, k, _ in a if k == "topical")
        assert len(set(t.lower() for t, _, _ in qs)) == 500
    for text, kind, row in a:
        if kind == "entity":
            assert corpus.vocab[corpus.code_of[row]] == text
        else:
            own = set(corpus.vocab[w] for w in corpus.words[row])
            hits = sum(w in own for w in text.split())
            assert hits >= round(q["from_passage"] * len(text.split()))


def test_arrivals_keep_their_rate():
    for seed in (1, BIG_SEED):
        t = arrival_times(2500.0, 12.0, seed_rng(seed, 3))
        assert len(t) == 30000
        assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 12.0
        per_s = np.bincount(t.astype(int), minlength=12)
        assert np.all(np.abs(per_s - 2500) < 5 * np.sqrt(2500))
        gaps = np.diff(t)
        assert abs(gaps.mean() * 2500 - 1) < 0.02
        assert abs(gaps.std() / gaps.mean() - 1) < 0.05   # exponential


def test_updates_deterministic_and_skewed():
    c, _ = spec("dpr-flat", updates=3000)
    corpus = make_corpus(c, 13)
    def chunked():
        maker = UpdateMaker(corpus, 0.99, 13)
        return maker.make(1000) + maker.make(2000)   # warm-up, then window
    a, b = chunked(), chunked()
    assert [(r, w.tolist(), k) for r, w, k in a] == [
        (r, w.tolist(), k) for r, w, k in b]
    codes = [k for _, _, k in a]
    assert len(set(codes)) == 3000
    rows, hits = np.unique([r for r, _, _ in a], return_counts=True)
    assert hits.max() > 50          # Zipf(0.99): the head row recurs
    with pytest.raises(ValueError):
        UpdateMaker(corpus, 0.99, 13).make(3001)
