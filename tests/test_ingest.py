"""Paper §3.2–3.3: multimodal sniffing and O(U) incremental ingestion."""
import json
import os

import numpy as np

from repro.core import ingest
from repro.core.ingest import KnowledgeBase
from repro.core.retrieval import Retriever


def test_sniffing():
    assert ingest.sniff_modality(b"%PDF-1.7 ...") == "pdf"
    assert ingest.sniff_modality(b"\x89PNG\r\n") == "image"
    assert ingest.sniff_modality(b"\xff\xd8\xff\xe0") == "image"
    assert ingest.sniff_modality(b"PK\x03\x04") == "zip"
    assert ingest.sniff_modality(b'{"a": 1}') == "json"
    assert ingest.sniff_modality(b"a,b\n1,2", "t.csv") == "csv"
    assert ingest.sniff_modality(b"plain words") == "text"


def test_sniffing_whitespace_padded_json():
    """JSON behind >15 bytes of leading whitespace used to fall out of
    the 16-byte probe window and route to text."""
    data = b" " * 40 + b'{"deep": {"key": 1}}'
    assert ingest.sniff_modality(data[: ingest.SNIFF_WINDOW]) == "json"
    text, kind = ingest.extract(data)
    assert kind == "json" and "deep.key: 1" in text


def test_sniffing_csv_with_bracket_cell():
    """A CSV whose first cell starts with '[' used to hit the JSON
    structural probe before the extension hint."""
    data = b"[tag],value\n[a],1\n[b],2"
    assert ingest.sniff_modality(data, "rows.csv") == "csv"
    text, kind = ingest.extract(data, "rows.csv")
    assert kind == "csv" and "[tag]=[a]" in text and "value=2" in text
    # without the extension hint the structural probe still applies
    assert ingest.sniff_modality(b'["x", "y"]') == "json"


def test_sniffing_json_extension_hint():
    assert ingest.sniff_modality(b"  \n 1234", "data.json") == "json"
    assert ingest.sniff_modality(b"whatever", "log.jsonl") == "json"


def test_csv_overflow_cells_preserved():
    """Rows longer than the header keep their tail as positional colN=
    cells instead of being zip-truncated away."""
    data = b"a,b\n1,2,OVERFLOW-77,9"
    text, kind = ingest.extract(data, "t.csv")
    assert kind == "csv"
    assert text == "a=1, b=2, col2=OVERFLOW-77, col3=9"


def test_extractors():
    text, kind = ingest.extract(b'{"name": "ada", "tags": ["x", "y"]}')
    assert kind == "json" and "name: ada" in text and "tags[0]: x" in text
    text, kind = ingest.extract(b"id,amount\n7,42\n8,99", "x.csv")
    assert kind == "csv"
    assert "id=7" in text and "amount=42" in text  # headers preserved
    text, kind = ingest.extract(b"%PDF-1.4 binarybits")
    assert kind == "pdf" and "pdf-frontend-stub" in text


def _write(d, name, content):
    with open(os.path.join(d, name), "w") as f:
        f.write(content)


def test_incremental_o_of_u(tmp_path):
    src = str(tmp_path / "src")
    os.makedirs(src)
    for i in range(30):
        _write(src, f"f{i}.txt", f"document number {i} about topic{i % 5}")
    kb = KnowledgeBase(dim=512)
    s_cold = kb.sync(src)
    assert s_cold.added == 30 and s_cold.skipped == 0

    s_warm = kb.sync(src)
    assert s_warm.processed == 0 and s_warm.skipped == 30

    _write(src, "f3.txt", "totally new content INV-2024")
    _write(src, "f31.txt", "a brand new file")
    os.unlink(os.path.join(src, "f9.txt"))
    s_delta = kb.sync(src)
    assert s_delta.updated == 1 and s_delta.added == 1
    assert s_delta.removed == 1 and s_delta.skipped == 28
    assert kb.n_docs == 30

    # retrieval reflects the delta
    r = Retriever(kb)
    assert r.query("INV-2024", k=1)[0].doc_id == "f3.txt"
    assert all(x.doc_id != "f9.txt" for x in r.query("topic4", k=30))


def test_same_content_rename_reprocessed_as_new_path(tmp_path):
    src = str(tmp_path / "src")
    os.makedirs(src)
    _write(src, "a.txt", "same content")
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    os.rename(os.path.join(src, "a.txt"), os.path.join(src, "b.txt"))
    s = kb.sync(src)
    assert s.added == 1 and s.removed == 1


def test_container_roundtrip_arms_stat_fast_path(tmp_path, monkeypatch):
    """Regression: save() used to drop DocRecord.size/mtime_ns, so the
    first sync() after reopening a container re-hashed every file.  A
    save → load → sync round-trip on an unchanged directory must skip
    every doc without a single file read (O(stat) fast path armed)."""
    import builtins

    src = str(tmp_path / "src")
    os.makedirs(src)
    for i in range(12):
        _write(src, f"f{i}.txt", f"document number {i}")
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path)

    kb2 = KnowledgeBase.load(path)
    for rec in kb2.records.values():
        assert rec.size >= 0 and rec.mtime_ns >= 0  # persisted, not -1

    reads = []
    real_open = builtins.open

    def counting_open(file, mode="r", *a, **k):
        if "r" in mode and "b" in mode:
            reads.append(file)
        return real_open(file, mode, *a, **k)

    monkeypatch.setattr(builtins, "open", counting_open)
    stats = kb2.sync(src)
    monkeypatch.undo()
    assert stats.skipped == 12 and stats.processed == 0
    assert reads == []  # zero file reads: stat-only


def test_pre_size_container_loads_and_rearms(tmp_path):
    """Backward compat: containers written before size/mtime_ns were
    persisted load with the fast path unarmed (-1), fall back to content
    hashing once, and re-arm it for the next sync."""
    from repro.core.container import Container, write_container

    src = str(tmp_path / "src")
    os.makedirs(src)
    for i in range(5):
        _write(src, f"f{i}.txt", f"document number {i}")
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path)

    # strip the new meta keys to simulate an old container
    c = Container.open(path)
    meta = c.meta
    for d in meta["docs"]:
        d.pop("size", None)
        d.pop("mtime_ns", None)
    old = str(tmp_path / "old.ragdb")
    write_container(old, c.read_all(), meta, 0)

    kb2 = KnowledgeBase.load(old)
    assert all(r.size == -1 and r.mtime_ns == -1
               for r in kb2.records.values())
    s1 = kb2.sync(src)  # hash fallback: everything skipped by sha256
    assert s1.skipped == 5 and s1.processed == 0
    assert all(r.size >= 0 and r.mtime_ns >= 0
               for r in kb2.records.values())  # re-armed


def test_generation_roundtrip_and_monotonic_continuation(tmp_path):
    """Regression: Container.open parses the generation but load() used
    to discard it — a save/load round-trip reset the lineage the serving
    plane pins snapshots against.  It must survive the round-trip, and
    save()/save_delta() must continue it monotonically by default."""
    src = str(tmp_path / "src")
    os.makedirs(src)
    _write(src, "a.txt", "alpha")
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path, generation=7)
    assert kb.loaded_generation == 7

    kb2 = KnowledgeBase.load(path)
    assert kb2.loaded_generation == 7  # restored, not dropped
    kb2.add_text("b.txt", "beta")
    kb2.save(path)  # default: continue the lineage
    assert kb2.loaded_generation == 8
    kb3 = KnowledgeBase.load(path)
    assert kb3.loaded_generation == 8
    kb3.add_text("c.txt", "gamma")
    assert kb3.save_delta(path) == 9  # delta continues it too
    assert KnowledgeBase.load(path).loaded_generation == 9


def test_fresh_kb_save_defaults_to_generation_zero(tmp_path):
    kb = KnowledgeBase(dim=512)
    kb.add_text("a.txt", "alpha")
    path = str(tmp_path / "kb.ragdb")
    kb.save(path)
    from repro.core.container import Container
    assert Container.open(path).generation == 0


def test_container_roundtrip_preserves_everything(tmp_path):
    src = str(tmp_path / "src")
    os.makedirs(src)
    _write(src, "a.txt", "alpha beta UNIQUE_CODE_7")
    _write(src, "b.json", json.dumps({"k": "gamma"}))
    kb = KnowledgeBase(dim=512)
    kb.sync(src)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path, generation=3)

    kb2 = KnowledgeBase.load(path)
    assert kb2.n_docs == kb.n_docs
    assert kb2.records["a.txt"].sha256 == kb.records["a.txt"].sha256
    assert kb2.records["b.json"].modality == "json"
    m1, s1, i1 = kb.materialize()
    m2, s2, i2 = kb2.materialize()
    np.testing.assert_array_equal(m1, m2)
    np.testing.assert_array_equal(s1, s2)
    assert i1 == i2
    # and incremental sync continues to work post-restore
    s = kb2.sync(src)
    assert s.processed == 0 and s.skipped == 2


def test_changes_since_after_rewrites_and_removals():
    """The change log reads only its newest entries, so it must stay in
    version order when a doc changes again or comes back after removal."""
    kb = KnowledgeBase(dim=256)
    for name in ("a", "b", "c", "d"):
        kb.add_text(name, f"text of {name}")
    v0 = kb.version
    kb.add_text("b", "b rewritten")
    v1 = kb.version
    kb.add_text("c", "c rewritten")
    kb._remove_doc("d")
    v2 = kb.version
    kb.add_text("b", "b rewritten again")
    kb.add_text("d", "d is back")
    assert kb.changes_since(v0) == (["b", "c", "d"], [])
    assert kb.changes_since(v1) == (["b", "c", "d"], [])
    assert kb.changes_since(v2) == (["b", "d"], [])
    assert kb.changes_since(kb.version) == ([], [])
    kb._remove_doc("a")
    assert kb.changes_since(v2) == (["b", "d"], ["a"])
    assert kb.changes_since(0) == (["b", "c", "d"], ["a"])


def test_text_bytes_tracks_texts_through_changes_and_reload(tmp_path):
    """The running text-byte sum the resource ledger reads matches the
    texts after adds, rewrites, removals, a load and a journal replay."""
    def check(kb):
        assert kb._text_bytes == sum(len(t) for t in kb.texts.values())

    kb = KnowledgeBase(dim=256)
    for name in ("a", "b", "c"):
        kb.add_text(name, f"text of {name} " * 3)
    check(kb)
    kb.add_text("b", "b is shorter")
    kb._remove_doc("c")
    check(kb)
    path = str(tmp_path / "kb.ragdb")
    kb.save(path)
    kb.add_text("a", "a rewritten after the save, and longer than before")
    kb._remove_doc("b")
    kb.add_text("d", "a new doc")
    kb.save_delta(path, compact_ratio=None)
    check(kb)
    check(KnowledgeBase.load(path))
