"""``correct`` has to fail when the timed path is broken underneath: an
answer altered where it is produced, half of a batch's answers left out,
and a publish that leaves the served state unchanged."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny import run_tiny  # noqa: E402

from repro.serving import snapshot  # noqa: E402

SEED = 2**31 + 99


def test_altered_answer_is_not_correct(monkeypatch):
    produce = snapshot.results_from_topk

    def altered(*args):
        out = produce(*args)
        for row in out:
            if len(row) > 1:    # rank 1 and 2 trade ids
                row[0].doc_id, row[1].doc_id = row[1].doc_id, row[0].doc_id
        return out
    monkeypatch.setattr(snapshot, "results_from_topk", altered)
    out = run_tiny("dpr-flat.steady", SEED)["result"]
    assert out["correct"] is False
    assert out["checks"]["score_gap"]["value"] > out["checks"][
        "score_gap"]["limit"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    serve = snapshot.EngineSnapshot.query_batch

    def half(self, texts, k=5, **kw):
        out = serve(self, texts, k, **kw)
        keep = (len(texts) + 1) // 2
        return out[:keep] + [[] for _ in out[keep:]]
    monkeypatch.setattr(snapshot.EngineSnapshot, "query_batch", half)
    out = run_tiny("dpr-flat.steady", SEED)["result"]
    assert out["correct"] is False
    assert out["checks"]["short_results"]["value"] > 0


def test_publish_that_changes_nothing_is_not_correct(monkeypatch):
    def unchanged(self, durable=False):
        return self._current
    monkeypatch.setattr(snapshot.SnapshotManager, "publish", unchanged)
    out = run_tiny("dpr-flat.live", SEED)["result"]
    assert out["correct"] is False
    assert out["checks"]["entity_misses"]["value"] > 0
